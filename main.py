#!/usr/bin/env python
"""Entry point.

Equivalent of reference main.py — mode 1 trains the configured agent
topology, mode 2 tests a checkpoint — plus the CLI the reference never had
(it is edit-the-file configured, reference README.md:41-49): every CONFIGS
row is selectable and the common knobs are flags.

Examples:
    python main.py --config 4 --num-actors 8            # DQN on sim-Pong
    python main.py --config 1 --steps 2000 --backend thread
    python main.py --config 2 --mode 2 --model-file models/run.msgpack
"""

from __future__ import annotations

import argparse
import sys

from pytorch_distributed_tpu.config import CONFIGS, build_options


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__,
                                formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--config", type=int, default=0,
                   help=f"CONFIGS row 0..{len(CONFIGS) - 1} "
                        "(reference utils/options.py:10-14)")
    p.add_argument("--mode", type=int, default=1, choices=(1, 2),
                   help="1=train, 2=test (reference main.py:34,107)")
    p.add_argument("--seed", type=int, default=100)
    p.add_argument("--num-actors", type=int, default=None)
    p.add_argument("--num-envs-per-actor", type=int, default=None,
                   help="vector-env width per actor (batched inference)")
    p.add_argument("--steps", type=int, default=None,
                   help="max learner steps (reference utils/options.py:119)")
    p.add_argument("--memory-size", type=int, default=None)
    p.add_argument("--batch-size", type=int, default=None)
    p.add_argument("--nstep", type=int, default=None)
    p.add_argument("--enable-double", action="store_true")
    p.add_argument("--publish-freq", type=int, default=None,
                   help="learner steps between param publications")
    p.add_argument("--model-file", type=str, default=None,
                   help="finetune (mode 1) / test (mode 2) checkpoint")
    p.add_argument("--resume", type=str, default=None, metavar="REFS",
                   help="resume run REFS from its newest complete "
                        "checkpoint epoch (models/REFS_ckpt): train "
                        "state, replay, clock counters, best-score and "
                        "RNG continue; fails fast if no complete epoch "
                        "or legacy snapshot exists")
    p.add_argument("--backend", choices=("process", "thread"),
                   default="process")
    p.add_argument("--no-tensorboard", action="store_true")
    p.add_argument("--render", action="store_true",
                   help="dump eval frames (tester in mode 2, evaluator in "
                        "mode 1) as PNGs under the run's log dir (headless "
                        "stand-in for the reference's cv2.imshow display)")
    p.add_argument("--dp-size", type=int, default=-1,
                   help="learner mesh data-parallel width (-1 = all devices)")
    p.add_argument("--set", action="append", default=[], metavar="K=V",
                   help="any Options override, e.g. --set seq_len=16 "
                        "--set lr=2e-3 (repeatable)")
    return p.parse_args(argv)


def options_from_args(args):
    from pytorch_distributed_tpu.config import parse_set_overrides

    overrides = dict(mode=args.mode, seed=args.seed)
    # --set wins over flag defaults (and may name the same keys)
    overrides.update(parse_set_overrides(args.set))
    if args.num_actors is not None:
        overrides["num_actors"] = args.num_actors
    if args.num_envs_per_actor is not None:
        overrides["num_envs_per_actor"] = args.num_envs_per_actor
    if args.steps is not None:
        overrides["steps"] = args.steps
    if args.memory_size is not None:
        overrides["memory_size"] = args.memory_size
    if args.batch_size is not None:
        overrides["batch_size"] = args.batch_size
    if args.nstep is not None:
        overrides["nstep"] = args.nstep
    if args.enable_double:
        overrides["enable_double"] = True
    if args.publish_freq is not None:
        overrides["param_publish_freq"] = args.publish_freq
    if args.model_file is not None:
        overrides["model_file"] = args.model_file
    if args.resume is not None:
        overrides["refs"] = args.resume
        overrides["resume"] = "must"
    if args.no_tensorboard:
        overrides["visualize"] = False
    if args.render:
        overrides["render"] = True
    if args.dp_size != -1:
        overrides["dp_size"] = args.dp_size
    return build_options(config=args.config, **overrides)


def main(argv=None):
    args = parse_args(argv)
    opt = options_from_args(args)

    from pytorch_distributed_tpu.utils.helpers import enable_compile_cache

    enable_compile_cache()

    from pytorch_distributed_tpu import runtime

    if opt.mode == 1:
        print(f"[main] training config {args.config} "
              f"({opt.agent_type}/{opt.env_type}/{opt.game}/"
              f"{opt.memory_type}/{opt.model_type}) -> {opt.refs}")
        topo = runtime.train(opt, backend=args.backend)
        if topo.stop_reason in runtime.FATAL_STOP_REASONS:
            # Topology.run returns normally once the monitor has stopped
            # the run; a run that lost a worker did not succeed
            sys.exit(f"[main] run FAILED: stopped by {topo.stop_reason}")
    else:
        runtime.test(opt)


if __name__ == "__main__":
    main()
