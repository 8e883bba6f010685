#!/usr/bin/env python3
"""The check of ``kimi_linear_pong.learner_only`` against its controls, at
the timed size, on ONE program side (PERF.md section 6, PR 34): build the
cell's learner as the runner does, fill the ring from the seed, run the
program's K = 1 fused update ``--updates`` times and once more
(``families/kimi_linear.py program_side``), then compare it with the sound
reference and with each control (``CONTROLS``: the reference with one term
wrong, or its recurrent state in bfloat16).  Prints, and writes to
``out.json``, every reading beside its limit and the limits each comparison
fails.

    python3 -m benchmark.tools.kimi_linear_controls --seed N [--out out.json]
        [--only name,name]

Chip only (a full-size train state and a float32 "highest" reference)."""

import argparse
import json
import os
import sys
import time

CELL = "kimi_linear_pong.learner_only"


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--out", default="")
    p.add_argument("--only", default="")
    p.add_argument("--updates", type=int, default=8)
    a = p.parse_args()

    import jax
    import numpy as np

    from benchmark.families import kimi_linear as family
    from benchmark.harness import manifest, program

    if jax.devices()[0].platform == "cpu":
        sys.exit("needs the chip")
    from pytorch_distributed_tpu.utils.helpers import enable_compile_cache

    enable_compile_cache()
    cell = manifest.load_cell(CELL)
    opt = program.build_opt(
        cell.config, a.seed, os.path.join(manifest.ROOT, ".bench_run",
                                          "kimi_linear_controls"),
        refs=cell.name, num_actors=0, evaluator_nepisodes=0)
    lrn = program.build_learner(opt)
    program.fill_ring(lrn, a.seed, int(cell.config["fill_chunk"]), family)
    # a few updates first, as the cell's window makes them: at the seeded
    # weights the Q head is zero and no TD gradient reaches the trunk
    fused = family.build_step(lrn)
    keys = jax.random.split(jax.random.PRNGKey(a.seed), a.updates)
    beta = jax.device_put(np.float32(lrn.replay.beta(0)))
    for key in keys:
        lrn.state, lrn.replay.state, _ = fused(lrn.state, lrn.replay.state,
                                               key, beta)
    reference = manifest.load_module("reference", cell.config["reference"])
    side = family.program_side(lrn, a.seed, reference)
    names = ["sound"] + list(family.CONTROLS)
    if a.only:
        names = [n for n in names if n in a.only.split(",")]
    out = {"seed": a.seed, "tolerance": {
        k: v for k, v in cell.config["tolerance"].items() if k != "why"}}
    for name in names:
        t = time.perf_counter()
        got = family.compare(side, cell.config, reference,
                             **family.CONTROLS.get(name, {}))
        out[name] = {
            "failed": got["failed"], "seconds": time.perf_counter() - t,
            "loss_rel": got["loss"]["rel_err"],
            "grad_cosine": got["grad_cosine"],
            "grad_cosine_leaf": got["grad"]["worst_leaf"],
            "grad_norm_leaf": got["grad"]["worst_norm"],
            "td_p50_over_mean": got["td"]["p50_err_over_mean"],
            "td_max_over_mean": got["td"]["max_err_over_mean"],
            "moe_rows_rel": got["moe"]["rows_rel_err"],
            "kda_state_rel": got["kda_state"]["rel_err_by_layer"],
            "kda_state_slow_rel": got["kda_state_slow"]["rel_err_by_layer"],
            "mla_out_rel": got["mla_out"]["rel_err"],
            "kda_decay": got["kda_decay"]}
        print(name, json.dumps(out[name], default=float), flush=True)
    if a.out:
        os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
        with open(a.out, "w") as f:
            json.dump(out, f, indent=1, default=float)
    return 0


if __name__ == "__main__":
    sys.exit(main())
