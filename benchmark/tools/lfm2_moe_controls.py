#!/usr/bin/env python3
"""The check of ``lfm2_moe_pong.learner_only`` against its controls, at the
timed size, on ONE program side (PERF.md section 6): build the
cell's learner as the runner does, fill the ring from the seed, run the
program's K = 1 fused update ``--updates`` times and once more
(``families/lfm2_moe.py program_side``), then compare it with the sound
reference and with each control (``CONTROLS``: the reference with one term
wrong, or the whole trunk in bfloat16).  Prints, and writes to ``out.json``,
every reading beside its limit and the limits each comparison fails.

    python3 -m benchmark.tools.lfm2_moe_controls --seed N [--out out.json]
        [--only name,name] [--updates 26]

Chip only (a full-size train state and a float32 "highest" reference)."""

import argparse
import json
import os
import sys
import time

CELL = "lfm2_moe_pong.learner_only"


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--out", default="")
    p.add_argument("--only", default="")
    # about as many as the cell's window makes before its check
    p.add_argument("--updates", type=int, default=26)
    a = p.parse_args()

    import jax
    import numpy as np

    from benchmark.families import lfm2_moe as family
    from benchmark.harness import manifest, program

    if jax.devices()[0].platform == "cpu":
        sys.exit("needs the chip")
    from pytorch_distributed_tpu.utils.helpers import enable_compile_cache

    enable_compile_cache()
    cell = manifest.load_cell(CELL)
    opt = program.build_opt(
        cell.config, a.seed, os.path.join(manifest.ROOT, ".bench_run",
                                          "lfm2_moe_controls"),
        refs=cell.name, num_actors=0, evaluator_nepisodes=0)
    lrn = program.build_learner(opt)
    program.fill_ring(lrn, a.seed, int(cell.config["fill_chunk"]), family)
    # updates first, as the cell's window makes them: at the seeded weights
    # the Q head is zero and no TD gradient reaches the trunk, and b_sel has
    # not yet moved from its seed
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
    out = {"seed": a.seed, "updates": a.updates, "tolerance": {
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
            "moe": got["moe"],
            **{key: got[key]["rel_err_by_layer"] for key in (
                *family.COMPARED.values(), "route_weight")}}
        print(name, json.dumps(out[name], default=float), flush=True)
    if a.out:
        os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
        with open(a.out, "w") as f:
            json.dump(out, f, indent=1, default=float)
    return 0


if __name__ == "__main__":
    sys.exit(main())
