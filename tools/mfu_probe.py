#!/usr/bin/env python
"""Name the resource that bounds the flagship learner's MFU.

The bench (bench.py micro) reports ~17% MFU for the fused batch-128
Nature-DQN update at the chip-bound asymptote — this probe explains WHY,
with a real XLA profile rather than an assertion:

It sweeps the levers that would move the number if the bound were
elsewhere: batch scaling (128 -> 512 at constant FLOP intensity per row)
and compute dtype (bf16 vs f32), and prints one JSON blob with the
per-lever rates and MFUs.  (Its op-by-op ranking of a trace, which needed
the xprof converter, is gone: a traced run of a benchmark cell,
``python3 benchmark/run.py --workload <cell> ... --trace 1``, gives device
time per PROGRAM PHASE and per op from the program's own scopes;
PERF.md section 3.)

Usage: python tools/mfu_probe.py [--skip-levers] [--json] [--out FILE]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_fused(B: int, K: int, compute_dtype, channels_last: bool = False):
    import jax

    from pytorch_distributed_tpu.memory.device_replay import (
        DeviceReplay, build_uniform_fused_step,
    )
    from pytorch_distributed_tpu.models import DqnCnnModel
    from pytorch_distributed_tpu.ops.losses import (
        build_dqn_train_step, init_train_state, make_optimizer,
    )
    from pytorch_distributed_tpu.utils.experience import Transition

    model = DqnCnnModel(action_space=6, norm_val=255.0,
                        compute_dtype=compute_dtype,
                        nhwc_input=channels_last)
    obs = np.zeros((1, 84, 84, 4) if channels_last else (1, 4, 84, 84),
                   dtype=np.uint8)
    params = model.init(jax.random.PRNGKey(0), obs)
    tx = make_optimizer(lr=1e-4)
    state = init_train_state(params, tx)
    step = build_dqn_train_step(model.apply, tx, target_model_update=250)
    ring = DeviceReplay(capacity=2048, state_shape=(4, 84, 84),
                        state_dtype=np.uint8, channels_last=channels_last)
    rng = np.random.default_rng(0)
    C = 512
    for _ in range(ring.capacity // C):
        ring.feed_chunk(Transition(
            state0=rng.integers(0, 255, (C, 4, 84, 84)).astype(np.uint8),
            action=rng.integers(0, 6, C).astype(np.int32),
            reward=rng.normal(size=C).astype(np.float32),
            gamma_n=np.full(C, 0.99 ** 5, np.float32),
            state1=rng.integers(0, 255, (C, 4, 84, 84)).astype(np.uint8),
            terminal1=(rng.random(C) < 0.1).astype(np.float32)))
    fused = build_uniform_fused_step(step, B, steps_per_call=K)
    return fused, state, ring


def measure(fused, state, ring, K: int, windows: int = 5,
            iters: int = 24) -> tuple:
    """Fetch-bounded updates/s + XLA cost-analysis flops/update."""
    import jax

    key = jax.random.PRNGKey(0)

    def keymat():
        nonlocal key
        key, sub = jax.random.split(key)
        return jax.random.split(sub, K)

    compiled = fused.lower(state, ring.state, keymat()).compile()
    # shared with bench.py and the live perf plane (utils/perf.py) —
    # one extraction, three consumers
    from pytorch_distributed_tpu.utils.perf import flops_of_compiled

    flops = flops_of_compiled(compiled)
    for _ in range(6):
        state, m = compiled(state, ring.state, keymat())
    float(jax.device_get(m["learner/critic_loss"]))
    rates = []
    for _ in range(windows):
        ks = [keymat() for _ in range(iters)]
        jax.block_until_ready(ks[-1])
        t0 = time.perf_counter()
        for k in ks:
            state, m = compiled(state, ring.state, k)
        float(jax.device_get(m["learner/critic_loss"]))  # fetch-bounded
        rates.append(iters * K / (time.perf_counter() - t0))
    return float(np.median(rates)), flops, state, compiled


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-levers", action="store_true")
    ap.add_argument("--json", action="store_true",
                    help="one-line machine-readable JSON")
    ap.add_argument("--out", default=None, metavar="FILE",
                    help="also write the JSON blob to FILE")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.utils.helpers import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    from pytorch_distributed_tpu.utils.perf import peak_flops_of

    peak = peak_flops_of(dev)
    if peak is None:
        sys.exit(f"[mfu_probe] needs a TPU; default backend is "
                 f"{dev.platform!r} ({dev.device_kind!r})")
    out = {"device_kind": getattr(dev, "device_kind", "?")}

    # production point: B=128, K=32, bf16
    fused, state, ring = build_fused(128, 32, jnp.bfloat16)
    rate, flops, _s, _c = measure(fused, state, ring, 32)
    out["b128_bf16"] = {
        "updates_per_sec": round(rate, 1),
        "flops_per_update": flops,
        "mfu": round(rate * flops / peak, 4) if flops else None,
    }

    if not args.skip_levers:
        # lever 1: batch 512 (same program shape, 4x rows) — if the bound
        # were dispatch or bandwidth this rises sharply; if the MXU lanes
        # are the wall it rises only mildly
        fused4, state4, ring4 = build_fused(512, 8, jnp.bfloat16)
        r4, f4, _s, _c = measure(fused4, state4, ring4, 8)
        out["b512_bf16"] = {
            "updates_per_sec": round(r4, 1),
            "flops_per_update": f4,
            "mfu": round(r4 * f4 / peak, 4) if f4 else None,
        }
        # lever 2: f32 compute — halves MXU peak; if bf16 were underused
        # (e.g. everything upcast anyway) the rate would barely move
        fusedf, statef, ringf = build_fused(128, 32, jnp.float32)
        rf, ff, _s, _c = measure(fusedf, statef, ringf, 32)
        out["b128_f32"] = {
            "updates_per_sec": round(rf, 1),
            "flops_per_update": ff,
            "mfu_vs_bf16_peak": round(rf * ff / peak, 4) if ff else None,
        }

    blob = json.dumps(out) if args.json else json.dumps(out, indent=1)
    if args.out:
        with open(args.out, "w") as f:
            f.write(json.dumps(out, indent=1) + "\n")
    print(blob)


if __name__ == "__main__":
    main()
