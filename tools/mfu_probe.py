#!/usr/bin/env python
"""Name the resource that bounds the flagship learner's MFU.

The bench (bench.py micro) reports ~17% MFU for the fused batch-128
Nature-DQN update at the chip-bound asymptote — this probe explains WHY,
with a real XLA profile rather than an assertion:

1. captures a ``jax.profiler`` trace of the production fused K=32
   program on the chip and converts it op-by-op with xprof to a
   self-time ranking;
2. sweeps the levers that would move the number if the bound were
   elsewhere: batch scaling (128 -> 512 at constant FLOP intensity per
   row) and compute dtype (bf16 vs f32);
3. prints one JSON blob with the top ops, the per-lever MFUs, and the
   inferred ``mfu_bound`` string the bench can quote.

Usage: python tools/mfu_probe.py [--trace-dir DIR] [--skip-trace]
"""

from __future__ import annotations

import argparse
import glob
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


def capture_trace(compiled, state, ring, K: int, trace_dir: str) -> None:
    import jax

    key = jax.random.PRNGKey(1)
    os.makedirs(trace_dir, exist_ok=True)
    with jax.profiler.trace(trace_dir):
        for _ in range(4):
            key, sub = jax.random.split(key)
            state, m = compiled(state, ring.state,
                                jax.random.split(sub, K))
        float(jax.device_get(m["learner/critic_loss"]))


def op_breakdown(trace_dir: str, top: int = 12) -> list:
    """Convert the captured xplane with xprof and rank ops by self time."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return [{"error": "no xplane.pb captured"}]
    path = max(paths, key=os.path.getmtime)
    os.environ.setdefault("PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION",
                          "python")
    from xprof.convert import raw_to_tool_data
    data, _ = raw_to_tool_data.xspace_to_tool_data([path], "hlo_stats", {})
    if isinstance(data, bytes):
        data = data.decode()
    table = json.loads(data)
    # gviz DataTable: {"cols": [{id,label}...], "rows": [{"c": [{"v":..}]}]}
    cols = [c.get("label", c.get("id", "")).lower()
            for c in table.get("cols", [])]
    rows = [[cell.get("v") if isinstance(cell, dict) else cell
             for cell in r.get("c", [])] for r in table.get("rows", [])]
    if not rows:
        return [{"error": "empty hlo_stats"}]

    def col(*names):
        for n in names:
            for i, h in enumerate(cols):
                if n in h:
                    return i
        return None

    i_name = col("hlo op name", "op name", "op_name")
    i_cat = col("category")
    i_self = col("total self time (us)", "self time (us)", "self")
    i_pct = col("total self time (%)", "self time (%)")
    out = []
    rows.sort(key=lambda r: -float(r[i_self] or 0))
    for r in rows[:top]:
        out.append({
            "op": str(r[i_name])[:90],
            "category": r[i_cat] if i_cat is not None else "?",
            "self_us": round(float(r[i_self] or 0), 1),
            "self_pct": (round(float(r[i_pct] or 0), 2)
                         if i_pct is not None else None),
        })
    return out


# trace categories that are layout work, not model math: the re-tiling
# share the bench's ``mfu_bound`` note quotes (ISSUE-13 satellite)
_RETILING_CATS = ("copy", "transpose", "reshape", "convert",
                  "data formatting")


def attribution_of(top_ops: list) -> dict:
    """Machine-readable attribution over an ``op_breakdown`` ranking:
    per-category self-time bins (fractions of the ranked total) and the
    re-tiling share (copy/transpose/reshape/convert categories) —
    what ``bench.py`` micro's ``mfu_bound`` note consumes from an
    ``MFU_PROBE.json`` artifact instead of a hand-copied string."""
    rows = [r for r in top_ops if "error" not in r]
    total = sum(r.get("self_us", 0.0) for r in rows)
    bins: dict = {}
    for r in rows:
        cat = str(r.get("category", "?")).lower() or "?"
        bins[cat] = bins.get(cat, 0.0) + r.get("self_us", 0.0)
    if total <= 0:
        return {"error": "no ranked ops", "bins": {}, "retiling_share": None}
    bins = {k: round(v / total, 4) for k, v in bins.items()}
    retiling = sum(v for k, v in bins.items()
                   if any(t in k for t in _RETILING_CATS))
    return {"retiling_share": round(retiling, 4), "bins": bins,
            "basis": "fraction of ranked-op self time"}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace-dir", default="/tmp/mfu_probe_trace")
    ap.add_argument("--skip-trace", action="store_true")
    ap.add_argument("--skip-levers", action="store_true")
    ap.add_argument("--json", action="store_true",
                    help="one-line machine-readable JSON (adds the "
                         "'attribution' section: re-tiling share + "
                         "per-category self-time bins)")
    ap.add_argument("--out", default=None, metavar="FILE",
                    help="also write the JSON blob to FILE (point it "
                         "at MFU_PROBE.json in the repo root so "
                         "bench.py's mfu_bound note quotes this probe)")
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
    rate, flops, state, compiled = measure(fused, state, ring, 32)
    out["b128_bf16"] = {
        "updates_per_sec": round(rate, 1),
        "flops_per_update": flops,
        "mfu": round(rate * flops / peak, 4) if flops else None,
    }
    if not args.skip_trace:
        capture_trace(compiled, state, ring, 32, args.trace_dir)
        out["top_ops"] = op_breakdown(args.trace_dir, top=24)
        out["trace_dir"] = args.trace_dir
        out["attribution"] = attribution_of(out["top_ops"])

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
