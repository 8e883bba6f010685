#!/usr/bin/env python
"""Compile both Pallas kernels for the TPU at production geometry and
check each against its XLA reference; and read the packed replay ring
back from the chip, byte for byte.

The tier-1 tests run the kernels under the Pallas interpreter
(tests/test_pallas_sampling.py, tests/test_pallas_torso.py); this is the
compiled twin, the only place Mosaic lowering is exercised, and one leg
of ``chip_smoke.py``.  It never interprets and never falls back: off a
TPU it exits 2 without running anything.  It also turns the persistent
compile cache off on purpose — a cache hit would skip the very compile
it exists to prove.

1. PER sampler (ops/pallas_sampling.py): ``hierarchical_sample`` at the
   config-12 ring geometry (N = 50,000 rows, and N = 50,048), B = 128,
   block 1024, against ``flat_sample`` on shared uniforms.  A draw whose
   index differs from the flat scheme's is accepted only when both
   indices bracket the draw's target in a float64 CDF within f32
   rounding of the total mass (fp addition order differs between the
   MXU prefix sums and XLA's cumsum); anything else fails.
2. Torso (ops/pallas_torso.py): the apply ``factory._dqn_train_apply``
   selects under ``pallas_torso=true`` for config 12 — forward and
   gradient at batch 128 bf16 — against the model's XLA apply, at the
   tolerances of tests/test_pallas_torso.py (forward rtol/atol 0.05,
   whole-tree gradient cosine > 0.999).

3. Ring rows (memory/device_replay.py ``RowCodec``): random uint8
   (4, 84, 84) frames through ``feed_chunk`` across a cursor wrap, read
   back through ``snapshot`` (host unpack) and ``sample`` (the compiled
   unpack): byte-identical, and the stored column row-major on the
   device.  The tier-1 tests prove the same on the CPU; the TPU lowers
   one-byte shifts and lays arrays out differently.

Usage: JAX_PLATFORMS=tpu,cpu python tools/kernel_check.py
Last stdout line: one JSON object with per-kernel status and wall times
(compile included; set-up time, not a rate).
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BATCH = 128
RING_ROWS = (50_000, 50_048)


def _priorities(n: int, fill: int, seed: int) -> np.ndarray:
    """A ring ``fill`` rows full: p^alpha-like positive leaves, a share
    of exact duplicates (rows entered at the running max), zeros past
    the fill."""
    rng = np.random.default_rng(seed)
    p = np.zeros(n, np.float32)
    p[:fill] = rng.random(fill).astype(np.float32) + 1e-3
    p[:fill][rng.random(fill) < 0.25] = 1.0
    return p


def check_sampler() -> dict:
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.ops.pallas_sampling import (
        flat_sample, hierarchical_sample,
    )

    out = {"draws": 0, "exact": 0, "edge": 0}
    for n in RING_ROWS:
        for fill, seed in ((n, 0), (n // 3, 1)):
            prio = _priorities(n, fill, seed)
            cdf64 = np.cumsum(prio.astype(np.float64))
            total = cdf64[-1]
            # one f32 ulp of the total mass, with headroom for the few
            # roundings between a uniform and its searchsorted slot
            tol = 8.0 * float(np.spacing(np.float32(total)))
            dev = jnp.asarray(prio)
            for k in range(8):
                key = jax.random.PRNGKey(100 * seed + k)
                idx_h, p_h = hierarchical_sample(dev, key, BATCH,
                                                 interpret=False)
                idx_f, p_f = flat_sample(dev, key, BATCH)
                idx_h, idx_f = np.asarray(idx_h), np.asarray(idx_f)
                assert idx_h.shape == (BATCH,) and idx_h.dtype == np.int32
                assert (idx_h < fill).all() and (prio[idx_h] > 0).all(), (
                    f"sampler drew an empty row at n={n} fill={fill}")
                np.testing.assert_allclose(
                    np.asarray(p_h), prio[idx_h] / total, rtol=1e-5)
                u = np.asarray(jax.random.uniform(key, (BATCH,)),
                               np.float64) * total
                for i in np.flatnonzero(idx_h != idx_f):
                    for idx in (idx_h[i], idx_f[i]):
                        lo = cdf64[idx - 1] if idx else 0.0
                        assert lo - tol <= u[i] <= cdf64[idx] + tol, (
                            f"draw {i} (n={n} fill={fill} key={k}): "
                            f"index {idx} does not bracket target "
                            f"{u[i]!r} in [{lo!r}, {cdf64[idx]!r}]")
                    out["edge"] += 1
                out["draws"] += BATCH
                out["exact"] += int((idx_h == idx_f).sum())
    return out


def check_torso() -> dict:
    import jax
    import jax.numpy as jnp
    from jax.flatten_util import ravel_pytree

    from pytorch_distributed_tpu.config import build_options
    from pytorch_distributed_tpu.factory import (
        _dqn_train_apply, build_model, init_params, probe_env, select_torso,
    )

    opt = build_options(12, pallas_torso=True, batch_size=BATCH)
    spec = probe_env(opt)
    model = build_model(opt, spec)
    assert jnp.dtype(model.compute_dtype) == jnp.bfloat16, model
    params = init_params(opt, spec, model, seed=opt.seed)
    assert select_torso(opt) == "pallas", select_torso(opt)
    pallas_apply = _dqn_train_apply(opt, model)
    obs = jnp.asarray(np.random.default_rng(0).integers(
        0, 255, (BATCH, *spec.state_shape)).astype(np.uint8))

    def fwd_and_grad(apply):
        q = jax.jit(apply)(params, obs)
        g = jax.jit(jax.grad(
            lambda p: jnp.mean(apply(p, obs) ** 2)))(params)
        # float64 on the host: an f32 dot over 1.7M gradient elements
        # at default matmul precision is itself only good to ~1e-3
        return np.asarray(q), np.asarray(ravel_pytree(g)[0], np.float64)

    q_pal, g_pal = fwd_and_grad(pallas_apply)
    q_ref, g_ref = fwd_and_grad(model.apply)
    assert q_pal.shape == (BATCH, spec.num_actions), q_pal.shape
    assert np.isfinite(q_pal).all() and np.isfinite(g_pal).all()
    np.testing.assert_allclose(q_pal, q_ref, rtol=0.05, atol=0.05)
    cos = float(g_ref @ g_pal
                / (np.linalg.norm(g_ref) * np.linalg.norm(g_pal)))
    assert cos > 0.999, f"gradient cosine {cos}"
    return {"q_max_abs_err": float(np.abs(q_pal - q_ref).max()),
            "grad_cosine": round(cos, 6)}


def check_ring_rows() -> dict:
    import jax

    from pytorch_distributed_tpu.memory.device_per import DevicePerReplay
    from pytorch_distributed_tpu.utils.experience import Transition

    cap, n, shape, seed = 4096, 1536, (4, 84, 84), 7
    rng = np.random.default_rng(seed)
    ring = DevicePerReplay(cap, shape, state_dtype=np.uint8)
    fed = []
    for start in range(0, 3 * n, n):          # 4,608 rows: wraps once
        fed.append(Transition(
            state0=rng.integers(0, 256, (n, *shape)).astype(np.uint8),
            action=np.zeros(n, np.int32),
            reward=np.arange(start, start + n, dtype=np.float32),
            gamma_n=np.ones(n, np.float32),
            state1=rng.integers(0, 256, (n, *shape)).astype(np.uint8),
            terminal1=np.zeros(n, np.float32)))
        ring.feed_chunk(fed[-1])
    host = {f: np.concatenate([getattr(c, f) for c in fed])
            for f in ("state0", "state1")}
    snap = ring.snapshot()                    # oldest first
    first = int(snap["reward"][0])
    assert first == 3 * n - cap, first
    for f in host:
        assert np.array_equal(snap[f], host[f][first:]), f"snapshot {f}"
    b = jax.device_get(ring.sample(512, jax.random.PRNGKey(seed), beta=0.4))
    rows = b.reward.astype(np.int64)
    for f in host:
        assert np.array_equal(getattr(b, f), host[f][rows]), f"sample {f}"
    col = ring.state.state0
    layout = getattr(getattr(col, "format", None), "layout", None)
    order = tuple(getattr(layout, "major_to_minor", ()) or ())
    assert order in ((), (0, 1)), f"ring column not row-major: {layout}"
    return {"stored": ring.stored_rows, "layout": str(layout),
            "rows_checked": int(cap + len(rows))}


def main() -> int:
    import jax

    # no persistent cache, even where the environment names one: a hit
    # would skip the Mosaic compile this tool exists to prove
    jax.config.update("jax_compilation_cache_dir", None)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"[kernel_check] needs a TPU; default backend is "
              f"{dev.platform!r}", file=sys.stderr)
        return 2
    report = {"platform": dev.platform, "device_kind": dev.device_kind,
              "device_count": len(jax.devices())}
    failed = False
    for name, check in (("per_sampler", check_sampler),
                        ("torso", check_torso),
                        ("ring_rows", check_ring_rows)):
        t0 = time.monotonic()
        try:
            report[name] = dict(check(), status="ok")
        except Exception as e:  # noqa: BLE001 - report, then fail the leg
            import traceback

            traceback.print_exc()
            report[name] = {"status": "failed",
                            "error": f"{type(e).__name__}: {e}"[:2000]}
            failed = True
        report[name]["wall_s"] = round(time.monotonic() - t0, 1)
    report["ok"] = not failed
    print(json.dumps(report))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
