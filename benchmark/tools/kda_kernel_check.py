#!/usr/bin/env python3
"""The channel-gated delta rule's Pallas kernel pair
(``pytorch_distributed_tpu/ops/pallas_kda.py``) against the XLA form of
``kda_chunked`` on the chip, at the ``kimi_linear`` cell's shapes: ONE block
(4 x 2,048 positions, 32 heads of 128, chunks of 64 in sub-blocks of 16).

For each case (the cell's seeded decays; a slow decay, every channel keeping
0.99, with keys at cosine 0.5) it reads, in bf16 compute: the kernels' ``o``,
last state and the five gradients against the XLA form in bf16 and against
the XLA form in float32 (every product at ``highest``), and the XLA form in
bf16 against the float32 form (the rounding the configuration already
states).  Then it times, in ms a call: the XLA form's forward and forward +
backward, and the kernels' in each form asked for.

    python3 -m benchmark.tools.kda_kernel_check [out.json] [form ...]

a form being the heads a grid step (``4``, ``8``); ``--time-only`` among
them skips the comparisons and the XLA form.

Chip only.  Times are host-clock means over repeated, blocked calls of
jitted programs: a ranking of forms, not a cell's metric."""

import json
import sys
import time

B, T, H, D, L, SUB = 4, 2048, 32, 128, 64, 16


def timed(f, *args, n=5):
    import jax

    jax.block_until_ready(f(*args))
    jax.block_until_ready(f(*args))
    t = time.perf_counter()
    for _ in range(n):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / n * 1e3


def inputs(seed, slow):
    import jax
    import jax.numpy as jnp
    import numpy as np

    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, T, H, D))) / D ** 0.5
    k = unit(jax.random.normal(ks[1], (B, T, H, D)))
    v = jax.random.normal(ks[2], (B, T, H, D))
    if slow:
        k = unit(k + k[:, :1, :, :])            # keys at cosine 0.5
        g = jnp.full((B, T, H, D), np.log(0.99), jnp.float32)
    else:   # the cell's init: -U(1, 16) a head x softplus(a channel's gate)
        a = jax.random.uniform(ks[3], (H, 1), minval=1.0, maxval=16.0)
        dt = jnp.exp(jax.random.uniform(ks[5], (H, D), minval=np.log(1e-3),
                                        maxval=np.log(0.1)))
        f = jax.random.normal(ks[3], (B, T, H, D)) + jnp.log(jnp.expm1(dt))
        g = -a * jax.nn.softplus(f)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    return q, k, v, g, beta


def main() -> int:
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.models import gated_delta
    from pytorch_distributed_tpu.ops import pallas_kda

    out_path = sys.argv[1] if len(sys.argv) > 1 else None
    forms = sys.argv[2:] or [str(pallas_kda.HEADS_A_STEP)]
    check = "--time-only" not in forms
    forms = [f for f in forms if f != "--time-only"]
    assert jax.default_backend() == "tpu", jax.default_backend()
    window = lambda kernel, cd: jax.jit(lambda *a: gated_delta.kda_chunked(
        *a, L, SUB, cd, kernel=kernel))
    scalar = lambda f: lambda *a: (jnp.sum(jnp.sin(f(*a)[0]))
                                   + jnp.sum(jnp.square(f(*a)[1])))
    grads = lambda kernel, cd: jax.jit(jax.grad(
        scalar(lambda *a: gated_delta.kda_chunked(*a, L, SUB, cd,
                                                  kernel=kernel)),
        argnums=(0, 1, 2, 3, 4)))
    rel = lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
    bf16 = jnp.bfloat16
    report = {"shapes": [B, T, H, D, L, SUB], "cases": {}, "ms": {}}
    for case, seed, slow in (("seeded", 2147480037, False),
                             ("slow", 2147480038, True))[:2 * check]:
        args = inputs(seed, slow)
        got = [window("auto", bf16)(*args), grads("auto", bf16)(*args)]
        xla = [window("xla", bf16)(*args), grads("xla", bf16)(*args)]
        with jax.default_matmul_precision("highest"):
            f32 = [window("xla", jnp.float32)(*args),
                   grads("xla", jnp.float32)(*args)]
        names = ["o", "S"] + ["d" + n for n in ("q", "k", "v", "g", "beta")]
        flat = lambda r: list(r[0]) + list(r[1])
        report["cases"][case] = {n: {
            "finite": bool(jnp.all(jnp.isfinite(a))),
            "kernel_vs_xla": rel(a, b), "kernel_vs_f32": rel(a, c),
            "xla_vs_f32": rel(b, c)}
            for n, a, b, c in zip(names, flat(got), flat(xla), flat(f32))}
        print(case, json.dumps(report["cases"][case]), flush=True)
    args = inputs(2147480037, False)
    if check:
        report["ms"]["xla"] = [timed(window("xla", bf16), *args),
                               timed(grads("xla", bf16), *args)]
    for form in forms:      # heads a grid step
        pallas_kda.HEADS_A_STEP = int(form)
        pallas_kda._chunk.cache_clear()
        pallas_kda._launchers.cache_clear()
        report["ms"][f"kernel_h{form}"] = [
            timed(window("auto", bf16), *args),
            timed(grads("auto", bf16), *args)]
    print("ms (forward, forward + backward):", json.dumps(report["ms"]),
          flush=True)
    if out_path:
        with open(out_path, "w") as f:
            json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
