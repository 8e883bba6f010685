#!/usr/bin/env python3
"""The choices the ``qwen3_next`` configuration's program left to the chip,
timed in one process at the cell's real shapes (PERF.md section 6, PR 31):

  A. the grouped matmuls of an expert block at 160-row groups: one run of
     10,240 sorted rows over 32 experts of 2,048 x 512 (gate / up) and 512
     x 2,048 (down), forward and backward, at row tiles 128 / 256 / 512;
  B. the delta rule's chunked recurrence of one block (4 x 2,048 positions,
     16 / 32 heads of 128, chunks of 64), forward and backward, with the
     triangular inverse as ten float32 "highest" products (with its own
     cotangent, as shipped, and differentiated through), the same at
     precision "high" (three bf16 passes), and as a triangular solve.

    python3 -m benchmark.tools.qwen3_next_forms [out.json]

Chip only.  Times are host-clock means over repeated, blocked calls of
jitted programs: a ranking of forms, not a cell's metric."""

import json
import sys
import time


def timed(f, *args, n=10):
    import jax

    jax.block_until_ready(f(*args))
    jax.block_until_ready(f(*args))
    t = time.perf_counter()
    for _ in range(n):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / n * 1e3


def main() -> int:
    import jax
    import jax.numpy as jnp

    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from pytorch_distributed_tpu.models import gated_delta, hybrid

    if jax.devices()[0].platform == "cpu":
        sys.exit("needs the chip")
    out = {}
    # ---- A: grouped matmuls ------------------------------------------------
    R, H, d, w = 10240, 32, 2048, 512
    k = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(k[0], (R, d), jnp.bfloat16)
    wg, wu = (jax.random.normal(k_, (H, d, w), jnp.bfloat16) * 0.02
              for k_ in k[1:3])
    wd = jax.random.normal(k[3], (H, w, d), jnp.bfloat16) * 0.02
    # 5,120 routed rows, level (160 an expert); the tail rides on the last
    sizes = jnp.full((H,), 160, jnp.int32).at[-1].add(R - 160 * H)
    for tile in (128, 256, 512):
        def ffn(x, wg, wu, wd, tile=tile):
            # models/hybrid.py grouped_dot's call, at this row tile
            dot = lambda a, b: gmm(a, b, sizes, jnp.float32, (
                tile, hybrid._tile(a.shape[1], 896),
                hybrid._tile(b.shape[2], 896)))
            hid = jax.nn.silu(dot(x, wg)) * dot(x, wu)
            return dot(hid.astype(jnp.bfloat16), wd)
        fwd = jax.jit(ffn)
        bwd = jax.jit(jax.grad(lambda *a: jnp.sum(ffn(*a)).astype(
            jnp.float32), argnums=(0, 1, 2, 3)))
        out[f"experts_tile{tile}_fwd_ms"] = timed(fwd, x, wg, wu, wd)
        out[f"experts_tile{tile}_fwd_bwd_ms"] = timed(bwd, x, wg, wu, wd)
    # ---- B: the recurrence ---------------------------------------------------
    b, T, hk, hv, dh = 4, 2048, 16, 32, 128
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q = unit(jax.random.normal(k[0], (b, T, hk, dh))) / dh ** 0.5
    kk = unit(jax.random.normal(k[1], (b, T, hk, dh)))
    v = jax.random.normal(k[2], (b, T, hv, dh))
    g = -0.05 * jax.nn.softplus(jax.random.normal(k[3], (b, T, hv)))
    beta = jax.nn.sigmoid(jax.random.normal(k[4], (b, T, hv)))
    real_inverse = gated_delta.unit_lower_inverse

    def high(A):
        L = A.shape[-1]
        mm = lambda a, c: jnp.matmul(a, c, precision=jax.lax.Precision.HIGH)
        inv, power, n = jnp.eye(L, dtype=A.dtype) - A, A, 2
        while n < L:
            power = mm(power, power)
            inv = inv + mm(inv, power)
            n *= 2
        return inv

    def solve(A):
        eye = jnp.eye(A.shape[-1], dtype=A.dtype)
        return jax.scipy.linalg.solve_triangular(
            eye + A, jnp.broadcast_to(eye, A.shape), lower=True,
            unit_diagonal=True)

    # the shipped form first; "autodiff" differentiates through the ten
    # products instead of using the inverse's own cotangent
    forms = {"product_highest": real_inverse,
             "product_highest_autodiff": gated_delta._inverse_by_products,
             "product_high_autodiff": high, "solve_autodiff": solve}
    ref = None
    for name, inverse in forms.items():
        gated_delta.unit_lower_inverse = inverse
        run = lambda *a: gated_delta.gated_delta_chunked(*a, 64)
        fwd = jax.jit(run)
        bwd = jax.jit(jax.grad(lambda *a: jnp.sum(run(*a)[0])
                               + jnp.sum(run(*a)[1]), argnums=(0, 1, 2, 3, 4)))
        out[f"gdn_{name}_fwd_ms"] = timed(fwd, q, kk, v, g, beta, n=5)
        out[f"gdn_{name}_fwd_bwd_ms"] = timed(bwd, q, kk, v, g, beta, n=5)
        S = fwd(q, kk, v, g, beta)[1]
        if ref is None:
            ref = S
        out[f"gdn_{name}_state_rel_to_first"] = float(
            jnp.linalg.norm(S - ref) / jnp.linalg.norm(ref))
    gated_delta.unit_lower_inverse = real_inverse
    text = json.dumps(out, indent=1)
    print(text)
    if len(sys.argv) > 1:
        with open(sys.argv[1], "w") as f:
            f.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
