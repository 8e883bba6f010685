#!/usr/bin/env python3
"""The choice the ``kimi_linear`` configuration's program left to the chip,
timed in one process at the cell's real shapes (PERF.md section 6, PR 34):
the channel-gated delta rule's chunked recurrence of ONE block (4 x 2,048
positions, 32 heads of 128, chunks of 64), forward and forward + backward,
with the intra-chunk products (models/gated_delta.py
``intra_chunk_products``) in their candidate forms:

  sub1 .. sub16          the sub-block inside which the decays are taken
                         pair by pair ((sub, sub, 128) differences a
                         sub-block, float32); above it blocks are joined in
                         pairs, level by level, one product a level against
                         the later block's first position.  ``sub1`` has no
                         pairwise array at all (six levels of products);
  sub16_remat            sub16 under a ``jax.checkpoint`` of its own, so
                         that the backward forms the pairwise decays again
                         and keeps none;
  flat16                 the first form tried: sub-block I against ALL
                         earlier positions in one product (``flat`` below),
                         the pairs inside it;
  sub16_products         sub16 with the inverse by ten products of powers of
                         ``A`` (row 21's ``unit_lower_inverse``) in place of
                         the inverse by blocks: what the stable form costs;
  scalar_gate            row 21's ``gated_delta_chunked`` on a gate that is
                         a head's (the mean over the channels): what the
                         per-channel gate costs over the scalar one.

    python3 -m benchmark.tools.kimi_linear_forms [out.json]

Chip only.  Times are host-clock means over repeated, blocked calls of
jitted programs: a ranking of forms, not a cell's metric.  A form the chip
cannot hold reads ``null``."""

import json
import sys
import time


def timed(f, *args, n=5):
    import jax

    jax.block_until_ready(f(*args))
    jax.block_until_ready(f(*args))
    t = time.perf_counter()
    for _ in range(n):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / n * 1e3


def flat(q, k, gamma, sub, cd):
    """``intra_chunk_products`` with every sub-block's rows against ALL
    earlier positions in one product (k rescaled once a sub-block: ``(.., I,
    L, d)``), in place of the levels."""
    import jax.numpy as jnp

    L, d = k.shape[-2:]
    lead, ns = k.shape[:-2], L // sub
    blocks = lambda t: t.reshape(*lead, ns, sub, d)
    gb = blocks(gamma)
    ref = gb[..., 0, :]
    before = jnp.arange(L)[None, :] < sub * jnp.arange(ns)[:, None]
    k_up = (k[..., None, :, :] * jnp.exp(jnp.where(
        before[..., None], ref[..., None, :] - gamma[..., None, :, :],
        -jnp.inf))).astype(cd)
    down = jnp.exp(gb - ref[..., None, :])
    causal = jnp.tril(jnp.ones((sub, sub), bool))
    k_pair = blocks(k)[..., None, :, :] * jnp.exp(jnp.where(
        causal[..., None], gb[..., :, None, :] - gb[..., None, :, :],
        -jnp.inf))

    def against_k(x):
        off = jnp.einsum("...Iid,...Ijd->...Iij",
                         (blocks(x) * down).astype(cd), k_up,
                         preferred_element_type=jnp.float32)
        diag = jnp.sum(blocks(x)[..., :, None, :] * k_pair, axis=-1)
        return off.reshape(*lead, L, L) + jnp.einsum(
            "...Iij,IJ->...IiJj", diag, jnp.eye(ns)).reshape(*lead, L, L)

    return against_k(k), against_k(q)


def main() -> int:
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.models import gated_delta

    if jax.devices()[0].platform == "cpu":
        sys.exit("needs the chip")
    b, T, h, d = 4, 2048, 32, 128
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q = unit(jax.random.normal(k[0], (b, T, h, d))) / d ** 0.5
    kk = unit(jax.random.normal(k[1], (b, T, h, d)))
    v = jax.random.normal(k[2], (b, T, h, d))
    # a seeded block's decays: a channel keeps 0.83 a position in the mean
    g = -0.2 * jnp.exp(jax.random.normal(k[3], (b, T, h, d)))
    beta = jax.nn.sigmoid(jax.random.normal(k[4], (b, T, h)))
    real = gated_delta.intra_chunk_products

    by_blocks = gated_delta.unit_lower_inverse_by_blocks

    def kda(sub, products=real, inverse=by_blocks):
        def run(*a):
            gated_delta.intra_chunk_products = products
            gated_delta.unit_lower_inverse_by_blocks = inverse
            try:
                return gated_delta.kda_chunked(*a, 64, sub)
            finally:
                gated_delta.intra_chunk_products = real
                gated_delta.unit_lower_inverse_by_blocks = by_blocks
        return run

    forms = {
        **{f"sub{n}": (kda(n), g) for n in (1, 2, 4, 8, 16)},
        "sub16_remat": (kda(16, jax.checkpoint(real, static_argnums=(3, 4))),
                        g),
        "flat16": (kda(16, flat), g),
        "sub16_products": (kda(16, inverse=gated_delta.unit_lower_inverse),
                           g),
        "scalar_gate": (lambda *a: gated_delta.gated_delta_chunked(*a, 64),
                        jnp.mean(g, axis=-1))}
    out, ref = {}, None
    for name, (run, gate) in forms.items():
        args = (q, kk, v, gate, beta)
        fwd = jax.jit(run)
        bwd = jax.jit(jax.grad(lambda *a: jnp.sum(run(*a)[0])
                               + jnp.sum(run(*a)[1]), argnums=(0, 1, 2, 3, 4)))
        for key, f in ((f"kda_{name}_fwd_ms", fwd),
                       (f"kda_{name}_fwd_bwd_ms", bwd)):
            try:
                out[key] = timed(f, *args)
            except Exception as e:  # noqa: BLE001 - the chip's refusal
                out[key] = None
                print(f"[forms] {key}: {type(e).__name__}: {str(e)[:300]}",
                      file=sys.stderr)
        if out[f"kda_{name}_fwd_ms"] is not None and name != "scalar_gate":
            S = fwd(*args)[1]
            ref = S if ref is None else ref
            out[f"kda_{name}_state_rel_to_first"] = float(
                jnp.linalg.norm(S - ref) / jnp.linalg.norm(ref))
        print(name, json.dumps({k_: v_ for k_, v_ in out.items()
                                if f"_{name}_" in k_}), flush=True)
    text = json.dumps(out, indent=1)
    print(text)
    if len(sys.argv) > 1:
        with open(sys.argv[1], "w") as f:
            f.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
