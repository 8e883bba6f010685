"""Pallas TPU kernel: hierarchical inverse-CDF sampling for prioritized
replay.

The XLA path in memory/device_per.py draws proportional samples by
materializing the full N-row cumulative sum every learner step
(``cumsum`` + ``searchsorted`` over the whole priority vector,
device_per.py per_sample).  At Atari-57 scale (N in the millions) that is
an O(N) HBM write + read per step for 128 draws.  The hierarchical scheme
here does the O(N) work once as a block *reduction* (no cumsum
materialization) and then touches only one priority block per draw:

1. (XLA) ``block_sums[b] = sum(priority[b*K:(b+1)*K])`` — a reduction XLA
   fuses, output is N/K floats;
2. (XLA) tiny ``cumsum`` + ``searchsorted`` over the N/K block sums picks
   the block and residual target per draw;
3. (Pallas) one kernel instance per draw DMAs exactly its block row from
   HBM to VMEM (scalar-prefetched block index steers the BlockSpec
   index_map), runs the in-block inverse-CDF scan on the VPU, and emits
   the local offset.

Exact-equivalence contract: for identical uniforms the hierarchical
sampler returns exactly the inverse-CDF index of the flat scheme (modulo
fp addition order inside a block), verified in tests against the flat
reference in interpret mode, and compiled on the chip at the config-12
geometry by tools/kernel_check.py (on a v5e, 8 of 4,096 draws land one
row off at a CDF edge and bracket their target in a float64 CDF).

Sharding note: the kernel addresses the priority vector as one local
array, so the Pallas path engages only when replay rows are unsharded
(single-chip, or replicated rings).  dp-sharded rings keep the XLA path —
per-chip sampling work there is N/ndev and the gather already rides the
same collectives as the row fetch.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

DEFAULT_BLOCK = 1024  # one f32 min-tile superblock (8 x 128); 4 KB per draw


def _tril(n: int, strict: bool = False):
    """Lower-triangular ones, built from 2D iotas (1D iota does not lower
    on TPU)."""
    r = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return ((c < r) if strict else (c <= r)).astype(jnp.float32)


def _draw_kernel(block_ids_ref, targets_ref, prio_ref, out_ref):
    """One grid step = one draw: in-superblock inverse-CDF search.

    ``prio_ref`` is the (1, 8, SUB) priority superblock the index_map
    selected from this draw's scalar-prefetched block id (8 sublanes x SUB
    lanes — the min f32 tile); ``targets_ref`` holds the residual target
    u - block_cdf[b-1].  Pallas TPU has no cumsum lowering, so prefix sums
    run as triangular matmuls on the MXU: P = tile @ L^T gives in-row
    inclusive prefixes, a strict-triangular 8x8 matvec gives row offsets;
    the row-major global prefix G then yields the index as a pure
    count(G <= t) reduction — no dynamic indexing anywhere.
    """
    i = pl.program_id(0)
    tile = prio_ref[0]                                   # (8, SUB)
    sub = tile.shape[1]
    t = targets_ref[i]
    pref = jax.lax.dot_general(
        tile, _tril(sub), (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)              # in-row prefixes
    row_sums = jnp.sum(tile, axis=1, keepdims=True)      # (8, 1)
    offs = jax.lax.dot_general(
        _tril(tile.shape[0], strict=True), row_sums,
        (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)              # (8, 1) exclusive
    g = pref + offs                                      # row-major prefix
    local = jnp.sum((g <= t).astype(jnp.int32))
    out_ref[i] = jnp.minimum(local, tile.shape[0] * sub - 1)


# pallas imports deferred so CPU-only environments that never touch the
# TPU path don't pay for (or break on) experimental imports at module load
pl = None
pltpu = None


def _ensure_pallas() -> None:
    global pl, pltpu
    if pl is None:
        from jax.experimental import pallas as _pl
        from jax.experimental.pallas import tpu as _pltpu

        pl = _pl
        pltpu = _pltpu


@functools.partial(jax.jit,
                   static_argnames=("batch_size", "block", "interpret"))
def hierarchical_sample(priority: jax.Array, key: jax.Array,
                        batch_size: int, block: int = DEFAULT_BLOCK,
                        interpret: bool = False
                        ) -> Tuple[jax.Array, jax.Array]:
    """Proportional sample of ``batch_size`` indices from an (N,) priority
    vector (zeros = empty rows, never drawn).  Returns (idx, probs).
    """
    _ensure_pallas()
    n = priority.shape[0]
    sub = block // 8  # lanes per sublane row; superblock = 8 x sub = block
    assert block % 8 == 0 and sub % 128 == 0, block
    num_blocks = -(-n // block)
    padded = num_blocks * block
    p = priority
    if padded != n:
        p = jnp.pad(priority, (0, padded - n))
    p3 = p.reshape(num_blocks, 8, sub)

    # phase 1+2 (XLA): block reduction + tiny top-level inverse CDF
    block_sums = p3.sum(axis=(1, 2))
    block_cdf = jnp.cumsum(block_sums)
    total = block_cdf[-1]
    u = jax.random.uniform(key, (batch_size,)) * total
    bid = jnp.clip(jnp.searchsorted(block_cdf, u, side="right"),
                   0, num_blocks - 1).astype(jnp.int32)
    prev = jnp.where(bid > 0, block_cdf[bid - 1], 0.0)
    targets = (u - prev).astype(jnp.float32)

    # phase 3 (Pallas): per-draw in-superblock scan; one (8, sub) DMA per
    # draw.  Each grid step emits one scalar, so the output lives whole in
    # SMEM and every step writes its own slot (sequential grid => no write
    # races).
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block_ids, targets
        grid=(batch_size,),
        in_specs=[
            pl.BlockSpec((1, 8, sub), lambda i, bids, tgts: (bids[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
    )
    local = pl.pallas_call(
        _draw_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch_size,), jnp.int32),
        interpret=interpret,
        # the kernel's name in a device trace, pinned against a rename of
        # the Python function (today's default, so the kernel is unchanged)
        name="_draw_kernel",
    )(bid, targets, p3)

    idx = jnp.minimum(bid * block + local, n - 1)
    # fp-order disagreement between the XLA block reduction and the MXU
    # prefix sums can (rarely, at a block's upper CDF edge) clamp a draw
    # onto a zero-priority row; a 0-prob draw would blow up its IS weight
    # and let the priority write-back make an empty row drawable, so remap
    # those draws to the max-priority row instead.
    fallback = jnp.argmax(priority).astype(jnp.int32)
    idx = jnp.where(priority[idx] > 0, idx, fallback)
    probs = priority[idx] / jnp.maximum(total, 1e-12)
    return idx, probs


def flat_sample(priority: jax.Array, key: jax.Array, batch_size: int
                ) -> Tuple[jax.Array, jax.Array]:
    """The flat XLA reference scheme (device_per.py per_sample's search),
    exposed here so tests can pin hierarchical == flat on shared
    uniforms."""
    cdf = jnp.cumsum(priority)
    total = cdf[-1]
    u = jax.random.uniform(key, (batch_size,)) * total
    idx = jnp.clip(jnp.searchsorted(cdf, u, side="right"),
                   0, priority.shape[0] - 1).astype(jnp.int32)
    return idx, priority[idx] / jnp.maximum(total, 1e-12)
