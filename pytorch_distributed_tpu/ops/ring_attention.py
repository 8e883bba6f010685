"""Ring attention: sequence-parallel attention over the mesh's sp axis.

No reference equivalent (the reference has no attention at all); this is
the long-context backbone the TPU framework provides for transformer
models over long windows (models/dtqn.py): the sequence axis is sharded
across devices, each device holds one Q/K/V block, and K/V blocks rotate
around the ring via ``jax.lax.ppermute`` over ICI while every device
accumulates its Q block's attention with a numerically stable online
softmax (the blockwise/flash recipe of Liu et al. 2023, "Ring Attention
with Blockwise Transformers").  Compute of step s overlaps the transfer
of step s+1's blocks — XLA pipelines the ppermute against the matmuls —
so the ring hides ICI latency behind MXU work.

Causality across blocks is resolved by carrying each K/V block's global
offset around the ring with it: a (Tq_local, Tk_local) position mask is
rebuilt per step from the query shard's offset and the visiting block's
offset.

``ring_attention`` is the sharded entry point (shard_map over an existing
mesh); ``full_attention`` is the single-device reference both tests and
small models use.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def full_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   causal: bool = True,
                   key_pad_mask: Optional[jnp.ndarray] = None
                   ) -> jnp.ndarray:
    """Plain softmax attention, (B, H, T, D) in and out — the reference
    implementation ring_attention must match.  ``key_pad_mask`` (B, Tk)
    marks valid keys (models/dtqn.py masks unfilled acting-window slots
    with it)."""
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    tq, tk = scores.shape[-2], scores.shape[-1]
    if causal:
        mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        scores = jnp.where(mask, scores, NEG_INF)
    if key_pad_mask is not None:
        scores = jnp.where(key_pad_mask[:, None, None, :], scores, NEG_INF)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v)


def _ring_body(q, k, v, *, axis_name: str, causal: bool, num_blocks: int):
    """Per-device shard_map body: online-softmax accumulation over the
    ring of K/V blocks.  The device's own block is folded in before the
    loop, so the ring rotates exactly num_blocks - 1 times and the visiting
    block's identity is derived from the step counter (nothing but K/V
    rides the ring)."""
    scale = q.shape[-1] ** -0.5
    tq = q.shape[2]
    tk = k.shape[2]
    my = jax.lax.axis_index(axis_name)
    B, H = q.shape[0], q.shape[1]

    q_pos = my * tq + jnp.arange(tq)                     # global q positions

    def fold(acc, k_blk, v_blk, blk_idx):
        m, l, o = acc
        k_pos = blk_idx * tk + jnp.arange(tk)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k_blk) * scale
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]
            scores = jnp.where(mask[None, None], scores, NEG_INF)
        s_max = jnp.max(scores, axis=-1)                 # (B, H, tq)
        m_new = jnp.maximum(m, s_max)
        p = jnp.exp(scores - m_new[..., None])
        # a row that has seen no unmasked key yet has m_new == NEG_INF and
        # exp(NEG_INF - NEG_INF) == 1 would accumulate garbage V; with the
        # own (causal-diagonal) block folded first this cannot happen for
        # equal q/k shards, but guard it rather than rely on the invariant
        p = jnp.where((m_new == NEG_INF)[..., None], 0.0, p)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        o_new = o * corr[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p,
                                                 v_blk)
        return m_new, l_new, o_new

    acc0 = (
        jnp.full((B, H, tq), NEG_INF, q.dtype),          # running max
        jnp.zeros((B, H, tq), q.dtype),                  # normalizer
        jnp.zeros_like(q),                               # output acc
    )
    acc = fold(acc0, k, v, my)                           # own block, step 0

    perm = [(i, (i + 1) % num_blocks) for i in range(num_blocks)]

    def step(carry, s):
        k_blk, v_blk, m, l, o = carry
        # rotate, then fold the block that just arrived (originally from
        # device (my - s) mod n)
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        m, l, o = fold((m, l, o), k_blk, v_blk,
                       (my - s) % num_blocks)
        return (k_blk, v_blk, m, l, o), None

    if num_blocks > 1:
        (_, _, m, l, o), _ = jax.lax.scan(
            step, (k, v, *acc), jnp.arange(1, num_blocks))
    else:
        m, l, o = acc
    return o / jnp.maximum(l[..., None], 1e-30)


def sharded_attention_call(body, q, k, v, mesh: Mesh, axis: str,
                           batch_axis: Optional[str]) -> jnp.ndarray:
    """Shared shard_map entry for the sequence-parallel strategies: T
    sharded over ``axis``, B optionally over ``batch_axis``; ``body`` is
    the per-device (q, k, v) -> out function (ring or Ulysses)."""
    bspec = batch_axis if (batch_axis and mesh.shape[batch_axis] > 1) \
        else None
    spec = P(bspec, None, axis, None)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return fn(q, k, v)


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   mesh: Mesh, axis: str = "sp", causal: bool = True,
                   batch_axis: Optional[str] = "dp") -> jnp.ndarray:
    """Sequence-parallel attention: (B, H, T, D) with T sharded over
    ``axis`` (and optionally B over ``batch_axis``).  Matches
    ``full_attention`` up to fp reduction order."""
    body = functools.partial(_ring_body, axis_name=axis, causal=causal,
                             num_blocks=mesh.shape[axis])
    return sharded_attention_call(body, q, k, v, mesh, axis, batch_axis)
