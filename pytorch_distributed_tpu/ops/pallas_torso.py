"""Pallas TPU kernels: the fused dqn-cnn torso as hand-tiled MXU matmuls.

XLA's conv lowering of the Nature CNN leaves the MXU underfilled: the
4/32/64-wide conv channels do not fill 128 lanes (the flagship learner's
``mfu`` is 13.7 %, and the first convolution's forward and backward are
half of ``train.online``: ledger, PR 27, ``apex_pong.learner_only``;
PERF.md section 5), and the compiler re-tiles activations between conv
ops.  This module is the opt-in alternative (``pallas_torso``, default
off; what it buys over XLA's convolutions on the chip is not measured:
no benchmark cell turns it on, ROADMAP queue 3): every GEMM in the torso — the three im2col'd convolutions, the FC-512
and the Q head — runs as ONE hand-tiled Pallas kernel each, with the
contraction and lane dimensions padded to the 128-lane grid ONCE at the
kernel boundary instead of re-tiled between every XLA op.  Patch
extraction (im2col) stays in XLA: strided slices are layout-friendly
and differentiate for free, so the kernel surface is exactly the GEMMs
the MXU runs.

Differentiability: the matmul kernel carries a ``jax.custom_vjp`` whose
backward is two more invocations of the same kernel (dx = g @ w^T,
dw = x^T @ g), so the whole torso trains through Pallas — forward AND
backward GEMMs bypass the re-tiling.

Numerics: accumulation is fp32 on the MXU (``preferred_element_type``),
outputs rounded to the compute dtype between layers, mirroring XLA's
bf16 conv behaviour; parity vs the XLA reference is tolerance-based
(tests/test_pallas_torso.py, fwd + grads, bf16 and fp32), not bitwise —
fp summation order inside a hand-tiled GEMM differs from XLA's.

CPU story: ``interpret=True`` runs the same kernels under the Pallas
interpreter so the tier-1 parity tests execute on a CPU; the compiled
kernels are checked on the chip by tools/kernel_check.py.  The
production gate (factory.select_torso) only engages the kernel on a
TPU backend (or under the explicit ``pallas_interpret`` knob) and
downgrades LOUDLY otherwise.  Knobs: config.LearnerPerfParams
(``TPU_APEX_MXU_PALLAS_TORSO`` / ``TPU_APEX_MXU_PALLAS_INTERPRET``).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

# pallas imports deferred so CPU-only environments that never touch the
# kernels don't pay for (or break on) experimental imports at module
# load — the ops/pallas_sampling.py convention
pl = None
pltpu = None


def _ensure_pallas() -> None:
    global pl, pltpu
    if pl is None:
        from jax.experimental import pallas as _pl
        from jax.experimental.pallas import tpu as _pltpu

        pl = _pl
        pltpu = _pltpu


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _mm_kernel(x_ref, w_ref, o_ref):
    """One grid step = one (TM, TK) x-tile @ one (TK, Np) w-tile,
    accumulated into the (TM, Np) output tile across the contraction
    grid axis (the output block is revisited for every k-step; fp32
    accumulation on the MXU)."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[:] = jnp.zeros_like(o_ref)

    o_ref[:] += jnp.dot(x_ref[:], w_ref[:],
                        preferred_element_type=jnp.float32)


# one (M-tile, K-tile) block per grid step.  BOTH dims are tiled: the
# backward dw = x^T @ g GEMM contracts over B*OH*OW rows (51k at the
# production batch 128 on Conv_0), so an untiled contraction dim would
# stage ~26 MB x-tiles and blow the ~16 MB VMEM budget on exactly the
# TPU the kernel targets.  N is NOT tiled: the resident set per step is
# (TM, TK) + (TK, Np) + (TM, Np) — ~1.5 MB forward at the FC-512's
# Np=512, but the custom-VJP backward rotates the dims, and dx = g @ w^T
# for the FC-512 has Np = 3,200: a 6.5 MB f32 weight tile plus a 1.6 MB
# output tile.  That compiles and runs on a v5e under jax 0.9.0
# (tools/kernel_check.py, chip run of ISSUE 21) with little room to
# spare; a wider layer would need an N grid axis.
_TILE_M = 128
_TILE_K = 512
_LANES = 128


def _mm(x: jax.Array, w: jax.Array, interpret: bool) -> jax.Array:
    """Padded, tiled ``x (M, K) @ w (K, N) -> (M, N) fp32`` through the
    Pallas kernel.  Pads K and N up to the 128-lane grid and M up to the
    tile height ONCE here — the re-tiling XLA would otherwise re-derive
    between ops happens exactly once per GEMM."""
    _ensure_pallas()
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    tk = min(_TILE_K, _round_up(k, _LANES))
    kp, np_ = _round_up(k, tk), _round_up(n, _LANES)
    mp = _round_up(m, _TILE_M)
    if (mp, kp) != (m, k):
        x = jnp.pad(x, ((0, mp - m), (0, kp - k)))
    if (kp, np_) != (k, n):
        w = jnp.pad(w, ((0, kp - k), (0, np_ - n)))
    out = pl.pallas_call(
        _mm_kernel,
        grid=(mp // _TILE_M, kp // tk),
        in_specs=[
            pl.BlockSpec((_TILE_M, tk), lambda i, j: (i, j)),
            pl.BlockSpec((tk, np_), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((_TILE_M, np_), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
        name="_mm_kernel",   # pinned: see ops/pallas_sampling.py
    )(x, w)
    return out[:m, :n]


@functools.lru_cache(maxsize=4)
def make_mxu_matmul(interpret: bool = False):
    """A differentiable ``(x, w) -> x @ w`` whose forward and backward
    GEMMs all run through the hand-tiled kernel (custom VJP: dx = g @
    w^T, dw = x^T @ g).  Cached per interpret flag so repeated apply
    builds share one jaxpr identity."""

    @jax.custom_vjp
    def mm(x, w):
        return _mm(x, w, interpret)

    def fwd(x, w):
        return _mm(x, w, interpret), (x, w)

    def bwd(res, g):
        x, w = res
        g = g.astype(jnp.float32)
        dx = _mm(g, w.T.astype(jnp.float32), interpret).astype(x.dtype)
        dw = _mm(x.T.astype(jnp.float32), g, interpret).astype(w.dtype)
        return dx, dw

    mm.defvjp(fwd, bwd)
    return mm


def _patches(x: jax.Array, k: int, stride: int) -> jax.Array:
    """im2col: (B, H, W, C) -> (B, OH, OW, k*k*C) with patch features in
    (kh, kw, c) order — exactly ``kernel.reshape(k*k*C, out)``'s HWIO
    flattening, so the GEMM consumes the flax Conv kernel verbatim."""
    h, w = x.shape[1], x.shape[2]
    oh = (h - k) // stride + 1
    ow = (w - k) // stride + 1
    cols = []
    for di in range(k):
        for dj in range(k):
            cols.append(x[:, di:di + oh * stride:stride,
                          dj:dj + ow * stride:stride, :])
    return jnp.concatenate(cols, axis=-1)


# the Nature-CNN torso geometry the kernel serves (models/dqn_cnn.py):
# (flax param scope, kernel size, stride)
_CONV_LAYERS: Tuple[Tuple[str, int, int], ...] = (
    ("Conv_0", 8, 4), ("Conv_1", 4, 2), ("Conv_2", 3, 1),
)


def build_pallas_torso_apply(norm_val: float = 255.0,
                             compute_dtype=jnp.bfloat16,
                             interpret: bool = False):
    """The learner-side ``(variables, obs) -> q`` apply running the
    whole dqn-cnn torso through the MXU matmul kernel.

    Consumes the EXACT DqnCnnModel param tree (Conv_0/1/2 + Dense_0/1),
    so checkpoints, the ParamStore publication plane and the actors'
    standard apply are untouched — only the learner's train program
    swaps its torso.  Wired by factory._dqn_train_apply behind the
    ``pallas_torso`` knob."""
    mm = make_mxu_matmul(interpret)

    def apply_fn(variables, x):
        p = variables["params"]
        x = x.astype(compute_dtype) / jnp.asarray(norm_val,
                                                  dtype=compute_dtype)
        x = jnp.transpose(x, (0, 2, 3, 1))
        for name, k, stride in _CONV_LAYERS:
            ker = p[name]["kernel"]
            bias = p[name]["bias"]
            pat = _patches(x, k, stride)
            b, oh, ow, feat = pat.shape
            cout = ker.shape[-1]
            y = mm(pat.reshape(b * oh * ow, feat).astype(compute_dtype),
                   ker.reshape(feat, cout).astype(compute_dtype))
            y = y.astype(compute_dtype) + bias.astype(compute_dtype)
            x = jax.nn.relu(y).reshape(b, oh, ow, cout)
        b = x.shape[0]
        x = x.reshape(b, -1)
        y = mm(x, p["Dense_0"]["kernel"].astype(compute_dtype))
        x = jax.nn.relu(y.astype(compute_dtype)
                        + p["Dense_0"]["bias"].astype(compute_dtype))
        q = mm(x, p["Dense_1"]["kernel"].astype(compute_dtype))
        q = (q.astype(compute_dtype)
             + p["Dense_1"]["bias"].astype(compute_dtype))
        return q.astype(jnp.float32)

    return apply_fn
