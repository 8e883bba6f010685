"""Pallas TPU kernels: the gated delta rule's window (models/gated_delta.py
``gated_delta_chunked``) with every intermediate of a chunk in fast memory.

The XLA form of that recurrence passes ``A``, the inverse's powers, ``T``,
``W``, ``U`` and the stacked chunk states through HBM as float32 ``(chunk-heads,
64, 64)`` / ``(.., 64, 128)`` arrays, thousands of small ops for 2.5 ms of
arithmetic an update (PERF.md section 5).  Here one grid step is one chunk of
``HEADS_A_STEP`` KEY heads, each with the ``r`` value heads it serves, the
chunk axis sequential and the ``(d_k, d_v)`` float32 states carried in VMEM
across it:

- a key head's ``r`` value heads are STACKED along the rows: ``N = r L`` rows,
  so with ``r`` = 2 and ``L`` = 64 every chunk matrix (``K K^T``, ``Q K^T``,
  the decay, ``A``, ``T``) is one 128 x 128 tile, block diagonal by head (one
  pass of the MXU where two 64 x 64 products would each half fill it);
- ``T = (I + A)^-1`` BLOCK BY BLOCK, the rule of ``unit_lower_inverse_by_blocks``
  (every intermediate a true inverse of diagonal blocks), one product deep a
  level (``_inverse_by_blocks``), every product of it a float32 product
  (``_dot_f32``), as the XLA form's are;
- q, k, v are read in the layout ``gdn_window`` holds them, ``(b, T, heads x
  d)``, an ``(L, d)`` block a head through the ``BlockSpec`` index maps; g and
  beta arrive as rows ``(b, h_k, chunks, r L)`` (a megabyte: the caller
  transposes them in XLA and autodiff transposes their cotangents back);
- the forward also writes what the backward needs and cannot cheaply make
  again: each chunk's entry states in the compute dtype (as ``_scan_chunks``
  hands them on) and ``T`` in float32 (the heads' ``L x L`` diagonal blocks
  side by side, not the zeros around them).  The backward kernel walks the
  chunks in reverse with the states' cotangents carried in VMEM, recomputes
  the chunk's products from q, k, v, g, beta and reads ``T`` and the entry
  states.

Precision is the XLA form's: g, beta, gamma, the decays, the inverse and the
carried state float32; operands of the products in the compute dtype,
accumulation float32.

SET-UP (PERF.md section 6, PR 33 / PR 35): jax caches no ``pallas_call``
tracing or lowering between call sites, so each kernel is launched through ONE
primitive whose lowering is emitted out of line (``_launcher``): a program with
three delta blocks x (target, online, recomputed, backward) traces each body
once and its lowering holds each body once (tests/test_gdn_kernel.py).  The
bodies loop over chunks by the grid, never by Python over positions or chunks;
what Python writes out is the key heads of a step, their value heads and the
inverse's log2(L) levels.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

# pallas imports deferred: the ops/pallas_sampling.py convention
pl = None
pltpu = None

F32 = jnp.float32

# key heads a grid step: their chains of products are independent and the
# body is one basic block, so one head's run while another's are in flight;
# the bodies emit their work stage by stage over the step's heads, because the
# scheduler keeps close to program order (a forward call at row 21's shapes:
# 4.87 / 3.30 / 3.03 / 2.93 ms at 1 / 2 / 4 / 8 heads; 3.94 at 4 with each
# head's work emitted whole; my chip runs, PR 35).  The body a program's
# set-up traces and lowers grows with it
HEADS_A_STEP = 4


def _ensure_pallas() -> None:
    global pl, pltpu
    if pl is None:
        from jax.experimental import pallas as _pl
        from jax.experimental.pallas import tpu as _pltpu

        pl = _pl
        pltpu = _pltpu


def fits(chunk: int, r: int, d_k: int, d_v: int) -> bool:
    """Whether the kernels' tiles hold these shapes: a chunk a power of two
    of at least 16 positions (the inverse's levels; a bf16 tile's 16 rows),
    the stacked heads a whole number of 128-row tiles, both head widths whole
    128-lane tiles."""
    return (chunk >= 16 and chunk & (chunk - 1) == 0
            and (r * chunk) % 128 == 0 and d_k % 128 == 0 and d_v % 128 == 0)


def _dot(a, b, ca: int = 1, cb: int = 0):
    """``a`` contracted over its dim ``ca`` with ``b`` over its dim ``cb``,
    float32 accumulation."""
    return jax.lax.dot_general(a, b, (((ca,), (cb,)), ((), ())),
                               preferred_element_type=F32)


def _dot_f32(a, b, ca: int = 1, cb: int = 0):
    """``_dot`` of float32 operands as a float32 product
    (``Precision.HIGHEST``, Mosaic's ``contract_precision<fp32>``): the
    inverse's levels and its cotangent, which the XLA form computes at that
    precision too (``unit_lower_inverse`` / ``_inverse_bwd``)."""
    return jax.lax.dot_general(a, b, (((ca,), (cb,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=F32)


def _inverse_by_blocks(As, x, L: int):
    """``(I + A)^-1`` for each ``A`` (N, N) of ``As``, strictly lower
    triangular inside each head's L x L diagonal block and zero elsewhere;
    ``x`` = row index XOR column index (two positions lie in one aligned block
    of 2h exactly where ``x < 2h``, in different halves of it where ``x >=
    h``).  With ``T`` the inverse of the h x h diagonal blocks and ``M`` the
    mask of every 2h x 2h block's lower left quarter, the next level is ``T -
    T (A * M) T``; ``T`` is block diagonal and ``M`` constant over a block, so
    ``T (A * M) = (T A) * M`` and with ``F = T A`` carried beside ``T``

        Z = F * M,   T <- T - Z T,   F <- F - Z F

    ONE product deep a level where the plain rule is two (the chain of
    products is what the kernel waits for: PERF.md section 6, PR 35).  From
    ``T = I``, ``F = A``, h = 1, 2 .. L / 2: log2(L) levels written out, not
    looped, so that the scheduler runs one key head's products while
    another's are in flight."""
    eye = (x == 0).astype(F32)
    Ts, Fs, h = [None] * len(As), list(As), 1
    while h < L:            # a level of EVERY head, then the next level
        for n, (A, T, F) in enumerate(zip(As, Ts, Fs)):
            Z = jnp.where((x >= h) & (x < 2 * h), F, 0.0)
            Ts[n] = eye - jnp.where(x == 1, A, 0.0) if h == 1 \
                else T - _dot_f32(Z, T)
            if 2 * h < L:
                Fs[n] = F - _dot_f32(Z, F)
        h *= 2
    return Ts


def _masks(N: int, L: int):
    row = jax.lax.broadcasted_iota(jnp.int32, (N, N), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (N, N), 1)
    x = row ^ col
    same = x < L                            # one head's block
    le = same & (col <= row)
    return dict(x=x, same=same, le=le, lt=le & (x != 0), eye=x == 0)


def _chunk_terms(mask, q, k, v, g_row, b_row, L: int, r: int):
    """What the forward and the backward both need of one chunk of one key
    head: q, k (L, d_k) in the compute dtype, v (L, r d_v) float32, g_row and
    b_row (1, N) float32 (head-major).  Everything with N = r L rows, the
    value heads stacked."""
    cd = k.dtype
    dv = v.shape[1] // r
    le, eye = mask["le"], mask["eye"]
    gamma = jnp.sum(jnp.where(le, g_row, 0.0), 1, keepdims=True)   # (N, 1)
    gamma_row = jnp.sum(jnp.where(eye, gamma, 0.0), 0, keepdims=True)
    gamma_end = jnp.sum(jnp.where(mask["same"], g_row, 0.0), 1, keepdims=True)
    beta = jnp.sum(jnp.where(eye, b_row, 0.0), 1, keepdims=True)
    D = jnp.exp(jnp.where(le, gamma - gamma_row, -jnp.inf))
    k2, q2 = (jnp.concatenate([t] * r, axis=0) for t in (k, q))   # (N, d_k)
    v2 = jnp.concatenate([v[:, j * dv:(j + 1) * dv] for j in range(r)], 0)
    KK, QK = _dot(k2, k2, 1, 1), _dot(q2, k2, 1, 1)
    kf, qf = k2.astype(F32), q2.astype(F32)
    in_chunk, to_end = jnp.exp(gamma), jnp.exp(gamma_end - gamma)
    return dict(
        beta=beta, D=D, KK=KK, QK=QK, k2=k2, q2=q2, v2=v2, kf=kf, qf=qf,
        in_chunk=in_chunk, to_end=to_end, chunk_decay=jnp.exp(gamma_end),
        A=jnp.where(mask["lt"], beta * KK * D, 0.0),
        Kb=(kf * (beta * in_chunk)).astype(cd), Vb=(v2 * beta).astype(cd),
        K_end=(kf * to_end).astype(cd), q_in=(qf * in_chunk).astype(cd),
        P=(QK * D).astype(cd))


def _terms_of_step(c, q_ref, k_ref, v_ref, g_ref, b_ref, L: int, r: int):
    """(the masks, ``_chunk_terms`` of each key head of the grid step)."""
    hb = g_ref.shape[1]
    dk, dv = q_ref.shape[2] // hb, v_ref.shape[2] // (hb * r)
    mask = _masks(r * L, L)
    return mask, [_chunk_terms(
        mask, q_ref[0, :, p * dk:(p + 1) * dk], k_ref[0, :, p * dk:(p + 1) * dk],
        v_ref[0, :, p * r * dv:(p + 1) * r * dv], g_ref[0, p, pl.ds(c, 1)],
        b_ref[0, p, pl.ds(c, 1)], L, r) for p in range(hb)]


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref,
                o_ref, S_ref, entry_ref, T_ref, *, L: int, r: int):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _zero_state():
        S_ref[...] = jnp.zeros_like(S_ref)

    mask, heads = _terms_of_step(c, q_ref, k_ref, v_ref, g_ref, b_ref, L, r)
    Ts = _inverse_by_blocks([m["A"] for m in heads], mask["x"], L)
    dv = v_ref.shape[2] // (len(heads) * r)
    cd = heads[0]["k2"].dtype
    value_heads = [(p, j, slice(j * L, (j + 1) * L))
                   for p in range(len(heads)) for j in range(r)]
    # stage by stage over ALL the step's heads (HEADS_A_STEP's note)
    for p, T in enumerate(Ts):      # block diagonal: the heads' blocks,
        T_ref[0, 0, p] = sum(       # side by side (L, N)
            T[j * L:(j + 1) * L] for j in range(r))
    WU = [(_dot(T.astype(cd), m["Kb"]).astype(cd), _dot(T.astype(cd), m["Vb"]))
          for m, T in zip(heads, Ts)]
    S = [S_ref[0, p * r + j] for p, j, _ in value_heads]
    Sb = [t.astype(cd) for t in S]
    for (p, j, _), t in zip(value_heads, Sb):
        entry_ref[0, 0, p * r + j] = t
    V_new = [(WU[p][1][rows] - _dot(WU[p][0][rows], t)).astype(cd)
             for (p, j, rows), t in zip(value_heads, Sb)]
    for n, (p, j, rows) in enumerate(value_heads):
        S_ref[0, p * r + j] = (
            heads[p]["chunk_decay"][j * L:j * L + 1] * S[n]
            + _dot(heads[p]["K_end"][rows], V_new[n], 0, 0))
    o_state = [_dot(heads[p]["q_in"][rows], t)
               for (p, j, rows), t in zip(value_heads, Sb)]
    for p, m in enumerate(heads):
        o = jnp.concatenate(o_state[p * r:(p + 1) * r], 0) + _dot(
            m["P"], jnp.concatenate(V_new[p * r:(p + 1) * r], 0))
        for j in range(r):
            h = p * r + j
            o_ref[0, :, h * dv:(h + 1) * dv] = o[j * L:(j + 1) * L]


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, entry_ref, T_ref, do_ref,
                dS_end_ref, dq_ref, dk_ref, dv_ref, dg_ref, db_ref, dS_ref,
                *, L: int, r: int):
    step = pl.program_id(2)
    c = pl.num_programs(2) - 1 - step       # chunks in reverse

    @pl.when(step == 0)
    def _last_state():
        dS_ref[...] = dS_end_ref[0]

    mask, heads = _terms_of_step(c, q_ref, k_ref, v_ref, g_ref, b_ref, L, r)
    dk_w, dv = q_ref.shape[2] // len(heads), v_ref.shape[2] // (len(heads) * r)
    for p, m in enumerate(heads):
        _bwd_head(p, c, m, mask, entry_ref, T_ref, do_ref, dq_ref, dk_ref,
                  dv_ref, dg_ref, db_ref, dS_ref, L, r, dk_w, dv)


def _bwd_head(p, c, m, mask, entry_ref, T_ref, do_ref, dq_ref, dk_ref, dv_ref,
              dg_ref, db_ref, dS_ref, L, r, dk_w, dv):
    """One key head's chunk of the backward: key head ``p`` of the step."""
    cd = m["k2"].dtype
    beta, D, KK, QK = m["beta"], m["D"], m["KK"], m["QK"]
    T = jnp.where(mask["same"],     # the blocks back on the diagonal
                  jnp.concatenate([T_ref[0, 0, p]] * r, 0), 0.0)
    Tm = T.astype(cd)
    W = _dot(Tm, m["Kb"]).astype(cd)
    U = _dot(Tm, m["Vb"])
    do = jnp.concatenate(
        [do_ref[0, :, (p * r + j) * dv:(p * r + j + 1) * dv]
         for j in range(r)], 0).astype(cd)

    # per value head: the chunk's entry state, V', and what the state's
    # cotangent hands the chunk
    V_new, dV_state, dQ_in, dK_end, d_decay = [], [], [], [], []
    entry, dS_next = [], []
    for j in range(r):
        rows = slice(j * L, (j + 1) * L)
        Sb = entry_ref[0, 0, p * r + j]
        dS = dS_ref[p * r + j]
        dSb = dS.astype(cd)
        V_j = (U[rows] - _dot(W[rows], Sb)).astype(cd)
        dV_state.append(_dot(m["K_end"][rows], dSb))
        dQ_in.append(_dot(do[rows], Sb, 1, 1))
        dK_end.append(_dot(V_j, dSb, 1, 1))
        d_decay.append(jnp.broadcast_to(jnp.sum(jnp.sum(
            dS * Sb.astype(F32), 1, keepdims=True), 0, keepdims=True),
            (L, 1)))
        V_new.append(V_j)
        entry.append(Sb)
        dS_next.append(dS)
    V_new = jnp.concatenate(V_new, 0)
    dV = _dot(m["P"], do, 0, 0) + jnp.concatenate(dV_state, 0)    # = dU
    dVb = dV.astype(cd)
    dW = []
    for j in range(r):
        rows = slice(j * L, (j + 1) * L)
        dW.append(-_dot(dVb[rows], entry[j], 1, 1))
        dS_ref[p * r + j] = (m["chunk_decay"][j * L:j * L + 1] * dS_next[j]
                             + _dot(m["q_in"][rows], do[rows], 0, 0)
                             - _dot(W[rows], dVb[rows], 0, 0))
    dWb = jnp.concatenate(dW, 0).astype(cd)
    dQ_in, dK_end, d_decay = (jnp.concatenate(t, 0)
                              for t in (dQ_in, dK_end, d_decay))

    # through W = T Kb, U = T Vb and T = (I + A)^-1: dA = -T^T dT T^T
    dT = _dot(dVb, m["Vb"], 1, 1) + _dot(dWb, m["Kb"], 1, 1)
    dKb, dVb_in = _dot(Tm, dWb, 0, 0), _dot(Tm, dVb, 0, 0)
    dA = jnp.where(mask["lt"],
                   -_dot_f32(_dot_f32(T, dT, 0, 0), T, 1, 1), 0.0)
    dP = jnp.where(mask["le"], _dot(do, V_new, 1, 1), 0.0)
    dA_D = dA * D
    dKK, dQK = dA_D * beta, dP * D
    E = dKK * KK + dQK * QK                 # d(decay) * decay
    dKKb, dQKb = dKK.astype(cd), dQK.astype(cd)
    k2, q2, kf = m["k2"], m["q2"], m["kf"]
    in_chunk, to_end = m["in_chunk"], m["to_end"]
    eye = mask["eye"]
    rowsum = lambda t: jnp.sum(t, 1, keepdims=True)
    to_row = lambda t: jnp.sum(jnp.where(eye, t, 0.0), 0, keepdims=True)
    dq2 = _dot(dQKb, k2) + dQ_in * in_chunk
    dk2 = (_dot(dKKb, k2) + _dot(dKKb, k2, 0, 0) + _dot(dQKb, q2, 0, 0)
           + dKb * (beta * in_chunk) + dK_end * to_end)
    dv2 = dVb_in * beta
    d_beta = (rowsum(dA_D * KK) + rowsum(dVb_in * m["v2"])
              + rowsum(dKb * kf) * in_chunk)
    through_end = rowsum(dK_end * kf) * to_end              # (N, 1)
    d_gamma = (rowsum(E) + rowsum(dKb * kf) * (beta * in_chunk)
               + rowsum(dQ_in * m["qf"]) * in_chunk - through_end)
    # gamma at a head's last position: every K_end row's and the decay's
    d_end = (jnp.sum(jnp.where(mask["same"], to_row(through_end), 0.0), 1,
                     keepdims=True) + d_decay * m["chunk_decay"])   # (N, 1)
    d_gamma_row = to_row(d_gamma) - jnp.sum(E, 0, keepdims=True)
    # g -> gamma is a running sum inside the head: its transpose, row form
    d_gamma = jnp.sum(jnp.where(eye, d_gamma_row, 0.0), 1, keepdims=True)
    dg_ref[0, p, pl.ds(c, 1)] = jnp.sum(
        jnp.where(mask["le"], d_gamma, 0.0), 0, keepdims=True) + to_row(d_end)
    db_ref[0, p, pl.ds(c, 1)] = to_row(d_beta)
    dq_ref[0, :, p * dk_w:(p + 1) * dk_w] = sum(
        dq2[j * L:(j + 1) * L] for j in range(r))
    dk_ref[0, :, p * dk_w:(p + 1) * dk_w] = sum(
        dk2[j * L:(j + 1) * L] for j in range(r))
    for j in range(r):
        h = p * r + j
        dv_ref[0, :, h * dv:(h + 1) * dv] = dv2[j * L:(j + 1) * L]


def _geometry(k, v, g_rows):
    b, T, G, dk = k.shape
    hv, dv = v.shape[2:]
    r, nc, N = hv // G, g_rows.shape[2], g_rows.shape[3]
    L = N // r
    assert r * G == hv and nc * L == T and g_rows.shape[:2] == (b, G), (
        k.shape, v.shape, g_rows.shape)
    return b, T, G, dk, hv, dv, r, nc, N, L


def _specs(G, dk, dv, r, nc, N, L, chunk_of):
    """(key heads a grid step, the block specs of: q or k, v or o, g or beta
    rows, the states, the entry states, T), the chunk of grid step c being
    ``chunk_of(c)``."""
    hb = math.gcd(G, HEADS_A_STEP)
    at = lambda *idx: pl.BlockSpec(*idx)
    return hb, (
        at((1, L, hb * dk), lambda b, h, c: (b, chunk_of(c), h)),
        at((1, L, hb * r * dv), lambda b, h, c: (b, chunk_of(c), h)),
        at((1, hb, nc, N), lambda b, h, c: (b, h, 0, 0)),
        at((1, hb * r, dk, dv), lambda b, h, c: (b, h, 0, 0)),
        at((1, 1, hb * r, dk, dv), lambda b, h, c: (b, chunk_of(c), h, 0, 0)),
        at((1, 1, hb, L, N), lambda b, h, c: (b, chunk_of(c), h, 0, 0)))


def _fwd_call(q, k, v, g_rows, beta_rows, *, interpret: bool):
    """q, k (b, T, h_k, d_k) float32 or the compute dtype; v (b, T, h_v,
    d_v); g_rows, beta_rows (b, h_k, chunks, r L) float32.  Returns (o (b, T,
    h_v, d_v) float32, the last state (b, h_v, d_k, d_v) float32, and for the
    backward: q and k as the kernel read them, each chunk's entry states (b,
    chunks, h_v, d_k, d_v) in the compute dtype, T (b, chunks, h_k, L, N)
    float32: a key head's value heads' L x L blocks side by side)."""
    _ensure_pallas()
    b, T, G, dk, hv, dv, r, nc, N, L = _geometry(k, v, g_rows)
    cd = q.dtype
    q3, k3 = q.reshape(b, T, G * dk), k.reshape(b, T, G * dk)
    v3 = v.astype(F32).reshape(b, T, hv * dv)
    hb, (qk, vo, rows, state, entry, inv) = _specs(G, dk, dv, r, nc, N, L,
                                                   lambda c: c)
    o, S, entries, Tm = pl.pallas_call(
        functools.partial(_fwd_kernel, L=L, r=r),
        grid=(b, G // hb, nc),
        in_specs=[qk, qk, vo, rows, rows],
        out_specs=[vo, state, entry, inv],
        out_shape=[jax.ShapeDtypeStruct((b, T, hv * dv), F32),
                   jax.ShapeDtypeStruct((b, hv, dk, dv), F32),
                   jax.ShapeDtypeStruct((b, nc, hv, dk, dv), cd),
                   jax.ShapeDtypeStruct((b, nc, G, L, N), F32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="gdn_chunk_fwd",
    )(q3, k3, v3, g_rows, beta_rows)
    return o.reshape(b, T, hv, dv), S, entries, Tm


def _bwd_call(q, k, v, g_rows, beta_rows, entries, Tm, do, dS, *,
              interpret: bool):
    """The cotangents of (q, k, v, g_rows, beta_rows), float32, from those of
    ``o`` (b, T, h_v, d_v) and of the last state (b, h_v, d_k, d_v)."""
    _ensure_pallas()
    b, T, G, dk, hv, dv, r, nc, N, L = _geometry(k, v, g_rows)
    q3, k3 = q.reshape(b, T, G * dk), k.reshape(b, T, G * dk)
    v3 = v.astype(F32).reshape(b, T, hv * dv)
    do3 = do.astype(F32).reshape(b, T, hv * dv)
    hb, (qk, vo, rows, state, entry, inv) = _specs(
        G, dk, dv, r, nc, N, L, lambda c: nc - 1 - c)
    dq, dk_, dv_, dg, dbeta = pl.pallas_call(
        functools.partial(_bwd_kernel, L=L, r=r),
        grid=(b, G // hb, nc),
        in_specs=[qk, qk, vo, rows, rows, entry, inv, vo, state],
        out_specs=[qk, qk, vo, rows, rows],
        out_shape=[jax.ShapeDtypeStruct((b, T, G * dk), F32),
                   jax.ShapeDtypeStruct((b, T, G * dk), F32),
                   jax.ShapeDtypeStruct((b, T, hv * dv), F32),
                   jax.ShapeDtypeStruct((b, G, nc, N), F32),
                   jax.ShapeDtypeStruct((b, G, nc, N), F32)],
        scratch_shapes=[pltpu.VMEM((hb * r, dk, dv), F32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="gdn_chunk_bwd",
    )(q3, k3, v3, g_rows, beta_rows, entries, Tm, do3, dS.astype(F32))
    return (dq.reshape(b, T, G, dk), dk_.reshape(b, T, G, dk),
            dv_.reshape(b, T, hv, dv), dg, dbeta)


def _launcher(name: str, call, out_avals):
    """A kernel's launcher as a PRIMITIVE whose lowering is emitted out of
    line: a program's call sites are then equations of one primitive at one
    set of shapes, which jax lowers ONCE a module and calls from every site
    (``mlir._cached_lowering``), so the kernel's body is traced and lowered
    once a program however many blocks and passes launch it.  A module-level
    ``jax.jit`` does not give that: every dead-code pass over an enclosing
    jaxpr (``jax.checkpoint``'s, partial evaluation's) re-wraps the inner
    jaxpr in a NEW object, pjit keys its lowering on that object, and three
    delta blocks in a train step came out as three copies of the forward
    body (PERF.md section 6, PR 35).  Never differentiated or batched, and
    with no sharding rule (``gated_delta_chunked`` takes the XLA form where
    there is more than one device): the ``custom_vjp`` below is the only
    caller.  ``register_lowering(inline=False)`` and ``lower_fun`` are jax
    internals, checked against jax 0.9.0; tests/test_gdn_kernel.py holds the
    lowering they give."""
    from jax.extend import core as jex_core
    from jax.interpreters import mlir

    prim = jex_core.Primitive(name)
    prim.multiple_results = True
    prim.def_abstract_eval(out_avals)
    prim.def_impl(jax.jit(call, static_argnames="interpret"))
    mlir.register_lowering(
        prim, mlir.lower_fun(call, multiple_results=True), inline=False)
    return prim


def _fwd_avals(q, k, v, g_rows, beta_rows, *, interpret):
    from jax.core import ShapedArray

    b, T, G, dk, hv, dv, r, nc, N, L = _geometry(k, v, g_rows)
    return [ShapedArray((b, T, hv, dv), F32), ShapedArray((b, hv, dk, dv), F32),
            ShapedArray((b, nc, hv, dk, dv), q.dtype),
            ShapedArray((b, nc, G, L, N), F32)]


def _bwd_avals(q, k, v, g_rows, beta_rows, entries, Tm, do, dS, *, interpret):
    from jax.core import ShapedArray

    return [ShapedArray(t.shape, F32) for t in (q, k, v, g_rows, beta_rows)]


_FWD = _launcher("gdn_chunk_fwd", _fwd_call, _fwd_avals)
_BWD = _launcher("gdn_chunk_bwd", _bwd_call, _bwd_avals)


def _make_chunk(interpret: bool):
    @jax.custom_vjp
    def chunk(q, k, v, g_rows, beta_rows):
        return tuple(_FWD.bind(q, k, v, g_rows, beta_rows,
                               interpret=interpret)[:2])

    def fwd(q, k, v, g_rows, beta_rows):
        o, S, entries, Tm = _FWD.bind(q, k, v, g_rows, beta_rows,
                                      interpret=interpret)
        return (o, S), (q, k, v, g_rows, beta_rows, entries, Tm)

    def bwd(res, cts):
        # (runs under the name stack the forward was bound in: the scopes
        # ``model.gdn`` / ``gdn.chunk`` stand on the backward kernel's path)
        dq, dk, dv, dg, dbeta = _BWD.bind(*res, *cts, interpret=interpret)
        cd = res[0].dtype
        return dq.astype(cd), dk.astype(cd), dv, dg, dbeta

    chunk.defvjp(fwd, bwd)
    return chunk


_CHUNK = {interpret: _make_chunk(interpret) for interpret in (False, True)}


def gated_delta_window(q, k, v, g, beta, chunk: int, cd, interpret: bool):
    """``gated_delta_chunked``'s contract through the kernels: q, k (b, T,
    h_k, d_k); v (b, T, h_v, d_v); g, beta (b, T, h_v) float32; T whole
    chunks.  Returns (o (b, T, h_v, d_v) float32, the state after the last
    position (b, h_v, d_k, d_v) float32)."""
    b, T, G, _ = k.shape
    hv = v.shape[2]
    r, nc = hv // G, T // chunk
    assert nc * chunk == T and r * G == hv, (T, chunk, hv, G)
    rows = lambda t: jnp.moveaxis(          # (b, h_k, chunks, r L)
        t.astype(F32).reshape(b, nc, chunk, G, r), (3, 1, 4, 2),
        (1, 2, 3, 4)).reshape(b, G, nc, r * chunk)
    return _CHUNK[interpret](q.astype(cd), k.astype(cd), v.astype(F32),
                             rows(g), rows(beta))
