"""Pallas TPU kernels: the channel-gated delta rule's window
(models/gated_delta.py ``kda_chunked``) with every intermediate of a chunk in
fast memory.

The XLA form of that recurrence passes the pairwise ``(16, 16, d_k)`` decays
of every sub-block, the level-by-level assembly of ``K K^T`` and ``Q K^T``,
the inverse, ``W``, ``U`` and the stacked chunk states through HBM as
thousands of small float32 ops (PERF.md section 5).  Here one grid step is
one chunk of ``HEADS_A_STEP`` heads, the chunk axis sequential and each
head's ``(d_v, d_k)`` float32 state (the state TRANSPOSED: a key channel's
decay then scales a column) carried in VMEM across it:

- ``128 / L`` heads' chunks are STACKED along the rows, ``N = 128`` rows a
  tile, so every chunk matrix (``K K^T``, ``Q K^T``, ``A``, ``T``) is one
  128 x 128 tile, block diagonal by head;
- ``K K^T`` and ``Q K^T`` under ``intra_chunk_products``' overflow rule:
  inside a sub-block of ``sub`` positions the decays pair by pair in float32
  (the pairs ``(i, i - delta)`` are the rows rolled by ``delta``, a loop over
  the ``sub`` offsets); a sub-block's rows against ALL earlier columns of its
  head in one product, both factors taken against gamma at the sub-block's
  first position, so that each is at most 1;
- ``T = (I + A)^-1`` block by block, one product deep a level, every product
  of it a float32 product (``pallas_gated_delta._inverse_by_blocks``);
- q, k, v and g are read in the layout ``kda_window`` holds them, ``(b, T,
  heads x d)``, an ``(L, d)`` block a head through the ``BlockSpec`` index
  maps: g is per key channel, so it needs no transpose; beta arrives as rows
  ``(b, tiles, chunks, 128)`` (a megabyte: the caller transposes it in XLA);
- the forward also writes what the backward needs: each chunk's entry states
  in the compute dtype (as ``_scan_chunks`` hands them on) and ``T`` in
  float32 (a tile's heads' ``L x L`` blocks side by side).  The backward
  walks the chunks in reverse with the states' cotangents carried in VMEM,
  recomputes the chunk's products from q, k, v, g and beta and returns the
  cotangents of q, k, v, g (per key channel) and beta.

Precision is the XLA form's: g, beta, gamma, the pairwise decays, the inverse
and the carried state float32; operands of the products in the compute dtype,
accumulation float32.  The rule-independent helpers, and the launcher that
lowers each kernel's body once a program, are row 21's kernels'
(ops/pallas_gated_delta.py).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from pytorch_distributed_tpu.ops import pallas_gated_delta as _gdn
from pytorch_distributed_tpu.ops.pallas_gated_delta import (
    _dot, _dot_f32, _inverse_by_blocks, _launcher, _masks,
)

F32 = jnp.float32
TILE = 128          # rows of a tile: the stacked heads' chunks

# heads a grid step; their tiles' work is emitted stage by stage (the rule of
# ops/pallas_gated_delta.py HEADS_A_STEP)
HEADS_A_STEP = 4


def _pallas():
    _gdn._ensure_pallas()
    return _gdn.pl, _gdn.pltpu


def fits(chunk: int, sub: int, d_k: int, d_v: int, heads: int) -> bool:
    """Whether the kernels' tiles hold these shapes: a chunk a power of two
    of 16 to 128 positions, whose heads stack to whole 128-row tiles; a
    sub-block a power of two of at least 8 positions (whole sublane tiles)
    that divides the chunk; both head widths whole 128-lane tiles."""
    pow2 = lambda n: n > 0 and n & (n - 1) == 0
    return (pow2(chunk) and 16 <= chunk <= TILE and pow2(sub)
            and 8 <= sub <= chunk and heads % (TILE // chunk) == 0
            and d_k % 128 == 0 and d_v % 128 == 0)


def _tile_masks(N: int, L: int, sub: int, dk: int):
    """``_masks`` and what the channel-gated rule adds: ``blk`` two
    positions in one sub-block, ``diff`` row - column, ``part`` each row's
    sub-block inside its head ((N, N) and (N, d_k) forms), ``col_at`` each
    column's position inside its head, ``le_f`` / ``same_f`` as float32."""
    m = _masks(N, L)
    row = jax.lax.broadcasted_iota(jnp.int32, (N, N), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (N, N), 1)
    row_d = jax.lax.broadcasted_iota(jnp.int32, (N, dk), 0)
    m.update(blk=m["x"] < sub, diff=row - col, part=(row % L) // sub,
             part_d=(row_d % L) // sub, col_at=col % L,
             le_f=m["le"].astype(F32), same_f=m["same"].astype(F32))
    return m


def _rowsum(t):
    return jnp.sum(t, 1, keepdims=True)


def _refs(m, L: int, sub: int):
    """gamma at the first position of each sub-block s >= 1 of a head, as
    (N, d_k) rows (each head's rows its own head's)."""
    gamma, (N, dk) = m["gamma"], m["gamma"].shape
    return [jnp.concatenate([
        jnp.broadcast_to(gamma[h * L + s * sub:h * L + s * sub + 1], (L, dk))
        for h in range(N // L)], 0) for s in range(1, L // sub)]


def _keep(mask, s: int, sub: int):
    """Sub-block ``s``'s rows against every earlier column of its head."""
    return mask["same"] & (mask["part"] == s) & (mask["col_at"] < s * sub)


def _pair_products(mask, tiles, L: int, sub: int, cd):
    """``KK``, ``QK`` (N, N) float32 of each tile: ``sum_d x_id k_jd
    exp(gamma_id - gamma_jd)`` for ``j <= i`` in one head, x = k and x = q,
    zero elsewhere."""
    _, pltpu = _pallas()
    eye = mask["eye"]
    acc = []
    for m in tiles:                  # the diagonal: no decay
        acc += [jnp.where(eye, _rowsum(m["k"] * m["k"]), 0.0),
                jnp.where(eye, _rowsum(m["q"] * m["k"]), 0.0)]

    def offset(delta, acc):          # the pairs (i, i - delta) of a sub-block
        pairs = mask["blk"] & (mask["diff"] == delta)
        out = []
        for n, m in enumerate(tiles):
            k_j = pltpu.roll(m["k"], delta, 0)
            g_j = pltpu.roll(m["gamma"], delta, 0)
            Ek = k_j * jnp.exp(jnp.minimum(m["gamma"] - g_j, 0.0))
            out += [jnp.where(pairs, _rowsum(m["k"] * Ek), acc[2 * n]),
                    jnp.where(pairs, _rowsum(m["q"] * Ek), acc[2 * n + 1])]
        return out

    acc = jax.lax.fori_loop(1, sub, offset, acc)
    for n, m in enumerate(tiles):    # a sub-block against every earlier one
        refs = _refs(m, L, sub)
        at = m["gamma"]
        for s, ref in enumerate(refs, 1):
            at = jnp.where(mask["part_d"] == s, ref, at)
        down = jnp.exp(jnp.minimum(m["gamma"] - at, 0.0))
        Xk, Xq = ((x * down).astype(cd) for x in (m["k"], m["q"]))
        KK, QK = acc[2 * n], acc[2 * n + 1]
        ups = [jnp.exp(jnp.minimum(ref - m["gamma"], 0.0)) for ref in refs]
        for s, up in enumerate(ups, 1):
            Ku = (m["k"] * up).astype(cd)
            keep = _keep(mask, s, sub)
            KK = jnp.where(keep, _dot(Xk, Ku, 1, 1), KK)
            QK = jnp.where(keep, _dot(Xq, Ku, 1, 1), QK)
        m.update(KK=KK, QK=QK, down=down, Xk=Xk, Xq=Xq, ups=ups)


def _chunk_terms(mask, tiles, L: int, sub: int, cd):
    """What the forward and the backward both need of one chunk of each
    tile: q, k, g (N, d_k) and v (N, d_v) float32, b_row (1, N) float32, the
    tile's heads stacked along the rows."""
    for m in tiles:
        m["gamma"] = _dot_f32(mask["le_f"], m["g"])      # running sum of g
        m["gamma_end"] = _dot_f32(mask["same_f"], m["g"])   # a head's whole
        m["beta"] = _rowsum(jnp.where(mask["eye"], m["b_row"], 0.0))
    _pair_products(mask, tiles, L, sub, cd)
    for m in tiles:
        k, q, beta, gamma = m["k"], m["q"], m["beta"], m["gamma"]
        in_chunk = jnp.exp(gamma)
        to_end = jnp.exp(m["gamma_end"] - gamma)
        m.update(
            in_chunk=in_chunk, to_end=to_end,
            A=jnp.where(mask["lt"], beta * m["KK"], 0.0),
            Kb=(k * (beta * in_chunk)).astype(cd),
            Vb=(m["v"] * beta).astype(cd),
            K_end=(k * to_end).astype(cd), q_in=(q * in_chunk).astype(cd),
            P=m["QK"].astype(cd),
            decay=[jnp.exp(m["gamma_end"][h * L:h * L + 1])
                   for h in range(gamma.shape[0] // L)])
    return tiles


def _terms_of_step(c, q_ref, k_ref, v_ref, g_ref, b_ref, L: int, sub: int,
                   cd):
    """(the masks, ``_chunk_terms`` of each tile of the grid step)."""
    pl, _ = _pallas()
    tiles, N = b_ref.shape[1], b_ref.shape[3]
    R = N // L
    dk, dv = q_ref.shape[2] // (tiles * R), v_ref.shape[2] // (tiles * R)
    mask = _tile_masks(N, L, sub, dk)
    stack = lambda ref, w, t: jnp.concatenate(
        [ref[0, :, (t * R + j) * w:(t * R + j + 1) * w] for j in range(R)],
        0).astype(F32)
    return mask, _chunk_terms(mask, [
        dict(q=stack(q_ref, dk, t), k=stack(k_ref, dk, t),
             g=stack(g_ref, dk, t), v=stack(v_ref, dv, t),
             b_row=b_ref[0, t, pl.ds(c, 1)]) for t in range(tiles)],
        L, sub, cd)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref,
                o_ref, S_ref, entry_ref, T_ref, *, L: int, sub: int, cd):
    pl, _ = _pallas()
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _zero_state():
        S_ref[...] = jnp.zeros_like(S_ref)

    mask, tiles = _terms_of_step(c, q_ref, k_ref, v_ref, g_ref, b_ref, L,
                                 sub, cd)
    Ts = _inverse_by_blocks([m["A"] for m in tiles], mask["x"], L)
    R = Ts[0].shape[0] // L
    dv = v_ref.shape[2] // (len(tiles) * R)
    heads = [(t, j, slice(j * L, (j + 1) * L))
             for t in range(len(tiles)) for j in range(R)]
    # stage by stage over ALL the step's tiles (HEADS_A_STEP's note)
    for t, T in enumerate(Ts):      # block diagonal: the heads' blocks,
        T_ref[0, 0, t] = sum(       # side by side (L, N)
            T[j * L:(j + 1) * L] for j in range(R))
    WU = [(_dot(T.astype(cd), m["Kb"]).astype(cd), _dot(T.astype(cd), m["Vb"]))
          for m, T in zip(tiles, Ts)]
    S = [S_ref[0, t * R + j] for t, j, _ in heads]          # (d_v, d_k)
    Sb = [s.astype(cd) for s in S]
    for (t, j, _), s in zip(heads, Sb):
        entry_ref[0, 0, t * R + j] = s
    V_new = [(WU[t][1][rows] - _dot(WU[t][0][rows], s, 1, 1)).astype(cd)
             for (t, j, rows), s in zip(heads, Sb)]
    for n, (t, j, rows) in enumerate(heads):
        S_ref[0, t * R + j] = (tiles[t]["decay"][j] * S[n]
                               + _dot(V_new[n], tiles[t]["K_end"][rows], 0, 0))
    o_state = [_dot(tiles[t]["q_in"][rows], s, 1, 1)
               for (t, j, rows), s in zip(heads, Sb)]
    for t, m in enumerate(tiles):
        o = jnp.concatenate(o_state[t * R:(t + 1) * R], 0) + _dot(
            m["P"], jnp.concatenate(V_new[t * R:(t + 1) * R], 0))
        for j in range(R):
            h = t * R + j
            o_ref[0, :, h * dv:(h + 1) * dv] = o[j * L:(j + 1) * L]


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, entry_ref, T_ref, do_ref,
                dS_end_ref, dq_ref, dk_ref, dv_ref, dg_ref, db_ref, dS_ref,
                *, L: int, sub: int, cd):
    pl, _ = _pallas()
    step = pl.program_id(2)
    c = pl.num_programs(2) - 1 - step       # chunks in reverse

    @pl.when(step == 0)
    def _last_state():
        dS_ref[...] = dS_end_ref[0]

    mask, tiles = _terms_of_step(c, q_ref, k_ref, v_ref, g_ref, b_ref, L,
                                 sub, cd)
    for t, m in enumerate(tiles):
        _bwd_state(t, m, mask, entry_ref, T_ref, do_ref, dS_ref, L, cd)
    _pair_grads(mask, tiles, L, sub, cd)
    N = mask["x"].shape[0]
    R = N // L
    dk_w, dv_w = dq_ref.shape[2] // (len(tiles) * R), \
        dv_ref.shape[2] // (len(tiles) * R)
    for t, m in enumerate(tiles):
        # g -> gamma is a running sum inside the head: its transpose
        dg = _dot_f32(mask["le_f"], m["dgamma"], 0, 0) + m["d_end"]
        db_ref[0, t, pl.ds(c, 1)] = jnp.sum(
            jnp.where(mask["eye"], m["d_beta"], 0.0), 0, keepdims=True)
        for j in range(R):
            h, rows = t * R + j, slice(j * L, (j + 1) * L)
            for ref, x, w in ((dq_ref, m["dq"], dk_w), (dk_ref, m["dk"], dk_w),
                              (dg_ref, dg, dk_w), (dv_ref, m["dv"], dv_w)):
                ref[0, :, h * w:(h + 1) * w] = x[rows]


def _bwd_state(t, m, mask, entry_ref, T_ref, do_ref, dS_ref, L, cd):
    """One tile's chunk of the backward up to ``K K^T`` and ``Q K^T``: the
    states' cotangents handed to the chunk before, and in ``m`` the
    cotangents of q, k, v, gamma and beta through everything but the pairs,
    and those of ``KK`` and ``QK``."""
    N = mask["x"].shape[0]
    R = N // L
    beta = m["beta"]
    T = jnp.where(mask["same"],     # the blocks back on the diagonal
                  jnp.concatenate([T_ref[0, 0, t]] * R, 0), 0.0)
    Tm = T.astype(cd)
    W = _dot(Tm, m["Kb"]).astype(cd)
    U = _dot(Tm, m["Vb"])
    dv = do_ref.shape[2] // (T_ref.shape[2] * R)
    do = jnp.concatenate(
        [do_ref[0, :, (t * R + j) * dv:(t * R + j + 1) * dv]
         for j in range(R)], 0).astype(cd)

    # per head: the chunk's entry state, V', and what the state's cotangent
    # hands the chunk (states transposed: (d_v, d_k))
    V_new, dV_state, dQ_in, dK_end, entry, dS_next, d_end = ([] for _ in
                                                             range(7))
    for j in range(R):
        rows = slice(j * L, (j + 1) * L)
        Sb = entry_ref[0, 0, t * R + j]
        dS = dS_ref[t * R + j]
        dSb = dS.astype(cd)
        V_j = (U[rows] - _dot(W[rows], Sb, 1, 1)).astype(cd)
        dV_state.append(_dot(m["K_end"][rows], dSb, 1, 1))
        dQ_in.append(_dot(do[rows], Sb))
        dK_end.append(_dot(V_j, dSb))
        # the chunk's decay exp(gamma_L), a key channel
        d_end.append(jnp.broadcast_to(
            jnp.sum(dS * Sb.astype(F32), 0, keepdims=True) * m["decay"][j],
            (L, Sb.shape[1])))
        V_new.append(V_j)
        entry.append(Sb)
        dS_next.append(dS)
    V_new = jnp.concatenate(V_new, 0)
    dV = _dot(m["P"], do, 0, 0) + jnp.concatenate(dV_state, 0)    # = dU
    dVb = dV.astype(cd)
    dW = []
    for j in range(R):
        rows = slice(j * L, (j + 1) * L)
        dW.append(-_dot(dVb[rows], entry[j]))
        dS_ref[t * R + j] = (m["decay"][j] * dS_next[j]
                             + _dot(do[rows], m["q_in"][rows], 0, 0)
                             - _dot(dVb[rows], W[rows], 0, 0))
    dWb = jnp.concatenate(dW, 0).astype(cd)
    dQ_in, dK_end = (jnp.concatenate(x, 0) for x in (dQ_in, dK_end))

    # through W = T Kb, U = T Vb and T = (I + A)^-1: dA = -T^T dT T^T
    dT = _dot(dVb, m["Vb"], 1, 1) + _dot(dWb, m["Kb"], 1, 1)
    dKb, dVb_in = _dot(Tm, dWb, 0, 0), _dot(Tm, dVb, 0, 0)
    dA = jnp.where(mask["lt"],
                   -_dot_f32(_dot_f32(T, dT, 0, 0), T, 1, 1), 0.0)
    k, q, in_chunk, to_end = m["k"], m["q"], m["in_chunk"], m["to_end"]
    kd = dKb * k
    through_end = dK_end * k * to_end
    m.update(
        dKK=dA * beta, dQK=jnp.where(mask["le"], _dot(do, V_new, 1, 1), 0.0),
        dq=dQ_in * in_chunk, dk=dKb * (beta * in_chunk) + dK_end * to_end,
        dv=dVb_in * beta,
        dgamma=kd * (beta * in_chunk) + dQ_in * q * in_chunk - through_end,
        # gamma_L of a head is the sum of its g: every K_end row's and the
        # decay's cotangent reach each g of the head
        d_end=_dot_f32(mask["same_f"], through_end)
        + jnp.concatenate(d_end, 0),
        d_beta=(_rowsum(dA * m["KK"]) + _rowsum(dVb_in * m["v"])
                + _rowsum(kd * in_chunk)))


def _pair_grads(mask, tiles, L: int, sub: int, cd):
    """Adds to each tile's ``dq``, ``dk`` and ``dgamma`` what ``dKK`` and
    ``dQK`` give through ``_pair_products`` (its reference positions cancel:
    a factor's cotangent through one is the negative of the other's)."""
    _, pltpu = _pallas()
    N = mask["x"].shape[0]
    eye = mask["eye"]
    acc = []
    for m in tiles:                  # the diagonal (dKK's is zero: A < 0)
        e = _rowsum(jnp.where(eye, m["dQK"], 0.0))
        acc += [m["dq"] + e * m["k"], m["dk"] + e * m["q"], m["dgamma"]]

    def offset(delta, acc):
        pairs = mask["blk"] & (mask["diff"] == delta)
        back = N - delta            # a roll by -delta: row i - delta's
        out = []
        for n, m in enumerate(tiles):
            dq, dk, dgamma = acc[3 * n:3 * n + 3]
            c = _rowsum(jnp.where(pairs, m["dKK"], 0.0))
            e = _rowsum(jnp.where(pairs, m["dQK"], 0.0))
            k_j = pltpu.roll(m["k"], delta, 0)
            E = jnp.exp(jnp.minimum(
                m["gamma"] - pltpu.roll(m["gamma"], delta, 0), 0.0))
            Ek = k_j * E
            Y = (c * m["k"] + e * m["q"]) * E       # zero off the pairs
            YK = Y * k_j
            out += [dq + e * Ek, dk + c * Ek + pltpu.roll(Y, back, 0),
                    dgamma + YK - pltpu.roll(YK, back, 0)]
        return out

    acc = jax.lax.fori_loop(1, sub, offset, acc)
    for n, m in enumerate(tiles):    # a sub-block against every earlier one
        dq, dk, dgamma = acc[3 * n:3 * n + 3]
        dXk = dXq = 0.0
        for s, up in enumerate(m["ups"], 1):
            keep = _keep(mask, s, sub)
            Ku = (m["k"] * up).astype(cd)
            dKK = jnp.where(keep, m["dKK"], 0.0).astype(cd)
            dQK = jnp.where(keep, m["dQK"], 0.0).astype(cd)
            dXk = dXk + _dot(dKK, Ku)
            dXq = dXq + _dot(dQK, Ku)
            dKu = _dot(dKK, m["Xk"], 0, 0) + _dot(dQK, m["Xq"], 0, 0)
            dk = dk + dKu * up
            dgamma = dgamma - dKu * m["k"] * up
        down = m["down"]
        m.update(dq=dq + dXq * down, dk=dk + dXk * down,
                 dgamma=dgamma + (dXk * m["k"] + dXq * m["q"]) * down)


def _geometry(k, v, b_rows):
    b, T, h, dk = k.shape
    dv = v.shape[3]
    tiles, nc, N = b_rows.shape[1:]
    R = h // tiles
    L = N // R
    assert tiles * R == h and nc * L == T and b_rows.shape[0] == b, (
        k.shape, v.shape, b_rows.shape)
    return b, T, h, dk, dv, R, nc, N, L


def _specs(h, dk, dv, R, nc, N, L, chunk_of):
    """(heads a grid step, the block specs of: q, k or g, v or o, beta rows,
    the states, the entry states, T), the chunk of grid step c being
    ``chunk_of(c)``."""
    pl, _ = _pallas()
    hb = R * math.gcd(h // R, max(1, HEADS_A_STEP // R))
    at = lambda *idx: pl.BlockSpec(*idx)
    return hb, (
        at((1, L, hb * dk), lambda b, i, c: (b, chunk_of(c), i)),
        at((1, L, hb * dv), lambda b, i, c: (b, chunk_of(c), i)),
        at((1, hb // R, nc, N), lambda b, i, c: (b, i, 0, 0)),
        at((1, hb, dv, dk), lambda b, i, c: (b, i, 0, 0)),
        at((1, 1, hb, dv, dk), lambda b, i, c: (b, chunk_of(c), i, 0, 0)),
        at((1, 1, hb // R, L, N), lambda b, i, c: (b, chunk_of(c), i, 0, 0)))


def _params(interpret: bool):
    _, pltpu = _pallas()
    return None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _fwd_call(q, k, v, g, b_rows, *, sub: int, cd, interpret: bool):
    """q, k, g (b, T, h, d_k) float32; v (b, T, h, d_v) float32; b_rows (b,
    tiles, chunks, 128) float32.  Returns (o (b, T, h, d_v) float32, the last
    state (b, h, d_k, d_v) float32, and for the backward: each chunk's entry
    states (b, chunks, h, d_v, d_k) in the compute dtype, T (b, chunks,
    tiles, L, 128) float32: a tile's heads' L x L blocks side by side)."""
    pl, pltpu = _pallas()
    b, T, h, dk, dv, R, nc, N, L = _geometry(k, v, b_rows)
    flat = lambda t: t.reshape(b, T, -1)
    hb, (qk, vo, rows, state, entry, inv) = _specs(h, dk, dv, R, nc, N, L,
                                                   lambda c: c)
    o, S, entries, Tm = pl.pallas_call(
        functools.partial(_fwd_kernel, L=L, sub=sub, cd=cd),
        grid=(b, h // hb, nc),
        in_specs=[qk, qk, vo, qk, rows],
        out_specs=[vo, state, entry, inv],
        out_shape=[jax.ShapeDtypeStruct((b, T, h * dv), F32),
                   jax.ShapeDtypeStruct((b, h, dv, dk), F32),
                   jax.ShapeDtypeStruct((b, nc, h, dv, dk), cd),
                   jax.ShapeDtypeStruct((b, nc, h // R, L, N), F32)],
        compiler_params=_params(interpret),
        interpret=interpret,
        name="kda_chunk_fwd",
    )(flat(q), flat(k), flat(v), flat(g), b_rows)
    return o.reshape(b, T, h, dv), jnp.swapaxes(S, 2, 3), entries, Tm


def _bwd_call(q, k, v, g, b_rows, entries, Tm, do, dS, *, sub: int, cd,
              interpret: bool):
    """The cotangents of (q, k, v, g, b_rows), float32, from those of ``o``
    (b, T, h, d_v) and of the last state (b, h, d_k, d_v)."""
    pl, pltpu = _pallas()
    b, T, h, dk, dv, R, nc, N, L = _geometry(k, v, b_rows)
    flat = lambda t: t.astype(F32).reshape(b, T, -1)
    hb, (qk, vo, rows, state, entry, inv) = _specs(
        h, dk, dv, R, nc, N, L, lambda c: nc - 1 - c)
    dq, dk_, dv_, dg, dbeta = pl.pallas_call(
        functools.partial(_bwd_kernel, L=L, sub=sub, cd=cd),
        grid=(b, h // hb, nc),
        in_specs=[qk, qk, vo, qk, rows, entry, inv, vo, state],
        out_specs=[qk, qk, vo, qk, rows],
        out_shape=[jax.ShapeDtypeStruct((b, T, h * dk), F32),
                   jax.ShapeDtypeStruct((b, T, h * dk), F32),
                   jax.ShapeDtypeStruct((b, T, h * dv), F32),
                   jax.ShapeDtypeStruct((b, T, h * dk), F32),
                   jax.ShapeDtypeStruct(b_rows.shape, F32)],
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), F32)],
        compiler_params=_params(interpret),
        interpret=interpret,
        name="kda_chunk_bwd",
    )(flat(q), flat(k), flat(v), flat(g), b_rows, entries, Tm, flat(do),
      jnp.swapaxes(dS.astype(F32), 2, 3))
    return (dq.reshape(q.shape), dk_.reshape(k.shape), dv_.reshape(v.shape),
            dg.reshape(g.shape), dbeta)


def _fwd_avals(q, k, v, g, b_rows, *, cd, interpret):
    from jax.core import ShapedArray

    b, T, h, dk, dv, R, nc, N, L = _geometry(k, v, b_rows)
    return [ShapedArray((b, T, h, dv), F32), ShapedArray((b, h, dk, dv), F32),
            ShapedArray((b, nc, h, dv, dk), cd),
            ShapedArray((b, nc, h // R, L, N), F32)]


def _bwd_avals(q, k, v, g, b_rows, entries, Tm, do, dS, *, interpret):
    from jax.core import ShapedArray

    return [ShapedArray(t.shape, F32) for t in (q, k, v, g, b_rows)]


@functools.lru_cache(maxsize=None)
def _chunk(sub: int, cd_name: str, interpret: bool):
    """The window's ``custom_vjp`` through the two launchers at one
    sub-block size and compute dtype (static in the kernels' bodies; the
    launchers' implementation takes ``interpret`` alone)."""
    fwd_prim, bwd_prim = _launchers(sub, cd_name)

    @jax.custom_vjp
    def chunk(q, k, v, g, b_rows):
        return tuple(fwd_prim.bind(q, k, v, g, b_rows,
                                   interpret=interpret)[:2])

    def fwd(q, k, v, g, b_rows):
        o, S, entries, Tm = fwd_prim.bind(q, k, v, g, b_rows,
                                          interpret=interpret)
        return (o, S), (q, k, v, g, b_rows, entries, Tm)

    def bwd(res, cts):
        # (runs under the name stack the forward was bound in: the scopes
        # ``model.kda`` / ``kda.chunk`` stand on the backward kernel's path)
        return tuple(bwd_prim.bind(*res, *cts, interpret=interpret))

    chunk.defvjp(fwd, bwd)
    return chunk


@functools.lru_cache(maxsize=None)
def _launchers(sub: int, cd_name: str):
    """The two kernels' launchers (ops/pallas_gated_delta.py ``_launcher``)
    at one sub-block size and compute dtype."""
    cd = jnp.dtype(cd_name)
    return (_launcher("kda_chunk_fwd", functools.partial(
                _fwd_call, sub=sub, cd=cd),
                functools.partial(_fwd_avals, cd=cd)),
            _launcher("kda_chunk_bwd", functools.partial(
                _bwd_call, sub=sub, cd=cd), _bwd_avals))


def kda_window(q, k, v, g, beta, chunk: int, sub: int, cd, interpret: bool):
    """``kda_chunked``'s contract through the kernels: q, k, g (b, T, h,
    d_k); v (b, T, h, d_v); beta (b, T, h); T whole chunks.  Returns (o (b, T,
    h, d_v) float32, the state after the last position (b, h, d_k, d_v)
    float32)."""
    b, T, h, _ = k.shape
    R, nc = TILE // chunk, T // chunk
    assert nc * chunk == T and h % R == 0, (T, chunk, h)
    rows = jnp.moveaxis(                    # (b, tiles, chunks, R L)
        beta.astype(F32).reshape(b, nc, chunk, h // R, R), (3, 1, 4, 2),
        (1, 2, 3, 4)).reshape(b, h // R, nc, R * chunk)
    return _chunk(sub, jnp.dtype(cd).name, interpret)(
        *(t.astype(F32) for t in (q, k, v, g)), rows)
