"""The gated delta rule: a linear-attention recurrence whose state update
is a rank-one CORRECTION, not a decayed sum.  A value head, from ``S = 0``
(``S`` is ``d_k x d_v``):

    S' = exp(g_t) S_{t-1}                     decay, g_t <= 0
    delta_t = beta_t (v_t - S'^T k_t)         what the state gets wrong at k_t
    S_t = S' + k_t delta_t^T
    o_t = S_t^T q_t

``gated_delta_step`` is that, one position (the actor's path).  The learner
computes a window in chunks of ``L`` positions (``gated_delta_chunked``).
Inside a chunk the positions are coupled: position i's correction reads the
writes of every earlier position of its chunk, a unit-lower-triangular
system.  With ``gamma`` the cumulative ``g`` inside the chunk,

    A_ij = beta_i (k_i . k_j) exp(gamma_i - gamma_j)      j < i, else 0
    T = (I + A)^-1
    W = T (beta exp(gamma) * K),   U = T (beta * V)

and a chunk entered with state ``S`` has

    V' = U - W S
    O  = (Q * exp(gamma)) S + (Q K^T * exp(gamma_i - gamma_j), j <= i) V'
    S <- exp(gamma_L) S + (K * exp(gamma_L - gamma))^T V'

``A`` is strictly lower triangular of size ``L``, so ``A^L = 0`` and

    (I + A)^-1 = (I - A)(I + A^2)(I + A^4) ... (I + A^(L/2))

(``unit_lower_inverse``): for ``L = 64`` ten 64 x 64 products, five
squarings and five to combine, in place of a 64-step substitution; its
backward pass is the inverse's own cotangent, two products.  Only
``V'`` and the state need the scan over chunks (two products a step);
everything else is batched over the chunks.  float32 for ``g``, ``beta``,
``gamma``, the inverse and the scan's state; the products that read them
take both operands in the compute dtype and accumulate in float32.

ON A TPU ``gated_delta_chunked`` runs that window as a Pallas kernel pair
(ops/pallas_gated_delta.py: a chunk's intermediates in fast memory, the
inverse block by block) where the shapes fit its tiles; what is written here
is the XLA form, which every other backend runs and the kernels are held to.

Every key head serves ``h_v / h_k`` value heads: value head ``h`` reads key
head ``h // (h_v / h_k)``.  ``q`` and ``k`` arrive normalised (and ``q``
scaled); this module holds the recurrence and nothing of the layer around
it (models/hybrid.py ``gdn_window`` / ``gdn_step``, ``kda_window`` /
``kda_step``).

THE CHANNEL-GATED RULE (``kda_chunked``): ``g_t`` a vector over the key
channels, ``S' = Diag(exp(g_t)) S_{t-1}``, the rest as above.  ``gamma`` is
then ``(L, d_k)`` and the decay no longer comes out of the dot products:

    A_ij = beta_i sum_d k_id k_jd exp(gamma_id - gamma_jd)        j < i
    P_ij =        sum_d q_id k_jd exp(gamma_id - gamma_jd)        j <= i
    W = T (beta * K * exp(gamma)),   U = T (beta * V),   V' = U - W S
    O = (Q * exp(gamma)) S + P V'
    S <- Diag(exp(gamma_L)) S + (K * exp(gamma_L - gamma))^T V'

``(K * exp(gamma)) (K * exp(-gamma))^T`` overflows float32 once a channel's
cumulative decay inside a chunk passes e^88, and an ``(L, L, d_k)`` array of
differences is gigabytes.  So the chunk is cut into sub-blocks of ``sub``
positions (``intra_chunk_products``): inside a sub-block the differences are
taken pair by pair; sub-blocks are then joined in pairs, level by level, and
where a later block meets an earlier one both factors are taken against
``gamma`` at the LATER block's first position (``exp(gamma_i - gamma_ref) <=
1`` and ``exp(gamma_ref - gamma_j) <= 1`` for every earlier ``j``: nothing
overflows, and a factor that underflows is no larger than the true value).
The scan over chunk states is the scalar-gated rule's; the inverse is formed
block by block (``unit_lower_inverse_by_blocks``), because this rule's
published decays are slow and ``A`` is then no small matrix.  ON A TPU
``kda_chunked`` runs its window as a Pallas kernel pair of its own
(ops/pallas_kda.py), under the same rule; what is written here is again the
XLA form and the kernels' oracle.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from pytorch_distributed_tpu.ops import pallas_gated_delta, pallas_kda
from pytorch_distributed_tpu.utils.profiling import (
    SCOPE_GDN, SCOPE_GDN_CHUNK, SCOPE_KDA, SCOPE_KDA_CHUNK,
)

F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST


def _inverse_by_products(A):
    L = A.shape[-1]
    mm = lambda a, b: jnp.matmul(a, b, precision=_HIGHEST)
    inv, power, n = jnp.eye(L, dtype=A.dtype) - A, A, 2
    while n < L:
        power = mm(power, power)               # A^n
        inv = inv + mm(inv, power)             # ... (I + A^n)
        n *= 2
    return inv


@jax.custom_vjp
def unit_lower_inverse(A):
    """``T = (I + A)^-1`` for strictly lower triangular ``A`` (.., L, L) in
    float32, by the products above.  Its cotangent is ``-T^T g T^T``: two
    products, where differentiating through the ten would be twenty and
    keep each one's operands.  (On the chip a triangular solve against the
    identity took half as long again forward: PERF.md section 6, PR 31.)"""
    return _inverse_by_products(A)


def _inverse_fwd(A):
    T = _inverse_by_products(A)
    return T, T


def _inverse_bwd(T, g):
    Tt = jnp.swapaxes(T, -1, -2)
    return (-jnp.matmul(jnp.matmul(Tt, g, precision=_HIGHEST), Tt,
                        precision=_HIGHEST),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def _inverse_by_blocks(A):
    L = A.shape[-1]
    lead = A.shape[:-2]
    mm = lambda a, b: jnp.matmul(a, b, precision=_HIGHEST)
    T, h = jnp.ones((*lead, L, 1, 1), A.dtype), 1          # (.., b, h, h)
    while h < L:
        nb = L // (2 * h)
        # each 2h x 2h diagonal block's lower left quarter
        A21 = jnp.einsum("...bicj,bc->...bij",
                         A.reshape(*lead, nb, 2 * h, nb, 2 * h),
                         jnp.eye(nb, dtype=A.dtype))[..., h:, :h]
        T = T.reshape(*lead, nb, 2, h, h)
        T11, T22 = T[..., 0, :, :], T[..., 1, :, :]
        T = jnp.concatenate([
            jnp.concatenate([T11, jnp.zeros_like(T11)], -1),
            jnp.concatenate([-mm(mm(T22, A21), T11), T22], -1)], -2)
        h *= 2
    return T[..., 0, :, :]


@jax.custom_vjp
def unit_lower_inverse_by_blocks(A):
    """``T = (I + A)^-1`` for strictly lower triangular ``A`` (.., L, L), L a
    power of two, in float32, block by block: the inverse of a 2h x 2h
    diagonal block from its two h x h halves' inverses, ``[[T11, 0], [-T22
    A21 T11, T22]]``, from h = 1 up.  Every intermediate is a true inverse of
    a block, bounded as ``T`` is.  ``unit_lower_inverse``'s ten products pass
    through powers of ``A`` up to the 32nd instead, whose entries reach
    ``C(62, 31) a^32`` where keys resemble each other and the decay is slow
    (``A_ij`` near a constant ``a``): 1e8 at ``a`` = 0.5, and the inverse,
    whose entries stay below 1, is lost to cancellation in float32 (seen on
    the chip and repeated on the CPU, PERF.md section 6, PR 34).  Same
    cotangent as that one's."""
    return _inverse_by_blocks(A)


unit_lower_inverse_by_blocks.defvjp(
    lambda A: (_inverse_by_blocks(A),) * 2, _inverse_bwd)


def _scan_chunks(W, U, K_end, decay, scopes, cd):
    """The scan over chunk states, the one part of a window that is
    sequential: ``V' = U - W S``, ``S <- decay S + K_end^T V'``, a chunk a
    step, from ``S = 0`` in float32.  W (b, c, g, r, L, d_k) float32 (read
    in the compute dtype), K_end the same in the compute dtype; U (b, c, g,
    r, L, d_v) float32; ``decay`` a chunk's whole decay, (b, c, g, r) a head
    or (b, c, g, r, d_k) a key channel; ``scopes`` the caller's two, entered
    inside the body.  Returns (the state
    after the last chunk (b, g, r, d_k, d_v) float32, the state each chunk
    was entered with and each chunk's V', chunk-major (b, c, ..) in the
    compute dtype: both are read only through products in it)."""
    b, _, G, r, _, dk = W.shape
    dv = U.shape[-1]
    wide = (...,) + (None,) * (6 - decay.ndim)     # up to (b, g, r, d_k, d_v)

    def chunk_state(S, inp):
        with jax.named_scope(scopes[0]), jax.named_scope(scopes[1]):
            W_c, U_c, K_c, dec = inp
            V_new = U_c - jnp.einsum(
                "bgrld,bgrde->bgrle", W_c, S.astype(cd),
                preferred_element_type=F32)
            V_new = V_new.astype(cd)
            S_next = dec[wide] * S + jnp.einsum(
                "bgrld,bgrle->bgrde", K_c, V_new,
                preferred_element_type=F32)
        return S_next, (S.astype(cd), V_new)

    cm = lambda t: jnp.moveaxis(t, 1, 0)
    S_end, (S_prev, V_new) = jax.lax.scan(
        chunk_state, jnp.zeros((b, G, r, dk, dv), F32),
        (cm(W.astype(cd)), cm(U), cm(K_end), cm(decay)))
    S_prev, V_new = (jnp.moveaxis(t, 0, 1) for t in (S_prev, V_new))
    return S_end, S_prev, V_new


def gated_delta_chunked(q, k, v, g, beta, chunk: int, cd=jnp.bfloat16,
                        kernel: str = "auto"):
    """The recurrence over a window from a zero state, chunk by chunk.  q, k
    (b, T, h_k, d_k); v (b, T, h_v, d_v); g, beta (b, T, h_v) float32; T a
    whole number of chunks (pad with g = beta = 0: such a position decays
    nothing and writes nothing).  Returns (o (b, T, h_v, d_v) float32, the
    state after the last position (b, h_v, d_k, d_v) float32); the scan
    carries the state in float32.

    ``kernel``: "auto", "xla" or "interpret" (the kernels under the Pallas
    interpreter: tests).  On ONE TPU chip (the launchers have no sharding
    rule: a mesh takes the XLA form), where the shapes fit their tiles, the
    window runs as the Pallas kernel pair of ops/pallas_gated_delta.py
    (``gdn_chunk_fwd`` / ``gdn_chunk_bwd`` in a trace), a chunk's
    intermediates in fast memory; the XLA form below everywhere else, and it
    is the kernels' oracle (tests/test_gdn_kernel.py).  ONE of the two is in
    a program, chosen here in Python."""
    with jax.named_scope(SCOPE_GDN_CHUNK):
        b, T, G, dk = k.shape
        hv, dv = v.shape[2:]
        r, L, nc = hv // G, chunk, T // chunk
        assert nc * L == T and r * G == hv, (T, chunk, hv, G)
        assert kernel in ("auto", "xla", "interpret"), kernel
        if kernel == "auto" and (
                jax.default_backend() != "tpu" or jax.device_count() > 1
                or not pallas_gated_delta.fits(L, r, dk, dv)):
            kernel = "xla"
        if kernel != "xla":
            return pallas_gated_delta.gated_delta_window(
                q, k, v, g, beta, L, cd, interpret=kernel == "interpret")
        tril = jnp.tril(jnp.ones((L, L), bool))
        heads = lambda t: jnp.moveaxis(                    # (b,c,g,r,L)
            t.astype(F32).reshape(b, nc, L, G, r), 2, -1)
        # cumulative log-decay inside each chunk as a product with the
        # triangle of ones (the chip's cumsum is a reduce-window: PERF.md)
        gamma = jnp.einsum("bcgrs,ls->bcgrl", heads(g), tril.astype(F32),
                           precision=_HIGHEST)
        bt = heads(beta)
        qc, kc = (t.astype(cd).reshape(b, nc, L, G, dk) for t in (q, k))
        vc = v.astype(F32).reshape(b, nc, L, G, r, dv)
        KK = jnp.einsum("bclgd,bcsgd->bcgls", kc, kc,
                        preferred_element_type=F32)[:, :, :, None]
        QK = jnp.einsum("bclgd,bcsgd->bcgls", qc, kc,
                        preferred_element_type=F32)[:, :, :, None]
        decay = jnp.exp(jnp.where(
            tril, gamma[..., :, None] - gamma[..., None, :], -jnp.inf))
        A = jnp.where(jnp.tril(tril, -1), bt[..., None] * KK * decay, 0.0)
        Tm = unit_lower_inverse(A).astype(cd)              # (b,c,g,r,L,L)
        kf = jnp.moveaxis(kc.astype(F32), 2, 3)[:, :, :, None]  # (b,c,g,1,L,d)
        W = jnp.einsum("bcgrls,bcgrsd->bcgrld", Tm,
                       (kf * (bt * jnp.exp(gamma))[..., None]).astype(cd),
                       preferred_element_type=F32)
        U = jnp.einsum("bcgrls,bcsgrd->bcgrld", Tm,
                       (vc * jnp.moveaxis(bt, -1, 2)[..., None]).astype(cd),
                       preferred_element_type=F32)
        to_end = jnp.exp(gamma[..., -1:] - gamma)          # (b,c,g,r,L)
        K_end = (kf * to_end[..., None]).astype(cd)        # (b,c,g,r,L,dk)
        chunk_decay = jnp.exp(gamma[..., -1])              # (b,c,g,r)

        S_end, S_prev, V_new = _scan_chunks(
            W, U, K_end, chunk_decay, (SCOPE_GDN, SCOPE_GDN_CHUNK), cd)
        q_in = (jnp.moveaxis(qc.astype(F32), 2, 3)[:, :, :, None]
                * jnp.exp(gamma)[..., None]).astype(cd)    # (b,c,g,r,L,dk)
        o = jnp.einsum("bcgrld,bcgrde->bcgrle", q_in, S_prev,
                       preferred_element_type=F32)
        o = o + jnp.einsum("bcgrls,bcgrse->bcgrle", (QK * decay).astype(cd),
                           V_new, preferred_element_type=F32)
        o = jnp.moveaxis(o, 4, 2).reshape(b, T, hv, dv)    # (b,c,L,g,r,dv)
        return o, S_end.reshape(b, hv, dk, dv)


def intra_chunk_products(q, k, gamma, sub: int, cd):
    """``sum_d x_id k_jd exp(gamma_id - gamma_jd)`` for ``j <= i`` inside each
    chunk, for x = k and x = q: q, k, gamma (.., L, d) float32 -> two (.., L,
    L) float32, zero above the diagonal; ``L = sub * 2^n``.  Inside a block
    of ``sub`` positions the ``(sub, sub, d)`` differences themselves, in
    float32 (``sub`` = 1: the diagonal alone).  Then blocks are joined in
    pairs, level by level up to the chunk: the later block's rows against the
    earlier block's columns are ONE product of ``x * exp(gamma - ref)`` with
    ``k * exp(ref - gamma)``, operands in the compute dtype, ``ref`` = gamma
    at the later block's first position, so that both factors are at most
    1."""
    L, d = k.shape[-2:]
    lead = k.shape[:-2]
    blocks = lambda t, h: t.reshape(*lead, L // h, h, d)
    gb = blocks(gamma, sub)
    causal = jnp.tril(jnp.ones((sub, sub), bool))
    k_pair = blocks(k, sub)[..., None, :, :] * jnp.exp(jnp.where(
        causal[..., None], gb[..., :, None, :] - gb[..., None, :, :],
        -jnp.inf))                                         # (.., b, i, j, d)
    # k's and q's products side by side, not stacked: stacked, a block's
    # backward held 3 GB more at the cell's shapes (PERF.md section 6)
    xs = (k, q)
    Ms = [jnp.sum(blocks(x, sub)[..., :, None, :] * k_pair, axis=-1)
          for x in xs]                                     # (.., b, h, h)
    h = sub
    while h < L:
        assert L % (2 * h) == 0, (L, sub)
        halves = lambda t: t.reshape(*lead, L // (2 * h), 2, h, t.shape[-1])
        g2 = halves(gamma)
        ref = g2[..., 1, :1, :]                            # (.., b, 1, d)
        down = jnp.exp(g2[..., 1, :, :] - ref)
        k_up = (halves(k)[..., 0, :, :]
                * jnp.exp(ref - g2[..., 0, :, :])).astype(cd)
        for n, x in enumerate(xs):
            below = jnp.einsum(
                "...id,...jd->...ij",
                (halves(x)[..., 1, :, :] * down).astype(cd), k_up,
                preferred_element_type=F32)
            M = halves(Ms[n])                              # (.., b, 2, h, h)
            Ms[n] = jnp.concatenate([
                jnp.concatenate([M[..., 0, :, :], jnp.zeros_like(below)], -1),
                jnp.concatenate([below, M[..., 1, :, :]], -1)], -2)
        h *= 2
    return Ms[0][..., 0, :, :], Ms[1][..., 0, :, :]


def kda_chunked(q, k, v, g, beta, chunk: int, sub: int, cd=jnp.bfloat16,
                kernel: str = "auto"):
    """The channel-gated recurrence over a window from a zero state, chunk by
    chunk.  q, k (b, T, h, d_k); v (b, T, h, d_v); g (b, T, h, d_k) and beta
    (b, T, h) float32; T a whole number of chunks of ``chunk`` positions,
    each of whole sub-blocks of ``sub`` (pad with g = beta = 0).  Returns (o
    (b, T, h, d_v) float32, the state after the last position (b, h, d_k,
    d_v) float32).

    ``kernel`` as ``gated_delta_chunked``'s: on ONE TPU chip, where the
    shapes fit their tiles, the window runs as the Pallas kernel pair of
    ops/pallas_kda.py (``kda_chunk_fwd`` / ``kda_chunk_bwd`` in a trace); the
    XLA form below everywhere else, and it is the kernels' oracle
    (tests/test_gdn_kernel.py)."""
    with jax.named_scope(SCOPE_KDA_CHUNK):
        b, T, h, dk = k.shape
        dv = v.shape[-1]
        L, nc = chunk, T // chunk
        assert nc * L == T, (T, chunk)
        assert kernel in ("auto", "xla", "interpret"), kernel
        if kernel == "auto" and (
                jax.default_backend() != "tpu" or jax.device_count() > 1
                or not pallas_kda.fits(L, sub, dk, dv, h)):
            kernel = "xla"
        if kernel != "xla":
            return pallas_kda.kda_window(q, k, v, g, beta, L, sub, cd,
                                         interpret=kernel == "interpret")
        heads = lambda t: jnp.moveaxis(                    # (b,c,h,L,.)
            t.astype(F32).reshape(b, nc, L, h, -1), 2, 3)
        tril = jnp.tril(jnp.ones((L, L), bool))
        gamma = jnp.einsum("bchsd,ls->bchld", heads(g), tril.astype(F32),
                           precision=_HIGHEST)
        bt = heads(beta)                                   # (b,c,h,L,1)
        qf, kf, vf = heads(q), heads(k), heads(v)
        KK, QK = intra_chunk_products(qf, kf, gamma, sub, cd)
        A = jnp.where(jnp.tril(tril, -1), bt * KK, 0.0)
        Tm = unit_lower_inverse_by_blocks(A).astype(cd)    # (b,c,h,L,L)
        in_chunk = jnp.exp(gamma)                          # decay since the
        #                                                    chunk's start
        W = jnp.einsum("bchls,bchsd->bchld", Tm,
                       (kf * (bt * in_chunk)).astype(cd),
                       preferred_element_type=F32)
        U = jnp.einsum("bchls,bchsd->bchld", Tm, (vf * bt).astype(cd),
                       preferred_element_type=F32)
        K_end = (kf * jnp.exp(gamma[..., -1:, :] - gamma)).astype(cd)
        one = lambda t: t[:, :, :, None]                   # r = 1
        S_end, S_prev, V_new = _scan_chunks(
            one(W), one(U), one(K_end),
            one(jnp.exp(gamma[..., -1, :])), (SCOPE_KDA, SCOPE_KDA_CHUNK),
            cd)
        o = jnp.einsum("bchld,bchde->bchle", (qf * in_chunk).astype(cd),
                       S_prev[:, :, :, 0], preferred_element_type=F32)
        o = o + jnp.einsum("bchls,bchse->bchle", QK.astype(cd),
                           V_new[:, :, :, 0], preferred_element_type=F32)
        return (jnp.moveaxis(o, 3, 2).reshape(b, T, h, dv),
                S_end.reshape(b, h, dk, dv))


def gated_delta_step(q, k, v, g, beta, S):
    """One position: q, k (b, h_k, d_k); v (b, h_v, d_v); beta (b, h_v); g
    (b, h_v) a head or (b, h_v, d_k) a key channel; S (b, h_v, d_k, d_v)
    float32.  Returns (o (b, h_v, d_v), S')."""
    r = v.shape[1] // k.shape[1]
    qh, kh = (jnp.repeat(t.astype(F32), r, axis=1) for t in (q, k))
    S = jnp.exp(g)[(...,) + (None,) * (4 - g.ndim)] * S
    delta = beta[..., None] * (v.astype(F32)
                               - jnp.einsum("bhkv,bhk->bhv", S, kh))
    S = S + kh[..., :, None] * delta[..., None, :]
    return jnp.einsum("bhkv,bhk->bhv", S, qh), S
