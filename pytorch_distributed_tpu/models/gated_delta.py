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

Every key head serves ``h_v / h_k`` value heads: value head ``h`` reads key
head ``h // (h_v / h_k)``.  ``q`` and ``k`` arrive normalised (and ``q``
scaled); this module holds the recurrence and nothing of the layer around
it (models/hybrid.py ``gdn_window`` / ``gdn_step``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from pytorch_distributed_tpu.utils.profiling import (
    SCOPE_GDN, SCOPE_GDN_CHUNK,
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


def gated_delta_chunked(q, k, v, g, beta, chunk: int, cd=jnp.bfloat16):
    """The recurrence over a window from a zero state, chunk by chunk.  q, k
    (b, T, h_k, d_k); v (b, T, h_v, d_v); g, beta (b, T, h_v) float32; T a
    whole number of chunks (pad with g = beta = 0: such a position decays
    nothing and writes nothing).  Returns (o (b, T, h_v, d_v) float32, the
    state after the last position (b, h_v, d_k, d_v) float32); the scan
    carries the state in float32."""
    with jax.named_scope(SCOPE_GDN_CHUNK):
        b, T, G, dk = k.shape
        hv, dv = v.shape[2:]
        r, L, nc = hv // G, chunk, T // chunk
        assert nc * L == T and r * G == hv, (T, chunk, hv, G)
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

        def chunk_state(S, inp):
            with jax.named_scope(SCOPE_GDN), \
                    jax.named_scope(SCOPE_GDN_CHUNK):
                W_c, U_c, K_c, dec = inp
                V_new = U_c - jnp.einsum(
                    "bgrld,bgrde->bgrle", W_c, S.astype(cd),
                    preferred_element_type=F32)
                V_new = V_new.astype(cd)
                S_next = dec[..., None, None] * S + jnp.einsum(
                    "bgrld,bgrle->bgrde", K_c, V_new,
                    preferred_element_type=F32)
            # both are read only through products in the compute dtype
            return S_next, (S.astype(cd), V_new)

        cm = lambda t: jnp.moveaxis(t, 1, 0)
        S_end, (S_prev, V_new) = jax.lax.scan(
            chunk_state, jnp.zeros((b, G, r, dk, dv), F32),
            (cm(W.astype(cd)), cm(U), cm(K_end), cm(chunk_decay)))
        S_prev, V_new = (jnp.moveaxis(t, 0, 1) for t in (S_prev, V_new))
        q_in = (jnp.moveaxis(qc.astype(F32), 2, 3)[:, :, :, None]
                * jnp.exp(gamma)[..., None]).astype(cd)    # (b,c,g,r,L,dk)
        o = jnp.einsum("bcgrld,bcgrde->bcgrle", q_in, S_prev,
                       preferred_element_type=F32)
        o = o + jnp.einsum("bcgrls,bcgrse->bcgrle", (QK * decay).astype(cd),
                           V_new, preferred_element_type=F32)
        o = jnp.moveaxis(o, 4, 2).reshape(b, T, hv, dv)    # (b,c,L,g,r,dv)
        return o, S_end.reshape(b, hv, dk, dv)


def gated_delta_step(q, k, v, g, beta, S):
    """One position: q, k (b, h_k, d_k); v (b, h_v, d_v); g, beta (b, h_v);
    S (b, h_v, d_k, d_v) float32.  Returns (o (b, h_v, d_v), S')."""
    r = v.shape[1] // k.shape[1]
    qh, kh = (jnp.repeat(t.astype(F32), r, axis=1) for t in (q, k))
    S = jnp.exp(g)[..., None, None] * S
    delta = beta[..., None] * (v.astype(F32)
                               - jnp.einsum("bhkv,bhk->bhv", S, kh))
    S = S + kh[..., :, None] * delta[..., None, :]
    return jnp.einsum("bhkv,bhk->bhv", S, qh), S
