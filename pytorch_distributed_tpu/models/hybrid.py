"""Hybrid sequence Q-network: a layer PATTERN of state-space (Mamba-2),
gated-delta-rule (a head's or a key channel's gate), gated short-convolution,
sparse-expert, dense feed-forward, grouped-query and latent attention blocks
over one frame per position (model_type ``dtqn-hybrid``, CONFIGS rows 20 to
23).

The trunk is the first layers of a published hybrid language model at their
published widths (``PRESETS["nemotron-h-9"]``: layers 0-8, CONFIGS row 20,
benchmark/configs/nemotron_h_pong.json; ``PRESETS["qwen3-next-4"]``: layers
0-3, eight blocks, CONFIGS row 21, benchmark/configs/qwen3_next_pong.json;
``PRESETS["kimi-linear-5"]``: layers 0-4, ten blocks, CONFIGS row 22,
benchmark/configs/kimi_linear_pong.json; ``PRESETS["lfm2-moe-5"]``: layers
1-5, ten blocks, CONFIGS row 23, benchmark/configs/lfm2_moe_pong.json;
source and every departure in those files), with the token embedding and LM
head replaced by the repo's sequence-family contract (models/dtqn.py): one
84x84 uint8 frame a position -> Dense -> trunk -> final RMSNorm ->
zero-initialised Q head.  Pre-norm residual blocks ``x <- x +
mixer(RMSNorm(x))``, no biases but Mamba's conv's; the norm's scale is
``w`` or, zero-centred (``norm_plus_one``), ``1 + w``.  One letter a layer:

- ``M``  Mamba-2: ``[z | xBC | dt] = u W_in``; causal depth-wise conv +
  silu on xBC; per head ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``,
  ``y_t = S_t C_t + D x_t``; gated grouped RMSNorm; ``W_out``.  The
  learner computes it in chunks (``ssd_chunked``: matmuls inside a chunk,
  a scan over chunk states in float32), the actor one position at a time.
- ``D``  gated delta rule (models/gated_delta.py): ``[q | k | v | z] = u
  W_qkvz``, ``[b | a] = u W_ba``; causal depth-wise conv + silu on
  ``[q | k | v]``; ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a +
  dt_bias)``; q, k L2-normalised a head, ``q / sqrt(d_k)``; the recurrence;
  a head's output through a gated RMSNorm (``w_n * o / rms(o) * silu(z)``);
  ``W_out``.  In chunks for the learner, one position at a time for the
  actor.
- ``K``  channel-gated delta rule (Kimi Delta Attention): ``q, k, v = u
  W_q, u W_k, u W_v``, each through its own causal depth-wise conv + silu;
  q, k L2-normalised a head, ``q / sqrt(d)``; ``g = -exp(A_log) softplus(f_b
  (f_a u) + dt_bias)`` a KEY CHANNEL (``A_log`` a head, ``dt_bias`` a
  channel, ``f`` a low-rank pair), ``beta = sigmoid(u W_b)`` a head; the
  recurrence with ``S' = Diag(exp(g_t)) S_{t-1}`` (models/gated_delta.py
  ``kda_chunked``); a head's output through ``w_n * o / rms(o) * sigmoid(g_b
  (g_a u) + b)``; ``W_out``.  In chunks for the learner, one position at a
  time for the actor (carry: three conv tails and the float32 state).
- ``L``  latent attention, position-free: ``q = u W_q`` (heads x (nope +
  rope)); ``[c | k_r] = u W_kva``; ``c <- RMSNorm(c)``; ``[k_n | v] = c
  W_kvb`` a head; a head's key is ``[k_n | k_r]``, ``k_r`` SHARED by all
  heads; causal softmax at scale ``(nope + rope)^-1/2``; ``W_o``.  The
  learner expands keys and values and attends in query blocks; the actor
  keeps a ring of LATENTS ``[c | k_r]`` (576 values a position where the
  expanded keys and values are 32 x 320) and attends in the absorbed form
  ``(W_kvb_k^T q_n) . c + q_r . k_r``, values rebuilt from the weighted
  latent.
- ``C``  gated short convolution: ``[B | C | x] = u W_in`` (three of
  ``d_model``); ``z = B * x``; ``y_t = sum_j w_j * z_{t-(K-1)+j}``, a causal
  depth-wise conv of ``conv_kernel`` taps, zero before t = 0, no bias and no
  activation; ``out = (C * y) W_out``, under ``model.sconv`` with the
  gates and taps under ``sconv.mix``.  The actor carries the last K - 1
  rows of ``z``.
- ``F``  a dense SwiGLU block ``(silu(u W_gate) * u W_up) W_down``.
- ``*``  causal grouped-query attention in query blocks: no ``(B, heads, T,
  T)`` array exists.  Position-free as published for the first preset;
  with the second's options a query / key RMSNorm a head (``qk_norm``),
  rotary on the first ``rotary_dim`` dimensions of each head (rotate-half;
  position = index in the window, for the actor the carry's count: its key
  ring holds keys rotated at their own position) and a sigmoid output gate
  from the doubled query projection (``attn_gate``).
- ``E``  sigmoid-scored experts: top-k of ``s + b_sel`` over ALL experts,
  weights ``s`` (without ``b_sel``) normalised, times ``route_scale``;
  ``relu(u W_up)^2 W_down`` per expert, plus one shared expert (none where
  ``shared_width`` is 0; with ``route_eps`` the weights are divided by their
  sum + ``route_eps``).  The layer
  is TOLD WHICH EXPERTS IT HOLDS (``first_expert``, ``experts_held``): it
  routes over all of them and computes its own experts' part for the rows
  routed to them, sorted by expert and multiplied as grouped matmuls (on a
  TPU the Pallas ``megablox.gmm`` that ships with JAX, elsewhere
  ``jax.lax.ragged_dot``): no capacity, no dropped token at any skew,
  device work by the rows routed here, in steps of a run.  What the absent
  experts would add is left out (one chip of a layer shared by several; the exchange is
  parallel/expert_parallel.py's to add).  ``b_sel`` has no gradient: after
  every update it moves by ``bias_rate`` against each expert's load
  (``balance_selection_bias``), the family's way of spreading the tokens
  over the experts (at ``bias_rate`` 1e-3 slower than Adam's first updates
  move the router: PERF.md section 6).  With ``router = "softmax"``: ``p =
  softmax(u W_r)`` over all experts, top-k of ``p``, weights ``p`` of the
  chosen normalised to sum 1, no ``b_sel``; balance is an auxiliary loss
  ``n_experts * sum_e f_e P_e`` a layer (``f_e`` the share of (token,
  choice) pairs that chose expert e, ``P_e`` the mean of ``p_e``; over ALL
  experts) that joins the TD loss with weight ``aux_weight``.  With
  ``gated_experts``: SwiGLU experts ``(silu(u W_gate) * u W_up) W_down``,
  and the shared expert the same, behind ``sigmoid(u . shared_gate)`` where
  the preset has ``shared_expert_gate``.  Router and expert form are chosen
  apart: the third preset routes by sigmoid scores with ``b_sel`` over
  SwiGLU experts and an ungated SwiGLU shared expert, the fourth the same
  with no shared expert.

Contracts shared with the other sequence families (recurrent actor,
evaluator, sequence learner): ``window_q(frames (B, T, H, W))`` is the
learner's one causal pass, zero state at position 0; ``__call__(obs,
carry)`` acts one step through a carry of (conv tails, SSM or delta-rule
states, short-convolution tails, key / value caches or a latent ring, count)
whose leaves all lead with the batch dimension;
``state_for_segment`` stores the 1-dim placeholder of every ``dtqn*``
model.  bfloat16 matmuls; float32 parameters, router, softmax, norms,
``dt``, ``A`` and scan state.  Every mixer runs under ``jax.checkpoint``
and names its device work ``model.*`` (utils/profiling.py) INSIDE the
checkpointed function and inside each scan body.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from pytorch_distributed_tpu.models.gated_delta import (
    gated_delta_chunked, gated_delta_step, kda_chunked,
)
from pytorch_distributed_tpu.ops.sequence_losses import AUX_LOSS_KEY
from pytorch_distributed_tpu.utils.profiling import (
    SCOPE_ATTN, SCOPE_EMBED, SCOPE_GDN, SCOPE_HEAD, SCOPE_KDA, SCOPE_MLA,
    SCOPE_MLP, SCOPE_MOE, SCOPE_MOE_EXPERTS, SCOPE_MOE_ROUTE,
    SCOPE_MOE_SHARED, SCOPE_SCONV, SCOPE_SCONV_MIX, SCOPE_SSM,
)

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class HybridPreset:
    """Every width of the trunk, in ONE place."""

    pattern: str                 # one letter a layer: M, D, K, C, L, F, E or *
    d_model: int
    conv_kernel: int             # M, D, K and C: the causal depth-wise conv
    # *: grouped-query attention
    attn_heads: int
    kv_heads: int
    attn_head_dim: int
    attn_block: int              # query rows per block
    # E: experts
    n_experts: int               # routed over
    top_k: int
    expert_width: int
    shared_width: int            # 0: no shared expert
    route_scale: float
    experts_held: int            # computed here ...
    first_expert: int            # ... starting at this one
    bias_rate: float = 1e-3      # b_sel's step against the load, an update
    norm_eps: float = 1e-5
    # M: Mamba-2
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    chunk: int = 0
    dt_min: float = 1e-3
    dt_max: float = 0.1
    dt_floor: float = 1e-4
    # D: gated delta rule
    gdn_k_heads: int = 0
    gdn_v_heads: int = 0         # each key head serves v_heads / k_heads
    gdn_head_dim: int = 0        # of keys and of values
    gdn_chunk: int = 64
    # what the second published trunk does otherwise
    norm_plus_one: bool = False  # norm scales are 1 + w, w initialised 0
    qk_norm: bool = False        # *: RMSNorm of queries and keys, a head
    rotary_dim: int = 0          # *: leading dimensions of a head rotated
    rope_theta: float = 1e4
    attn_gate: bool = False      # *: sigmoid output gate beside the query
    router: str = "sigmoid"      # E: or "softmax" (no b_sel, no scale)
    gated_experts: bool = False  # E: SwiGLU experts, gated shared expert
    aux_weight: float = 0.0      # E: weight of the load-balancing loss
    # what the third published trunk adds
    shared_expert_gate: bool = True  # E, gated: sigmoid gate on the shared
    run_headroom: int = 2        # E: the first run of sorted rows holds this
    #                              many times the balanced load (expert_runs)
    kda_heads: int = 0           # K: heads of keys and of values alike
    kda_head_dim: int = 0
    kda_gate_rank: int = 0       # K: of the decay's and the output gate's pair
    kda_chunk: int = 64
    kda_sub: int = 16            # K: sub-block whose decays are taken in pairs
    mla_nope: int = 0            # L: a head's key part rebuilt from the latent
    mla_rope: int = 0            # L: the key part all heads share (unrotated)
    mla_v: int = 0               # L: a head's value
    mla_latent: int = 0
    mlp_width: int = 0           # F
    # what the fourth published trunk adds
    route_eps: float = 0.0       # E, sigmoid: weights / (their sum + this)
    # the step metrics of "nemotron-h-9" are pinned with its lowered step
    # (CHANGES.md, PR 31); a preset made since also counts the rows its
    # grouped matmuls were handed (learner/moe_rows_computed)
    count_rows_computed: bool = True

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def gdn_key_dim(self) -> int:
        return self.gdn_k_heads * self.gdn_head_dim

    @property
    def gdn_value_dim(self) -> int:
        return self.gdn_v_heads * self.gdn_head_dim

    @property
    def gdn_conv_dim(self) -> int:
        return 2 * self.gdn_key_dim + self.gdn_value_dim

    @property
    def kda_dim(self) -> int:
        return self.kda_heads * self.kda_head_dim


PRESETS: Dict[str, HybridPreset] = {
    # layers 0-8 of the published 52 (one period: 4 M, 4 E, 1 *), every
    # width as published; 8 of the 128 experts held: one of the 16 chips
    # that share each layer
    "nemotron-h-9": HybridPreset(
        pattern="MEMEM*EME", d_model=2688,
        ssm_heads=64, ssm_head_dim=64, ssm_state=128, ssm_groups=8,
        conv_kernel=4, chunk=128, dt_min=1e-3, dt_max=0.1, dt_floor=1e-4,
        attn_heads=32, kv_heads=2, attn_head_dim=128, attn_block=256,
        n_experts=128, top_k=6, expert_width=1856, shared_width=3712,
        route_scale=2.5, experts_held=8, first_expert=0,
        count_rows_computed=False),
    # layers 0-3 of the published 48 (one period: three gated-delta-rule
    # mixers to one gated attention, an expert layer after each: eight
    # blocks), every width as published; 32 of the 512 experts held: one of
    # the 16 chips that share each layer
    "qwen3-next-4": HybridPreset(
        pattern="DEDEDE*E", d_model=2048, conv_kernel=4,
        gdn_k_heads=16, gdn_v_heads=32, gdn_head_dim=128, gdn_chunk=64,
        attn_heads=16, kv_heads=2, attn_head_dim=256, attn_block=256,
        n_experts=512, top_k=10, expert_width=512, shared_width=512,
        route_scale=1.0, experts_held=32, first_expert=0, norm_eps=1e-6,
        norm_plus_one=True, qk_norm=True, rotary_dim=64, rope_theta=1e7,
        attn_gate=True, router="softmax", gated_experts=True,
        aux_weight=1e-3),
    # layers 0-4 of the published 27: the leading dense layer once and one
    # whole period of what follows (three channel-gated delta-rule mixers to
    # one latent attention; a dense block after the first mixer, an expert
    # block after each other one: ten blocks), every width as published; 8
    # of the 256 experts held: one of the 32 chips that share each layer
    "kimi-linear-5": HybridPreset(
        pattern="KFKEKELEKE", d_model=2304, conv_kernel=4,
        kda_heads=32, kda_head_dim=128, kda_gate_rank=128, kda_chunk=64,
        kda_sub=16,
        attn_heads=32, kv_heads=32, attn_head_dim=192, attn_block=256,
        mla_nope=128, mla_rope=64, mla_v=128, mla_latent=512,
        mlp_width=9216,
        n_experts=256, top_k=8, expert_width=1024, shared_width=1024,
        route_scale=2.446, experts_held=8, first_expert=0, norm_eps=1e-5,
        gated_experts=True, shared_expert_gate=False, run_headroom=4),
    # layers 1-5 of the published 24: the second leading dense layer (a
    # short-convolution mixer and the dense SwiGLU) and one whole period of
    # what follows (attention, then three short-convolution mixers, an expert
    # block after each: ten blocks), every width as published; 8 of the 32
    # experts held: one of the 4 chips that share each layer
    "lfm2-moe-5": HybridPreset(
        pattern="CF*ECECECE", d_model=2048, conv_kernel=3,
        attn_heads=32, kv_heads=8, attn_head_dim=64, attn_block=256,
        qk_norm=True, rotary_dim=64, rope_theta=1e6, mlp_width=7168,
        n_experts=32, top_k=4, expert_width=1792, shared_width=0,
        route_scale=1.0, route_eps=1e-6, experts_held=8, first_expert=0,
        gated_experts=True, router="sigmoid", norm_eps=1e-5),
    # CPU tests: every mechanism, no width
    "tiny": HybridPreset(
        pattern="ME*E", d_model=32,
        ssm_heads=4, ssm_head_dim=8, ssm_state=8, ssm_groups=2,
        conv_kernel=4, chunk=4, dt_min=1e-3, dt_max=0.1, dt_floor=1e-4,
        attn_heads=4, kv_heads=2, attn_head_dim=8, attn_block=4,
        n_experts=16, top_k=3, expert_width=16, shared_width=24,
        route_scale=2.5, experts_held=4, first_expert=0),
    "tiny-qwen": HybridPreset(
        pattern="DE*E", d_model=32, conv_kernel=4,
        gdn_k_heads=2, gdn_v_heads=4, gdn_head_dim=8, gdn_chunk=4,
        attn_heads=4, kv_heads=2, attn_head_dim=8, attn_block=4,
        n_experts=16, top_k=3, expert_width=16, shared_width=16,
        route_scale=1.0, experts_held=4, first_expert=0, norm_eps=1e-6,
        norm_plus_one=True, qk_norm=True, rotary_dim=4, rope_theta=1e7,
        attn_gate=True, router="softmax", gated_experts=True,
        aux_weight=1e-3),
    "tiny-kimi": HybridPreset(
        pattern="KFLE", d_model=32, conv_kernel=4,
        kda_heads=4, kda_head_dim=8, kda_gate_rank=8, kda_chunk=4, kda_sub=2,
        attn_heads=4, kv_heads=4, attn_head_dim=12, attn_block=4,
        mla_nope=8, mla_rope=4, mla_v=8, mla_latent=16, mlp_width=48,
        n_experts=16, top_k=3, expert_width=16, shared_width=16,
        route_scale=2.446, experts_held=4, first_expert=0,
        gated_experts=True, shared_expert_gate=False),
    "tiny-lfm2": HybridPreset(
        pattern="CF*ECE", d_model=32, conv_kernel=3,
        attn_heads=4, kv_heads=2, attn_head_dim=8, attn_block=4,
        qk_norm=True, rotary_dim=8, rope_theta=1e6, mlp_width=48,
        n_experts=16, top_k=4, expert_width=16, shared_width=0,
        route_scale=1.0, route_eps=1e-6, experts_held=4, first_expert=0,
        gated_experts=True, router="sigmoid", norm_eps=1e-5),
}


def is_matmul_weight(name: str) -> bool:
    """Leaves the trunk reads only through bfloat16 matmuls (named
    ``w_*``): the ones a bfloat16 target copy may round."""
    return name.startswith("w_")


def bf16_target(params: Any) -> Any:
    """The target network's copy for this model type: matmul weights in
    bfloat16 (they are cast to it at every use anyway), the rest
    (norms, router, ``dt_bias``, ``A_log``, ``D``, conv, head) float32.
    14 bytes a parameter of train state (weights, Adam's two moments,
    this copy) and not 16."""
    def cast(path, leaf):
        name = getattr(path[-1], "key", "")
        return leaf.astype(jnp.bfloat16) if is_matmul_weight(name) \
            else jnp.array(leaf)

    return jax.tree_util.tree_map_with_path(cast, params)


# ---------------------------------------------------------------------------
# the layer equations, as pure functions of a layer's parameter dict
# ---------------------------------------------------------------------------

def rms_norm(x, scale, eps: float, groups: int = 1):
    """RMSNorm in float32 over the last axis (in ``groups`` equal parts),
    learned scale; returns float32."""
    x = x.astype(F32)
    shape = x.shape
    xg = x.reshape(*shape[:-1], groups, shape[-1] // groups)
    xg = xg * jax.lax.rsqrt(jnp.mean(jnp.square(xg), -1, keepdims=True) + eps)
    return xg.reshape(shape) * scale


def norm_scale(w, c: "HybridPreset"):
    """A norm's scale from its parameter: ``w``, or ``1 + w`` where the
    preset's norms are zero-centred."""
    return 1.0 + w if c.norm_plus_one else w


def _mm(x, w, cd):
    """``x @ w`` with both operands in the compute dtype, float32 out."""
    return jnp.matmul(x.astype(cd), w.astype(cd), preferred_element_type=F32)


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def ssd_chunked(x, dt, A, Bm, Cm, chunk: int, cd=jnp.bfloat16,
                state_dtype=F32):
    """The state-space recurrence over a window from a zero state, chunk
    by chunk: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t =
    S_t C_t``.  x (b,T,h,p), dt (b,T,h) float32 after softplus, A (h,)
    float32 negative, Bm / Cm (b,T,g,n); head h reads group h // (h/g).
    Inside a chunk the recurrence is two matmuls through the decay
    matrix; between chunks a scan carries the (h, p, n) state in
    ``state_dtype``.  Returns (y (b,T,h,p) float32, final state)."""
    b, T, h, p = x.shape
    g, n = Bm.shape[2:]
    k, L, nc = h // g, chunk, T // chunk
    assert nc * L == T, (T, chunk)
    xdt = (x.astype(F32) * dt[..., None]).reshape(b, nc, L, g, k, p)
    tril = jnp.tril(jnp.ones((L, L), bool))
    # cumulative log-decay inside each chunk, as a product with the
    # triangle of ones: the chip's cumsum (a reduce-window) took as long as
    # the layer's largest matmul
    cum = jnp.einsum("bcsgk,ls->bcgkl", (dt * A).reshape(b, nc, L, g, k),
                     tril.astype(F32),
                     precision=jax.lax.Precision.HIGHEST)  # (b,c,g,k,L)
    Bc = Bm.astype(cd).reshape(b, nc, L, g, n)
    Cc = Cm.astype(cd).reshape(b, nc, L, g, n)
    # inside a chunk: y_l += sum_{s<=l} (C_l . B_s) exp(cum_l - cum_s) x_s
    G = jnp.einsum("bclgn,bcsgn->bcgls", Cc, Bc, preferred_element_type=F32)
    decay = jnp.exp(jnp.where(tril, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))                   # (b,c,g,k,l,s)
    y = jnp.einsum("bcgkls,bcsgkp->bclgkp",
                   (G[:, :, :, None] * decay).astype(cd), xdt.astype(cd),
                   preferred_element_type=F32)
    # what each chunk adds to the state by its end
    to_end = jnp.exp(cum[..., -1:] - cum)                  # (b,c,g,k,s)
    xw = (xdt * jnp.moveaxis(to_end, -1, 2)[..., None]).astype(cd)
    S_local = jnp.einsum("bcsgn,bcsgkp->bcgkpn", Bc, xw,
                         preferred_element_type=F32)
    chunk_decay = jnp.exp(cum[..., -1])                    # (b,c,g,k)

    def carry_state(S, inp):
        with jax.named_scope(SCOPE_SSM):
            S_add, dec = inp
            S_next = (dec[..., None, None] * S.astype(F32)
                      + S_add).astype(state_dtype)
        return S_next, S

    S_end, S_prev = jax.lax.scan(
        carry_state, jnp.zeros((b, g, k, p, n), state_dtype),
        (jnp.moveaxis(S_local, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    S_prev = jnp.moveaxis(S_prev, 0, 1)                    # (b,c,g,k,p,n)
    y_off = jnp.einsum("bclgn,bcgkpn->bclgkp", Cc, S_prev.astype(cd),
                       preferred_element_type=F32)
    y = y + y_off * jnp.moveaxis(jnp.exp(cum), -1, 2)[..., None]
    return y.reshape(b, T, h, p), S_end.reshape(b, h, p, n)


def _split_in_proj(zxbcdt, c: HybridPreset):
    z, xBC, dt = jnp.split(
        zxbcdt, [c.d_inner, c.d_inner + c.conv_dim], axis=-1)
    return z, xBC, dt


def _split_xbc(xBC, c: HybridPreset):
    gn = c.ssm_groups * c.ssm_state
    x, Bm, Cm = jnp.split(xBC, [c.d_inner, c.d_inner + gn], axis=-1)
    lead = x.shape[:-1]
    return (x.reshape(*lead, c.ssm_heads, c.ssm_head_dim),
            Bm.reshape(*lead, c.ssm_groups, c.ssm_state),
            Cm.reshape(*lead, c.ssm_groups, c.ssm_state))


def _ssm_out(p, y, x, z, c: HybridPreset, cd):
    """``+ D x``, the gate, the grouped norm and the out projection."""
    y = y + p["D"][:, None] * x.astype(F32)
    y = y.reshape(*y.shape[:-2], c.d_inner)
    y = rms_norm(y * jax.nn.silu(z.astype(F32)), p["gate_norm"],
                 c.norm_eps, groups=c.ssm_groups)
    return _mm(y, p["w_out"], cd)


def mamba_window(p, u, c: HybridPreset, cd):
    """One M mixer over (b, T, d) normed input, zero state at t = 0; T
    padded up to whole chunks (causal, and ``dt`` = 0 in the padding, so it
    changes neither an output nor the state).  Returns (out (b, T, d)
    float32, the state after position T - 1 (b, h, p, n) float32)."""
    with jax.named_scope(SCOPE_SSM):
        b, T, _ = u.shape
        pad = -T % c.chunk
        z, xBC, dt = _split_in_proj(_mm(u, p["w_in"], cd), c)
        # causal depth-wise conv: tap j reads position t - (K-1) + j
        K = c.conv_kernel
        xp = jnp.pad(xBC, ((0, 0), (K - 1, 0), (0, 0)))
        xBC = jax.nn.silu(sum(xp[:, j:j + T] * p["conv_w"][j]
                              for j in range(K)) + p["conv_b"])
        x, Bm, Cm = _split_xbc(xBC, c)
        dt = jax.nn.softplus(dt + p["dt_bias"])
        A = -jnp.exp(p["A_log"])
        grow = lambda t: jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        y, S = ssd_chunked(grow(x), grow(dt), A, grow(Bm), grow(Cm),
                           c.chunk, cd)
        return _ssm_out(p, y[:, :T], x, z, c, cd), S


def mamba_step(p, u, tail, S, c: HybridPreset, cd):
    """One position: u (b, d); tail (b, K-1, conv_dim) the conv's last
    inputs; S (b, h, p, n) float32."""
    with jax.named_scope(SCOPE_SSM):
        z, xBC, dt = _split_in_proj(_mm(u, p["w_in"], cd), c)
        taps = jnp.concatenate([tail, xBC[:, None]], axis=1)   # (b, K, .)
        xBC = jax.nn.silu(jnp.einsum("bkc,kc->bc", taps, p["conv_w"])
                          + p["conv_b"])
        x, Bm, Cm = _split_xbc(xBC, c)
        dt = jax.nn.softplus(dt + p["dt_bias"])                # (b, h)
        k = c.ssm_heads // c.ssm_groups
        Bh, Ch = (jnp.repeat(t, k, axis=1) for t in (Bm, Cm))  # (b, h, n)
        xdt = x * dt[..., None]
        S = (jnp.exp(dt * -jnp.exp(p["A_log"]))[..., None, None] * S
             + xdt[..., None] * Bh[:, :, None, :])
        y = jnp.einsum("bhpn,bhn->bhp", S, Ch)
        return _ssm_out(p, y, x, z, c, cd), taps[:, 1:], S


def _gdn_inputs(p, qkv, ba, c: HybridPreset):
    """After the conv: q, k (.., h_k, d) L2-normalised a head and q scaled;
    v (.., h_v, d); the log-decay g and the write strength beta (.., h_v),
    float32."""
    lead, d = qkv.shape[:-1], c.gdn_head_dim
    q, k, v = jnp.split(qkv, [c.gdn_key_dim, 2 * c.gdn_key_dim], axis=-1)
    unit = lambda t: t * jax.lax.rsqrt(
        jnp.sum(jnp.square(t), -1, keepdims=True) + 1e-6)
    q = unit(q.reshape(*lead, c.gdn_k_heads, d)) / math.sqrt(d)
    k = unit(k.reshape(*lead, c.gdn_k_heads, d))
    b, a = jnp.split(ba, 2, axis=-1)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
    return q, k, v.reshape(*lead, c.gdn_v_heads, d), g, jax.nn.sigmoid(b)


def _gdn_out(p, o, z, c: HybridPreset, cd):
    """A head's gated norm (``w_n * o / rms(o) * silu(z)``), then the out
    projection."""
    z = z.astype(F32).reshape(o.shape)
    o = rms_norm(o, p["gate_norm"], c.norm_eps) * jax.nn.silu(z)
    return _mm(o.reshape(*o.shape[:-2], c.gdn_value_dim), p["w_out"], cd)


def gdn_window(p, u, c: HybridPreset, cd):
    """One D mixer over (b, T, d) normed input, zero state at t = 0; T
    padded up to whole chunks (``g`` = ``beta`` = 0 in the padding: it decays
    nothing and writes nothing).  Returns (out (b, T, d) float32, the state
    after position T - 1 (b, h_v, d_k, d_v) float32, the mean decay
    ``exp(g)``: how long the memory is)."""
    with jax.named_scope(SCOPE_GDN):
        b, T, _ = u.shape
        pad = -T % c.gdn_chunk
        qkv, z = jnp.split(_mm(u, p["w_qkvz"], cd), [c.gdn_conv_dim], axis=-1)
        K = c.conv_kernel
        xp = jnp.pad(qkv, ((0, 0), (K - 1, 0), (0, 0)))
        qkv = jax.nn.silu(sum(xp[:, j:j + T] * p["conv_w"][j]
                              for j in range(K)))
        q, k, v, g, beta = _gdn_inputs(p, qkv, _mm(u, p["w_ba"], cd), c)
        grow = lambda t: jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        o, S = gated_delta_chunked(grow(q), grow(k), grow(v), grow(g),
                                   grow(beta), c.gdn_chunk, cd)
        return (_gdn_out(p, o[:, :T], z, c, cd), S,
                jax.lax.stop_gradient(jnp.mean(jnp.exp(g))))


def gdn_step(p, u, tail, S, c: HybridPreset, cd):
    """One position: u (b, d); tail (b, K-1, gdn_conv_dim) the conv's last
    inputs; S (b, h_v, d_k, d_v) float32."""
    with jax.named_scope(SCOPE_GDN):
        qkv, z = jnp.split(_mm(u, p["w_qkvz"], cd), [c.gdn_conv_dim], axis=-1)
        taps = jnp.concatenate([tail, qkv[:, None]], axis=1)   # (b, K, .)
        qkv = jax.nn.silu(jnp.einsum("bkc,kc->bc", taps, p["conv_w"]))
        q, k, v, g, beta = _gdn_inputs(p, qkv, _mm(u, p["w_ba"], cd), c)
        o, S = gated_delta_step(q, k, v, g, beta, S)
        return _gdn_out(p, o, z, c, cd), taps[:, 1:], S


def _causal_conv(x, w):
    """Causal depth-wise conv over (b, T, c), zero before t = 0: tap j reads
    position t - (K-1) + j."""
    K, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(xp[:, j:j + T] * w[j] for j in range(K))


def _conv_silu(x, w):
    """Causal depth-wise conv + silu over (b, T, c)."""
    return jax.nn.silu(_causal_conv(x, w))


def _kda_inputs(p, u, q, k, v, c: HybridPreset, cd):
    """After the convs: q, k (.., h, d) L2-normalised a head and q scaled; v
    (.., h, d); the log-decay g (.., h, d) a key channel and the write
    strength beta (.., h), float32."""
    lead, h, d = q.shape[:-1], c.kda_heads, c.kda_head_dim
    unit = lambda t: t * jax.lax.rsqrt(
        jnp.sum(jnp.square(t), -1, keepdims=True) + 1e-6)
    q = unit(q.reshape(*lead, h, d)) / math.sqrt(d)
    k = unit(k.reshape(*lead, h, d))
    f = _mm(_mm(u, p["w_fa"], cd), p["w_fb"], cd) + p["dt_bias"]
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        f.reshape(*lead, h, d))
    beta = jax.nn.sigmoid(_mm(u, p["w_b"], cd))
    return q, k, v.reshape(*lead, h, d), g, beta


def _kda_out(p, u, o, c: HybridPreset, cd):
    """A head's gated norm (``w_n * o / rms(o) * sigmoid(g_b (g_a u) +
    b)``), then the out projection."""
    gate = _mm(_mm(u, p["w_ga"], cd), p["w_gb"], cd) + p["gate_bias"]
    o = rms_norm(o, p["gate_norm"], c.norm_eps) * jax.nn.sigmoid(
        gate.reshape(o.shape))
    return _mm(o.reshape(*o.shape[:-2], c.kda_dim), p["w_out"], cd)


def kda_window(p, u, c: HybridPreset, cd):
    """One K mixer over (b, T, d) normed input, zero state at t = 0; T
    padded up to whole chunks (``g`` = ``beta`` = 0 in the padding).  Returns
    (out (b, T, d) float32, the state after position T - 1 (b, h, d_k, d_v)
    float32, the decay ``exp(g)`` of each key channel, its mean over the
    window (h, d_k): how long each channel remembers)."""
    with jax.named_scope(SCOPE_KDA):
        T = u.shape[1]
        pad = -T % c.kda_chunk
        q, k, v, g, beta = _kda_inputs(p, u, *(
            _conv_silu(_mm(u, p[f"w_{x}"], cd), p[f"conv_{x}"])
            for x in "qkv"), c, cd)
        grow = lambda t: jnp.pad(
            t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        o, S = kda_chunked(grow(q), grow(k), grow(v), grow(g), grow(beta),
                           c.kda_chunk, c.kda_sub, cd)
        return (_kda_out(p, u, o[:, :T], c, cd), S,
                jax.lax.stop_gradient(jnp.mean(jnp.exp(g), axis=(0, 1))))


def kda_step(p, u, tails, S, c: HybridPreset, cd):
    """One position: u (b, d); tails, three (b, K-1, h d): the last inputs
    of the convs of q, k and v; S (b, h, d_k, d_v) float32."""
    with jax.named_scope(SCOPE_KDA):
        taps = [jnp.concatenate([tail, _mm(u, p[f"w_{x}"], cd)[:, None]],
                                axis=1) for x, tail in zip("qkv", tails)]
        q, k, v, g, beta = _kda_inputs(p, u, *(
            jax.nn.silu(jnp.einsum("bkc,kc->bc", t, p[f"conv_{x}"]))
            for x, t in zip("qkv", taps)), c, cd)
        o, S = gated_delta_step(q, k, v, g, beta, S)
        return _kda_out(p, u, o, c, cd), [t[:, 1:] for t in taps], S


def short_conv_window(p, u, c: HybridPreset, cd):
    """One C mixer over (b, T, d) normed input, zero before t = 0: ``[B | C
    | x] = u W_in``, the causal depth-wise conv of ``B * x``, gated by ``C``,
    ``W_out``.  Out (b, T, d) float32."""
    with jax.named_scope(SCOPE_SCONV):
        gate_b, gate_c, x = jnp.split(_mm(u, p["w_in"], cd), 3, axis=-1)
        with jax.named_scope(SCOPE_SCONV_MIX):
            y = gate_c * _causal_conv(gate_b * x, p["conv_w"])
        return _mm(y, p["w_out"], cd)


def short_conv_step(p, u, tail, c: HybridPreset, cd):
    """One position: u (b, d); tail (b, K-1, d) the last K - 1 rows of ``B
    * x`` the conv reads."""
    with jax.named_scope(SCOPE_SCONV):
        gate_b, gate_c, x = jnp.split(_mm(u, p["w_in"], cd), 3, axis=-1)
        with jax.named_scope(SCOPE_SCONV_MIX):
            taps = jnp.concatenate([tail, (gate_b * x)[:, None]], axis=1)
            y = gate_c * jnp.einsum("bkc,kc->bc", taps, p["conv_w"])
        return _mm(y, p["w_out"], cd), taps[:, 1:]


def mlp_block(p, u, cd):
    """One F mixer: a dense SwiGLU block over (.., d) normed input."""
    with jax.named_scope(SCOPE_MLP):
        return _mm(jax.nn.silu(_mm(u, p["w_gate"], cd))
                   * _mm(u, p["w_up"], cd), p["w_down"], cd)


def _attend(q, k, v, mask, cd):
    """softmax(q k^T / sqrt(hd)) v for one block of queries: q (b, kv, r,
    Tq, hd), k / v (b, kv, Tk, hd), mask (.., Tq, Tk) or None.  Scores and
    softmax in float32."""
    s = jnp.einsum("bgrqd,bgkd->bgrqk", q.astype(cd), k.astype(cd),
                   preferred_element_type=F32) / math.sqrt(q.shape[-1])
    if mask is not None:
        s = jnp.where(mask, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bgrqk,bgkd->bgrqd", w.astype(cd), v.astype(cd),
                      preferred_element_type=F32)


def rotary(x, pos, c: HybridPreset):
    """Rotate-half rotary on the first ``rotary_dim`` dimensions of each
    head: x (.., heads, hd) float32, pos broadcastable to x's leading
    dimensions."""
    half = c.rotary_dim // 2
    freq = c.rope_theta ** (-jnp.arange(half, dtype=F32) / half)
    angle = jnp.asarray(pos, F32)[..., None, None] * freq      # (.., 1, half)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2, rest = jnp.split(x, [half, 2 * half], axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def _qkv(p, u, pos, c: HybridPreset, cd):
    """Queries, keys and values, (.., heads, hd), and the output gate (..,
    attn_heads * hd) or None.  With the preset's options: ``[q | gate] = u
    W_q`` a head (``attn_gate``), an RMSNorm of q and k a head
    (``qk_norm``), rotary at ``pos`` (``rotary_dim``)."""
    lead, hd = u.shape[:-1], c.attn_head_dim
    q = _mm(u, p["w_q"], cd).reshape(*lead, c.attn_heads, -1)
    gate = None
    if c.attn_gate:
        q, gate = q[..., :hd], q[..., hd:].reshape(*lead, -1)
    k = _mm(u, p["w_k"], cd).reshape(*lead, c.kv_heads, hd)
    v = _mm(u, p["w_v"], cd).reshape(*lead, c.kv_heads, hd)
    if c.qk_norm:
        q = rms_norm(q, norm_scale(p["q_norm"], c), c.norm_eps)
        k = rms_norm(k, norm_scale(p["k_norm"], c), c.norm_eps)
    if c.rotary_dim:
        q, k = rotary(q, pos, c), rotary(k, pos, c)
    return q, k, v, gate


def _causal_blocks(q, k, v, Q: int, scope: str, cd):
    """Causal attention over a window, block of ``Q`` queries by block, a
    block reading only the keys up to its own end (the causal half), each
    under ``jax.checkpoint`` and the caller's ``scope``: q (b, kv, r, T,
    hd), k / v (b, kv, T, .) -> (b, kv, r, T, hd_v) float32."""
    T = q.shape[3]

    @jax.checkpoint
    def block(qb, kb, vb, lo):
        with jax.named_scope(scope):
            rows = lo + jnp.arange(qb.shape[3])[:, None]
            return _attend(qb, kb, vb,
                           jnp.arange(kb.shape[2])[None, :] <= rows, cd)

    out = [block(q[:, :, :, lo:lo + Q], k[:, :, :lo + Q], v[:, :, :lo + Q],
                 lo) for lo in range(0, T, Q)]
    return jnp.concatenate(out, axis=3)


def attention_window(p, u, c: HybridPreset, cd):
    """One * mixer over (b, T, d): causal, each key-value head shared by
    ``attn_heads / kv_heads`` query heads, in query blocks."""
    with jax.named_scope(SCOPE_ATTN):
        b, T, _ = u.shape
        r, Q = c.attn_heads // c.kv_heads, min(c.attn_block, T)
        q, k, v, gate = _qkv(p, u, jnp.arange(T), c, cd)
        q = q.reshape(b, T, c.kv_heads, r, -1).transpose(0, 2, 3, 1, 4)
        k, v = (t.transpose(0, 2, 1, 3) for t in (k, v))       # (b,kv,T,hd)
        o = _causal_blocks(q, k, v, Q, SCOPE_ATTN, cd)         # (b,kv,r,T,hd)
        o = o.transpose(0, 3, 1, 2, 4).reshape(b, T, -1)
        if gate is not None:
            o = o * jax.nn.sigmoid(gate)
        return _mm(o, p["w_o"], cd)


def _ring_put(ring, new, count):
    """Each env's newest entry ``new`` (b, ..) into slot ``count % W`` of its
    ring (b, W, ..), in the ring's dtype."""
    at = (count % ring.shape[1]).astype(jnp.int32)
    return jax.vmap(lambda r, x, i: jax.lax.dynamic_update_slice_in_dim(
        r, x[None], i, 0))(ring, new.astype(ring.dtype), at)


def attention_step(p, u, kc, vc, count, c: HybridPreset, cd):
    """One position against the caches kc / vc (b, W, kv, hd), a ring of
    the last W keys (position-free, or each key rotated at its own position
    before it is stored: either way their order in the ring does not
    matter); ``count`` (b,) positions seen before this one."""
    with jax.named_scope(SCOPE_ATTN):
        b, W = kc.shape[:2]
        r = c.attn_heads // c.kv_heads
        q, k, v, gate = _qkv(p, u, count, c, cd)
        kc, vc = _ring_put(kc, k, count), _ring_put(vc, v, count)
        valid = jnp.arange(W)[None, :] < jnp.minimum(count + 1, W)[:, None]
        o = _attend(q.reshape(b, c.kv_heads, r, 1, -1),
                    kc.transpose(0, 2, 1, 3), vc.transpose(0, 2, 1, 3),
                    valid[:, None, None, None, :], cd)
        o = o.reshape(b, -1)
        if gate is not None:
            o = o * jax.nn.sigmoid(gate)
        return _mm(o, p["w_o"], cd), kc, vc


def _mla_latent(p, u, c: HybridPreset, cd):
    """Queries (.., heads, nope + rope) and what a position leaves behind:
    its normalised latent (.., latent) and the key part all heads share
    (.., rope), float32."""
    q = _mm(u, p["w_q"], cd).reshape(*u.shape[:-1], c.attn_heads, -1)
    lat, k_r = jnp.split(_mm(u, p["w_kva"], cd), [c.mla_latent], axis=-1)
    return q, rms_norm(lat, p["kv_norm"], c.norm_eps), k_r


def mla_window(p, u, c: HybridPreset, cd):
    """One L mixer over (b, T, d): keys and values expanded from the
    latent, a head's key ``[k_n | k_r]`` with ``k_r`` the same for every
    head, then causal attention in query blocks as the * mixer's."""
    with jax.named_scope(SCOPE_MLA):
        b, T, _ = u.shape
        h = c.attn_heads
        q, lat, k_r = _mla_latent(p, u, c, cd)
        kv = _mm(lat, p["w_kvb"], cd).reshape(b, T, h, c.mla_nope + c.mla_v)
        k = jnp.concatenate([kv[..., :c.mla_nope], jnp.broadcast_to(
            k_r[:, :, None], (b, T, h, c.mla_rope))], axis=-1)
        q = q.transpose(0, 2, 1, 3)[:, :, None]             # (b,h,1,T,hd)
        k, v = (t.transpose(0, 2, 1, 3)
                for t in (k, kv[..., c.mla_nope:]))         # (b,h,T,.)
        o = _causal_blocks(q, k, v, min(c.attn_block, T), SCOPE_MLA, cd)
        return _mm(o.transpose(0, 3, 1, 2, 4).reshape(b, T, -1), p["w_o"],
                   cd)


def mla_step(p, u, ring, count, c: HybridPreset, cd):
    """One position against the ring (b, W, latent + rope) of the last W
    positions' ``[normalised latent | shared key part]`` (position-free:
    their order in the ring does not matter), in the absorbed form: a
    head's query is taken into the latent's space (``W_kvb_k^T q_n``), the
    scores are read off the ring itself, and the values are rebuilt from
    the weighted latent.  ``count`` (b,) positions seen before this one."""
    with jax.named_scope(SCOPE_MLA):
        b, W = ring.shape[:2]
        q, lat, k_r = _mla_latent(p, u, c, cd)
        ring = _ring_put(ring, jnp.concatenate([lat, k_r], -1), count)
        w_kvb = p["w_kvb"].astype(cd).reshape(c.mla_latent, c.attn_heads, -1)
        q_abs = jnp.concatenate([jnp.einsum(
            "bhn,lhn->bhl", q[..., :c.mla_nope].astype(cd),
            w_kvb[..., :c.mla_nope], preferred_element_type=F32),
            q[..., c.mla_nope:]], axis=-1)                   # (b,h,lat+rope)
        s = jnp.einsum("bhl,bwl->bhw", q_abs.astype(cd), ring.astype(cd),
                       preferred_element_type=F32) / math.sqrt(q.shape[-1])
        valid = jnp.arange(W)[None, :] < jnp.minimum(count + 1, W)[:, None]
        w = jax.nn.softmax(jnp.where(valid[:, None], s, -jnp.inf), axis=-1)
        ctx = jnp.einsum("bhw,bwl->bhl", w.astype(cd),
                         ring[..., :c.mla_latent].astype(cd),
                         preferred_element_type=F32)
        o = jnp.einsum("bhl,lhv->bhv", ctx.astype(cd),
                       w_kvb[..., c.mla_nope:], preferred_element_type=F32)
        return _mm(o.reshape(b, -1), p["w_o"], cd), ring


def route(p, u, c: HybridPreset):
    """Router in float32 over ALL experts: (chosen (N, k) int32, weights
    (N, k) float32, load (E,) int32: the tokens that chose each expert).
    Sigmoid scores: selected with ``b_sel``, weighed without (``route_aux``
    is the softmax router)."""
    with jax.named_scope(SCOPE_MOE_ROUTE):
        s = jax.nn.sigmoid(jnp.matmul(u.astype(F32), p["router"],
                                      precision=jax.lax.Precision.HIGHEST))
        _, chosen = jax.lax.top_k(s + jax.lax.stop_gradient(p["b_sel"]),
                                  c.top_k)
        w = jnp.take_along_axis(s, chosen, axis=-1)
        total = jnp.sum(w, axis=-1, keepdims=True)
        if c.route_eps:
            total = total + c.route_eps
        w = w / total * c.route_scale
        load = jnp.sum(jax.nn.one_hot(chosen, c.n_experts, dtype=jnp.int32),
                       axis=(0, 1))
        return chosen.astype(jnp.int32), w, load


def route_aux(p, u, c: HybridPreset):
    """The softmax router: ``route``'s three for ``p = softmax(u W_r)``
    over all experts, top-k of ``p``, the chosen's ``p`` normalised to sum
    1, and fourth the layer's load-balancing loss ``n_experts * sum_e f_e
    P_e`` (``f_e`` the share of the (token, choice) pairs that chose expert
    e, ``P_e`` the mean of ``p_e`` over the tokens; 1 at a level load)."""
    with jax.named_scope(SCOPE_MOE_ROUTE):
        prob = jax.nn.softmax(jnp.matmul(
            u.astype(F32), p["router"], precision=jax.lax.Precision.HIGHEST))
        w, chosen = jax.lax.top_k(prob, c.top_k)
        w = w / jnp.sum(w, axis=-1, keepdims=True)
        load = jnp.sum(jax.nn.one_hot(chosen, c.n_experts, dtype=jnp.int32),
                       axis=(0, 1))
        share = load.astype(F32) / (chosen.shape[0] * c.top_k)
        aux = c.n_experts * jnp.sum(share * jnp.mean(prob, axis=0))
        return chosen.astype(jnp.int32), w, load, aux


def _tile(dim: int, most: int) -> int:
    """A tile of at most ``most`` for a dimension the kernel may mask: the
    largest multiple of 128 that divides it, else ``most`` itself (or the
    whole dimension where that is smaller)."""
    if dim <= most:
        return dim
    return next((t for t in range(most, 0, -128) if dim % t == 0), most)


def expert_kernel(kernel: str = "auto") -> str:
    """The grouped matmul ``kernel`` names: "auto" is "pallas" on a TPU
    backend and "xla" elsewhere; "xla", "pallas" and "interpret" stand.
    Resolved once a program, so that the rows handed to the kernel and the
    counter of them (``moe_stats``) follow one choice."""
    if kernel == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    assert kernel in ("xla", "pallas", "interpret"), kernel
    return kernel


def grouped_dot(x, w, sizes, kernel: str = "auto"):
    """``x[rows of group g] @ w[g]`` for rows sorted by group: x (R, k), w
    (H, k, n), sizes (H,) summing to at most R (the rows past their sum
    belong to no group, and what stands there in the output is undefined);
    float32 out.  On a TPU the Pallas grouped matmul that ships with JAX
    (``megablox.gmm``, kernels ``gmm`` and ``tgmm`` in a trace), at tiles
    of 256 rows by up to 896 (the rows: at row 21's 160-row groups 128 and
    512 were both slower), which visits only the row tiles its groups
    cover: the compiler's own ``jax.lax.ragged_dot`` kernel, at 512 x 128
    x 128 tiles, took 3.3 times as long (PERF.md section 6).  Elsewhere
    ``jax.lax.ragged_dot``.  ``kernel``: as ``expert_kernel`` ("interpret":
    the Pallas kernel under the interpreter, tests)."""
    kernel = expert_kernel(kernel)
    if kernel == "xla":
        return jax.lax.ragged_dot(x, w, sizes, preferred_element_type=F32)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    R, k = x.shape
    tiling = (_tile(R, 256), _tile(k, 896), _tile(w.shape[2], 896))
    return gmm(x, w, sizes, F32, tiling, interpret=kernel == "interpret")


def _grouped_ffn(p, u, tok, w_pair, sizes, cd, kernel="auto"):
    """The held experts' part for a run of sorted rows ``tok`` (R,) (rows
    of one expert adjacent, ``sizes`` rows each; the rows that belong to
    no expert here point at token N, and may lie outside every group where
    ``sizes`` sum to less than R): two grouped matmuls (three where the
    experts are gated: ``w_gate`` beside ``w_up``), then each row's result
    is added to its token.  (N, d) float32.  Rows of no token are zero
    going in, and each grouped matmul's output is masked before anything
    reads it, which masks its cotangents too (``where``'s, not a product's):
    the chip's kernels leave rows outside every group unwritten, forward
    and in the backward's ``gmm``, and what stands there, NaN or Inf
    included, must reach neither a token nor, times a zero, a gradient."""
    N = u.shape[0]
    valid = (tok < N)[:, None]
    keep = lambda t: jnp.where(valid, t, 0.0)
    dot = lambda a, name: keep(grouped_dot(
        a.astype(cd), p[name].astype(cd), sizes, kernel))
    x = keep(jnp.take(u, tok, axis=0, mode="fill", fill_value=0))
    hid = jax.nn.silu(dot(x, "w_gate")) * dot(x, "w_up") if "w_gate" in p \
        else relu2(dot(x, "w_up"))
    y = dot(hid, "w_down") * w_pair[:, None]
    return jnp.zeros((N, y.shape[1]), F32).at[tok].add(y, mode="drop")


def expert_runs(c: HybridPreset, pairs: int) -> Tuple[int, ...]:
    """Lengths of the runs the sorted (token, choice) pairs go through the
    held experts in: the first holds twice the rows a balanced router sends
    here (``pairs * experts_held / n_experts``; ``run_headroom`` times: four
    where 8 experts of 256 are held, whose seeded router sends them up to
    twice their share, so that no update of a window needs a second run),
    each further one as many as all before it, until every pair has a
    place: whatever the skew no row is dropped, and memory is the largest
    run's.  In whole tiles of the kernel's 256 rows (of 8 where a run is
    shorter).  These are the shapes of the sort, gathers and scatter-adds
    around the grouped matmuls; what the matmuls compute of a run is
    ``routed_experts``' rule."""
    first = -(-c.run_headroom * pairs * c.experts_held // c.n_experts)
    first = -(-first // 256) * 256 if first >= 256 else -(-first // 8) * 8
    runs = [first]
    while sum(runs) < pairs:
        runs.append(sum(runs))
    return tuple(runs)


def routed_experts(p, u, chosen, w, sizes, c: HybridPreset, cd,
                   kernel="auto"):
    """The weighted sum over those of each token's chosen experts that are
    held here, (N, d) float32; ``sizes`` (H,) the rows each held expert
    received.  Dropless at any skew: the (token, choice) pairs are sorted
    by held expert, the pairs of absent experts last, and go through the
    grouped matmuls in runs of static length (``expert_runs``); a run that
    starts past the last row routed here is skipped.  Of a run that holds
    one, the Pallas kernels ("pallas", "interpret": ``expert_kernel``) are
    handed each held expert's TRUE rows in it: the run's tail past the last
    routed row belongs to no group, and they launch no tile for it, so
    their work follows the rows routed here tile by tile (rows outside
    every group are masked: ``_grouped_ffn``).  ``jax.lax.ragged_dot``
    ("xla": the CPU, the actor's step, the oracle) has static shapes and
    saves nothing by that, so there the tail rides as zero rows of the last
    expert and the run is computed whole.  The runs are unrolled: a
    ``scan`` over them would save each run's inputs, the weights among
    them, once a run for the backward pass."""
    kernel = expert_kernel(kernel)
    N, H, k = u.shape[0], c.experts_held, c.top_k
    local = chosen.reshape(-1) - c.first_expert                # (N k,)
    key = jnp.where((local >= 0) & (local < H), local, H)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    rows = ends[-1]
    runs = expert_runs(c, N * k)
    fill = sum(runs) - N * k
    tok = jnp.pad(order // k, (0, fill), constant_values=N)
    w_sorted = jnp.pad(jnp.take(w.reshape(-1), order), (0, fill))
    p = {name: p[name].astype(cd) for name in ("w_gate", "w_up", "w_down")
         if name in p}

    def run(lo, R):
        """The part of the sorted rows [lo, lo + R)."""
        @jax.checkpoint
        def part(p, u, w_sorted):
            with jax.named_scope(SCOPE_MOE), \
                    jax.named_scope(SCOPE_MOE_EXPERTS):
                here = jnp.clip(jnp.minimum(ends, lo + R)
                                - jnp.maximum(ends - sizes, lo), 0)
                if kernel == "xla":
                    here = here.at[-1].add(R - jnp.sum(here))
                return _grouped_ffn(
                    p, u, jnp.where(lo + jnp.arange(R) < rows,
                                    tok[lo:lo + R], N),
                    w_sorted[lo:lo + R], here, cd, kernel)
        return part(p, u, w_sorted)

    routed, lo = jnp.zeros((N, u.shape[1]), F32), 0
    for R in runs:
        routed = jax.lax.cond(lo < rows,
                              lambda a, lo=lo, R=R: a + run(lo, R),
                              lambda a: a, routed)
        lo += R
    return routed


def moe_layer(p, u, c: HybridPreset, cd, kernel="auto"):
    """One E mixer over (N, d) normed tokens: the shared expert (where the
    preset has one) plus the routed experts held here.  Returns (out (N, d)
    float32, the tokens that chose each of ALL experts (E,) int32, the
    layer's load-balancing loss or None where the router has none); the rows
    of the experts held here are ``held_load`` of the load."""
    with jax.named_scope(SCOPE_MOE):
        if c.router == "softmax":
            chosen, w, load, aux = route_aux(p, u, c)
        else:
            (chosen, w, load), aux = route(p, u, c), None
        with jax.named_scope(SCOPE_MOE_EXPERTS):
            routed = routed_experts(p, u, chosen, w, held_load(load, c), c,
                                    cd, kernel)
        if not c.shared_width:
            return routed, load, aux
        with jax.named_scope(SCOPE_MOE_SHARED):
            if c.gated_experts:
                shared = _mm(jax.nn.silu(_mm(u, p["w_shared_gate"], cd))
                             * _mm(u, p["w_shared_up"], cd),
                             p["w_shared_down"], cd)
                if c.shared_expert_gate:
                    shared = shared * jax.nn.sigmoid(jnp.matmul(
                        u.astype(F32), p["shared_gate"],
                        precision=jax.lax.Precision.HIGHEST))
            else:
                shared = _mm(relu2(_mm(u, p["w_shared_up"], cd)),
                             p["w_shared_down"], cd)
        return routed + shared, load, aux


def moe_apply(p, u, c: HybridPreset, cd, kernel="auto"):
    """``moe_layer``'s output and load."""
    return moe_layer(p, u, c, cd, kernel)[:2]


def held_load(load, c: HybridPreset):
    """The rows each expert held here received, (H,), of the (E,) load."""
    return load[c.first_expert:c.first_expert + c.experts_held]


def moe_stats(load: Dict[int, jnp.ndarray], c: HybridPreset, kernel: str,
              pairs: int = 0) -> Dict[str, jnp.ndarray]:
    """The program's routing counters as step metrics, from each E layer's
    load (the tokens that chose each expert): ``learner/moe_rows_here``
    (the rows the held experts received, mean over the E layers; per layer
    as ``.../E<layer>``), ``learner/moe_rows_absent_share`` (mean: the
    (token, choice) pairs whose expert is on another chip) and
    ``learner/moe_load_max_over_mean`` (the busiest held expert over the
    mean load of ALL experts, the worst layer: 1 is a balanced router);
    where the preset counts them, ``learner/moe_rows_computed`` (mean: the
    rows inside the groups the grouped matmuls were handed, by
    ``routed_experts``' rule for the RESOLVED ``kernel`` the layers ran:
    the rows routed here under "pallas" and "interpret", every run that
    holds one whole under "xla"; ``pairs`` = the update's (token, choice)
    pairs, a Python int)."""
    if not load:
        return {}
    sizes = {i: held_load(n, c) for i, n in load.items()}
    n_pairs = jnp.sum(next(iter(load.values()))).astype(F32)
    mean = n_pairs / c.n_experts
    rows = {i: jnp.sum(n).astype(F32) for i, n in sizes.items()}
    here = jnp.mean(jnp.stack(list(rows.values())))
    out = {f"learner/moe_rows_here/E{i}": r for i, r in rows.items()}
    out["learner/moe_rows_here"] = here
    out["learner/moe_rows_absent_share"] = 1.0 - here / n_pairs
    out["learner/moe_load_max_over_mean"] = jnp.max(jnp.stack([
        jnp.max(n) / mean for n in sizes.values()]))
    if c.count_rows_computed and pairs and kernel != "xla":
        out["learner/moe_rows_computed"] = here
    elif c.count_rows_computed and pairs:
        runs = expert_runs(c, pairs)
        starts = jnp.array([sum(runs[:j]) for j in range(len(runs))], F32)
        out["learner/moe_rows_computed"] = jnp.mean(jnp.stack([
            jnp.sum(jnp.where(starts < r, jnp.array(runs, F32), 0.0))
            for r in rows.values()]))
    return out


def bias_step(b_sel, load, rate: float):
    """One step of an E layer's selection bias against its load: up by
    ``rate`` for the experts that received fewer tokens than the mean, down
    for those that received more (the sign of the difference, nothing of
    its size): the balancing without an auxiliary loss that the family is
    trained with."""
    load = load.astype(F32)
    return b_sel + rate * jnp.sign(jnp.mean(load) - load)


def balance_selection_bias(params, load: Dict[int, jnp.ndarray],
                           c: HybridPreset):
    """The update ``b_sel`` gets in place of a gradient, after every
    optimizer step: ``bias_step`` in each E layer.  ``load`` = {layer
    index: (E,) tokens per expert, this update}."""
    layers = dict(params["params"])
    for i, n in load.items():
        layer = layers[f"layers_{i}"]
        layers[f"layers_{i}"] = dict(layer, b_sel=bias_step(
            layer["b_sel"], n, c.bias_rate))
    return dict(params, params=layers)


# ---------------------------------------------------------------------------
# parameters and the model
# ---------------------------------------------------------------------------

_lecun = nn.initializers.lecun_normal()
_lecun_experts = nn.initializers.variance_scaling(
    1.0, "fan_in", "truncated_normal", in_axis=-2, out_axis=-1, batch_axis=0)
_ones = nn.initializers.ones


def _dt_bias_init(c: HybridPreset):
    def init(key, shape, dtype=F32):
        dt = jnp.exp(jax.random.uniform(key, shape, dtype)
                     * (math.log(c.dt_max) - math.log(c.dt_min))
                     + math.log(c.dt_min))
        dt = jnp.maximum(dt, c.dt_floor)
        return dt + jnp.log(-jnp.expm1(-dt))       # softplus^-1
    return init


def _a_log_init(key, shape, dtype=F32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _b_sel_init(key, shape, dtype=F32):
    return 0.01 * jax.random.normal(key, shape, dtype)


def _gdn_a_log_init(key, shape, dtype=F32):
    """The delta-rule family's: ``A = U(0, 16)`` (floored where its log
    would not be finite)."""
    return jnp.log(jnp.maximum(
        jax.random.uniform(key, shape, dtype, 0.0, 16.0), 1e-4))


def _norm_init(c: HybridPreset):
    """A norm's parameter at scale 1: zero where the scale is ``1 + w``."""
    return nn.initializers.zeros if c.norm_plus_one else _ones


def layer_param_specs(kind: str, c: HybridPreset):
    """name -> (initialiser, shape) of one layer's parameters."""
    d = c.d_model
    if kind == "M":
        return {
            "w_in": (_lecun, (d, 2 * c.d_inner + 2 * c.ssm_groups
                              * c.ssm_state + c.ssm_heads)),
            "conv_w": (_lecun, (c.conv_kernel, c.conv_dim)),
            "conv_b": (nn.initializers.zeros, (c.conv_dim,)),
            "dt_bias": (_dt_bias_init(c), (c.ssm_heads,)),
            "A_log": (_a_log_init, (c.ssm_heads,)),
            "D": (_ones, (c.ssm_heads,)),
            "gate_norm": (_ones, (c.d_inner,)),
            "w_out": (_lecun, (c.d_inner, d)),
        }
    if kind == "D":
        return {
            "w_qkvz": (_lecun, (d, c.gdn_conv_dim + c.gdn_value_dim)),
            "w_ba": (_lecun, (d, 2 * c.gdn_v_heads)),
            "conv_w": (_lecun, (c.conv_kernel, c.gdn_conv_dim)),
            "dt_bias": (_ones, (c.gdn_v_heads,)),
            "A_log": (_gdn_a_log_init, (c.gdn_v_heads,)),
            "gate_norm": (_ones, (c.gdn_head_dim,)),
            "w_out": (_lecun, (c.gdn_value_dim, d)),
        }
    if kind == "*":
        hq, hk = c.attn_heads * c.attn_head_dim, c.kv_heads * c.attn_head_dim
        specs = {"w_q": (_lecun, (d, 2 * hq if c.attn_gate else hq)),
                 "w_k": (_lecun, (d, hk)),
                 "w_v": (_lecun, (d, hk)), "w_o": (_lecun, (hq, d))}
        if c.qk_norm:
            specs.update(q_norm=(_norm_init(c), (c.attn_head_dim,)),
                         k_norm=(_norm_init(c), (c.attn_head_dim,)))
        return specs
    if kind == "K":
        hd, r = c.kda_dim, c.kda_gate_rank
        return {
            **{f"w_{x}": (_lecun, (d, hd)) for x in "qkv"},
            **{f"conv_{x}": (_lecun, (c.conv_kernel, hd)) for x in "qkv"},
            "w_fa": (_lecun, (d, r)), "w_fb": (_lecun, (r, hd)),
            "dt_bias": (_dt_bias_init(c), (hd,)),
            "A_log": (_a_log_init, (c.kda_heads,)),
            "w_b": (_lecun, (d, c.kda_heads)),
            "w_ga": (_lecun, (d, r)), "w_gb": (_lecun, (r, hd)),
            "gate_bias": (nn.initializers.zeros, (hd,)),
            "gate_norm": (_ones, (c.kda_head_dim,)),
            "w_out": (_lecun, (hd, d)),
        }
    if kind == "L":
        h = c.attn_heads
        return {
            "w_q": (_lecun, (d, h * (c.mla_nope + c.mla_rope))),
            "w_kva": (_lecun, (d, c.mla_latent + c.mla_rope)),
            "kv_norm": (_norm_init(c), (c.mla_latent,)),
            "w_kvb": (_lecun, (c.mla_latent, h * (c.mla_nope + c.mla_v))),
            "w_o": (_lecun, (h * c.mla_v, d)),
        }
    if kind == "C":
        return {"w_in": (_lecun, (d, 3 * d)),
                "conv_w": (_lecun, (c.conv_kernel, d)),
                "w_out": (_lecun, (d, d))}
    if kind == "F":
        return {"w_gate": (_lecun, (d, c.mlp_width)),
                "w_up": (_lecun, (d, c.mlp_width)),
                "w_down": (_lecun, (c.mlp_width, d))}
    if kind == "E":
        H, gated = c.experts_held, c.gated_experts
        experts = lambda *shape: (_lecun_experts, (H, *shape))
        shared = lambda *shape: (_lecun, shape)
        specs = {"router": (_lecun, (d, c.n_experts))}
        if c.router == "sigmoid":
            # selection has no gradient, so Adam never moves it: it is
            # balance_selection_bias's to move
            specs["b_sel"] = (_b_sel_init, (c.n_experts,))
        if gated:
            specs["w_gate"] = experts(d, c.expert_width)
        specs.update(w_up=experts(d, c.expert_width),
                     w_down=experts(c.expert_width, d))
        if not c.shared_width:
            return specs
        if gated:
            specs["w_shared_gate"] = shared(d, c.shared_width)
        specs.update(w_shared_up=shared(d, c.shared_width),
                     w_shared_down=shared(c.shared_width, d))
        if gated and c.shared_expert_gate:
            specs["shared_gate"] = shared(d, 1)
        return specs
    raise ValueError(f"unknown layer kind {kind!r} in a hybrid pattern "
                     f"(known: M, D, K, *, L, F, E, C)")


class _Layer(nn.Module):
    """A layer's parameters (its pre-norm scale and its mixer's)."""

    kind: str
    preset: HybridPreset

    def setup(self):
        self.norm = self.param("norm", _norm_init(self.preset),
                               (self.preset.d_model,))
        for name, (init, shape) in layer_param_specs(
                self.kind, self.preset).items():
            setattr(self, name, self.param(name, init, shape))

    def params_dict(self):
        return {name: getattr(self, name) for name in
                ("norm", *layer_param_specs(self.kind, self.preset))}


class HybridQModel(nn.Module):
    """Frame embed -> the pattern's layers -> final norm -> Q head."""

    action_space: int
    state_shape: Tuple[int, ...] = ()    # the env's (C, H, W) frame stack
    window: int = 32                     # T + 1 positions of a segment
    preset: HybridPreset = PRESETS["tiny"]
    norm_val: float = 255.0
    compute_dtype: Any = jnp.bfloat16

    def setup(self):
        c = self.preset
        H, W = self.state_shape[-2:]
        self.w_embed = self.param("w_embed", _lecun, (H * W, c.d_model))
        self.layers = [_Layer(kind, c) for kind in c.pattern]
        self.final_norm = self.param("final_norm", _norm_init(c),
                                     (c.d_model,))
        self.head_w = self.param("head_w", nn.initializers.zeros,
                                 (c.d_model, self.action_space))
        self.head_b = self.param("head_b", nn.initializers.zeros,
                                 (self.action_space,))

    # -- shared ends -------------------------------------------------------

    def _embed(self, frames):
        """(.., H, W) uint8 or float frames -> (.., d) in the compute dtype."""
        with jax.named_scope(SCOPE_EMBED):
            x = frames.astype(F32) / self.norm_val
            x = x.reshape(*frames.shape[:-2], -1)
            return _mm(x, self.w_embed, self.compute_dtype).astype(
                self.compute_dtype)

    def _head(self, x):
        with jax.named_scope(SCOPE_HEAD):
            x = rms_norm(x, norm_scale(self.final_norm, self.preset),
                         self.preset.norm_eps)
            return jnp.matmul(x, self.head_w,
                              precision=jax.lax.Precision.HIGHEST) + self.head_b

    # -- learner path --------------------------------------------------------

    def window_pass(self, frames):
        """One causal pass over (B, T, H, W) frames from a zero state:
        (Q (B, T, A) float32, {E layer index: (E,) tokens that chose each
        expert}, {M or D layer index: the layer's float32 state after the
        last position: (B, h, p, n), (B, h_v, d_k, d_v)})."""
        return self.window_pass_full(frames)[:3]

    def window_pass_full(self, frames, kernel: str = "auto"):
        """``window_pass``'s three, and fourth what the second trunk's
        and third trunks' layers report besides: {"aux": {E layer: its
        load-balancing loss}, "decay": {D layer: its mean decay ``exp(g)``},
        "kda_decay": {K layer: each key channel's mean decay (h, d_k)}}; a
        K layer's state (B, h, d_k, d_v) stands in the third beside M's and
        D's.  ``kernel``: the E layers' grouped matmul (``expert_kernel``)."""
        c, cd = self.preset, self.compute_dtype
        x = self._embed(frames)
        B, T, d = x.shape
        load, states, aux, decay, kda_decay = {}, {}, {}, {}, {}
        norm = lambda p, x: rms_norm(x, norm_scale(p["norm"], c), c.norm_eps)
        for i, layer in enumerate(self.layers):
            p = layer.params_dict()
            if layer.kind == "E":
                @jax.checkpoint
                def mix(p, x):
                    u = norm(p, x).reshape(B * T, d)
                    out, n, loss = moe_layer(p, u, c, cd, kernel)
                    return x + out.reshape(B, T, d).astype(cd), n, loss
                x, load[i], loss = mix(p, x)
                if loss is not None:
                    aux[i] = loss
            elif layer.kind == "M":
                @jax.checkpoint
                def mix(p, x):
                    out, S = mamba_window(p, norm(p, x), c, cd)
                    return x + out.astype(cd), S
                x, states[i] = mix(p, x)
            elif layer.kind == "D":
                @jax.checkpoint
                def mix(p, x):
                    out, S, kept = gdn_window(p, norm(p, x), c, cd)
                    return x + out.astype(cd), S, kept
                x, states[i], decay[i] = mix(p, x)
            elif layer.kind == "K":
                @jax.checkpoint
                def mix(p, x):
                    out, S, kept = kda_window(p, norm(p, x), c, cd)
                    return x + out.astype(cd), S, kept
                x, states[i], kda_decay[i] = mix(p, x)
            elif layer.kind in "LFC":
                @jax.checkpoint
                def mix(p, x, kind=layer.kind):
                    u = norm(p, x)
                    if kind == "L":
                        out = mla_window(p, u, c, cd)
                    elif kind == "F":
                        out = mlp_block(p, u, cd)
                    else:
                        out = short_conv_window(p, u, c, cd)
                    return x + out.astype(cd)
                x = mix(p, x)
            else:
                @jax.checkpoint
                def mix(p, x):
                    u = norm(p, x)
                    return x + attention_window(p, u, c, cd).astype(cd)
                x = mix(p, x)
        return self._head(x), load, states, {
            "aux": aux, "decay": decay, "kda_decay": kda_decay}

    def window_q(self, frames):
        return self.window_pass(frames)[0]

    def target_copy(self, params):
        """How the train state keeps this model's target network."""
        return bf16_target(params)

    def train_parts(self, pack_frames: int = 0):
        """What factory.build_train_state_and_step hands the sequence
        train step: ``window_applies``."""
        return window_applies(self, pack_frames)

    # -- acting path ---------------------------------------------------------

    @property
    def act_window(self) -> int:
        """Keys the acting cache keeps: the trained positions [0, T)."""
        return self.window - 1

    def zero_carry(self, batch: int):
        """A flat tuple, every leaf leading with the batch dimension: per M
        or D layer (conv tail, float32 state), per K layer (the conv tails
        of q, k and v, float32 state), per C layer (its conv tail), per *
        layer (keys, values), per L layer one ring of latents (W, latent +
        rope), then the count of positions seen."""
        c, out = self.preset, []
        for kind in c.pattern:
            if kind == "M":
                out += [jnp.zeros((batch, c.conv_kernel - 1, c.conv_dim), F32),
                        jnp.zeros((batch, c.ssm_heads, c.ssm_head_dim,
                                   c.ssm_state), F32)]
            elif kind == "D":
                out += [jnp.zeros((batch, c.conv_kernel - 1, c.gdn_conv_dim),
                                  F32),
                        jnp.zeros((batch, c.gdn_v_heads, c.gdn_head_dim,
                                   c.gdn_head_dim), F32)]
            elif kind == "K":
                out += [jnp.zeros((batch, c.conv_kernel - 1, c.kda_dim), F32)
                        for _ in "qkv"]
                out += [jnp.zeros((batch, c.kda_heads, c.kda_head_dim,
                                   c.kda_head_dim), F32)]
            elif kind == "C":
                out += [jnp.zeros((batch, c.conv_kernel - 1, c.d_model), F32)]
            elif kind == "*":
                kv = (batch, self.act_window, c.kv_heads, c.attn_head_dim)
                out += [jnp.zeros(kv, self.compute_dtype),
                        jnp.zeros(kv, self.compute_dtype)]
            elif kind == "L":
                out += [jnp.zeros((batch, self.act_window,
                                   c.mla_latent + c.mla_rope),
                                  self.compute_dtype)]
        return tuple(out) + (jnp.zeros((batch,), jnp.int32),)

    def state_for_segment(self, carry, j: int):
        """No stored state in the ring: a segment starts from zero state
        and its burn-in prefix is context (as every ``dtqn*`` model)."""
        return (np.zeros(1, np.float32), np.zeros(1, np.float32))

    def __call__(self, obs, carry=None):
        """One acting step: obs (B, C, H, W), the env's frame stack, of
        which the newest frame is this position's; -> (Q (B, A), carry')."""
        c, cd = self.preset, self.compute_dtype
        if carry is None:
            carry = self.zero_carry(obs.shape[0])
        carry, count = list(carry[:-1]), carry[-1]
        x = self._embed(obs[:, -1])
        at = 0
        for layer in self.layers:
            p = layer.params_dict()
            u = rms_norm(x, norm_scale(p["norm"], c), c.norm_eps)
            if layer.kind in "MD":
                step = mamba_step if layer.kind == "M" else gdn_step
                out, carry[at], carry[at + 1] = step(
                    p, u, carry[at], carry[at + 1], c, cd)
                at += 2
            elif layer.kind == "K":
                out, carry[at:at + 3], carry[at + 3] = kda_step(
                    p, u, carry[at:at + 3], carry[at + 3], c, cd)
                at += 4
            elif layer.kind == "C":
                out, carry[at] = short_conv_step(p, u, carry[at], c, cd)
                at += 1
            elif layer.kind == "*":
                out, carry[at], carry[at + 1] = attention_step(
                    p, u, carry[at], carry[at + 1], count, c, cd)
                at += 2
            elif layer.kind == "L":
                out, carry[at] = mla_step(p, u, carry[at], count, c, cd)
                at += 1
            elif layer.kind == "F":
                out = mlp_block(p, u, cd)
            else:
                # a handful of rows, on whichever backend the actor,
                # evaluator or tester was pinned to: never the TPU kernel
                out = moe_apply(p, u, c, cd, kernel="xla")[0]
            x = x + out.astype(cd)
        return self._head(x), tuple(carry) + (count + 1,)


LOAD_KEY = "moe_load/E"      # + layer index: an E layer's (E,) load


def window_applies(model: HybridQModel, pack_frames: int = 0):
    """The learner's parts for ops/sequence_losses.py
    build_dtqn_train_step: ``online(params, obs) -> (Q, aux)``, where aux
    holds the routing counters of the E layers as step metrics (scalars)
    and each E layer's load under ``LOAD_KEY``; ``target(params, obs) ->
    Q``; ``after_update(params, aux) -> params``, the step ``b_sel`` takes
    against that load (None for a router without one).  Where the expert
    layers have a load-balancing loss, aux carries their weighted sum under
    ``AUX_LOSS_KEY``: the train step adds it to the TD loss and reports
    it.  ``pack_frames`` = C: obs arrives frame-packed (B,
    T + C, H, W) as the ring stores it, and position t reads frame t + C -
    1, the newest of its stack."""
    newest = lambda obs: obs[:, pack_frames - 1:] if pack_frames else obs

    c = model.preset

    def online(params, obs):
        # one choice of kernel for the rows the layers hand it and the count
        kernel = expert_kernel()
        q, load, _, more = model.apply(params, newest(obs), kernel,
                                       method=model.window_pass_full)
        aux = moe_stats(load, c, kernel, q.shape[0] * q.shape[1] * c.top_k)
        aux.update({f"{LOAD_KEY}{i}": n for i, n in load.items()})
        if more["aux"]:
            # the one entry the train step adds to the TD loss
            aux[AUX_LOSS_KEY] = c.aux_weight * sum(more["aux"].values())
        if more["decay"]:
            aux["learner/gdn_decay_mean"] = jnp.mean(jnp.stack(
                list(more["decay"].values())))
        if more["kda_decay"]:
            # over positions, heads, channels and K blocks; and the channel
            # of any block that forgets fastest
            kept = jnp.stack(list(more["kda_decay"].values()))
            aux["learner/kda_decay_mean"] = jnp.mean(kept)
            aux["learner/kda_decay_min"] = jnp.min(kept)
        return q, aux

    def target(params, obs):
        return model.apply(params, newest(obs), method=model.window_q)

    def after_update(params, aux):
        return balance_selection_bias(
            params, {int(k[len(LOAD_KEY):]): n for k, n in aux.items()
                     if k.startswith(LOAD_KEY)}, model.preset)

    # only the sigmoid router has a selection bias to step
    return online, target, after_update if c.router == "sigmoid" else None
