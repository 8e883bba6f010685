"""Plain float32 reference of the third hybrid sequence Q-network's update
(models/hybrid.py PRESETS["kimi-linear-5"]): layers of a published
linear-attention / latent-attention / sparse-expert language model
(config.json of moonshotai/Kimi-Linear-48B-A3B-Instruct, ``model_type:
kimi_linear``) as the trunk of an R2D2-style Q-network.
benchmark/reference/kimi_linear.py is a byte-for-byte copy of this file
(tests/test_kimi_trunk.py holds them equal).  It imports nothing from the
program.

Written down from the published description, straightforwardly: every
matmul in float32 under ``default_matmul_precision("highest")``, the
channel-gated delta rule as a per-position recurrence (no chunks, no
sub-blocks, no inverse), latent attention from EXPANDED keys and values
through the full masked score matrix (no latent cache, nothing absorbed),
the experts as a loop over the experts held with masks (no sort, no grouped
matmul).  One segment at a time, each block under ``jax.checkpoint``, so
that it fits beside nothing else on one chip.

Pre-norm residual blocks ``x <- x + mixer(N(x))``, ``N(x) = x / sqrt(mean
x^2 + eps) * w``, no biases but the output gate's; one letter of ``pattern``
a block (a published layer is a mixer block, K or L, and a feed-forward
block, F for the first ``first_k_dense_replace`` layers and E after):

  K  Kimi Delta Attention.  q, k, v = u W_q, u W_k, u W_v, each through its
     own causal depth-wise conv (``short_conv_kernel_size`` taps) + silu;
     q, k <- q / |q|, k / |k| a head (eps 1e-6), q <- q / sqrt(d);
     beta = sigmoid(u W_b) a head;
     g = -exp(A_log) softplus(f_b (f_a u) + dt_bias) a KEY CHANNEL (A_log a
     head, dt_bias a channel); from S = 0 (d x d a head):
        S' = Diag(exp(g_t)) S_{t-1};  delta_t = beta_t (v_t - S'^T k_t);
        S_t = S' + k_t delta_t^T;  o_t = S_t^T q_t
     a head: o <- w_n * o / rms(o) * sigmoid(g_b (g_a u) + b);  out = o W_out.
  L  latent attention, no positions (``mla_use_nope``).  q = u W_q, a head
     (qk_nope_head_dim + qk_rope_head_dim); [c | k_r] = u W_kva
     (kv_lora_rank + qk_rope_head_dim); c <- N_kv(c); [k_n | v] = c W_kvb a
     head (qk_nope_head_dim + v_head_dim); a head's key is [k_n | k_r], k_r
     the SAME for every head; causal softmax(q k^T / sqrt(qk_nope_head_dim +
     qk_rope_head_dim)) v; W_o.
  F  (silu(u W_gate) * u W_up) W_down.
  E  s = sigmoid(u W_r) over all experts; the top_k largest of s + b_sel are
     chosen, their weights s (without b_sel) / sum of the chosen s
     (``moe_renormalize``), times ``routed_scaling_factor``.  Expert e:
     (silu(u W_gate,e) * u W_up,e) W_down,e.  Plus one shared expert of the
     same form, ungated.  Only experts ``first_expert .. first_expert + held``
     exist here: what the others would add is left out, and that partial
     result goes on.  ``b_sel`` has no gradient.

Ends (the repo's sequence-family contract): one H x W frame a position,
/ norm_val, flattened, @ w_embed; final N; @ head_w + head_b.

The update is R2D2's on a window without stored state (zero state at
position 0; the first ``burn_in`` positions are context only): double-Q
bootstrap through the value rescaling, n-step returns inside the window
shrinking at its end and at masked tails, masked importance-weighted MSE,
eta-blended per-segment priorities (benchmark/reference/r2d2.py steps 3-6).

``hyper`` (the configuration's ``reference_hyper``) holds the update's
constants and, under ``model``, the architecture's numbers under their
published names plus ``pattern``, ``first_expert`` (the experts held are
counted from the weights), ``scan_state_dtype`` (float32: the precision the
configuration states for the recurrent state) and ``wrong``: names of terms
to get wrong ON PURPOSE, each a control the check must tell (``WRONG``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

PRIORITY_EPS = 1e-6
RESCALE_EPS = 1e-3
SCAN_BLOCK = 64     # positions whose states are recomputed in the backward
# ``head_mean_decay``: each head's g_t replaced by its mean over the head's
# key channels: the scalar-gated delta rule under this model's weights
WRONG = ("head_mean_decay", "no_beta", "no_latent_norm", "key_part_a_head",
         "no_topk_renorm", "no_route_scale")


def h(x):
    return jnp.sign(x) * (jnp.sqrt(jnp.abs(x) + 1.0) - 1.0) + RESCALE_EPS * x


def h_inv(x):
    e = RESCALE_EPS
    return jnp.sign(x) * (jnp.square(
        (jnp.sqrt(1.0 + 4.0 * e * (jnp.abs(x) + 1.0 + e)) - 1.0)
        / (2.0 * e)) - 1.0)


def rms(x, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def f32(tree):
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)


# ---------------------------------------------------------------------------
# the mixers, one segment: u is (T, d)
# ---------------------------------------------------------------------------

def short_conv(x, w):
    """silu of the causal depth-wise conv: tap j reads position t - (K-1) +
    j."""
    K, T = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, x.shape[1])), x])
    return jax.nn.silu(sum(padded[j:j + T] * w[j] for j in range(K)))


def delta_attention(p, u, m):
    """-> (the block's output, the state after the last position (heads,
    d, d))."""
    wrong = m.get("wrong", ())
    heads, d = m["kda_num_heads"], m["kda_head_dim"]
    T = u.shape[0]
    q, k, v = (short_conv(u @ p[f"w_{x}"], p[f"conv_{x}"]).reshape(T, heads, d)
               for x in "qkv")
    q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6)
    k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    q = q / math.sqrt(d)
    beta = jax.nn.sigmoid(u @ p["w_b"])
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        (u @ p["w_fa"] @ p["w_fb"] + p["dt_bias"]).reshape(T, heads, d))
    if "no_beta" in wrong:
        beta = jnp.ones_like(beta)
    if "head_mean_decay" in wrong:
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    state_dtype = jnp.dtype(m.get("scan_state_dtype", "float32"))

    def position(S, inp):
        q_t, k_t, v_t, g_t, beta_t = inp
        S = jnp.exp(g_t)[:, :, None] * S.astype(jnp.float32)
        delta = beta_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
        S = S + k_t[:, :, None] * delta[:, None, :]
        return S.astype(state_dtype), jnp.einsum("hkv,hk->hv", S, q_t)

    @jax.checkpoint
    def block(S, inp):
        return jax.lax.scan(position, S, inp)

    pad = -T % SCAN_BLOCK
    blocks = lambda t: jnp.concatenate(
        [t, jnp.zeros((pad, *t.shape[1:]))]).reshape(-1, SCAN_BLOCK,
                                                     *t.shape[1:])
    # the padding has g = 0 and beta = 0: it decays nothing, writes nothing
    S, o = jax.lax.scan(block, jnp.zeros((heads, d, d), state_dtype),
                        tuple(blocks(t) for t in (q, k, v, g, beta)))
    o = o.reshape(-1, heads, d)[:T]
    gate = (u @ p["w_ga"] @ p["w_gb"] + p["gate_bias"]).reshape(T, heads, d)
    o = p["gate_norm"] * rms(o, m["rms_norm_eps"]) * jax.nn.sigmoid(gate)
    return o.reshape(T, heads * d) @ p["w_out"], S.astype(jnp.float32)


def latent_attention(p, u, m):
    wrong = m.get("wrong", ())
    heads = m["num_attention_heads"]
    nope, rope, vd = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    T = u.shape[0]
    q = (u @ p["w_q"]).reshape(T, heads, nope + rope)
    ckr = u @ p["w_kva"]
    c, k_r = ckr[:, :m["kv_lora_rank"]], ckr[:, m["kv_lora_rank"]:]
    if "no_latent_norm" not in wrong:
        c = rms(c, m["rms_norm_eps"]) * p["kv_norm"]
    kv = (c @ p["w_kvb"]).reshape(T, heads, nope + vd)
    k_r = jnp.broadcast_to(k_r[:, None, :], (T, heads, rope))
    if "key_part_a_head" in wrong:
        # head h reads the shared part turned by h places: a part of its own
        k_r = jnp.stack([jnp.roll(k_r[:, i], i, axis=-1)
                         for i in range(heads)], axis=1)
    k = jnp.concatenate([kv[..., :nope], k_r], axis=-1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(nope + rope)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), kv[..., nope:])
    return o.reshape(T, heads * vd) @ p["w_o"]


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def dense_mlp(p, u):
    return swiglu(u, p["w_gate"], p["w_up"], p["w_down"])


def experts(p, u, m):
    """-> (the block's output, rows routed to the experts held here, the
    tokens that chose each of ALL experts)."""
    wrong = m.get("wrong", ())
    first, held = int(m["first_expert"]), p["w_up"].shape[0]
    s = jax.nn.sigmoid(u @ p["router"])
    _, chosen = jax.lax.top_k(s + p["b_sel"], m["num_experts_per_token"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if m.get("moe_renormalize", True) and "no_topk_renorm" not in wrong:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    if "no_route_scale" not in wrong:
        w = w * m["routed_scaling_factor"]
    out = swiglu(u, p["w_shared_gate"], p["w_shared_up"], p["w_shared_down"])

    def one(out, inp):
        e, gate, up, down = inp
        mine = chosen == first + e                              # (T, k)
        return out + jnp.sum(jnp.where(mine, w, 0.0), axis=-1)[:, None] \
            * swiglu(u, gate, up, down), jnp.sum(mine)

    out, rows = jax.lax.scan(one, out, (jnp.arange(held), p["w_gate"],
                                        p["w_up"], p["w_down"]))
    load = jnp.sum(jax.nn.one_hot(chosen, s.shape[-1], dtype=jnp.int32),
                   axis=(0, 1))
    return out, jnp.sum(rows), load


def segment_pass(params, frames, m, norm_val):
    """(T, H, W) frames of one segment -> (Q (T, A), rows per E block, [each
    E block's load], [each K block's state after the last position])."""
    p = f32(params["params"])
    x = (frames.astype(jnp.float32) / norm_val).reshape(
        frames.shape[0], -1) @ p["w_embed"]
    norm = lambda x, w: rms(x, m["rms_norm_eps"]) * w
    rows, load, states = [], [], []
    for i, kind in enumerate(m["pattern"]):
        lp = p[f"layers_{i}"]

        @jax.checkpoint
        def block(lp, x, kind=kind):
            u = norm(x, lp["norm"])
            if kind == "L":
                return x + latent_attention(lp, u, m), ()
            if kind == "F":
                return x + dense_mlp(lp, u), ()
            out, *rest = delta_attention(lp, u, m) if kind == "K" \
                else experts(lp, u, m)
            return x + out, rest

        x, rest = block(lp, x)
        if kind == "E":
            rows.append(rest[0])
            load.append(rest[1])
        elif kind == "K":
            states.append(rest[0])
    q = norm(x, p["final_norm"]) @ p["head_w"] + p["head_b"]
    rows = jnp.stack(rows) if rows else jnp.zeros((0,), jnp.int32)
    return q, rows, load, states


def segment_q(params, frames, m, norm_val):
    return segment_pass(params, frames, m, norm_val)[0]


def window_q(params, frames, m, norm_val):
    """(B, T, H, W) -> Q (B, T, A), a segment at a time."""
    return jax.lax.map(lambda f: segment_q(params, f, m, norm_val), frames)


# ---------------------------------------------------------------------------
# the update
# ---------------------------------------------------------------------------

def nstep_returns(boot, r, d, m, nstep: int, gamma: float):
    """boot (L+1,); r, d, m (L,): one segment."""
    L = r.shape[0]
    pad = lambda x: jnp.concatenate([x, jnp.zeros((nstep,), x.dtype)])
    rp, dp, mp = pad(r), pad(d), pad(m)
    ret, alive = jnp.zeros_like(r), jnp.ones_like(r)
    for k in range(nstep):
        ret = ret + gamma ** k * rp[k:k + L] * alive * mp[k:k + L]
        alive = alive * (1.0 - dp[k:k + L])
    t = jnp.arange(L)
    at = jnp.minimum(jnp.minimum(t + nstep, jnp.sum(m).astype(jnp.int32)), L)
    K = jnp.maximum(at - t, 0).astype(jnp.float32)
    return ret + gamma ** K * alive * boot[at]


def segment_loss(params, q_target, seg, *, model, norm_val, burn_in, nstep,
                 gamma, eta, double, rescale):
    """One segment's share of the loss, its priority signal and its rows per
    E block."""
    fwd = h if rescale else (lambda x: x)
    inv = h_inv if rescale else (lambda x: x)
    q, rows, _, _ = segment_pass(params, seg["frames"], model, norm_val)
    q, q_t = q[burn_in:], q_target[burn_in:]
    a, r, d, m = (seg[k][burn_in:] for k in
                  ("action", "reward", "terminal", "mask"))
    L = a.shape[0]
    q_sel = jnp.take_along_axis(q[:L], a[:, None].astype(jnp.int32),
                                axis=-1)[:, 0]
    if double:
        boot = jnp.take_along_axis(q_t, jnp.argmax(q, axis=-1)[:, None],
                                   axis=-1)[:, 0]
    else:
        boot = jnp.max(q_t, axis=-1)
    target = fwd(nstep_returns(inv(boot), r, d, m, nstep, gamma))
    td = q_sel - jax.lax.stop_gradient(target)
    td_abs = jnp.abs(td) * m
    seq_pr = eta * jnp.max(td_abs) + (1 - eta) * (
        jnp.sum(td_abs) / jnp.maximum(jnp.sum(m), 1.0))
    return (jnp.sum(jnp.square(td) * m) * seg["weight"] / seg["valid"],
            (seq_pr, rows))


STATIC = ("model", "norm_val", "burn_in", "nstep", "gamma", "eta", "double",
          "rescale")


def _model(static):
    return dict(static)


@functools.partial(jax.jit, static_argnames=("model", "norm_val"))
def _segment_pass(params, frames, *, model, norm_val):
    return segment_pass(params, frames, _model(model), norm_val)


@functools.partial(jax.jit, static_argnames=STATIC)
def _segment_grad(params, q_target, seg, *, model, **static):
    return jax.value_and_grad(segment_loss, has_aux=True)(
        params, q_target, seg, model=_model(model), **static)


def _static_model(model: dict):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in model.items()))


def update_rows(params, target_params, batch, hyper: dict, norm_val: float):
    """``(loss, per-segment priority signal, gradient, rows routed to the
    experts held (B, E blocks))``; ``batch["obs"]`` is frame-packed (B, T +
    C, H, W) when ``hyper["pack_frames"]`` = C (position t reads frame t + C
    - 1), else (B, T + 1, H, W).  Two passes a segment: the target's Q, then
    the gradient."""
    C = int(hyper.get("pack_frames", 0))
    frames = batch["obs"][:, C - 1:] if C else batch["obs"]
    static = dict(model=_static_model(hyper["model"]),
                  norm_val=float(norm_val), burn_in=int(hyper["burn_in"]),
                  nstep=int(hyper["nstep"]), gamma=float(hyper["gamma"]),
                  eta=float(hyper["eta"]), double=bool(hyper["double"]),
                  rescale=bool(hyper["value_rescale"]))
    ends = dict(model=static["model"], norm_val=static["norm_val"])
    # once onto the device, not once a call
    params, target_params = jax.device_put((params, target_params))
    with jax.default_matmul_precision("highest"):
        valid = jnp.maximum(jnp.sum(batch["mask"][:, static["burn_in"]:]),
                            1.0)
        loss, grads, signal, rows = 0.0, None, [], []
        for b in range(frames.shape[0]):
            seg = {k: batch[k][b] for k in
                   ("action", "reward", "terminal", "mask", "weight")}
            seg.update(frames=frames[b], valid=valid)
            q_target = _segment_pass(target_params, frames[b], **ends)[0]
            (part, (seq_pr, n)), g = _segment_grad(params, q_target, seg,
                                                   **static)
            loss = loss + part
            grads = g if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, g)
            signal.append(seq_pr)
            rows.append(n)
    return loss, jnp.stack(signal), grads, jnp.stack(rows)


@functools.partial(jax.jit, static_argnames=("model",))
def _delta_state(layer, u, *, model):
    return delta_attention(f32(layer), u.astype(jnp.float32),
                           _model(model))[1]


def delta_states(layer, u, model: dict):
    """One K block on given normed inputs (B, T, d) -> its states after the
    last position (B, heads, d, d), a segment at a time."""
    layer = jax.device_put(layer)
    with jax.default_matmul_precision("highest"):
        return jnp.stack([_delta_state(layer, jnp.asarray(seg),
                                       model=_static_model(model))
                          for seg in u])


@functools.partial(jax.jit, static_argnames=("model",))
def _latent_output(layer, u, *, model):
    return latent_attention(f32(layer), u.astype(jnp.float32), _model(model))


def latent_outputs(layer, u, model: dict):
    """The L block's mixer on given normed inputs (B, T, d) -> its outputs
    (B, T, d), a segment at a time."""
    layer = jax.device_put(layer)
    with jax.default_matmul_precision("highest"):
        return jnp.stack([_latent_output(layer, jnp.asarray(seg),
                                         model=_static_model(model))
                          for seg in u])


def window_states(params, frames, model: dict, norm_val: float):
    """(B, T, H, W) frames -> ([each E block's load (E,), the segments
    together], [each K block's states after the last position (B, heads, d,
    d)]), a segment at a time."""
    static = dict(model=_static_model(model), norm_val=float(norm_val))
    params = jax.device_put(params)
    with jax.default_matmul_precision("highest"):
        out = [_segment_pass(params, f, **static) for f in frames]
    return ([sum(seg[2][i] for seg in out) for i in range(len(out[0][2]))],
            [jnp.stack([seg[3][i] for seg in out])
             for i in range(len(out[0][3]))])


def update(params, target_params, batch, hyper: dict, norm_val: float):
    """``(loss, per-segment priority signal, gradient)``: the interface of
    every reference of the benchmark."""
    return update_rows(params, target_params, batch, hyper, norm_val)[:3]


def batch_of(sample) -> dict:
    return {k: getattr(sample, k) for k in (
        "obs", "action", "reward", "terminal", "mask", "weight")}
