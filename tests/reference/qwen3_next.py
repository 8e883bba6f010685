"""Plain float32 reference of the second hybrid sequence Q-network's update
(models/hybrid.py PRESETS["qwen3-next-4"]): layers of a published
linear-attention / sparse-expert / gated-attention language model
(config.json of Qwen/Qwen3-Next-80B-A3B-Instruct, ``model_type:
qwen3_next``) as the trunk of an R2D2-style Q-network.
benchmark/reference/qwen3_next.py is a byte-for-byte copy of this file
(tests/test_gated_delta.py holds them equal).  It imports nothing from the
program.

Written down from the published description, straightforwardly: every
matmul in float32 under ``default_matmul_precision("highest")``, the gated
delta rule as a per-position recurrence (no chunks, no inverse), the experts
as a loop over the experts held with masks (no sort, no grouped matmul),
attention through the full score matrix.  One segment at a time, each layer
under ``jax.checkpoint``, so that it fits beside nothing else on one chip.

Every published layer is two pre-norm residual blocks, ``x <- x +
mixer(N(x))`` then ``x <- x + experts(N(x))``, ``N(x) = x / sqrt(mean x^2 +
eps) * (1 + w)``, no biases; one letter of ``pattern`` a block:

  D  gated delta rule.  [q | k | v | z] = u W_qkvz, [b | a] = u W_ba;
     [q | k | v] <- silu(causal depth-wise conv([q | k | v]));
     beta = sigmoid(b), g = -exp(A_log) softplus(a + dt_bias);
     q, k <- q / |q|, k / |k| a head (eps 1e-6), q <- q / sqrt(d_k); value
     head h reads key head h // (value heads / key heads); from S = 0:
        S' = exp(g_t) S_{t-1};  delta_t = beta_t (v_t - S'^T k_t);
        S_t = S' + k_t delta_t^T;  o_t = S_t^T q_t
     a head: o <- w_n * o / rms(o) * silu(z);  out = o W_out.
  *  [q | gate] = u W_q a head (heads x 2 x head_dim), k = u W_k, v = u W_v;
     q, k <- N(q), N(k) a head; rotary (rotate-half) on the first
     ``partial_rotary_factor`` of each head, position = index in the
     window; causal softmax(q k^T / sqrt(head_dim)) v, each key-value head
     shared by heads / kv_heads query heads; o <- o * sigmoid(gate); W_o.
  E  p = softmax(u W_r) over all experts; the top_k largest are chosen,
     their weights p / sum of the chosen p.  Expert e: (silu(u W_gate,e) *
     u W_up,e) W_down,e.  Plus sigmoid(u . w_sg) times one shared expert of
     the same form.  Only experts ``first_expert .. first_expert + held``
     exist here: what the others would add is left out, and that partial
     result goes on.  Its balance loss: n_experts * sum_e f_e P_e over ALL
     experts, f_e the share of the update's (token, choice) pairs that chose
     e, P_e the mean of p_e over the update's tokens.

Ends (the repo's sequence-family contract): one H x W frame a position,
/ norm_val, flattened, @ w_embed; final N; @ head_w + head_b.

The update is R2D2's on a window without stored state (zero state at
position 0; the first ``burn_in`` positions are context only): double-Q
bootstrap through the value rescaling, n-step returns inside the window
shrinking at its end and at masked tails, masked importance-weighted MSE,
eta-blended per-segment priorities (benchmark/reference/r2d2.py steps 3-6),
plus ``router_aux_loss_coef`` times the balance losses of the expert
layers, summed.

``hyper`` (the configuration's ``reference_hyper``) holds the update's
constants and, under ``model``, the architecture's numbers under their
published names plus ``pattern``, ``first_expert`` (the experts held are
counted from the weights), ``router_aux_loss_coef``, ``scan_state_dtype``
(float32: the precision the configuration states for the recurrent state)
and ``wrong``: names of terms to get wrong ON PURPOSE, each a control the
check must tell (``WRONG``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

PRIORITY_EPS = 1e-6
RESCALE_EPS = 1e-3
SCAN_BLOCK = 64     # positions whose states are recomputed in the backward
WRONG = ("no_beta", "no_decay", "no_qk_l2norm", "no_attn_gate", "no_rotary",
         "no_topk_renorm", "no_aux")


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
# the three mixers, one segment: u is (T, d)
# ---------------------------------------------------------------------------

def delta_rule(p, u, m):
    """-> (the layer's output, the state after the last position (value
    heads, d_k, d_v))."""
    wrong = m.get("wrong", ())
    hk, hv = m["linear_num_key_heads"], m["linear_num_value_heads"]
    dk, dv = m["linear_key_head_dim"], m["linear_value_head_dim"]
    key_dim, T = hk * dk, u.shape[0]
    qkvz = u @ p["w_qkvz"]
    qkv, z = qkvz[:, :2 * key_dim + hv * dv], qkvz[:, 2 * key_dim + hv * dv:]
    K = p["conv_w"].shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, qkv.shape[1])), qkv])
    qkv = jax.nn.silu(sum(padded[j:j + T] * p["conv_w"][j] for j in range(K)))
    q = qkv[:, :key_dim].reshape(T, hk, dk)
    k = qkv[:, key_dim:2 * key_dim].reshape(T, hk, dk)
    v = qkv[:, 2 * key_dim:].reshape(T, hv, dv)
    ba = u @ p["w_ba"]
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[:, hv:] + p["dt_bias"])
    if "no_beta" in wrong:
        beta = jnp.ones_like(beta)
    if "no_decay" in wrong:
        g = jnp.zeros_like(g)
    if "no_qk_l2norm" not in wrong:
        q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6)
        k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    q = q / math.sqrt(dk)
    q, k = (jnp.repeat(t, hv // hk, axis=1) for t in (q, k))
    state_dtype = jnp.dtype(m.get("scan_state_dtype", "float32"))

    def position(S, inp):
        q_t, k_t, v_t, g_t, beta_t = inp
        S = jnp.exp(g_t)[:, None, None] * S.astype(jnp.float32)
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
    S, o = jax.lax.scan(block, jnp.zeros((hv, dk, dv), state_dtype),
                        tuple(blocks(t) for t in (q, k, v, g, beta)))
    o = o.reshape(-1, hv, dv)[:T]
    o = p["gate_norm"] * rms(o, m["rms_norm_eps"]) * jax.nn.silu(
        z.reshape(T, hv, dv))
    return o.reshape(T, hv * dv) @ p["w_out"], S.astype(jnp.float32)


def rotate(x, m):
    """x (T, heads, head_dim): rotate-half rotary on the leading
    ``partial_rotary_factor`` of each head, position = row."""
    n = int(m["head_dim"] * m["partial_rotary_factor"])
    inv_freq = 1.0 / (m["rope_theta"] ** (jnp.arange(0, n, 2) / n))
    angle = jnp.arange(x.shape[0])[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angle), jnp.cos(angle)], axis=-1)[:, None]
    sin = jnp.concatenate([jnp.sin(angle), jnp.sin(angle)], axis=-1)[:, None]
    rot, rest = x[..., :n], x[..., n:]
    half = jnp.concatenate([-rot[..., n // 2:], rot[..., :n // 2]], axis=-1)
    return jnp.concatenate([rot * cos + half * sin, rest], axis=-1)


def attention(p, u, m):
    wrong = m.get("wrong", ())
    heads, kv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                     m["head_dim"])
    T, eps = u.shape[0], m["rms_norm_eps"]
    qg = (u @ p["w_q"]).reshape(T, heads, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = (u @ p["w_k"]).reshape(T, kv, hd)
    v = (u @ p["w_v"]).reshape(T, kv, hd)
    q = rms(q, eps) * (1.0 + p["q_norm"])
    k = rms(k, eps) * (1.0 + p["k_norm"])
    if "no_rotary" not in wrong:
        q, k = rotate(q, m), rotate(k, m)
    k, v = (jnp.repeat(t, heads // kv, axis=1) for t in (k, v))
    s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    if "no_attn_gate" not in wrong:
        o = o * jax.nn.sigmoid(gate)
    return o.reshape(T, heads * hd) @ p["w_o"]


def experts(p, u, m):
    """-> (the layer's output, rows routed to the experts held here, the
    tokens that chose each of ALL experts, the mean of p over the tokens)."""
    first, held = int(m["first_expert"]), p["w_up"].shape[0]
    prob = jax.nn.softmax(u @ p["router"], axis=-1)
    w, chosen = jax.lax.top_k(prob, m["num_experts_per_tok"])
    if m.get("norm_topk_prob", True) and "no_topk_renorm" not in m.get(
            "wrong", ()):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    ffn = lambda x, gate, up, down: (jax.nn.silu(x @ gate) * (x @ up)) @ down
    out = jax.nn.sigmoid(u @ p["shared_gate"]) * ffn(
        u, p["w_shared_gate"], p["w_shared_up"], p["w_shared_down"])

    def one(out, inp):
        e, gate, up, down = inp
        mine = chosen == first + e                              # (T, k)
        return out + jnp.sum(jnp.where(mine, w, 0.0), axis=-1)[:, None] * ffn(
            u, gate, up, down), jnp.sum(mine)

    out, rows = jax.lax.scan(one, out, (jnp.arange(held), p["w_gate"],
                                        p["w_up"], p["w_down"]))
    load = jnp.sum(jax.nn.one_hot(chosen, prob.shape[-1], dtype=jnp.int32),
                   axis=(0, 1))
    return out, jnp.sum(rows), load, jnp.mean(prob, axis=0)


def segment_pass(params, frames, m, norm_val):
    """(T, H, W) frames of one segment -> (Q (T, A), rows per E layer,
    [each E layer's load], [each E layer's mean router probabilities],
    [each D layer's state after the last position])."""
    p = f32(params["params"])
    x = (frames.astype(jnp.float32) / norm_val).reshape(
        frames.shape[0], -1) @ p["w_embed"]
    norm = lambda x, w: rms(x, m["rms_norm_eps"]) * (1.0 + w)
    rows, load, prob, states = [], [], [], []
    for i, kind in enumerate(m["pattern"]):
        lp = p[f"layers_{i}"]

        @jax.checkpoint
        def layer(lp, x, kind=kind):
            u = norm(x, lp["norm"])
            if kind == "*":
                return x + attention(lp, u, m), ()
            out, *rest = delta_rule(lp, u, m) if kind == "D" \
                else experts(lp, u, m)
            return x + out, rest

        x, rest = layer(lp, x)
        if kind == "E":
            rows.append(rest[0])
            load.append(rest[1])
            prob.append(rest[2])
        elif kind == "D":
            states.append(rest[0])
    q = norm(x, p["final_norm"]) @ p["head_w"] + p["head_b"]
    rows = jnp.stack(rows) if rows else jnp.zeros((0,), jnp.int32)
    return q, rows, load, prob, states


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


def segment_loss(params, q_target, seg, share, *, model, norm_val, burn_in,
                 nstep, gamma, eta, double, rescale):
    """One segment's share of the TD loss's numerator and of the balance
    loss (``share``: each E layer's f_e over the WHOLE update, constants),
    its priority signal and its rows per E layer."""
    fwd = h if rescale else (lambda x: x)
    inv = h_inv if rescale else (lambda x: x)
    q, rows, _, prob, _ = segment_pass(params, seg["frames"], model, norm_val)
    balance = sum(f.shape[0] * jnp.sum(f * P) for f, P in zip(share, prob))
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
    return (jnp.sum(jnp.square(td) * m) * seg["weight"] / seg["valid"]
            + seg["balance_weight"] * balance,
            (seq_pr, rows, balance))


STATIC = ("model", "norm_val", "burn_in", "nstep", "gamma", "eta", "double",
          "rescale")


def _model(static):
    return dict(static)


@functools.partial(jax.jit, static_argnames=("model", "norm_val"))
def _segment_pass(params, frames, *, model, norm_val):
    return segment_pass(params, frames, _model(model), norm_val)


@functools.partial(jax.jit, static_argnames=STATIC)
def _segment_grad(params, q_target, seg, share, *, model, **static):
    return jax.value_and_grad(segment_loss, has_aux=True)(
        params, q_target, seg, share, model=_model(model), **static)


def _static_model(model: dict):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in model.items()))


def update_rows(params, target_params, batch, hyper: dict, norm_val: float):
    """``(loss, per-segment priority signal, gradient, rows routed to the
    experts held (B, E layers), the weighted balance loss inside the
    loss)``; ``batch["obs"]`` is frame-packed (B, T + C, H, W) when
    ``hyper["pack_frames"]`` = C (position t reads frame t + C - 1), else
    (B, T + 1, H, W).  Three passes a segment: the target's Q, the online
    network's loads (the balance loss needs every expert's share of the
    WHOLE update before any segment's gradient), then the gradient."""
    C = int(hyper.get("pack_frames", 0))
    frames = batch["obs"][:, C - 1:] if C else batch["obs"]
    B = frames.shape[0]
    model = hyper["model"]
    coef = 0.0 if "no_aux" in model.get("wrong", ()) \
        else float(model["router_aux_loss_coef"])
    static = dict(model=_static_model(model),
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
        loads = [_segment_pass(params, frames[b], **ends)[2]
                 for b in range(B)]
        pairs = B * frames.shape[1] * model["num_experts_per_tok"]
        share = [sum(seg[i] for seg in loads).astype(jnp.float32) / pairs
                 for i in range(len(loads[0]))]
        loss, balance, grads, signal, rows = 0.0, 0.0, None, [], []
        for b in range(B):
            seg = {k: batch[k][b] for k in
                   ("action", "reward", "terminal", "mask", "weight")}
            seg.update(frames=frames[b], valid=valid,
                       balance_weight=jnp.float32(coef / B))
            q_target = _segment_pass(target_params, frames[b], **ends)[0]
            (part, (seq_pr, n, bal)), g = _segment_grad(
                params, q_target, seg, share, **static)
            loss, balance = loss + part, balance + coef / B * bal
            grads = g if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, g)
            signal.append(seq_pr)
            rows.append(n)
    return loss, jnp.stack(signal), grads, jnp.stack(rows), balance


@functools.partial(jax.jit, static_argnames=("model",))
def _delta_state(layer, u, *, model):
    return delta_rule(f32(layer), u.astype(jnp.float32), _model(model))[1]


def delta_states(layer, u, model: dict):
    """One D layer on given normed inputs (B, T, d) -> its states after the
    last position (B, value heads, d_k, d_v), a segment at a time."""
    layer = jax.device_put(layer)
    with jax.default_matmul_precision("highest"):
        return jnp.stack([_delta_state(layer, jnp.asarray(seg),
                                       model=_static_model(model))
                          for seg in u])


@functools.partial(jax.jit, static_argnames=("model",))
def _attention_output(layer, u, *, model):
    return attention(f32(layer), u.astype(jnp.float32), _model(model))


def attention_outputs(layer, u, model: dict):
    """The * block's mixer on given normed inputs (B, T, d) -> its outputs
    (B, T, d), a segment at a time."""
    layer = jax.device_put(layer)
    with jax.default_matmul_precision("highest"):
        return jnp.stack([_attention_output(layer, jnp.asarray(seg),
                                            model=_static_model(model))
                          for seg in u])


def window_states(params, frames, model: dict, norm_val: float):
    """(B, T, H, W) frames -> ([each E layer's load (E,), the segments
    together], [each D layer's states after the last position (B, h, d_k,
    d_v)]), a segment at a time."""
    static = dict(model=_static_model(model), norm_val=float(norm_val))
    params = jax.device_put(params)
    with jax.default_matmul_precision("highest"):
        out = [_segment_pass(params, f, **static) for f in frames]
    return ([sum(seg[2][i] for seg in out) for i in range(len(out[0][2]))],
            [jnp.stack([seg[4][i] for seg in out])
             for i in range(len(out[0][4]))])


def update(params, target_params, batch, hyper: dict, norm_val: float):
    """``(loss, per-segment priority signal, gradient)``: the interface of
    every reference of the benchmark."""
    return update_rows(params, target_params, batch, hyper, norm_val)[:3]


def batch_of(sample) -> dict:
    return {k: getattr(sample, k) for k in (
        "obs", "action", "reward", "terminal", "mask", "weight")}
