"""Plain float32 reference of the hybrid sequence Q-network's update
(models/hybrid.py): layers of a published hybrid state-space / sparse-expert
/ grouped-query language model (config.json of
nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, ``model_type: nemotron_h``) as
the trunk of an R2D2-style Q-network.  benchmark/reference/nemotron_h.py is
a byte-for-byte copy of this file (tests/test_hybrid.py holds them equal).

Written down from the published description, straightforwardly: every
matmul in float32 under ``default_matmul_precision("highest")``, the
state-space layer as a per-position recurrence (no chunks), the experts as a
loop over the experts held (no sort, no grouped matmul), attention through
the full score matrix.  One segment at a time, each layer under
``jax.checkpoint``, so that it fits beside nothing else on one chip.

Pre-norm residual blocks ``x <- x + mixer(RMSNorm(x))``, eps 1e-5, no biases
but the conv's; one letter of ``pattern`` a layer:

  M  [z | xBC | dt] = u W_in;  xBC <- silu(causal depth-wise conv(xBC) + b);
     xBC = [x (heads x head_dim) | B | C (groups x state each)];
     dt <- softplus(dt + dt_bias),  A = -exp(A_log);  per head h, with the
     B, C of group h // (heads / groups), from S = 0:
        S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t,   y_t = S_t C_t + D x_t
     out = RMSNorm_grouped(y * silu(z)) W_out   (groups of d_inner / groups)
  *  q = u W_q (heads x head_dim), k, v = u W_k, u W_v (kv_heads x head_dim);
     causal softmax(q k^T / sqrt(head_dim)) v, each key-value head shared by
     heads / kv_heads query heads; W_o.  NO rotary, NO position table.
  E  s = sigmoid(u W_r) over all experts; the top_k largest of s + b_sel are
     chosen; their weights are their s (without b_sel) normalised to sum 1,
     times ``route_scale``.  Expert e: act(u W_up,e) W_down,e with act =
     relu^2.  Plus one shared expert, unweighted.  Only experts
     ``first_expert .. first_expert + held`` exist here: what the others
     would add is left out, and that partial result goes on.  b_sel has no
     gradient; after an update it moves by a fixed step against each
     expert's load, the tokens that chose it (``balanced_bias``).

Ends (the repo's sequence-family contract): one H x W frame a position,
/ norm_val, flattened, @ w_embed; final RMSNorm; @ head_w + head_b.

The update is R2D2's on a window without stored state (zero state at
position 0; the first ``burn_in`` positions are context only): double-Q
bootstrap through the value rescaling, n-step returns inside the window
shrinking at its end and at masked tails, masked importance-weighted MSE,
eta-blended per-segment priorities (see benchmark/reference/r2d2.py, whose
steps 3-6 these are).

``hyper`` (the configuration's ``reference_hyper``) holds the update's
constants and, under ``model``, the architecture's numbers under their
published names plus ``first_expert`` (the experts held are counted from the
weights), ``mlp_hidden_act`` and ``scan_state_dtype`` (float32: the
precision the configuration states for the recurrent state).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

PRIORITY_EPS = 1e-6
RESCALE_EPS = 1e-3
SCAN_BLOCK = 64     # positions whose states are recomputed in the backward


def h(x):
    return jnp.sign(x) * (jnp.sqrt(jnp.abs(x) + 1.0) - 1.0) + RESCALE_EPS * x


def h_inv(x):
    e = RESCALE_EPS
    return jnp.sign(x) * (jnp.square(
        (jnp.sqrt(1.0 + 4.0 * e * (jnp.abs(x) + 1.0 + e)) - 1.0)
        / (2.0 * e)) - 1.0)


def rms_norm(x, scale, eps, groups=1):
    xg = x.reshape(*x.shape[:-1], groups, -1)
    xg = xg / jnp.sqrt(jnp.mean(xg * xg, axis=-1, keepdims=True) + eps)
    return xg.reshape(x.shape) * scale


def f32(tree):
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)


# ---------------------------------------------------------------------------
# the three mixers, one segment: u is (T, d)
# ---------------------------------------------------------------------------

def mamba(p, u, m):
    """-> (the layer's output, the state after the last position)."""
    heads, hd = m["mamba_num_heads"], m["mamba_head_dim"]
    groups, n = m["n_groups"], m["ssm_state_size"]
    d_inner, T = heads * hd, u.shape[0]
    zxbcdt = u @ p["w_in"]
    z = zxbcdt[:, :d_inner]
    xBC = zxbcdt[:, d_inner:d_inner + d_inner + 2 * groups * n]
    dt = zxbcdt[:, -heads:]
    K = p["conv_w"].shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, xBC.shape[1])), xBC])
    xBC = jax.nn.silu(sum(padded[j:j + T] * p["conv_w"][j] for j in range(K))
                      + p["conv_b"])
    x = xBC[:, :d_inner].reshape(T, heads, hd)
    B = xBC[:, d_inner:d_inner + groups * n].reshape(T, groups, n)
    C = xBC[:, d_inner + groups * n:].reshape(T, groups, n)
    B, C = (jnp.repeat(t, heads // groups, axis=1) for t in (B, C))
    dt = jax.nn.softplus(dt + p["dt_bias"])                     # (T, heads)
    A = -jnp.exp(p["A_log"])
    state_dtype = jnp.dtype(m.get("scan_state_dtype", "float32"))

    def position(S, inp):
        x_t, B_t, C_t, dt_t = inp
        S = (jnp.exp(dt_t * A)[:, None, None] * S.astype(jnp.float32)
             + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
        y_t = jnp.einsum("hpn,hn->hp", S, C_t)
        return S.astype(state_dtype), y_t

    @jax.checkpoint
    def block(S, inp):
        return jax.lax.scan(position, S, inp)

    pad = -T % SCAN_BLOCK
    blocks = lambda t: jnp.concatenate(
        [t, jnp.zeros((pad, *t.shape[1:]))]).reshape(-1, SCAN_BLOCK,
                                                     *t.shape[1:])
    # the padding has dt = 0: it decays nothing and adds nothing
    S, y = jax.lax.scan(block, jnp.zeros((heads, hd, n), state_dtype),
                        tuple(blocks(t) for t in (x, B, C, dt)))
    y = y.reshape(-1, heads, hd)[:T] + p["D"][:, None] * x
    y = y.reshape(T, d_inner) * jax.nn.silu(z)
    return (rms_norm(y, p["gate_norm"], m["norm_eps"], groups) @ p["w_out"],
            S.astype(jnp.float32))


def attention(p, u, m):
    heads, kv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                     m["head_dim"])
    T = u.shape[0]
    q = (u @ p["w_q"]).reshape(T, heads, hd)
    k = jnp.repeat((u @ p["w_k"]).reshape(T, kv, hd), heads // kv, axis=1)
    v = jnp.repeat((u @ p["w_v"]).reshape(T, kv, hd), heads // kv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(T, heads * hd) @ p["w_o"]


def experts(p, u, m):
    """-> (the layer's output, rows routed to the experts held here, the
    tokens that chose each of ALL experts)."""
    act = {"relu2": lambda x: jnp.square(jax.nn.relu(x)),
           "relu": jax.nn.relu}[m.get("mlp_hidden_act", "relu2")]
    first, held = int(m["first_expert"]), p["w_up"].shape[0]
    s = jax.nn.sigmoid(u @ p["router"])
    _, chosen = jax.lax.top_k(s + p["b_sel"], m["num_experts_per_tok"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * m["routed_scaling_factor"]
    out = act(u @ p["w_shared_up"]) @ p["w_shared_down"]
    rows = 0
    for e in range(held):
        mine = chosen == first + e                              # (T, k)
        out = out + jnp.sum(jnp.where(mine, w, 0.0), axis=-1)[:, None] * (
            act(u @ p["w_up"][e]) @ p["w_down"][e])
        rows = rows + jnp.sum(mine)
    load = jnp.stack([jnp.sum(chosen == e) for e in range(s.shape[-1])])
    return out, rows, load


def balanced_bias(b_sel, load, rate):
    """b_sel after an update in which ``load`` tokens chose each expert."""
    load = load.astype(jnp.float32)
    return b_sel + rate * jnp.sign(jnp.mean(load) - load)


def segment_pass(params, frames, m, norm_val):
    """(T, H, W) frames of one segment -> (Q (T, A), rows per E layer,
    [each E layer's load], [each M layer's state after the last
    position])."""
    p = f32(params["params"])
    x = (frames.astype(jnp.float32) / norm_val).reshape(
        frames.shape[0], -1) @ p["w_embed"]
    rows, load, states = [], [], []
    for i, kind in enumerate(m["pattern"]):
        lp = p[f"layers_{i}"]

        @jax.checkpoint
        def layer(lp, x, kind=kind):
            u = rms_norm(x, lp["norm"], m["norm_eps"])
            if kind == "*":
                return x + attention(lp, u, m), ()
            out, *rest = mamba(lp, u, m) if kind == "M" else experts(lp, u, m)
            return x + out, rest

        x, rest = layer(lp, x)
        if kind == "E":
            rows.append(rest[0])
            load.append(rest[1])
        elif kind == "M":
            states.append(rest[0])
    q = rms_norm(x, p["final_norm"], m["norm_eps"]) @ p["head_w"] + p["head_b"]
    rows = jnp.stack(rows) if rows else jnp.zeros((0,), jnp.int32)
    return q, rows, load, states


def segment_q(params, frames, m, norm_val):
    """-> (Q (T, A), rows per E layer)."""
    return segment_pass(params, frames, m, norm_val)[:2]


def window_q(params, frames, m, norm_val):
    """(B, T, H, W) -> (Q (B, T, A), rows (B, E layers)), a segment at a
    time."""
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
    """One segment's share of the loss numerator, its priority signal and
    its rows per E layer."""
    fwd = h if rescale else (lambda x: x)
    inv = h_inv if rescale else (lambda x: x)
    q, rows = segment_q(params, seg["frames"], model, norm_val)
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
    return jnp.sum(jnp.square(td) * m) * seg["weight"], (seq_pr, rows)


STATIC = ("model", "norm_val", "burn_in", "nstep", "gamma", "eta", "double",
          "rescale")


@functools.partial(jax.jit, static_argnames=("model", "norm_val"))
def _segment_q(params, frames, *, model, norm_val):
    return segment_q(params, frames, dict(model), norm_val)[0]


@functools.partial(jax.jit, static_argnames=("model", "norm_val"))
def _segment_pass(params, frames, *, model, norm_val):
    return segment_pass(params, frames, dict(model), norm_val)[2:]


@functools.partial(jax.jit, static_argnames=STATIC)
def _segment_grad(params, q_target, seg, *, model, **static):
    return jax.value_and_grad(segment_loss, has_aux=True)(
        params, q_target, seg, model=dict(model), **static)


def update_rows(params, target_params, batch, hyper: dict, norm_val: float):
    """``(loss, per-segment priority signal, gradient, rows routed to the
    experts held (B, E layers))``; ``batch["obs"]`` is frame-packed (B, T +
    C, H, W) when ``hyper["pack_frames"]`` = C (position t reads frame t +
    C - 1), else (B, T + 1, H, W)."""
    C = int(hyper.get("pack_frames", 0))
    frames = batch["obs"][:, C - 1:] if C else batch["obs"]
    static = dict(model=tuple(sorted(hyper["model"].items())),
                  norm_val=float(norm_val), burn_in=int(hyper["burn_in"]),
                  nstep=int(hyper["nstep"]), gamma=float(hyper["gamma"]),
                  eta=float(hyper["eta"]), double=bool(hyper["double"]),
                  rescale=bool(hyper["value_rescale"]))
    # once onto the device, not once a call
    params, target_params = jax.device_put((params, target_params))
    with jax.default_matmul_precision("highest"):
        valid = jnp.maximum(jnp.sum(batch["mask"][:, static["burn_in"]:]),
                            1.0)
        loss, grads, signal, rows = 0.0, None, [], []
        for b in range(frames.shape[0]):
            seg = {k: batch[k][b] for k in
                   ("action", "reward", "terminal", "mask", "weight")}
            seg["frames"] = frames[b]
            q_target = _segment_q(target_params, frames[b],
                                  model=static["model"],
                                  norm_val=static["norm_val"])
            (num, (seq_pr, n)), g = _segment_grad(params, q_target, seg,
                                                  **static)
            loss = loss + num / valid
            g = jax.tree_util.tree_map(lambda x: x / valid, g)
            grads = g if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, g)
            signal.append(seq_pr)
            rows.append(n)
    return loss, jnp.stack(signal), grads, jnp.stack(rows)


@functools.partial(jax.jit, static_argnames=("model",))
def _mamba_state(layer, u, *, model):
    return mamba(f32(layer), u.astype(jnp.float32), dict(model))[1]


def mamba_states(layer, u, model: dict):
    """One M layer on given normed inputs (B, T, d) -> its states after the
    last position (B, h, p, n), a segment at a time."""
    layer = jax.device_put(layer)
    with jax.default_matmul_precision("highest"):
        return jnp.stack([_mamba_state(
            layer, jnp.asarray(seg), model=tuple(sorted(model.items())))
            for seg in u])


def window_states(params, frames, model: dict, norm_val: float):
    """(B, T, H, W) frames -> ([each E layer's load (E,), the segments
    together], [each M layer's states after the last position (B, h, p,
    n)]), a segment at a time."""
    static = dict(model=tuple(sorted(model.items())), norm_val=float(norm_val))
    params = jax.device_put(params)
    with jax.default_matmul_precision("highest"):
        out = [_segment_pass(params, f, **static) for f in frames]
    return ([sum(seg[0][i] for seg in out) for i in range(len(out[0][0]))],
            [jnp.stack([seg[1][i] for seg in out])
             for i in range(len(out[0][1]))])


def update(params, target_params, batch, hyper: dict, norm_val: float):
    """``(loss, per-segment priority signal, gradient)``: the interface of
    every reference of the benchmark."""
    return update_rows(params, target_params, batch, hyper, norm_val)[:3]


def batch_of(sample) -> dict:
    return {k: getattr(sample, k) for k in (
        "obs", "action", "reward", "terminal", "mask", "weight")}
