"""Plain float32 reference of the fourth hybrid sequence Q-network's update
(models/hybrid.py PRESETS["lfm2-moe-5"]): layers of a published
short-convolution / grouped-query-attention / sparse-expert language model
(config.json of LiquidAI/LFM2-8B-A1B, ``model_type: lfm2_moe``) as the trunk
of an R2D2-style Q-network.  benchmark/reference/lfm2_moe.py is a
byte-for-byte copy of this file (tests/test_lfm2_trunk.py holds them equal).
It imports nothing from the program.

Written down from the published description, straightforwardly: every
matmul in float32 under ``default_matmul_precision("highest")``, the short
convolution as a sum of shifted copies, attention through the full masked
score matrix with each key-value head repeated for its query heads (no
query blocks, no cache), the experts as a loop over the experts held with
masks (no sort, no grouped matmul).  One segment at a time, each block
under ``jax.checkpoint``, so that it fits beside nothing else on one chip.

Pre-norm residual blocks ``x <- x + mixer(N(x))``, ``N(x) = x / sqrt(mean
x^2 + eps) * w``, no biases; one letter of ``pattern`` a block (a published
layer is a mixer block, C or *, and a feed-forward block, F for the first
``num_dense_layers`` layers and E after):

  C  gated short convolution (``layer_types`` "conv").  [B | C | x] = u W_in
     (three of hidden_size); z = B * x; y_t = sum_j w_j * z_{t-(L-1)+j}, a
     causal depth-wise conv of ``conv_L_cache`` taps, zero before t = 0, no
     bias (``conv_bias`` false), no activation; out = (C * y) W_out.
  *  grouped-query attention (``layer_types`` "full_attention").  q = u W_q
     (num_attention_heads x head_dim), k = u W_k, v = u W_v
     (num_key_value_heads x head_dim); q <- N_q(q), k <- N_k(k) a head;
     rotate-half rotary on the whole head at position t (the index in the
     window), base ``rope_theta``; head h reads key-value head h // (heads /
     kv heads); causal softmax(q k^T / sqrt(head_dim)) v; W_o.
  F  (silu(u W_gate) * u W_up) W_down.
  E  s = sigmoid(u W_r) over all experts; the top_k largest of s + b_sel are
     chosen (``use_expert_bias``), their weights s (without b_sel) / (sum of
     the chosen s + ``router_eps``) (``norm_topk_prob``), times
     ``routed_scaling_factor``.  Expert e: (silu(u W_gate,e) * u W_up,e)
     W_down,e.  No shared expert.  Only experts ``first_expert .. first_expert
     + held`` exist here: what the others would add is left out, and that
     partial result goes on.  ``b_sel`` has no gradient (the program steps it
     against the load after every update, an ``assumed`` rule).

Departures from the published model, all of them the configuration file's
``assumed`` and ``reduced``: ends of the repo's sequence-family contract in
place of the token embedding and the LM head (one H x W frame a position,
/ norm_val, flattened, @ w_embed; final N; @ head_w + head_b); the experts
held here are a share of each layer's 32; the layers kept are published
layers 1-5.

The update is R2D2's on a window without stored state (zero state at
position 0; the first ``burn_in`` positions are context only): double-Q
bootstrap through the value rescaling, n-step returns inside the window
shrinking at its end and at masked tails, masked importance-weighted MSE,
eta-blended per-segment priorities (benchmark/reference/r2d2.py steps 3-6).

``hyper`` (the configuration's ``reference_hyper``) holds the update's
constants and, under ``model``, the architecture's numbers under their
published names plus ``pattern``, ``first_expert`` (the experts held are
counted from the weights), ``dtype`` (float32: the precision the reference
computes in; a control computes the whole trunk in a lower one) and
``wrong``: names of terms to get wrong ON PURPOSE, each a control the check
must tell (``WRONG``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

PRIORITY_EPS = 1e-6
RESCALE_EPS = 1e-3
# ``no_conv_gate``: z = x, B left out; ``conv_shift``: every tap reads one
# position later (the conv sees t + 1); ``b_sel_in_weights``: the selection
# bias leaks into the weights; ``half_rotary``: rotary on the first half of
# each head only
WRONG = ("no_conv_gate", "conv_shift", "b_sel_in_weights", "no_qk_norm",
         "half_rotary", "no_topk_renorm")


def h(x):
    return jnp.sign(x) * (jnp.sqrt(jnp.abs(x) + 1.0) - 1.0) + RESCALE_EPS * x


def h_inv(x):
    e = RESCALE_EPS
    return jnp.sign(x) * (jnp.square(
        (jnp.sqrt(1.0 + 4.0 * e * (jnp.abs(x) + 1.0 + e)) - 1.0)
        / (2.0 * e)) - 1.0)


def rms(x, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def cast(tree, dtype):
    return jax.tree_util.tree_map(lambda x: x.astype(dtype), tree)


def dtype_of(m):
    return jnp.dtype(m.get("dtype", "float32"))


# ---------------------------------------------------------------------------
# the mixers, one segment: u is (T, d)
# ---------------------------------------------------------------------------

def short_conv(p, u, m):
    wrong = m.get("wrong", ())
    T, d = u.shape
    bcx = u @ p["w_in"]
    b, c, x = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    z = x if "no_conv_gate" in wrong else b * x
    K = p["conv_w"].shape[0]
    lag = 1 if "conv_shift" in wrong else 0
    padded = jnp.concatenate([jnp.zeros((K - 1, d), z.dtype), z,
                              jnp.zeros((lag, d), z.dtype)])
    y = sum(padded[j + lag:j + lag + T] * p["conv_w"][j] for j in range(K))
    return (c * y) @ p["w_out"]


def rotary(x, dim, theta):
    """Rotate-half rotary on the first ``dim`` of each head, x (T, heads,
    head_dim), position = row."""
    half = dim // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * freq
    cos, sin = (f(angle).astype(x.dtype) for f in (jnp.cos, jnp.sin))
    x1, x2, rest = x[..., :half], x[..., half:dim], x[..., dim:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def attention(p, u, m):
    wrong = m.get("wrong", ())
    heads, kv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                     m["head_dim"])
    T = u.shape[0]
    q = (u @ p["w_q"]).reshape(T, heads, hd)
    k = (u @ p["w_k"]).reshape(T, kv, hd)
    v = (u @ p["w_v"]).reshape(T, kv, hd)
    if "no_qk_norm" not in wrong:
        q = rms(q, m["norm_eps"]) * p["q_norm"]
        k = rms(k, m["norm_eps"]) * p["k_norm"]
    turned = hd // 2 if "half_rotary" in wrong else hd
    q, k = (rotary(t, turned, m["rope_theta"]) for t in (q, k))
    k, v = (jnp.repeat(t, heads // kv, axis=1) for t in (k, v))
    s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(T, heads * hd) @ p["w_o"]


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def dense_mlp(p, u):
    return swiglu(u, p["w_gate"], p["w_up"], p["w_down"])


def route(p, u, m, chosen=None):
    """-> (the experts each token chose (T, k), their weights (T, k)); with
    ``chosen`` given, those experts and their weights."""
    wrong = m.get("wrong", ())
    s = jax.nn.sigmoid(u @ p["router"])
    if chosen is None:
        _, chosen = jax.lax.top_k(s + p["b_sel"], m["num_experts_per_tok"])
    w = jnp.take_along_axis(
        s + p["b_sel"] if "b_sel_in_weights" in wrong else s, chosen,
        axis=-1)
    if m.get("norm_topk_prob", True) and "no_topk_renorm" not in wrong:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + m["router_eps"])
    return chosen, w * m["routed_scaling_factor"]


def experts(p, u, m):
    """-> (the block's output, rows routed to the experts held here, the
    tokens that chose each of ALL experts)."""
    first, held = int(m["first_expert"]), p["w_up"].shape[0]
    chosen, w = route(p, u, m)

    def one(out, inp):
        e, gate, up, down = inp
        mine = chosen == first + e                              # (T, k)
        return out + jnp.sum(jnp.where(mine, w, 0.0), axis=-1)[:, None] \
            * swiglu(u, gate, up, down), jnp.sum(mine)

    out, rows = jax.lax.scan(one, jnp.zeros_like(u),
                             (jnp.arange(held), p["w_gate"], p["w_up"],
                              p["w_down"]))
    load = jnp.sum(jax.nn.one_hot(chosen, p["router"].shape[-1],
                                  dtype=jnp.int32), axis=(0, 1))
    return out, jnp.sum(rows), load


def block_output(p, u, kind, m):
    """One block's mixer on its normed input u (T, d): the output alone."""
    if kind == "C":
        return short_conv(p, u, m)
    if kind == "*":
        return attention(p, u, m)
    if kind == "F":
        return dense_mlp(p, u)
    return experts(p, u, m)[0]


def segment_pass(params, frames, m, norm_val):
    """(T, H, W) frames of one segment -> (Q (T, A), rows per E block, [each
    E block's load])."""
    dtype = dtype_of(m)
    p = cast(params["params"], dtype)
    x = ((frames.astype(jnp.float32) / norm_val).reshape(
        frames.shape[0], -1).astype(dtype) @ p["w_embed"])
    norm = lambda x, w: rms(x, m["norm_eps"]) * w
    rows, load = [], []
    for i, kind in enumerate(m["pattern"]):
        lp = p[f"layers_{i}"]

        @jax.checkpoint
        def block(lp, x, kind=kind):
            u = norm(x, lp["norm"])
            if kind == "E":
                out, *rest = experts(lp, u, m)
                return x + out, rest
            return x + block_output(lp, u, kind, m), ()

        x, rest = block(lp, x)
        if kind == "E":
            rows.append(rest[0])
            load.append(rest[1])
    q = (norm(x, p["final_norm"]) @ p["head_w"] + p["head_b"]).astype(
        jnp.float32)
    rows = jnp.stack(rows) if rows else jnp.zeros((0,), jnp.int32)
    return q, rows, load


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
    q, rows, _ = segment_pass(params, seg["frames"], model, norm_val)
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


@functools.partial(jax.jit, static_argnames=("kind", "model"))
def _block_output(layer, u, *, kind, model):
    m = _model(model)
    dtype = dtype_of(m)
    return block_output(cast(layer, dtype), u.astype(dtype), kind,
                        m).astype(jnp.float32)


def block_outputs(layer, u, kind: str, model: dict):
    """One block's mixer (kind C, *, F or E) on given normed inputs (B, T,
    d) -> its outputs (B, T, d), a segment at a time."""
    layer = jax.device_put(layer)
    with jax.default_matmul_precision("highest"):
        return jnp.stack([_block_output(layer, jnp.asarray(seg), kind=kind,
                                        model=_static_model(model))
                          for seg in u])


@functools.partial(jax.jit, static_argnames=("model",))
def _chosen_weights(layer, u, chosen, *, model):
    m = _model(model)
    dtype = dtype_of(m)
    return route(cast(layer, dtype), u.astype(dtype), m,
                 chosen)[1].astype(jnp.float32)


def chosen_weights(layer, u, chosen, model: dict):
    """An E block's routing weights of the experts ``chosen`` (B, T, k) on
    given normed inputs (B, T, d) -> (B, T, k), a segment at a time: the
    weights apart from the choice, which a near tie of two scores may turn
    between two programs."""
    layer = jax.device_put(layer)
    with jax.default_matmul_precision("highest"):
        return jnp.stack([_chosen_weights(layer, jnp.asarray(seg),
                                          jnp.asarray(c),
                                          model=_static_model(model))
                          for seg, c in zip(u, chosen)])


def window_loads(params, frames, model: dict, norm_val: float):
    """(B, T, H, W) frames -> [each E block's load (E,), the segments
    together], a segment at a time."""
    static = dict(model=_static_model(model), norm_val=float(norm_val))
    params = jax.device_put(params)
    with jax.default_matmul_precision("highest"):
        out = [_segment_pass(params, f, **static) for f in frames]
    return [sum(seg[2][i] for seg in out) for i in range(len(out[0][2]))]


def update(params, target_params, batch, hyper: dict, norm_val: float):
    """``(loss, per-segment priority signal, gradient)``: the interface of
    every reference of the benchmark."""
    return update_rows(params, target_params, batch, hyper, norm_val)[:3]


def batch_of(sample) -> dict:
    return {k: getattr(sample, k) for k in (
        "obs", "action", "reward", "terminal", "mask", "weight")}
