"""Plain float32 reference of the R2D2 update (Kapturowski et al. 2019,
section 2.3 and the appendix table): Nature conv torso -> LSTM(512) ->
linear Q head, trained on stored-state segments with burn-in.

For a segment of T steps with observations o_0..o_T (frame stacks rebuilt
from the packed frames), actions, rewards, terminals and a validity mask:

  1. burn-in: both nets unroll the first ``burn_in`` steps from the stored
     LSTM state (c0, h0); no loss, no gradient into the state.
  2. both nets unroll the remaining L+1 = T+1-burn_in steps.
  3. bootstrap b_t = h^-1( Q_target(o_t, argmax_a Q(o_t, a)) )  (double DQN;
     h is the value rescaling  h(x) = sign(x)(sqrt(|x|+1)-1) + eps*x).
  4. n-step return inside the window, shrinking at the window end and at
     masked tails, cut by terminals:
        G_t = sum_{k<K} gamma^k r_{t+k} alive_{t,k}
              + gamma^K alive_{t,K} b_{t+K},
        K = min(n, n_valid - t, L - t),  alive_{t,k} = prod_{j<k}(1 - d_{t+j}).
  5. td_t = Q(o_t, a_t) - h(G_t);  loss = sum(td^2 m w) / max(sum(m), 1).
  6. sequence priority  eta * max_t|td| + (1-eta) * mean_t|td|  over valid
     steps, written back as (p + 1e-6)^alpha.

Departures from the paper, shared with the program: no dueling head; the
LSTM is Flax's ``OptimizedLSTMCell`` parameterisation (four input kernels
without bias, four hidden kernels with bias); the gate order i, f, g, o.
Written with an explicit Python loop over n and ``lax.scan`` over time; no
kernels, no packing tricks beyond rebuilding the frame stacks.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import nature_cnn

PRIORITY_EPS = 1e-6
RESCALE_EPS = 1e-3


def h(x):
    return jnp.sign(x) * (jnp.sqrt(jnp.abs(x) + 1.0) - 1.0) + RESCALE_EPS * x


def h_inv(x):
    e = RESCALE_EPS
    return jnp.sign(x) * (jnp.square(
        (jnp.sqrt(1.0 + 4.0 * e * (jnp.abs(x) + 1.0 + e)) - 1.0)
        / (2.0 * e)) - 1.0)


def lstm_step(p, carry, x):
    c, hid = carry
    gate = lambda n: (x @ p[f"i{n}"]["kernel"] + hid @ p[f"h{n}"]["kernel"]
                      + p[f"h{n}"]["bias"])
    i, f, o = (jax.nn.sigmoid(gate(n)) for n in "ifo")
    c = f * c + i * jnp.tanh(gate("g"))
    hid = o * jnp.tanh(c)
    return (c, hid), hid


def step(params, obs, carry, norm_val: float):
    p = params["params"]
    x = nature_cnn.torso(p, obs, norm_val)
    carry, out = lstm_step(p["OptimizedLSTMCell_0"], carry, x)
    return carry, out @ p["Dense_1"]["kernel"] + p["Dense_1"]["bias"]


def unroll(params, carry, obs_tm, norm_val: float):
    return jax.lax.scan(
        lambda c, o: step(params, o, c, norm_val), carry, obs_tm)


def stacks(frames, channels: int, seq_len: int):
    """(B, T+C, H, W) packed frames -> (T+1, B, C, H, W) frame stacks."""
    x = jnp.stack([frames[:, i:i + seq_len + 1] for i in range(channels)],
                  axis=2)
    return jnp.moveaxis(x, 0, 1)


def nstep_returns(boot, r, d, m, nstep: int, gamma: float):
    """boot (L+1, B); r, d, m (L, B), all time-major."""
    L = r.shape[0]
    pad = lambda x: jnp.concatenate(
        [x, jnp.zeros((nstep, *x.shape[1:]), x.dtype)])
    rp, dp, mp = pad(r), pad(d), pad(m)
    ret, alive = jnp.zeros_like(r), jnp.ones_like(r)
    for k in range(nstep):
        ret = ret + gamma ** k * rp[k:k + L] * alive * mp[k:k + L]
        alive = alive * (1.0 - dp[k:k + L])
    t = jnp.arange(L)[:, None]
    n_valid = jnp.sum(m, axis=0).astype(jnp.int32)[None, :]
    at = jnp.minimum(jnp.minimum(t + nstep, n_valid), L)
    K = jnp.maximum(at - t, 0).astype(jnp.float32)
    return ret + gamma ** K * alive * jnp.take_along_axis(boot, at, axis=0)


def loss_fn(params, target_params, batch, *, norm_val, burn_in, nstep, gamma,
            eta, double, rescale, channels):
    T = batch["action"].shape[1]
    obs = stacks(batch["obs"], channels, T) if channels else jnp.moveaxis(
        batch["obs"], 0, 1)
    carry0 = (batch["c0"], batch["h0"])
    fwd = h if rescale else (lambda x: x)
    inv = h_inv if rescale else (lambda x: x)

    tcarry, _ = unroll(target_params, carry0, obs[:burn_in], norm_val)
    _, q_t = unroll(target_params, tcarry, obs[burn_in:], norm_val)
    ocarry, _ = unroll(params, carry0, obs[:burn_in], norm_val)
    _, q = unroll(params, jax.lax.stop_gradient(ocarry), obs[burn_in:],
                  norm_val)

    tm = lambda x: jnp.moveaxis(x, 0, 1)[burn_in:]
    a, r, d, m = (tm(batch[k]) for k in
                  ("action", "reward", "terminal", "mask"))
    L = T - burn_in
    q_sel = jnp.take_along_axis(
        q[:L], a[..., None].astype(jnp.int32), axis=-1)[..., 0]
    if double:
        boot = jnp.take_along_axis(
            q_t, jnp.argmax(q, axis=-1)[..., None], axis=-1)[..., 0]
    else:
        boot = jnp.max(q_t, axis=-1)
    target = fwd(nstep_returns(inv(boot), r, d, m, nstep, gamma))
    td = q_sel - jax.lax.stop_gradient(target)
    loss = jnp.sum(jnp.square(td) * m * batch["weight"][None, :]) / (
        jnp.maximum(jnp.sum(m), 1.0))
    td_abs = jnp.abs(td) * m
    valid = jnp.maximum(jnp.sum(m, axis=0), 1.0)
    seq_pr = eta * jnp.max(td_abs, axis=0) + (1 - eta) * (
        jnp.sum(td_abs, axis=0) / valid)
    return loss, seq_pr


def update(params, target_params, batch, hyper: dict, norm_val: float):
    """``(loss, per-segment priority signal, gradient)``; ``hyper`` is the
    configuration's ``reference_hyper`` group."""
    static = dict(norm_val=norm_val, burn_in=int(hyper["burn_in"]),
                  nstep=int(hyper["nstep"]), gamma=float(hyper["gamma"]),
                  eta=float(hyper["eta"]), double=bool(hyper["double"]),
                  rescale=bool(hyper["value_rescale"]),
                  channels=int(hyper["pack_frames"]))
    with jax.default_matmul_precision("highest"):
        (loss, seq_pr), grads = jax.jit(
            jax.value_and_grad(loss_fn, has_aux=True),
            static_argnames=tuple(static))(
                params, target_params, batch, **static)
    return loss, seq_pr, grads


def batch_of(sample) -> dict:
    return {k: getattr(sample, k) for k in (
        "obs", "action", "reward", "terminal", "mask", "c0", "h0", "weight")}
