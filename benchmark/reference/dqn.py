"""Plain float32 reference of the Ape-X DQN update (Horgan et al. 2018,
section 3 and Appendix; Nature-DQN network): loss, per-row |TD| and the
gradient of one prioritized n-step minibatch.

    y_i    = R_i + gamma_n_i * (1 - terminal_i) * max_a Q_target(s'_i, a)
    (double DQN: the online net picks the action the target net scores)
    td_i   = Q(s_i, a_i) - y_i
    loss   = mean_i( w_i * td_i^2 )           w_i: importance weights
    p_i'   = (|td_i| + 1e-6) ^ alpha          priority written back

Departures from the paper, shared with the program and the upstream
``pytorch-distributed`` it re-implements: plain squared error without the
1/2 factor (upstream's ``nn.MSELoss``); the importance weights are
normalised by the largest weight over the valid rows.  Everything is
float32 with matmul precision "highest": on a TPU a float32 matmul otherwise
runs in bf16 passes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import nature_cnn

PRIORITY_EPS = 1e-6


def q_values(params, obs, norm_val: float):
    p = params["params"]
    h = nature_cnn.torso(p, obs, norm_val)
    return h @ p["Dense_1"]["kernel"] + p["Dense_1"]["bias"]


def loss_fn(params, target_params, batch, *, norm_val: float, double: bool):
    q = q_values(params, batch["state0"], norm_val)
    q_sel = jnp.take_along_axis(
        q, batch["action"].astype(jnp.int32)[:, None], axis=1)[:, 0]
    q_next = q_values(target_params, batch["state1"], norm_val)
    if double:
        a_next = jnp.argmax(q_values(params, batch["state1"], norm_val), -1)
        boot = jnp.take_along_axis(q_next, a_next[:, None], axis=1)[:, 0]
    else:
        boot = jnp.max(q_next, axis=-1)
    target = batch["reward"] + batch["gamma_n"] * boot * (
        1.0 - batch["terminal1"])
    td = q_sel - jax.lax.stop_gradient(target)
    return jnp.mean(batch["weight"] * jnp.square(td)), jnp.abs(td)


def update(params, target_params, batch, hyper: dict, norm_val: float):
    """``(loss, per-row priority signal, gradient)`` of one minibatch;
    ``hyper`` is the configuration's ``reference_hyper`` group."""
    with jax.default_matmul_precision("highest"):
        (loss, td_abs), grads = jax.jit(
            jax.value_and_grad(loss_fn, has_aux=True),
            static_argnames=("norm_val", "double"))(
                params, target_params, batch, norm_val=norm_val,
                double=bool(hyper["double"]))
    return loss, td_abs, grads


def batch_of(sample) -> dict:
    """The fields of the program's sampled ``Batch`` the reference reads."""
    return {k: getattr(sample, k) for k in (
        "state0", "action", "reward", "gamma_n", "state1", "terminal1",
        "weight")}
