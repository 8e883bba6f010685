"""R2D2 sequence update as one pure XLA program.

The recurrent counterpart of ops/losses.py build_dqn_train_step: consumes
a SegmentBatch (memory/sequence_replay.py), runs

    per-observation half of each net over all of its frames at once
    -> burn-in unroll of the recurrent half (stored state, gradients
       stopped)
    -> train-window unroll of it (online + target nets)
    -> within-window n-step double-DQN targets with value rescaling
    -> masked, IS-weighted MSE
    -> Adam -> target update

all under one jit.  Key R2D2 mechanics (Kapturowski et al. 2019), each a
flag so ablations stay possible:

- **stored state + burn-in**: the sampled segment carries the actor's LSTM
  state at its first step; the first ``burn_in`` steps are replayed only
  to refresh that state under current weights (both online and target
  nets), no loss on them.
- **value rescaling**: targets use h(x) = sign(x)(sqrt(|x|+1)-1) + eps*x
  and its closed-form inverse instead of reward clipping.
- **sequence priorities**: eta-blended max/mean of per-step |TD| over
  valid steps, returned as ``td_abs`` for the replay's write-back — the
  same contract Batch-based steps use, so the learner loop is unchanged.

A recurrent Q-network comes as TWO halves (models/drqn.py ``halves``):
the per-observation ``embed`` (torso: stateless from one step to the next)
and the recurrent ``core`` (LSTM cell + Q head).  The step runs ``embed``
once per pass (burn-in prefix, train window) of each network, batched
over every frame of it in the batch-major order the segments arrive in;
only its ``(B, n, F)`` features are moved to time-major, and ``lax.scan``
carries the LSTM alone over time (compiler-friendly control flow — no Python loop over T, no
convolution inside one).  The n-step lookahead is a static unroll over
``nstep`` shifted views (nstep is small and static).
"""

from __future__ import annotations

import contextlib
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import optax

from pytorch_distributed_tpu.memory.sequence_replay import SegmentBatch
from pytorch_distributed_tpu.ops.losses import TrainState, online_grad
from pytorch_distributed_tpu.utils.health import finite_guard
from pytorch_distributed_tpu.utils.helpers import global_norm, update_target
from pytorch_distributed_tpu.utils.profiling import (
    PHASE_GATHER, PHASE_ONLINE, PHASE_OPTIMIZER, PHASE_TARGET,
    SCOPE_BURN_IN, SCOPE_EMBED, SCOPE_UNROLL,
)

PyTree = Any

RESCALE_EPS = 1e-3


def value_rescale(x: jnp.ndarray, eps: float = RESCALE_EPS) -> jnp.ndarray:
    return jnp.sign(x) * (jnp.sqrt(jnp.abs(x) + 1.0) - 1.0) + eps * x


def value_unrescale(x: jnp.ndarray, eps: float = RESCALE_EPS) -> jnp.ndarray:
    # closed-form inverse of value_rescale
    return jnp.sign(x) * (
        jnp.square((jnp.sqrt(1.0 + 4.0 * eps * (jnp.abs(x) + 1.0 + eps))
                    - 1.0) / (2.0 * eps)) - 1.0)


def unpack_frame_stacks(frames: jnp.ndarray, C: int,
                        seq_len: int) -> jnp.ndarray:
    """Rebuild C-stacked observations from a frame-packed segment
    (memory/sequence_replay.py SegmentBuilder pack_frames): frames
    (B, T+C, H, W) -> stacks (B, T+1, C, H, W), stack t = frames
    [t, t+C) with channel 0 oldest — exactly the env's frame-stack
    layout.  Runs inside the jitted step: the C-fold de-duplication
    lives on the wire/host, the redundancy is re-materialised only in
    device HBM where it is cheap."""
    return jnp.stack([frames[:, i:i + seq_len + 1] for i in range(C)],
                     axis=2)


def unroll(core_fn: Callable, params: PyTree, carry, x_tm: jnp.ndarray,
           phase: str | None = None) -> Tuple[Any, jnp.ndarray]:
    """Scan the recurrent half ``core_fn(params, x, carry) -> (q, carry')``
    over time-major features (T, B, F) -> (carry_out, q_seq (T, B, A)).

    ``phase`` names the device phase (utils/profiling.py) INSIDE the scan
    body as well: what JAX hoists out of a differentiated scan
    (loop-invariant casts of the weights) keeps the names entered in the
    body and loses every name entered around the scan."""

    def step(c, x):
        with (jax.named_scope(phase) if phase
              else contextlib.nullcontext()):
            q, c2 = core_fn(params, x, c)
        return c2, q

    return jax.lax.scan(step, carry, x_tm)


def nstep_window_returns(boot: jnp.ndarray, r_tm: jnp.ndarray,
                         d_tm: jnp.ndarray, m_tm: jnp.ndarray, *,
                         nstep: int, gamma: float) -> jnp.ndarray:
    """Within-window n-step returns, shared by the DRQN and DTQN steps.

    For each window position t:
        G_t = sum_{k<K} gamma^k r_{t+k} * alive_{t,k}
              + gamma^K * alive_{t,K} * boot_{t+K}
    with K = min(nstep, n_valid - t, L - t) — the lookahead shrinks at the
    window end AND at masked tails (truncated episodes end their segment
    without a terminal, so the bootstrap comes from the last valid
    position's successor obs, which SegmentBuilder stores right after the
    tail) — and alive_{t,k} = prod_{j<k} (1 - terminal_{t+j}) zeroing the
    bootstrap past real deaths.  ``boot`` is (L+1, B) already unrescaled;
    r/d/m are time-major (L, B).
    """
    L = r_tm.shape[0]
    pad = lambda x: jnp.concatenate(
        [x, jnp.zeros((nstep, *x.shape[1:]), x.dtype)], axis=0)
    r_p, d_p, m_p = pad(r_tm), pad(d_tm), pad(m_tm)
    ret = jnp.zeros_like(r_tm)
    alive = jnp.ones_like(r_tm)
    for k in range(nstep):  # static unroll; nstep is small
        ret = ret + (gamma ** k) * r_p[k:k + L] * alive * m_p[k:k + L]
        alive = alive * (1.0 - d_p[k:k + L])
    idx_t = jnp.arange(L)[:, None]                               # (L, 1)
    n_valid = jnp.sum(m_tm, axis=0).astype(jnp.int32)            # (B,)
    boot_idx = jnp.minimum(jnp.minimum(idx_t + nstep, n_valid[None, :]), L)
    boot_at = jnp.take_along_axis(boot, boot_idx, axis=0)        # (L, B)
    K = jnp.maximum(boot_idx - idx_t, 0).astype(jnp.float32)
    return ret + (gamma ** K) * alive * boot_at


def _masked_loss_and_priority(q_sel, target, m_tm, weight, eta):
    """IS-weighted masked MSE + eta-blended per-sequence priorities."""
    td = q_sel - jax.lax.stop_gradient(target)
    w = weight[None, :]
    loss = jnp.sum(jnp.square(td) * m_tm * w) / jnp.maximum(
        jnp.sum(m_tm), 1.0)
    td_abs = jnp.abs(td) * m_tm
    valid = jnp.maximum(jnp.sum(m_tm, axis=0), 1.0)
    seq_pr = (eta * jnp.max(td_abs, axis=0)
              + (1 - eta) * jnp.sum(td_abs, axis=0) / valid)
    return loss, seq_pr


def _bootstrap_values(q_tm, q_target_tm, enable_double, h_inv):
    """Per-position bootstrap values (double-DQN optional), unrescaled."""
    if enable_double:
        a_star = jnp.argmax(q_tm, axis=-1)
        boot = jnp.take_along_axis(q_target_tm, a_star[..., None],
                                   axis=-1)[..., 0]
    else:
        boot = jnp.max(q_target_tm, axis=-1)
    return h_inv(boot)


def _apply_update(state, grads, loss, seq_pr, q_mean, tx,
                  target_model_update, axis_name=None, extra_metrics=None):
    """Gradient mean over ``axis_name`` (if any), Adam, target sync and
    metrics: the ``train.optimizer`` phase of both sequence steps."""
    with jax.named_scope(PHASE_OPTIMIZER):
        if axis_name is not None:
            grads = jax.lax.pmean(grads, axis_name)
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        new_step = state.step + 1
        target_params = update_target(state.target_params, params, new_step,
                                      target_model_update)
        metrics = {
            "learner/critic_loss": loss,
            "learner/q_mean": q_mean,
            "learner/grad_norm": global_norm(grads),
        }
        if extra_metrics:
            metrics.update(extra_metrics)
    return (TrainState(params, target_params, opt_state, new_step),
            metrics, seq_pr)


def build_drqn_train_step(
    embed_fn: Callable,
    core_fn: Callable,
    tx: optax.GradientTransformation,
    *,
    burn_in: int = 10,
    nstep: int = 5,
    gamma: float = 0.99,
    enable_double: bool = True,
    target_model_update: float = 2500,
    rescale_values: bool = True,
    priority_eta: float = 0.9,
    axis_name: str | None = None,
    packed_frames: int = 0,
    guard: bool = True,
) -> Callable[[TrainState, SegmentBatch],
              Tuple[TrainState, Dict[str, jnp.ndarray], jnp.ndarray]]:
    """Returns ``(state, batch) -> (state, metrics, seq_priorities)``.

    ``embed_fn(params, obs (N, *S)) -> x (N, F)`` and ``core_fn(params,
    x (B, F), carry) -> (q, carry')`` are the network's two halves
    (models/drqn.py ``halves``).

    ``packed_frames=C``: ``batch.obs`` arrives frame-packed (B, T+C, H,
    W) and the stacks are rebuilt on device (unpack_frame_stacks) — the
    R2D2 pixel path's host->device transfer shrinks ~C-fold."""

    h = value_rescale if rescale_values else (lambda x: x)
    h_inv = value_unrescale if rescale_values else (lambda x: x)

    def features(params, obs):
        """The per-observation half over every frame of ``obs`` (B, n,
        *S) at once, batch-major as it arrives -> time-major (n, B, F)."""
        with jax.named_scope(SCOPE_EMBED):
            x = embed_fn(params, obs.reshape(-1, *obs.shape[2:]))
            return jnp.moveaxis(x.reshape(*obs.shape[:2], -1), 0, 1)

    def step(state: TrainState, batch: SegmentBatch):
        T = batch.action.shape[1]
        with jax.named_scope(PHASE_GATHER):
            obs = batch.obs                          # (B, T+1, *S)
            if packed_frames:
                obs = unpack_frame_stacks(obs, packed_frames, T)
        train_len = T - burn_in
        carry0 = (batch.c0, batch.h0)

        def burn_and_unroll(params, burn_params, phase):
            """Refresh the stored state over the burn-in prefix (under
            ``burn_params``; no gradient passes the refreshed state), then
            unroll the train window from it: (train_len+1, B, A).  Each
            stretch is its frames' features in one batched pass, then the
            scan of the recurrent half."""
            carry = carry0
            if burn_in:
                x_burn = features(burn_params, obs[:, :burn_in])
                with jax.named_scope(SCOPE_BURN_IN):
                    carry = jax.lax.stop_gradient(unroll(
                        core_fn, burn_params, carry0, x_burn, phase)[0])
            x_train = features(params, obs[:, burn_in:])
            with jax.named_scope(SCOPE_UNROLL):
                return unroll(core_fn, params, carry, x_train, phase)[1]

        # target-side state refresh + full unroll (no gradients flow here)
        with jax.named_scope(PHASE_TARGET):
            q_target_tm = burn_and_unroll(
                state.target_params, state.target_params, PHASE_TARGET)

        # time-major views of the train window
        with jax.named_scope(PHASE_ONLINE):
            a_tm = jnp.moveaxis(batch.action, 0, 1)[burn_in:]    # (L, B)
            r_tm = jnp.moveaxis(batch.reward, 0, 1)[burn_in:]
            d_tm = jnp.moveaxis(batch.terminal, 0, 1)[burn_in:]
            m_tm = jnp.moveaxis(batch.mask, 0, 1)[burn_in:]

        def loss_fn(params):
            # the burn-in prefix only refreshes the state and no gradient
            # passes that refresh: under frozen weights it is a forward
            # pass, nothing of it kept for a backward
            q_tm = burn_and_unroll(params, jax.lax.stop_gradient(params),
                                   PHASE_ONLINE)
            q_sel = jnp.take_along_axis(
                q_tm[:train_len], a_tm[..., None].astype(jnp.int32),
                axis=-1)[..., 0]                                  # (L, B)
            boot = _bootstrap_values(q_tm, q_target_tm, enable_double,
                                     h_inv)                       # (L+1, B)
            target = h(nstep_window_returns(boot, r_tm, d_tm, m_tm,
                                            nstep=nstep, gamma=gamma))
            loss, seq_pr = _masked_loss_and_priority(
                q_sel, target, m_tm, batch.weight, priority_eta)
            return loss, (seq_pr, jnp.mean(jnp.max(q_tm, axis=-1)))

        (loss, (seq_pr, q_mean)), grads = online_grad(
            loss_fn, has_aux=True)(state.params)
        return _apply_update(state, grads, loss, seq_pr, q_mean, tx,
                             target_model_update, axis_name)

    return finite_guard(step) if guard else step


# the entry of a window_apply's dict that is a LOSS: added to the TD loss as
# the model weighed it, and reported under this name like the dict's other
# scalars
AUX_LOSS_KEY = "learner/moe_aux_loss"


def build_dtqn_train_step(
    window_apply: Callable,
    tx: optax.GradientTransformation,
    *,
    burn_in: int = 10,
    nstep: int = 5,
    gamma: float = 0.99,
    enable_double: bool = True,
    target_model_update: float = 2500,
    rescale_values: bool = True,
    priority_eta: float = 0.9,
    axis_name: str | None = None,
    aux_weight: float = 0.0,
    target_window_apply: Callable | None = None,
    after_update: Callable | None = None,
    guard: bool = True,
) -> Callable[[TrainState, SegmentBatch],
              Tuple[TrainState, Dict[str, jnp.ndarray], jnp.ndarray]]:
    """Transformer (DTQN) sequence update: same contract as
    build_drqn_train_step but ONE causal pass per segment instead of a
    time scan — ``window_apply(params, obs_seq (B,T+1,*S)) -> (B,T+1,A)``
    (models/dtqn.py window_q).  There is no stored recurrent state: the
    burn-in prefix participates as attention context only (positions
    before ``burn_in`` are excluded from the loss).

    MoE models (models/moe.py) pass a ``window_apply`` returning
    ``(q, aux)`` instead — the auxiliary load-balancing loss joins the TD
    loss with weight ``aux_weight`` and surfaces as
    ``learner/moe_aux``.  A ``window_apply`` returning ``(q, {name:
    array})`` (models/hybrid.py: the routing counters of its expert
    layers) has the dict's scalars joined to the step's metrics instead,
    and the whole dict handed to ``after_update(params, dict) -> params``
    once the optimizer has stepped: the place for what a model updates by
    a rule of its own and not by a gradient.  The dict carries an
    auxiliary loss under ``AUX_LOSS_KEY``: already weighed by the model,
    it joins the TD loss as it is (``aux_weight`` is the scalar form's).
    ``target_window_apply``, when given, evaluates
    the target-network pass — MoE passes a q-only apply here so the
    frozen pass skips the mutable sow collection whose aux value is
    discarded anyway (round-2 advisor finding)."""

    h = value_rescale if rescale_values else (lambda x: x)
    h_inv = value_unrescale if rescale_values else (lambda x: x)

    def split_apply(params, obs):
        """-> (q, aux loss, step metrics)."""
        out = window_apply(params, obs)
        # tuple-vs-array is static python structure, resolved at trace time
        if not isinstance(out, tuple):
            return out, jnp.float32(0.0), {}
        q, aux = out
        return (q, jnp.float32(0.0), aux) if isinstance(aux, dict) \
            else (q, aux, {})

    def target_apply(params, obs):
        if target_window_apply is not None:
            return target_window_apply(params, obs)
        return split_apply(params, obs)[0]

    def step(state: TrainState, batch: SegmentBatch):
        T = batch.action.shape[1]
        train_len = T - burn_in
        # (L+1, B, A) over the train window, burn-in kept as context
        to_tm = lambda q: jnp.moveaxis(q, 0, 1)[burn_in:]
        with jax.named_scope(PHASE_TARGET):
            q_target_tm = to_tm(target_apply(state.target_params,
                                             batch.obs))

        with jax.named_scope(PHASE_ONLINE):
            a_tm = jnp.moveaxis(batch.action, 0, 1)[burn_in:]
            r_tm = jnp.moveaxis(batch.reward, 0, 1)[burn_in:]
            d_tm = jnp.moveaxis(batch.terminal, 0, 1)[burn_in:]
            m_tm = jnp.moveaxis(batch.mask, 0, 1)[burn_in:]

        def loss_fn(params):
            q, aux, stats = split_apply(params, batch.obs)
            q_tm = to_tm(q)
            q_sel = jnp.take_along_axis(
                q_tm[:train_len], a_tm[..., None].astype(jnp.int32),
                axis=-1)[..., 0]
            boot = _bootstrap_values(q_tm, q_target_tm, enable_double,
                                     h_inv)
            target = h(nstep_window_returns(boot, r_tm, d_tm, m_tm,
                                            nstep=nstep, gamma=gamma))
            loss, seq_pr = _masked_loss_and_priority(
                q_sel, target, m_tm, batch.weight, priority_eta)
            loss = loss + aux_weight * aux
            if AUX_LOSS_KEY in stats:
                loss = loss + stats[AUX_LOSS_KEY]
            return loss, (seq_pr, jnp.mean(jnp.max(q_tm, axis=-1)), aux,
                          stats)

        (loss, (seq_pr, q_mean, aux, stats)), grads = online_grad(
            loss_fn, has_aux=True)(state.params)
        extra = {k: v for k, v in stats.items() if jnp.ndim(v) == 0}
        if aux_weight:
            extra["learner/moe_aux"] = aux
        new, metrics, seq_pr = _apply_update(
            state, grads, loss, seq_pr, q_mean, tx, target_model_update,
            axis_name, extra)
        if after_update is not None:
            with jax.named_scope(PHASE_OPTIMIZER):
                new = new._replace(params=after_update(new.params, stats))
        return new, metrics, seq_pr

    return finite_guard(step) if guard else step
