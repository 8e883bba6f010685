"""Learner update steps as pure jitted functions.

Functional re-design of the reference learner hot loops
(reference core/single_processes/dqn_learner.py:50-95 and
ddpg_learner.py:50-106): where the reference mutates a shared CUDA model
with torch autograd + Adam in an OS process, here each update is a pure
``(TrainState, Batch, key) -> (TrainState, metrics)`` XLA program — the
whole step (forward, backward, optimizer, target update) compiles into one
fused computation that the parallel layer can shard over a device mesh with
gradient all-reduce over ICI (parallel/learner.py).

Semantics parity (each cited):
- n-step target ``r + gamma_n * bootstrap(s1) * (1 - terminal)`` with the
  *stored per-sample* effective discount gamma_n
  (reference dqn_learner.py:73-74);
- optional double-DQN action selection by the online net
  (reference dqn_learner.py:67-71, off by default utils/options.py:139);
- MSE value criterion (reference utils/options.py:114) — Huber available;
- gradient clip by value (torch ``clip_grad_value_``,
  reference dqn_learner.py:80-82; inf for DQN, 40 for DDPG);
- target update: hard every N steps for DQN, soft tau for DDPG
  (reference utils/helpers.py:19-25);
- DDPG: policy loss ``-Q(s, pi(s)).mean()`` + critic TD loss
  (reference ddpg_learner.py:66-86).  The reference couples both losses
  through one Adam step so policy-loss gradients also hit the critic
  (ddpg_learner.py:62-91, SURVEY.md "known quirks"); ``coupled=True``
  reproduces that, the default decouples per-net optimizers.

PER additions beyond the reference (its TODO): importance weights multiply
the per-sample TD loss, and |TD| errors are returned for priority
write-back.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import optax

from pytorch_distributed_tpu.utils.experience import Batch
from pytorch_distributed_tpu.utils.health import finite_guard
from pytorch_distributed_tpu.utils.helpers import global_norm, update_target
from pytorch_distributed_tpu.utils.profiling import (
    PHASE_ONLINE, PHASE_OPTIMIZER, PHASE_TARGET,
)

PyTree = Any


class TrainState(NamedTuple):
    params: PyTree
    target_params: PyTree
    opt_state: PyTree
    step: jnp.ndarray  # int32 learner step (the global clock's source)


def init_train_state(params: PyTree,
                     tx: optax.GradientTransformation) -> TrainState:
    """Build a fresh TrainState with the target net hard-synced to the
    online net (reference dqn_learner.py:21-35 syncs at start).  The target
    tree is an independent buffer copy — aliasing ``TrainState(params,
    params, ...)`` breaks donation (XLA rejects donating one buffer twice).
    """
    target = jax.tree_util.tree_map(jnp.array, params)
    return TrainState(params, target, tx.init(params), jnp.asarray(0))


def make_optimizer(lr: float, clip_grad: float = float("inf"),
                   weight_decay: float = 0.0,
                   lr_decay_steps: int = 0) -> optax.GradientTransformation:
    """Adam with optional by-value grad clipping, matching the reference's
    Adam + clip_grad_value_ pairing (reference dqn_learner.py:37-39,80-82).
    ``lr_decay_steps > 0`` linearly anneals the lr to zero over that many
    learner steps (the reference's ``lr_decay`` flag, utils/options.py)."""
    chain = []
    if clip_grad != float("inf"):
        chain.append(optax.clip(clip_grad))  # by-value, like clip_grad_value_
    if weight_decay > 0.0:
        chain.append(optax.add_decayed_weights(weight_decay))
    schedule = (optax.linear_schedule(lr, 0.0, lr_decay_steps)
                if lr_decay_steps > 0 else lr)
    chain.append(optax.adam(schedule))
    return optax.chain(*chain)


def _value_loss(pred: jnp.ndarray, target: jnp.ndarray, weight: jnp.ndarray,
                huber: bool) -> Tuple[jnp.ndarray, jnp.ndarray]:
    td = pred - jax.lax.stop_gradient(target)
    if huber:
        per = optax.huber_loss(pred, jax.lax.stop_gradient(target), delta=1.0)
    else:
        # plain squared error, matching the reference's nn.MSELoss
        # (reference utils/options.py:114) — no 1/2 factor, so gradient
        # magnitudes match the reference under identical learning rates
        per = jnp.square(td)
    return jnp.mean(weight * per), jnp.abs(td)


def online_grad(loss_fn: Callable, has_aux: bool = False) -> Callable:
    """``jax.value_and_grad(loss_fn)``, evaluated inside the device phase
    ``train.online``: entered around the whole differentiation, so the
    loss needs to name only what is NOT the online pass (the target
    pass; the innermost name on an op's path is its phase)."""
    grad_fn = jax.value_and_grad(loss_fn, has_aux=has_aux)

    def scoped(*args):
        with jax.named_scope(PHASE_ONLINE):
            return grad_fn(*args)

    return scoped


def _dqn_loss(apply_fn: Callable, params: PyTree, target_params: PyTree,
              batch: Batch, enable_double: bool, huber: bool):
    """The TD loss of one minibatch, ``(loss, (td_abs, q_mean))``: the one
    statement of the DQN objective (reference dqn_learner.py:55-76) that
    the fused step, the replica split and the megabatch group step all
    differentiate.  Device phases (utils/profiling.py): the callers
    differentiate it inside ``train.online`` (``online_grad``); the pass
    through ``target_params`` is ``train.target``, the innermost name."""
    q = apply_fn(params, batch.state0)                           # (B, A)
    a = batch.action.astype(jnp.int32).reshape(-1, 1)
    q_sel = jnp.take_along_axis(q, a, axis=1)[:, 0]
    with jax.named_scope(PHASE_TARGET):
        q_next = apply_fn(target_params, batch.state1)           # (B, A)
    if enable_double:
        a_next = jnp.argmax(apply_fn(params, batch.state1), axis=-1)
        bootstrap = jnp.take_along_axis(
            q_next, a_next[:, None], axis=1)[:, 0]
    else:
        bootstrap = jnp.max(q_next, axis=-1)
    target = (batch.reward
              + batch.gamma_n * bootstrap * (1.0 - batch.terminal1))
    loss, td_abs = _value_loss(q_sel, target, batch.weight, huber)
    return loss, (td_abs, jnp.mean(jnp.max(q, axis=-1)))


def build_dqn_train_step(
    apply_fn: Callable,
    tx: optax.GradientTransformation,
    *,
    enable_double: bool = False,
    target_model_update: float = 250,
    huber: bool = False,
    axis_name: str | None = None,
    guard: bool = True,
) -> Callable[[TrainState, Batch],
              Tuple[TrainState, Dict[str, jnp.ndarray], jnp.ndarray]]:
    """Returns the DQN update step ``(state, batch) -> (state, metrics,
    td_abs)`` (reference dqn_learner.py:55-95 as one XLA program); ``td_abs``
    feeds PER priority write-back.  ``guard`` (default on) wraps the step
    with the in-jit finite check (utils/health.finite_guard): a
    non-finite step passes the state through unchanged and reports
    ``learner/skipped`` instead of poisoning Adam."""

    def step(state: TrainState, batch: Batch):
        def loss_fn(params):
            return _dqn_loss(apply_fn, params, state.target_params, batch,
                             enable_double, huber)

        (loss, (td_abs, q_mean)), grads = online_grad(
            loss_fn, has_aux=True)(state.params)
        with jax.named_scope(PHASE_OPTIMIZER):
            # data-parallel: mean grads across the mesh's dp axis if present
            grads = _pmean(grads, axis_name)
            updates, opt_state = tx.update(grads, state.opt_state,
                                           state.params)
            params = optax.apply_updates(state.params, updates)
            new_step = state.step + 1
            target_params = update_target(state.target_params, params,
                                          new_step, target_model_update)
            metrics = {
                "learner/critic_loss": loss,
                "learner/q_mean": q_mean,
                "learner/grad_norm": global_norm(grads),
            }
        return (TrainState(params, target_params, opt_state, new_step),
                metrics, td_abs)

    return finite_guard(step) if guard else step


def build_dqn_grad_and_apply(
    apply_fn: Callable,
    tx: optax.GradientTransformation,
    *,
    enable_double: bool = False,
    target_model_update: float = 250,
    huber: bool = False,
) -> Tuple[Callable, Callable]:
    """The ISSUE-15 replica split of ``build_dqn_train_step``: the same
    update factored at the gradient boundary so N data-parallel learner
    replicas can allreduce over DCN between the two halves.

    - ``grad_fn(state, batch) -> (grads, ok, metrics, td_abs)`` computes
      the gradients at the CURRENT params (the exact loss/double-DQN/
      |TD| math of the fused step) plus a finiteness flag ``ok`` (f32
      0/1 over loss, td and every grad leaf — the per-contribution twin
      of ``finite_guard``: a diverged replica's NaN gradient must be
      excluded from the reduce, not poison every survivor).
    - ``apply_grads(state, grads, ok) -> state`` applies an (already
      reduced) gradient tree: optimizer update, step increment and the
      target cadence chained exactly as the fused step chains them;
      ``ok <= 0`` selects the INPUT state through unchanged (a round
      with zero valid contributions is a skipped step, like the guard).

    The halves compose to the fused step's semantics; at world size 1
    the reduced gradient IS the local gradient (mean over one
    contributor divides by 1.0 — an IEEE identity), which is what makes
    the degraded-to-solo parity oracle (tests/test_replicas.py) a
    bit-exact check rather than a tolerance one."""

    def grad_fn(state: TrainState, batch: Batch):
        def loss_fn(params):
            return _dqn_loss(apply_fn, params, state.target_params, batch,
                             enable_double, huber)

        (loss, (td_abs, q_mean)), grads = online_grad(
            loss_fn, has_aux=True)(state.params)
        with jax.named_scope(PHASE_OPTIMIZER):
            ok = jnp.isfinite(loss) & jnp.all(jnp.isfinite(td_abs))
            for leaf in jax.tree_util.tree_leaves(grads):
                ok = ok & jnp.all(jnp.isfinite(leaf))
            metrics = {
                "learner/critic_loss": loss,
                "learner/q_mean": q_mean,
                "learner/grad_norm": global_norm(grads),
            }
        return grads, ok.astype(jnp.float32), metrics, td_abs

    def apply_grads(state: TrainState, grads, ok):
        with jax.named_scope(PHASE_OPTIMIZER):
            updates, opt_state = tx.update(grads, state.opt_state,
                                           state.params)
            params = optax.apply_updates(state.params, updates)
            new_step = state.step + 1
            target_params = update_target(state.target_params, params,
                                          new_step, target_model_update)
            new = TrainState(params, target_params, opt_state, new_step)
            # ok <= 0: the whole round was invalid — pass the input state
            # through per-leaf, exactly finite_guard's skip semantics
            return jax.tree_util.tree_map(
                lambda a, b: jnp.where(ok > 0, a, b), new, state)

    return grad_fn, apply_grads


def _per_minibatch_ok(*arrays, grads=None):
    """(M,) float32 validity mask over a megabatch group: 1.0 where every
    per-minibatch quantity (loss/td rows, every grad leaf) is finite —
    the per-minibatch twin of ``finite_guard``'s whole-step check, so a
    poisoned minibatch skips ITS update without discarding the group's
    other M-1 updates."""
    ok = None
    for a in arrays:
        flat = a.reshape(a.shape[0], -1) if a.ndim > 1 else a[:, None]
        this = jnp.all(jnp.isfinite(flat), axis=1)
        ok = this if ok is None else ok & this
    if grads is not None:
        for leaf in jax.tree_util.tree_leaves(grads):
            this = jnp.all(jnp.isfinite(leaf.reshape(leaf.shape[0], -1)),
                           axis=1)
            ok = this if ok is None else ok & this
    return ok.astype(jnp.float32)


def build_dqn_megabatch_step(
    apply_fn: Callable,
    tx: optax.GradientTransformation,
    *,
    enable_double: bool = False,
    target_model_update: float = 250,
    huber: bool = False,
    guard: bool = True,
) -> Callable:
    """ISSUE-13 megabatch group step: ``(state, batches) -> (state,
    metrics, td_abs, ok)`` where ``batches`` carries M minibatches as
    (M, B)-leading leaves.

    All M per-minibatch gradients are computed at the GROUP-ENTRY
    params in ONE batched forward/backward (``jax.vmap`` over the
    minibatch axis — XLA sees (M*B)-row lane-filling GEMMs instead of M
    dispatch-bound small ones), then the M optimizer updates apply
    SEQUENTIALLY in-graph: Adam moments, the step counter and the
    target-update cadence chain exactly as M separate
    ``build_dqn_train_step`` calls would.  The one divergence from M
    sequential steps is within-group gradient freshness (gradients see
    the group-entry params, the Stooke & Abbeel 2018 large-effective-
    batch trade); with M=1 the program is the sequential step's exact
    semantics.  The tier-1 oracle (tests/test_megabatch.py) pins the
    program against an unfused reference of these semantics.

    ``guard`` applies the finite check PER MINIBATCH: a non-finite
    minibatch skips its own update (params/opt/target/step pass
    through), its td_abs row is zeroed, and ``metrics[SKIPPED_KEY]``
    counts the group's skips; ``ok`` (M,) float lets the PER write-back
    suppress exactly the skipped rows.

    ``axis_name`` is an argument of the returned STEP, ``step(state,
    batches, axis_name=...)``, and of nothing else: the fused programs of
    a row-sharded HBM ring run the step under ``shard_map`` on each
    chip's share of every minibatch (memory/device_replay.py
    ``group_step_on``, the one caller that names an axis).  Gradients,
    losses, q-means and the guard's flags are then reduced over that
    axis, so every chip applies the same updates to its replicated
    state."""
    from pytorch_distributed_tpu.utils.health import SKIPPED_KEY

    def minibatch_loss(params, target_params, batch: Batch):
        return _dqn_loss(apply_fn, params, target_params, batch,
                         enable_double, huber)

    def step(state: TrainState, batches: Batch, axis_name=None):
        grad_fn = jax.value_and_grad(minibatch_loss, has_aux=True)
        with jax.named_scope(PHASE_ONLINE):  # around vmap: its own
            # batching transposes carry no inner name
            (losses, (td_abs, q_means)), grads = jax.vmap(
                grad_fn, in_axes=(None, None, 0))(
                    state.params, state.target_params, batches)
        with jax.named_scope(PHASE_OPTIMIZER):
            return _apply_group(state, grads, losses, td_abs, q_means,
                                axis_name)

    def _apply_group(state, grads, losses, td_abs, q_means, axis_name):
        """The M sequential optimizer applies (``train.optimizer``)."""
        grads, losses, q_means = _pmean((grads, losses, q_means), axis_name)
        M = losses.shape[0]
        ok = (_pmin(_per_minibatch_ok(losses, td_abs, q_means, grads=grads),
                    axis_name)
              if guard else jnp.ones((M,), jnp.float32))

        def apply_one(carry, x):
            params, opt_state, target_params, step_c = carry
            g, ok_i = x
            updates, new_opt = tx.update(g, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            new_step = step_c + 1
            new_target = update_target(target_params, new_params,
                                       new_step, target_model_update)
            keep = ok_i > 0.5
            sel = lambda n, o: jax.tree_util.tree_map(
                lambda a, b: jnp.where(keep, a, b), n, o)
            return (sel(new_params, params), sel(new_opt, opt_state),
                    sel(new_target, target_params),
                    jnp.where(keep, new_step, step_c)), None

        (params, opt_state, target_params, new_step), _ = jax.lax.scan(
            apply_one,
            (state.params, state.opt_state, state.target_params,
             state.step),
            (grads, ok))
        last_grad = jax.tree_util.tree_map(lambda l: l[-1], grads)
        metrics = {
            "learner/critic_loss": losses[-1],
            "learner/q_mean": q_means[-1],
            "learner/grad_norm": global_norm(last_grad),
        }
        if guard:
            metrics[SKIPPED_KEY] = jnp.sum(1.0 - ok)
        td_abs = jnp.where(ok[:, None] > 0.5, td_abs,
                           jnp.zeros_like(td_abs))
        return (TrainState(params, target_params, opt_state, new_step),
                metrics, td_abs, ok)

    return step


def _ddpg_critic_loss(actor_apply_fn: Callable, critic_apply_fn: Callable,
                      actor_params: PyTree, critic_params: PyTree,
                      target_full: PyTree, batch: Batch, huber: bool):
    """Critic TD loss of the decoupled DDPG update, ``(loss, td_abs)``
    (reference ddpg_learner.py:76-86); the target actor and critic passes
    are ``train.target``; differentiated inside ``train.online``."""
    full = merge_ddpg_params(actor_params, critic_params)
    q = critic_apply_fn(full, batch.state0, batch.action)
    with jax.named_scope(PHASE_TARGET):
        a_next = actor_apply_fn(target_full, batch.state1)
        q_next = critic_apply_fn(target_full, batch.state1, a_next)
    tgt = (batch.reward
           + batch.gamma_n * q_next * (1.0 - batch.terminal1))
    return _value_loss(q, tgt, batch.weight, huber)


def _ddpg_actor_loss(actor_apply_fn: Callable, critic_apply_fn: Callable,
                     actor_params: PyTree, critic_params: PyTree,
                     batch: Batch):
    """Policy loss ``-Q(s, pi(s)).mean()`` (reference
    ddpg_learner.py:66-74)."""
    full = merge_ddpg_params(actor_params, critic_params)
    a = actor_apply_fn(full, batch.state0)
    return -jnp.mean(critic_apply_fn(full, batch.state0, a))


def build_ddpg_megabatch_step(
    actor_apply_fn: Callable,
    critic_apply_fn: Callable,
    actor_tx: optax.GradientTransformation,
    critic_tx: optax.GradientTransformation,
    *,
    target_model_update: float = 1e-3,
    huber: bool = False,
    guard: bool = True,
) -> Callable:
    """Decoupled-DDPG twin of ``build_dqn_megabatch_step``: same
    ``(state, batches(M, B)) -> (state, metrics, td_abs, ok)`` group
    contract.

    Group semantics (tests/test_megabatch.py pins the unfused
    reference): all M critic gradients batched at the group-entry
    params; the M critic updates apply sequentially; all M actor
    gradients batched at (group-entry actor, the FINAL post-group
    critic) — for M=1 this is exactly ``build_ddpg_train_step``'s
    "actor sees the freshly-updated critic"; the M actor updates apply
    sequentially and the soft target update chains per minibatch with
    the per-step (actor_i, critic_i) pair.

    Guard semantics (per minibatch, documented divergence from the
    whole-step ``finite_guard``): the critic-stage mask (critic
    loss/td/grads finite) gates the critic chain; the COMBINED mask
    (critic & actor stages) gates the actor/target/step chain, zeroes
    td_abs rows and is the returned ``ok`` — so a minibatch whose
    actor stage alone is non-finite keeps its (finite) critic update.

    ``axis_name``: the step's argument, as in ``build_dqn_megabatch_step``.
    """
    from pytorch_distributed_tpu.utils.health import SKIPPED_KEY

    def critic_loss_fn(critic_params, actor_params, target_full,
                       batch: Batch):
        return _ddpg_critic_loss(actor_apply_fn, critic_apply_fn,
                                 actor_params, critic_params, target_full,
                                 batch, huber)

    def actor_loss_fn(actor_params, critic_params, batch: Batch):
        return _ddpg_actor_loss(actor_apply_fn, critic_apply_fn,
                                actor_params, critic_params, batch)

    def step(state: TrainState, batches: Batch, axis_name=None):
        params, target = state.params, state.target_params
        target_full = merge_ddpg_params(target["actor"], target["critic"])

        # ---- stage 1: M critic grads at group entry, one batched bwd ----
        cgrad_fn = jax.value_and_grad(critic_loss_fn, has_aux=True)
        with jax.named_scope(PHASE_ONLINE):
            (closs, td_abs), cgrads = jax.vmap(
                cgrad_fn, in_axes=(None, None, None, 0))(
                    params["critic"], params["actor"], target_full,
                    batches)
        def capply(carry, x):
            cp, copt = carry
            g, ok_i = x
            updates, new_opt = critic_tx.update(g, copt, cp)
            new_cp = optax.apply_updates(cp, updates)
            keep = ok_i > 0.5
            sel = lambda n, o: jax.tree_util.tree_map(
                lambda a, b: jnp.where(keep, a, b), n, o)
            new_cp = sel(new_cp, cp)
            return (new_cp, sel(new_opt, copt)), new_cp

        with jax.named_scope(PHASE_OPTIMIZER):
            cgrads, closs = _pmean((cgrads, closs), axis_name)
            M = closs.shape[0]
            ones = jnp.ones((M,), jnp.float32)
            ok_c = (_pmin(_per_minibatch_ok(closs, td_abs, grads=cgrads),
                          axis_name)
                    if guard else ones)
            (final_critic, critic_opt), critics = jax.lax.scan(
                capply, (params["critic"], state.opt_state["critic"]),
                (cgrads, ok_c))

        # ---- stage 2: M actor grads at (entry actor, final critic) ----
        agrad_fn = jax.value_and_grad(actor_loss_fn)
        with jax.named_scope(PHASE_ONLINE):
            aloss, agrads = jax.vmap(agrad_fn, in_axes=(None, None, 0))(
                params["actor"], final_critic, batches)
        def aapply(carry, x):
            ap_, aopt, tgt, step_c = carry
            g, ok_i, critic_i = x
            updates, new_opt = actor_tx.update(g, aopt, ap_)
            new_ap = optax.apply_updates(ap_, updates)
            new_step = step_c + 1
            new_tgt = update_target(
                tgt, {"actor": new_ap, "critic": critic_i}, new_step,
                target_model_update)
            keep = ok_i > 0.5
            sel = lambda n, o: jax.tree_util.tree_map(
                lambda a, b: jnp.where(keep, a, b), n, o)
            return (sel(new_ap, ap_), sel(new_opt, aopt),
                    sel(new_tgt, tgt),
                    jnp.where(keep, new_step, step_c)), None

        with jax.named_scope(PHASE_OPTIMIZER):
            agrads, aloss = _pmean((agrads, aloss), axis_name)
            ok = ok_c * (_per_minibatch_ok(aloss, grads=agrads)
                         if guard else ones)
            (final_actor, actor_opt, new_target, new_step), _ = \
                jax.lax.scan(
                    aapply,
                    (params["actor"], state.opt_state["actor"], target,
                     state.step),
                    (agrads, ok, critics))

            last_g = jax.tree_util.tree_map(
                lambda l: l[-1], {"actor": agrads, "critic": cgrads})
            metrics = {
                "learner/critic_loss": closs[-1],
                "learner/actor_loss": aloss[-1],
                "learner/grad_norm": global_norm(last_g),
            }
            if guard:
                metrics[SKIPPED_KEY] = jnp.sum(1.0 - ok)
            td_abs = jnp.where(ok[:, None] > 0.5, td_abs,
                               jnp.zeros_like(td_abs))
        new_state = TrainState(
            {"actor": final_actor, "critic": final_critic}, new_target,
            {"actor": actor_opt, "critic": critic_opt}, new_step)
        return new_state, metrics, td_abs, ok

    return step


def init_ddpg_train_state(
    full_params: PyTree,
    actor_tx: optax.GradientTransformation,
    critic_tx: optax.GradientTransformation,
) -> TrainState:
    """TrainState for the decoupled DDPG update: params/opt_state are
    {'actor':..., 'critic':...} dicts over the split module tree; the target
    is an independent buffer copy (same donation-safety constraint as
    ``init_train_state``)."""
    split = split_ddpg_params(full_params)
    target = jax.tree_util.tree_map(jnp.array, split)
    return TrainState(
        split, target,
        {"actor": actor_tx.init(split["actor"]),
         "critic": critic_tx.init(split["critic"])},
        jnp.asarray(0))


def build_ddpg_train_step(
    actor_apply_fn: Callable,
    critic_apply_fn: Callable,
    actor_tx: optax.GradientTransformation,
    critic_tx: optax.GradientTransformation,
    *,
    target_model_update: float = 1e-3,
    huber: bool = False,
    axis_name: str | None = None,
    guard: bool = True,
) -> Callable:
    """Decoupled DDPG update: separate critic and actor gradient steps with
    per-net optimizers (textbook DDPG; see module docstring re the
    reference's coupled variant).

    ``TrainState.params``/``opt_state`` are dicts {'actor':..., 'critic':...}
    over the single DdpgMlpModel param tree split by submodule prefix — see
    ``split_ddpg_params``/``merge_ddpg_params``.
    """

    def step(state: TrainState, batch: Batch):
        params = state.params
        target = state.target_params

        # ---- critic update (reference ddpg_learner.py:76-86) ----
        target_full = merge_ddpg_params(target["actor"], target["critic"])

        def critic_loss_fn(critic_params):
            return _ddpg_critic_loss(actor_apply_fn, critic_apply_fn,
                                     params["actor"], critic_params,
                                     target_full, batch, huber)

        (critic_loss, td_abs), critic_grads = online_grad(
            critic_loss_fn, has_aux=True)(params["critic"])
        with jax.named_scope(PHASE_OPTIMIZER):
            critic_grads = _pmean(critic_grads, axis_name)
            critic_updates, critic_opt = critic_tx.update(
                critic_grads, state.opt_state["critic"], params["critic"])
            new_critic = optax.apply_updates(params["critic"],
                                             critic_updates)

        # ---- actor update (reference ddpg_learner.py:66-74) ----
        def actor_loss_fn(actor_params):
            return _ddpg_actor_loss(actor_apply_fn, critic_apply_fn,
                                    actor_params, new_critic, batch)

        actor_loss, actor_grads = online_grad(actor_loss_fn)(
            params["actor"])
        with jax.named_scope(PHASE_OPTIMIZER):
            actor_grads = _pmean(actor_grads, axis_name)
            actor_updates, actor_opt = actor_tx.update(
                actor_grads, state.opt_state["actor"], params["actor"])
            new_actor = optax.apply_updates(params["actor"], actor_updates)

            new_params = {"actor": new_actor, "critic": new_critic}
            new_step = state.step + 1
            # soft target every step (reference ddpg_learner.py:95,
            # tau=1e-3)
            new_target = update_target(target, new_params, new_step,
                                       target_model_update)
            metrics = {
                "learner/critic_loss": critic_loss,
                "learner/actor_loss": actor_loss,
                # norm over BOTH nets' grads so a diverging policy is
                # visible
                "learner/grad_norm": global_norm(
                    {"actor": actor_grads, "critic": critic_grads}),
            }
        return (TrainState(new_params, new_target,
                           {"actor": actor_opt, "critic": critic_opt},
                           new_step),
                metrics, td_abs)

    return finite_guard(step) if guard else step


def build_ddpg_train_step_coupled(
    actor_apply_fn: Callable,
    critic_apply_fn: Callable,
    tx: optax.GradientTransformation,
    *,
    target_model_update: float = 1e-3,
    huber: bool = False,
    axis_name: str | None = None,
    guard: bool = True,
) -> Callable:
    """Reference-faithful coupled DDPG update: one optimizer over the full
    param tree, one gradient step of ``policy_loss + critic_loss`` — so the
    policy-loss gradient also deposits into critic params, exactly the
    behaviour of the reference's single zero_grad / double backward /
    single Adam step (reference ddpg_learner.py:62-91).  TrainState.params
    is the *merged* tree here."""

    def step(state: TrainState, batch: Batch):
        def loss_fn(full):
            # critic TD loss (reference ddpg_learner.py:76-86)
            q = critic_apply_fn(full, batch.state0, batch.action)
            with jax.named_scope(PHASE_TARGET):
                a_next = actor_apply_fn(state.target_params, batch.state1)
                q_next = critic_apply_fn(state.target_params, batch.state1,
                                         a_next)
            tgt = (batch.reward
                   + batch.gamma_n * q_next * (1.0 - batch.terminal1))
            critic_loss, td_abs = _value_loss(q, tgt, batch.weight, huber)
            # policy loss (reference ddpg_learner.py:66-74)
            a = actor_apply_fn(full, batch.state0)
            actor_loss = -jnp.mean(critic_apply_fn(full, batch.state0, a))
            return critic_loss + actor_loss, (critic_loss, actor_loss, td_abs)

        (_, (critic_loss, actor_loss, td_abs)), grads = online_grad(
            loss_fn, has_aux=True)(state.params)
        with jax.named_scope(PHASE_OPTIMIZER):
            grads = _pmean(grads, axis_name)
            updates, opt_state = tx.update(grads, state.opt_state,
                                           state.params)
            params = optax.apply_updates(state.params, updates)
            new_step = state.step + 1
            new_target = update_target(state.target_params, params,
                                       new_step, target_model_update)
            metrics = {
                "learner/critic_loss": critic_loss,
                "learner/actor_loss": actor_loss,
                "learner/grad_norm": global_norm(grads),
            }
        return (TrainState(params, new_target, opt_state, new_step),
                metrics, td_abs)

    return finite_guard(step) if guard else step


# ---------------------------------------------------------------------------
# DDPG param-tree surgery: the model is one Flax module whose top-level
# submodules are actor_* / critic_* (models/ddpg_mlp.py setup()); split so
# each optimizer owns exactly its net.
# ---------------------------------------------------------------------------

def split_ddpg_params(full: PyTree) -> Dict[str, PyTree]:
    inner = full["params"]
    actor = {k: v for k, v in inner.items() if k.startswith("actor")}
    critic = {k: v for k, v in inner.items() if k.startswith("critic")}
    assert actor and critic, f"unexpected DDPG param layout: {list(inner)}"
    return {"actor": {"params": actor}, "critic": {"params": critic}}


def merge_ddpg_params(actor: PyTree, critic: PyTree) -> PyTree:
    return {"params": {**actor["params"], **critic["params"]}}


def _pmean(tree: PyTree, axis_name: str | None) -> PyTree:
    """Mean-reduce gradients across a mesh axis (the ICI all-reduce).  Only
    needed under shard_map, where collectives are explicit; under plain jit
    with sharded batch inputs XLA inserts the all-reduce itself, and
    axis_name stays None."""
    if axis_name is None:
        return tree
    return jax.lax.pmean(tree, axis_name=axis_name)


def _pmin(x: jnp.ndarray, axis_name: str | None) -> jnp.ndarray:
    """A guard flag that holds on EVERY shard (``_pmean``'s twin): one
    chip's non-finite rows skip the update on all of them."""
    if axis_name is None:
        return x
    return jax.lax.pmin(x, axis_name=axis_name)
