"""Action-selection as pure jitted functions.

The reference folds action selection into the torch modules
(``get_action``: reference core/models/dqn_cnn_model.py:58-78,
ddpg_mlp_model.py:74-78).  TPU-first, these are standalone functions of
``(params, obs, key, ...)`` with explicit randomness, jit-compiled once and
reused by actors / evaluators / testers; they are batch-shaped so one call
can serve a whole vector of envs (the batched-inference answer to the
reference's latency-bound batch-1 actor forward, SURVEY.md §7 "hard
parts").
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np


def apex_epsilon(process_ind: int, num_actors: int,
                 eps: float = 0.4, eps_alpha: float = 7.0) -> float:
    """Ape-X per-actor exploration schedule
    ``eps ** (1 + i/(N-1) * alpha)`` (reference dqn_actor.py:33-36, with the
    reference's 1-based indexing of actors and its single-actor debug value).
    """
    if num_actors <= 1:
        return 0.1  # reference dqn_actor.py:33-34 debug branch
    frac = process_ind / (num_actors - 1)
    return float(eps ** (1.0 + frac * eps_alpha))


def apex_epsilons(process_ind: int, num_actors: int, num_envs: int,
                  eps: float = 0.4, eps_alpha: float = 7.0):
    """Per-env epsilon vector for a vectorized actor: env j of actor i
    takes fleet slot i*num_envs + j of num_actors*num_envs, so exploration
    diversity spans the whole fleet exactly as the reference's per-actor
    schedule spans its actors (reference dqn_actor.py:33-36)."""
    import numpy as np

    total = num_actors * num_envs
    return np.asarray(
        [apex_epsilon(process_ind * num_envs + j, total, eps, eps_alpha)
         for j in range(num_envs)], dtype=np.float32)


def build_epsilon_greedy_act(apply_fn: Callable) -> Callable:
    """eps-greedy over a Q-network.

    Returns a jitted ``act(params, obs[B,...], key, eps) ->
    (action[B], q_sel[B], q_max[B])``; ``eps`` may be a scalar or a (B,)
    per-sample vector (the vectorized-actor fleet schedule).  q_sel/q_max
    feed PER initial priorities, mirroring the tuple the reference returns
    when PER is on (reference dqn_cnn_model.py:65-78) — here they are
    always returned (cost-free under jit).
    """

    def act(params, obs, key, eps):
        q = apply_fn(params, obs)                        # (B, A)
        batch, num_actions = q.shape
        greedy = jnp.argmax(q, axis=-1)
        key_explore, key_choice = jax.random.split(key)
        random_a = jax.random.randint(key_choice, (batch,), 0, num_actions)
        explore = jax.random.uniform(key_explore, (batch,)) < eps
        action = jnp.where(explore, random_a, greedy)
        q_sel = jnp.take_along_axis(q, action[:, None], axis=-1)[:, 0]
        return action, q_sel, jnp.max(q, axis=-1)

    return jax.jit(act)


def tick_keys(base_key, tick, num_envs: int):
    """Per-(tick, env-row) PRNG keys derived ON DEVICE: fold the tick
    counter into the actor's base key, then fold each row index.  This is
    the pipelined actor's replacement for the serial loop's host-side
    ``jax.random.split`` chain (ISSUE 4 tentpole): the base key is
    committed once and never leaves the device, per-tick randomness is a
    pure function of ``(base_key, tick, row)``, and — because rows are
    keyed independently — the SAME stream falls out whether rows are
    evaluated by the local inline loop, the local pipelined loop, or a
    shared inference server batching rows from many actors."""
    k = jax.random.fold_in(base_key, tick)
    return jax.vmap(lambda j: jax.random.fold_in(k, j))(
        jnp.arange(num_envs))


def _rowwise_eps_greedy(q, row_keys, eps):
    """Row-keyed eps-greedy: each row draws from its own key so action
    randomness is independent of how rows were batched together."""
    num_actions = q.shape[-1]

    def row(qr, key, e):
        key_explore, key_choice = jax.random.split(key)
        random_a = jax.random.randint(key_choice, (), 0, num_actions)
        explore = jax.random.uniform(key_explore) < e
        return jnp.where(explore, random_a, jnp.argmax(qr))

    return jax.vmap(row)(q, row_keys, eps)


def _pack_dqn(q, action):
    """One (3, B) float32 array — (action, q_sel, q_max) rows — so a tick
    costs ONE device->host copy instead of three (action indices are
    small integers, exactly representable in f32)."""
    q_sel = jnp.take_along_axis(q, action[:, None], axis=-1)[:, 0]
    return jnp.stack([action.astype(jnp.float32),
                      q_sel.astype(jnp.float32),
                      jnp.max(q, axis=-1).astype(jnp.float32)])


def build_packed_act(apply_fn: Callable) -> Callable:
    """The pipelined actor's fused per-tick program (ISSUE 4 tentpole).

    Returns a jitted ``act(params, obs[B,...], base_key, tick, eps[B]) ->
    packed[3, B]`` where ``packed`` stacks (action, q_sel, q_max) as one
    float32 array.  Everything the serial loop did on the host per tick —
    key split, action selection, the three separate device reads — is
    fused on-device: the PRNG key stays resident (``tick_keys`` folds the
    tick counter instead of a host-side split chain), and the single
    packed output means one dispatch + one D2H copy per tick.  ``tick``
    is a traced scalar, so consecutive ticks NEVER retrace.

    The obs is deliberately NOT donated: none of the shipped feedforward
    nets produce an output that could alias it (XLA would just warn the
    donation off).  The buffer donations that pay in this codebase are
    the recurrent carry (``build_recurrent_packed_act``) and the
    server-side roll stack (``build_packed_roll_act``).
    """

    def act(params, obs, base_key, tick, eps):
        q = apply_fn(params, obs)                        # (B, A)
        action = _rowwise_eps_greedy(q, tick_keys(base_key, tick,
                                                  q.shape[0]), eps)
        return _pack_dqn(q, action)

    return jax.jit(act)


def build_packed_roll_act(apply_fn: Callable) -> Callable:
    """Frame-packed variant of ``build_packed_act`` for the shared
    inference server (agents/inference.py): the client ships only the
    NEWEST frame per env and the device rolls its resident history stack
    before acting, fused into the same dispatch —
    ``act(params, stack[B,C,H,W], new[B,H,W], base_key, tick, eps) ->
    (stack', packed[3,B])``.

    This cuts the per-tick upload by the stack factor C (451 KB ->
    113 KB for the production 16-env Nature-CNN shape).  The stack is
    DONATED (stack' has its exact shape/dtype, so XLA rolls in place).
    The client only elects this path when the roll property held on the
    host (``obs[:, :-1] == prev[:, 1:]`` — any env reset falls back to a
    full upload that also reseeds the device stack), so the device
    reconstruction is bit-exact with what the env emitted."""

    def roll_act(params, stack, new, base_key, tick, eps):
        stack = jnp.concatenate([stack[:, 1:], new[:, None]], axis=1)
        q = apply_fn(params, stack)
        action = _rowwise_eps_greedy(q, tick_keys(base_key, tick,
                                                  q.shape[0]), eps)
        return stack, _pack_dqn(q, action)

    return jax.jit(roll_act, donate_argnums=(1,))


def build_packed_act_rowkeys(apply_fn: Callable) -> Callable:
    """Server-side variant of ``build_packed_act`` taking precomputed
    per-row keys: the inference batcher concatenates rows from several
    actors into one wide forward, so each row's key comes from ITS
    actor's (base_key, tick, row) fold — identical streams to the local
    paths regardless of batch composition."""

    def act_rows(params, obs, row_keys, eps):
        q = apply_fn(params, obs)
        return _pack_dqn(q, _rowwise_eps_greedy(q, row_keys, eps))

    return jax.jit(act_rows)


# ---------------------------------------------------------------------------
# The fused device rollout (ISSUE 7 tentpole): env + policy + n-step
# assembly in ONE donated on-device scan.
# ---------------------------------------------------------------------------

from typing import NamedTuple  # noqa: E402


class RolloutCarry(NamedTuple):
    """Everything the fused rollout keeps device-resident between
    dispatches: the env fleet's state and the open n-step windows.

    Window bookkeeping implements EXACTLY the ``ops/nstep.py``
    assembler semantics, restructured for fixed shapes: every env tick
    t opens exactly one window (s_t, a_t); a window closes when it
    accumulates ``nstep`` rewards or the episode ends (true terminals
    mark ``terminal1``; truncation closes but still bootstraps); and
    every window is EMITTED a fixed ``nstep`` ticks after it opened —
    by which point it is guaranteed closed and its bootstrap q_max
    (the NEXT forward after its close, the same forward the host
    actor's pending-queue used) has been stamped.  Fixed delay means
    exactly one emission slot per env per tick — no data-dependent
    output shapes — at the cost of rings of the last ``nstep + 1``
    ticks of per-window state and true post-step observations."""

    env_state: Any
    win_s0: Any          # (N, R, *obs) uint8 — s0 of window per slot
    win_action: Any      # (N, R) int32
    win_qsel: Any        # (N, R) f32 — q(s0, a) at open
    win_racc: Any        # (N, R) f32 — discounted reward accumulator
    win_age: Any         # (N, R) int32 — rewards accumulated
    win_open: Any        # (N, R) bool
    win_term: Any        # (N, R) f32 — terminal1 stamped at close
    win_prio_ok: Any     # (N, R) bool — False for truncated closes
    win_close_slot: Any  # (N, R) int32 — obs_true slot of the close
    win_qboot: Any       # (N, R) f32 — bootstrap q_max, stamped late
    win_need_boot: Any   # (N, R) bool — closed, awaiting next forward
    obs_true: Any        # (N, R, *obs) uint8 — true post-step obs ring


class RolloutChunk(NamedTuple):
    """Per-dispatch emission: ``(K, N)``-leading transition columns
    (the six replay fields) plus the PER scalars and per-tick env
    stats.  ``valid`` is False only for the run's first ``nstep``
    warmup ticks.  ``prio_ok`` False marks truncated-close windows —
    the host path feeds those with priority None (new-sample max)."""

    state0: Any
    action: Any
    reward: Any
    gamma_n: Any
    state1: Any
    terminal1: Any
    valid: Any
    q_sel: Any
    q_boot: Any
    prio_ok: Any
    step_reward: Any     # (K, N) f32 raw per-tick env rewards
    step_terminal: Any   # (K, N) bool
    step_truncated: Any  # (K, N) bool


class RolloutStats(NamedTuple):
    """The replay-emit variant's host-visible output (everything else
    stays in HBM): per-tick env stats only."""

    step_reward: Any
    step_terminal: Any
    step_truncated: Any
    fed: Any             # () int32 — rows written into the ring


def init_rollout_carry(env, nstep: int) -> RolloutCarry:
    """Fresh carry for ``build_fused_rollout``: env at reset, no open
    windows.  Ring depth R = nstep + 1: the emission slot (t - nstep)
    and the open slot (t) must never collide."""
    import jax.numpy as jnp

    n = env.num_envs
    R = nstep + 1
    obs_shape = tuple(env.state_shape)
    env_state = env.init()
    z = lambda dt: jnp.zeros((n, R), dt)
    return RolloutCarry(
        env_state=env_state,
        win_s0=jnp.zeros((n, R, *obs_shape), jnp.uint8),
        win_action=z(jnp.int32), win_qsel=z(jnp.float32),
        win_racc=z(jnp.float32), win_age=z(jnp.int32),
        win_open=z(bool), win_term=z(jnp.float32),
        win_prio_ok=z(bool), win_close_slot=z(jnp.int32),
        win_qboot=z(jnp.float32), win_need_boot=z(bool),
        obs_true=jnp.zeros((n, R, *obs_shape), jnp.uint8),
    )


def build_fused_rollout(apply_fn: Callable, env, *, nstep: int,
                        gamma: float, rollout_ticks: int,
                        emit: str = "chunk",
                        ring_write_fn: Callable = None) -> Callable:
    """ONE donated on-device scan advancing N envs x K ticks: per tick,
    the policy forward, row-keyed eps-greedy action selection, the
    vectorized env step, and n-step transition assembly all run inside
    the same XLA program — obs stacks, PRNG, env state and the open
    n-step windows never leave the device, and finished transitions
    are emitted device-side (no per-tick H2D/D2H).

    Randomness rides the exact ISSUE-4 stream contract: row keys are
    ``tick_keys(base_key, tick, row)`` folds, so the action stream for
    any (actor, tick, env-row) is bit-identical to what the
    inline/pipelined/batched backends produce over the same env.

    ``emit``:

    - ``"chunk"`` — the scan returns a ``RolloutChunk`` of (K, N)
      transition columns; the cross-process actor driver ships it to
      the replay feeder with ONE device->host copy per dispatch
      (amortized over K*N frames).  Returns a jitted
      ``rollout(params, carry, base_key, tick0, eps) ->
      (carry', RolloutChunk)`` with ``carry`` DONATED.
    - ``"replay"`` — the scan scatters valid rows straight into a
      device replay ``ReplayState`` carried through the program
      (memory/device_replay.ring_write_masked): experience lands in
      the learner-side HBM ring with ZERO host round-trip — the
      co-located Sebulba topology.
      Returns ``rollout(params, carry, ring_state, base_key, tick0,
      eps) -> (carry', ring_state', RolloutStats)`` with ``carry`` and
      ``ring_state`` donated.

    ``tick0`` is a traced scalar (the global tick of the dispatch's
    first tick), so consecutive dispatches NEVER retrace; the caller
    advances it by ``rollout_ticks`` per call.  Priorities: the chunk
    carries ``q_sel``/``q_boot``/``prio_ok`` columns so the host can
    form the actor-side PER priority |R + gamma_n*maxQ(s_end) - q_sel|
    with two flops per row — same estimate, no device sync.

    ``ring_write_fn`` (emit="replay" only) overrides the masked ring
    scatter — the hook the co-located Anakin loop (agents/anakin.py)
    uses to write into the HBM PER ring with new-row priority stamping
    (memory/device_per.per_write_masked); None keeps the uniform-ring
    ``ring_write_masked``.  The interleave contract for that loop: the
    rollout program reads ``params`` but never writes them, and the
    fused learner program reads the ring but only ever writes the
    priority column — so alternating (or double-buffer-interleaving)
    the two dispatches against the same device-resident state is
    race-free by construction, and the acting params ARE the train
    state's params (the published version is the acting version).
    """
    import jax
    import jax.numpy as jnp

    assert emit in ("chunk", "replay")
    n = env.num_envs
    R = nstep + 1
    K = int(rollout_ticks)
    # f64-computed discount powers (cast once): the host assembler
    # accumulates in python f64 and casts at emit, so a f32 pow chain
    # here would drift a final ulp on scoring windows
    gamma_pow = jnp.asarray(
        np.power(np.float64(gamma), np.arange(R)).astype(np.float32))

    if emit == "replay":
        from pytorch_distributed_tpu.memory.device_replay import (
            ring_write_masked,
        )
        from pytorch_distributed_tpu.utils.experience import Transition

        if ring_write_fn is None:
            ring_write_fn = ring_write_masked

    def one_tick(params, eps, base_key, c: RolloutCarry, t):
        obs = env.observe(c.env_state)
        q = apply_fn(params, obs)
        qmax = jnp.max(q, axis=-1).astype(jnp.float32)
        # late bootstrap stamp: windows closed at t-1 take THIS
        # forward's q_max — the same forward the host actor's pending
        # queue resolved against (agents/actor._resolve_pending); the
        # stamp satisfies every waiting window, so need_boot resets
        qboot = jnp.where(c.win_need_boot, qmax[:, None], c.win_qboot)
        need_boot = jnp.zeros_like(c.win_need_boot)
        action = _rowwise_eps_greedy(q, tick_keys(base_key, t, n), eps)
        q_sel = jnp.take_along_axis(
            q, action[:, None], axis=-1)[:, 0].astype(jnp.float32)
        env_state, out = env.step(c.env_state, action.astype(jnp.int32))
        slot = (t % R).astype(jnp.int32)
        cols = jnp.arange(R, dtype=jnp.int32)
        at_slot = cols[None, :] == slot             # (1, R) -> broadcast
        term = out.terminal
        trunc = out.truncated
        true_term = (term & ~trunc).astype(jnp.float32)

        # slot writes via dynamic_update_index_in_dim, NOT a where over
        # the whole ring: the obs rings are the carry's bulk (N x R
        # stacks), and a where-based write would stream the full ring
        # through memory every tick — measured ~4x on the whole engine
        def set_slot(ring, val):
            return jax.lax.dynamic_update_index_in_dim(ring, val, slot,
                                                       axis=1)

        # open this tick's window at ``slot``
        win_s0 = set_slot(c.win_s0, obs)
        win_action = set_slot(c.win_action, action.astype(jnp.int32))
        win_qsel = set_slot(c.win_qsel, q_sel)
        win_racc = set_slot(c.win_racc, jnp.zeros((n,), jnp.float32))
        win_age = set_slot(c.win_age, jnp.zeros((n,), jnp.int32))
        win_open = set_slot(c.win_open, jnp.ones((n,), bool))
        # accumulate this tick's reward into every open window
        win_racc = win_racc + jnp.where(
            win_open, gamma_pow[win_age] * out.reward[:, None], 0.0)
        win_age = win_age + win_open
        # true post-step obs ring (final_obs preserves the terminal
        # frame; non-terminal rows it equals the next obs)
        obs_true = set_slot(c.obs_true, out.final_obs)
        # closes: window full, or episode over (truncation included)
        closing = win_open & ((win_age >= nstep) | term[:, None])
        win_open = win_open & ~closing
        win_term = jnp.where(closing, true_term[:, None], c.win_term)
        win_prio_ok = jnp.where(closing, (~trunc)[:, None], c.win_prio_ok)
        win_close_slot = jnp.where(closing, slot, c.win_close_slot)
        need_boot = jnp.where(closing, (true_term == 0.0)[:, None],
                              need_boot)
        # emission: the window opened nstep ticks ago — closed by
        # t-1 at the latest, boot-stamped by this tick's forward
        slot_e = ((t - nstep) % R).astype(jnp.int32)
        rows = jnp.arange(n)
        valid = jnp.broadcast_to(t >= nstep, (n,))

        def get_slot(ring):
            return jax.lax.dynamic_index_in_dim(ring, slot_e, axis=1,
                                                keepdims=False)

        term1_e = get_slot(win_term)
        close_e = get_slot(win_close_slot)
        s1 = jnp.take_along_axis(
            obs_true, close_e.reshape((n, 1) + (1,) * (
                obs_true.ndim - 2)), axis=1)[:, 0]
        emitted = dict(
            state0=get_slot(win_s0),
            action=get_slot(win_action),
            reward=get_slot(win_racc),
            gamma_n=gamma_pow[get_slot(win_age)],
            state1=s1,
            terminal1=term1_e,
            valid=valid,
            q_sel=get_slot(win_qsel),
            # true terminals never bootstrap; zeroing the column keeps
            # the chunk self-describing (stale slot values otherwise)
            q_boot=jnp.where(term1_e > 0, 0.0, get_slot(qboot)),
            prio_ok=get_slot(win_prio_ok),
        )
        carry = RolloutCarry(
            env_state=env_state, win_s0=win_s0, win_action=win_action,
            win_qsel=win_qsel, win_racc=win_racc, win_age=win_age,
            win_open=win_open, win_term=win_term,
            win_prio_ok=win_prio_ok, win_close_slot=win_close_slot,
            win_qboot=qboot, win_need_boot=need_boot,
            obs_true=obs_true)
        stats = (out.reward, term, trunc)
        return carry, emitted, stats

    if emit == "chunk":
        def rollout(params, carry, base_key, tick0, eps):
            ticks = tick0 + jnp.arange(K)

            def body(c, t):
                c, emitted, (r, te, tr) = one_tick(params, eps,
                                                   base_key, c, t)
                return c, RolloutChunk(step_reward=r, step_terminal=te,
                                       step_truncated=tr, **emitted)

            carry, chunk = jax.lax.scan(body, carry, ticks)
            return carry, chunk

        return jax.jit(rollout, donate_argnums=(1,))

    def rollout(params, carry, ring_state, base_key, tick0, eps,
                prov=None):
        # ``prov`` (optional): a (3,) int32 of (actor_id, param_version,
        # birth_step) for THIS dispatch — scattered into the ring's
        # provenance columns alongside each emitted row, with env_slot =
        # the env's row index (ISSUE 8).  Stamps quantize to the
        # dispatch exactly like the chunk-mode host stamps; None leaves
        # the columns at the -1 sentinel (legacy callers).
        ticks = tick0 + jnp.arange(K)
        capacity = ring_state.reward.shape[0]
        rows_prov = None
        if prov is not None:
            rows_prov = jnp.stack([
                jnp.full((n,), prov[0], jnp.int32),
                jnp.arange(n, dtype=jnp.int32),
                jnp.full((n,), prov[1], jnp.int32),
                jnp.full((n,), prov[2], jnp.int32)], axis=1)

        def body(cs, t):
            c, ring, fed = cs
            c, e, (r, te, tr) = one_tick(params, eps, base_key, c, t)
            ring, wrote = ring_write_fn(
                ring, Transition(
                    state0=e["state0"], action=e["action"],
                    reward=e["reward"], gamma_n=e["gamma_n"],
                    state1=e["state1"], terminal1=e["terminal1"],
                    prov=rows_prov),
                e["valid"], capacity)
            return (c, ring, fed + wrote), (r, te, tr)

        (carry, ring_state, fed), (r, te, tr) = jax.lax.scan(
            body, (carry, ring_state, jnp.int32(0)), ticks)
        return carry, ring_state, RolloutStats(
            step_reward=r, step_terminal=te, step_truncated=tr, fed=fed)

    return jax.jit(rollout, donate_argnums=(1, 2))


def rollout_priorities(chunk_np: dict, enabled: bool):
    """Actor-side PER initial priorities off a fetched chunk's columns:
    |R + gamma_n * maxQ(s_end) - q_sel| with the bootstrap term zeroed
    on true terminals (the q_boot column already is) — the exact
    estimate the host actor's pending-queue computes
    (agents/actor.py).  Rows with ``prio_ok`` False (truncated closes)
    get None: the host path feeds those at new-sample max priority.
    Returns an object-dtype convenience: (N,) array of float-or-None.
    """
    if not enabled:
        return None
    f8 = lambda k: np.asarray(chunk_np[k], np.float64)
    # f64 like the host actor's python-float arithmetic, so the two
    # paths assign identical priorities to identical transitions
    pr = np.abs(f8("reward") + f8("gamma_n") * (1.0 - f8("terminal1"))
                * f8("q_boot") - f8("q_sel"))
    out = np.empty(pr.shape, dtype=object)
    ok = np.asarray(chunk_np["prio_ok"], bool)
    out[ok] = pr[ok].astype(np.float64)
    out[~ok] = None
    return out


def build_greedy_act(apply_fn: Callable) -> Callable:
    """Pure-greedy variant for evaluator/tester (reference evaluators.py:56-86
    runs eps=0 episodes)."""

    def act(params, obs):
        q = apply_fn(params, obs)
        return jnp.argmax(q, axis=-1), jnp.max(q, axis=-1)

    return jax.jit(act)


def build_recurrent_epsilon_greedy_act(apply_fn: Callable) -> Callable:
    """eps-greedy over a recurrent Q-network (models/drqn.py contract
    ``apply(params, obs, carry) -> (q, carry')``).  Returns a jitted
    ``act(params, obs[B,...], carry, key, eps) -> (action[B], carry')`` —
    the caller owns the carry and resets env rows at episode ends."""

    def act(params, obs, carry, key, eps):
        q, carry = apply_fn(params, obs, carry)
        batch, num_actions = q.shape
        greedy = jnp.argmax(q, axis=-1)
        key_explore, key_choice = jax.random.split(key)
        random_a = jax.random.randint(key_choice, (batch,), 0, num_actions)
        explore = jax.random.uniform(key_explore, (batch,)) < eps
        return jnp.where(explore, random_a, greedy), carry

    return jax.jit(act)


def build_recurrent_packed_act(apply_fn: Callable, zero_carry) -> Callable:
    """Fused recurrent act for the pipelined loop: the carry stays
    DEVICE-RESIDENT across ticks (no per-tick host round-trip of the LSTM
    state into the forward), and episode resets arrive as a per-row
    boolean mask folded in on-device — row j's carry is replaced with the
    model's zero carry before acting when ``reset_mask[j]`` is set, which
    is exactly the host-side row reset the serial loop performed between
    ticks.

    ``zero_carry`` is the model's ``zero_carry(1)`` pytree (leading dim 1
    broadcasts over rows).  Returns a jitted ``act(params, obs, carry,
    reset_mask[B], base_key, tick, eps[B]) -> (action[B] int32, carry')``.
    The caller owns the device carry and keeps a host copy for segment
    storage (agents/recurrent_actor.py).

    The carry argument is DONATED: carry' has exactly carry's shapes, so
    XLA updates it in place — for the transformer family, whose carry IS
    the rolling (B, window, *obs) context buffer, this is the ISSUE 4
    "donate the obs buffer" optimisation (no per-tick reallocation of
    the window).  Callers must treat the passed-in carry as consumed,
    which the engine's swap-on-submit discipline guarantees."""
    zero = jax.tree_util.tree_map(jnp.asarray, zero_carry)

    def act(params, obs, carry, reset_mask, base_key, tick, eps):
        def reset_rows(c, z):
            mask = reset_mask.reshape(reset_mask.shape[0],
                                      *([1] * (c.ndim - 1)))
            return jnp.where(mask, z.astype(c.dtype), c)

        carry = jax.tree_util.tree_map(reset_rows, carry, zero)
        q, carry = apply_fn(params, obs, carry)
        action = _rowwise_eps_greedy(
            q, tick_keys(base_key, tick, q.shape[0]), eps)
        return action.astype(jnp.int32), carry

    return jax.jit(act, donate_argnums=(2,))


def build_recurrent_greedy_act(apply_fn: Callable) -> Callable:
    """Greedy recurrent variant for evaluator/tester."""

    def act(params, obs, carry):
        q, carry = apply_fn(params, obs, carry)
        return jnp.argmax(q, axis=-1), carry

    return jax.jit(act)


def build_ddpg_act(actor_apply_fn: Callable) -> Callable:
    """Deterministic policy forward ``act(params, obs[B,...]) -> action[B,d]``
    in [-1,1]; exploration noise (OU) is added host-side by the actor
    process, as in the reference (reference ddpg_mlp_model.py:74-78 returns
    action + noise; here noise stays outside the jitted function so the OU
    state lives with the process)."""

    def act(params, obs):
        return actor_apply_fn(params, obs)

    return jax.jit(act)
