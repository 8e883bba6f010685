"""Actor processes: asynchronous experience collection.

Re-design of reference core/single_processes/dqn_actor.py and
ddpg_actor.py.  Same topology — rollout workers with a full local model
replica, diversified by the Ape-X exploration schedule and per-process
seeds — with three structural upgrades:

- the reference's implicit shared-CUDA weight pulls become versioned
  ``ParamStore`` fetches on the ``actor_sync_freq`` cadence (reference
  dqn_actor.py:176-178) — prefetched off the hot path by a
  ``ParamPrefetcher`` thread so a version swap never stalls a tick — and
  its inline deque bookkeeping becomes the unit-tested ``NStepAssembler``;
- every actor is **vectorized**: it steps ``num_envs_per_actor`` envs with
  ONE jitted batched forward per tick (envs/vector.py) — the reference
  reserves this knob but asserts it to 1 (reference utils/options.py:32);
- the hot loop is **software-pipelined** (ISSUE 4 tentpole): the jitted
  ``act`` for tick k+1 is dispatched asynchronously (JAX async dispatch)
  right after tick k's env step, so the device forward overlaps the
  host's feed/advance work, and the action sync happens at the last
  moment as ONE packed device→host copy.  The per-tick host work the
  serial loop carried — key splits, three separate device reads — is
  fused into the jitted step (models/policies.build_packed_act: the PRNG
  key stays on-device, a tick counter is folded in instead of a
  host-side split chain).

Three interchangeable backends (``env_params.actor_backend``), all
bit-identical action/transition streams under a fixed seed because
per-tick randomness is a pure function of (actor, tick, env row):

- ``inline``   — the serial schedule: dispatch, sync, step, feed.  The
  fallback and the determinism reference.
- ``pipelined`` — the two-stage overlapped schedule above (default).
- ``batched``  — SEED-style: no local model at all; obs go to the shared
  ``InferenceServer`` in the accelerator-owning process
  (agents/inference.py) and the wide forward runs there.  Requires the
  co-located server; downgrades to ``pipelined`` with a warning when
  none is wired in (e.g. remote DCN actor hosts).

A fourth backend, ``device`` (ISSUE 7), replaces the per-tick loop with
the fused on-device rollout below; and a fifth, ``anakin`` (ISSUE 12),
removes the actor process entirely — the env fleet lives in the learner
process and agents/anakin.py drives the same fused rollout against the
learner's own replay ring, so no actor worker ever spawns.

Cadences mirror the reference: stats pushed every ``actor_freq`` env steps
(reference dqn_actor.py:180-192), global actor-step counter advanced per
env step (reference :166-167), loop until the global learner clock reaches
``steps`` (reference :62).  The weight-sync cadence is checked at ONE
defined point per tick (after the env step, before the next dispatch) so
the inline and pipelined schedules see identical staleness.

Exploration diversity follows Ape-X across the whole fleet: env ``j`` of
actor ``i`` takes exploration slot ``i*N + j`` of ``num_actors*N``
(reference dqn_actor.py:33-36 has one slot per actor).
"""

from __future__ import annotations

import time
from typing import Any, List, Optional

import numpy as np

from pytorch_distributed_tpu.config import Options
from pytorch_distributed_tpu.factory import (
    EnvSpec, build_device_env, build_env_vector, build_model,
    init_params, resolve_actor_backend,
)
from pytorch_distributed_tpu.agents.clocks import ActorStats, GlobalClock
from pytorch_distributed_tpu.agents.param_store import (
    ParamPrefetcher, ParamStore, make_flattener,
)
from pytorch_distributed_tpu.ops.nstep import NStepAssembler
from pytorch_distributed_tpu.utils.experience import make_prov
from pytorch_distributed_tpu.utils.random_process import (
    OrnsteinUhlenbeckProcess,
)
from pytorch_distributed_tpu.utils.helpers import (
    pin_to_cpu, unravel_on_cpu,
)
from pytorch_distributed_tpu.utils.rngs import process_key, process_seed


class _ActorHarness:
    """Shared plumbing for both actor families: vector env + model/param
    setup, per-env n-step feeds, stat accumulation, sync cadence."""

    def __init__(self, opt: Options, spec: EnvSpec, process_ind: int,
                 memory: Any, param_store: ParamStore, clock: GlobalClock,
                 stats: ActorStats, backend: str = "pipelined"):
        self.opt = opt
        self.ap = opt.agent_params
        self.spec = spec
        self.process_ind = process_ind
        self.memory = memory
        self.param_store = param_store
        self.clock = clock
        self.stats = stats
        self.backend = backend

        self.num_envs = max(1, opt.env_params.num_envs_per_actor)
        if backend == "device":
            # Sebulba actor (ISSUE 7): the env fleet is a pure-JAX
            # program advanced inside the fused rollout dispatch — no
            # host env objects exist in this process at all
            self.env = None
            self.device_env = build_device_env(opt, process_ind,
                                               self.num_envs)
        else:
            self.env = build_env_vector(opt, process_ind, self.num_envs)
            self.env.train()
        self._prefetch: Optional[ParamPrefetcher] = None
        if backend == "batched":
            # SEED-style actor: inference lives with the accelerator, so
            # this process holds NO model replica — no init, no
            # flattener, no per-cadence fetch/unravel (the serial loop's
            # single biggest off-tick cost).  The initial wait stays: it
            # is the learner-alive barrier every worker starts behind.
            self.model = None
            self.unravel = None
            self.params = None
            _flat, self.version = param_store.wait(0, timeout=300.0,
                                                   stop=clock.stop)
        else:
            self.model = build_model(opt, spec)
            params0 = init_params(opt, spec, self.model, seed=process_seed(
                opt.seed, "actor", process_ind))
            _, self.unravel = make_flattener(params0)
            # block until the learner publishes the initial weights — the
            # explicit version of the reference's pre-spawn hard sync
            # (reference dqn_actor.py:26-30).  Generous timeout: the first
            # publication sits behind the learner process's backend
            # start-up and a possible checkpoint restore; a dead learner
            # is caught by the stop event, not this timeout.
            flat, self.version = param_store.wait(0, timeout=300.0,
                                                  stop=clock.stop)
            # rollout inference is pinned to the host CPU: the learner owns
            # the accelerator (helpers.pin_to_cpu)
            self.params = unravel_on_cpu(self.unravel, flat)
            # weight refresh happens off the hot path from here on: the
            # prefetcher thread does the fetch+unravel, the tick-side
            # swap is a reference exchange (ParamPrefetcher docstring)
            self._prefetch = ParamPrefetcher(
                param_store,
                lambda f: unravel_on_cpu(self.unravel, f),
                start_version=self.version)
        if hasattr(memory, "set_stop"):
            # stop-aware feeding: a flush blocked on a full queue after
            # the learner stopped draining must abort, not deadlock the
            # teardown join
            memory.set_stop(clock.stop)
        if hasattr(memory, "configure_flow"):
            # ISSUE-11 overload policy: shed-vs-block on the local
            # spawn-queue feeder, selected from the run's FlowParams
            # (env overrides land through flow.resolve_flow as usual)
            memory.configure_flow(opt.flow_params)

        # data-plane provenance (ISSUE 8): every transition this actor
        # emits carries (actor_id, env_slot, param_version, birth_step)
        # minted at action time.  ``_feed_version`` snapshots the version
        # that actually SELECTED this tick's actions — tick_sync captures
        # it BEFORE running the swap cadence, so the swap tick's rows
        # still carry the acting version; ``_birth_step`` is the global
        # learner step the actor observed (sample age is then a
        # learner-step subtraction on the learner side, no clock math).
        self._feed_version = getattr(self, "version", 0)
        self._birth_step = int(clock.learner_step.value)

        N = self.num_envs
        self.assemblers: List[NStepAssembler] = [
            NStepAssembler(self.ap.nstep, self.ap.gamma) for _ in range(N)]
        self.episode_steps = np.zeros(N, dtype=np.int64)
        self.episode_reward = np.zeros(N, dtype=np.float64)

        # Actor-computed initial PER priorities (the plumbing the reference
        # anticipated but never finished, reference dqn_actor.py:113-115):
        # per env, q_sel of each acted step FIFO-aligned with the
        # assembler's FIFO emissions, plus a one-tick holding pen for
        # steady-state emissions whose bootstrap state's q_max only becomes
        # known at the NEXT tick's batched forward.
        from collections import deque

        self.per_priorities = (opt.memory_params.enable_per
                               and opt.agent_type == "dqn")
        self._q_hist = [deque() for _ in range(N)]
        self._q_pending: List[list] = [[] for _ in range(N)]

        # local stat accumulators, flushed every actor_freq env steps
        self._acc = dict.fromkeys(ActorStats.FIELDS, 0.0)
        self.env_steps = 0
        self._next_flush = self.ap.actor_freq
        self._next_sync = self.ap.actor_sync_freq

        from pytorch_distributed_tpu.utils import perf, tracing
        from pytorch_distributed_tpu.utils.faults import FaultInjector
        from pytorch_distributed_tpu.utils.metrics import MetricsWriter
        from pytorch_distributed_tpu.utils.profiling import StepTimer

        # hang-watchdog liveness mark (utils/supervision.ProgressBoard,
        # attached to the clock by the topology) + the actor fault plane
        # (``ACTOR_FAULTS``, one frame per vector tick — ``hang@N``
        # makes this worker stop progressing without exiting, the drill
        # the watchdog must catch).  Test clocks may lack the surface.
        self._bump_progress = getattr(clock, "bump_progress",
                                      lambda label, n=1: None)
        self._progress_label = f"actor-{process_ind}"
        self._faults = FaultInjector.from_env("actor")

        self.timer = StepTimer("actor")
        self._timing_writer = MetricsWriter(
            opt.log_dir, enable_tensorboard=False,
            role=f"actor-{process_ind}", run_id=opt.refs)
        # perf plane (utils/perf.py, TPU_APEX_PERF=1): env-frames/s +
        # memory watermarks on the actor_freq cadence; tags stay
        # "actor/..." (fleet-comparable), rows carry this process's role
        self.perf = perf.get_monitor(f"actor-{process_ind}",
                                     opt.perf_params, prefix="actor")
        self.perf.drain()  # anchor the first rate window at startup
        # distributed-trace origin: every chunk this actor flushes is
        # stamped with a trace id here and records an "enqueue" span (a
        # blocking put IS backpressure); downstream hops — gateway, feed,
        # sample, learn — attach to the same id (utils/tracing.py)
        self.tracer = tracing.get_tracer("actor")
        if hasattr(memory, "set_tracer"):
            memory.set_tracer(self.tracer)

    # -- one vector tick ----------------------------------------------------

    def tick_sync(self) -> None:
        """Once per vector tick, at the ONE schedule-invariant point
        (after the env step, before the next act dispatch): bump the
        global/local step counters and run the weight-sync cadence.  The
        swap itself is non-blocking — the prefetcher already did the
        fetch+unravel on its own thread — and is timed as ``param_swap``
        so any residual stall is visible in traces (ISSUE 4
        satellite)."""
        N = self.num_envs
        self.env_steps += N
        self._feed_version = getattr(self, "version", 0)
        self._birth_step = int(self.clock.learner_step.value)
        self.perf.note_frames(N)  # one int add; no-op when disabled
        self.clock.add_actor_steps(N)  # reference dqn_actor.py:166-167
        self._bump_progress(self._progress_label)  # watchdog liveness
        self._faults.data_frame(())  # ACTOR_FAULTS: hang@N / crash@N
        self._acc["total_nframes"] += N
        if self.env_steps >= self._next_sync:
            self._next_sync += self.ap.actor_sync_freq
            if self._prefetch is not None:
                t0 = time.perf_counter()
                got = self._prefetch.take()
                if got is not None:
                    self.params, self.version = got
                    self.timer.add("param_swap",
                                   time.perf_counter() - t0)

    def advance(self, actions, next_obs, rewards, terminals, infos,
                q_sel=None, q_max=None) -> None:
        """Feed assemblers/memory for one batched env step and run the
        stat-flush cadence.  ``q_sel``/``q_max`` are this tick's per-env Q
        diagnostics from the batched forward (DQN actors); with PER
        enabled they become initial priorities.  In the pipelined
        schedule this host work runs while the NEXT tick's forward is
        already in flight on the device."""
        if self.per_priorities:
            self._resolve_pending(q_max)
        for j in range(self.num_envs):
            true_next = infos[j].get("final_obs", next_obs[j])
            truncated = bool(infos[j].get("truncated", False))
            if self.per_priorities:
                self._q_hist[j].append(float(q_sel[j]))
            transitions = self.assemblers[j].feed(
                self._obs[j], actions[j], float(rewards[j]), true_next,
                bool(terminals[j]), truncated=truncated,
                prov=make_prov(self.process_ind, j, self._feed_version,
                               self._birth_step))
            if self.per_priorities:
                self._feed_with_priorities(j, transitions,
                                           bool(terminals[j]), truncated)
            else:
                for t in transitions:
                    self.memory.feed(t, None)
            self.episode_steps[j] += 1
            self.episode_reward[j] += float(rewards[j])
            if terminals[j]:
                self._record_episode(j, infos[j])
                self.on_env_reset(j)
        self._obs = next_obs
        self._flush_cadence()

    def _record_episode(self, j: int, info: dict) -> None:
        """Fold env slot j's finished episode into the stat accumulators."""
        solved = bool(info.get("solved", self.episode_reward[j] > 0))
        self._acc["nepisodes"] += 1
        self._acc["nepisodes_solved"] += float(solved)
        self._acc["total_steps"] += float(self.episode_steps[j])
        self._acc["total_reward"] += float(self.episode_reward[j])
        self.episode_steps[j] = 0
        self.episode_reward[j] = 0.0

    def _flush_cadence(self) -> None:
        """Stat-flush cadence (reference dqn_actor.py:180-192); the
        weight-sync cadence lives in ``tick_sync``."""
        if self.env_steps >= self._next_flush:
            self._next_flush += self.ap.actor_freq
            self.flush_stats()
            step = self.clock.learner_step.value
            self._timing_writer.scalars(self.timer.drain(), step=step)
            if self.perf.enabled:
                self._timing_writer.scalars(self.perf.drain(step=step),
                                            step=step)
            self.tracer.flush_to(self._timing_writer, step=step)
            if hasattr(self.memory, "flush"):
                self.memory.flush()  # queue feeders drain on the cadence

    # -- actor-side TD-error priorities (PER) -------------------------------

    def _resolve_pending(self, q_max) -> None:
        """Steady-state emissions held from the previous tick bootstrap
        from the state the actor is looking at NOW — its q_max just arrived
        with this tick's forward.  priority = |R + gamma_m * maxQ(s_end) -
        q_sel(s_t)|, the n-step TD estimate under the actor's weights."""
        for j in range(self.num_envs):
            if not self._q_pending[j]:
                continue
            for t, q_t in self._q_pending[j]:
                pr = abs(float(t.reward)
                         + float(t.gamma_n) * float(q_max[j]) - q_t)
                self.memory.feed(t, pr)
            self._q_pending[j] = []

    def _feed_with_priorities(self, j: int, transitions,
                              terminal: bool, truncated: bool) -> None:
        if terminal or truncated:
            # episode boundary: every window closed this tick.  True
            # terminals have a zero bootstrap so the TD estimate needs no
            # future q; truncated tails would need q(final_obs), which was
            # never computed — they take the standard new-sample max
            # priority (None).
            for t in transitions:
                q_t = self._q_hist[j].popleft()
                if truncated:
                    self.memory.feed(t, None)
                else:
                    self.memory.feed(t, abs(float(t.reward) - q_t))
            self._q_hist[j].clear()  # next episode starts a fresh history
        else:
            for t in transitions:  # bootstrap q arrives next tick
                self._q_pending[j].append((t, self._q_hist[j].popleft()))

    def start(self) -> None:
        self._obs = self.env.reset()

    def on_env_reset(self, j: int) -> None:
        """Hook for per-env exploration state (DDPG OU paths)."""

    def flush_stats(self) -> None:
        if any(self._acc.values()):
            self.stats.add(**self._acc)
            self._acc = dict.fromkeys(ActorStats.FIELDS, 0.0)

    def shutdown(self) -> None:
        if self._prefetch is not None:
            self._prefetch.close()
        # Best-effort final drain: over DCN a terminally disconnected
        # transport raises from these feeds/flushes (parallel/dcn.py
        # DcnDisconnected), and a teardown crash here would mask WHY the
        # loop ended — the runner's exit code must come from the
        # stop-vs-disconnected split (fleet._remote_actor_main), not
        # from a flush traceback.  Local queue transports never raise
        # these, so nothing is hidden on the single-host path.
        try:
            for j in range(self.num_envs):  # unresolved holds: max priority
                for t, _q in self._q_pending[j]:
                    self.memory.feed(t, None)
                self._q_pending[j] = []
            self.flush_stats()
            if hasattr(self.memory, "flush"):
                self.memory.flush()
        except (ConnectionError, OSError):
            pass
        from pytorch_distributed_tpu.memory.feeder import QueueFeeder

        if isinstance(self.memory, QueueFeeder):
            self.memory.close()
        if self.perf.enabled:
            # final partial window: bounded runs still export a rate
            self._timing_writer.scalars(
                self.perf.drain(step=self.clock.learner_step.value),
                step=self.clock.learner_step.value)
        self.tracer.flush_to(self._timing_writer,
                             step=self.clock.learner_step.value)
        self._timing_writer.close()


# ---------------------------------------------------------------------------
# Act engines: submit/collect pairs the loop driver schedules.
#
# ``submit(obs, tick, reset_mask)`` dispatches the tick's forward and
# returns an opaque handle WITHOUT blocking on the result (JAX async
# dispatch locally; a queue send to the shared server in batched mode).
# ``collect(handle)`` syncs the result into numpy at the last moment and
# returns ``(actions, advance_kwargs)``.  One engine instance is scheduled
# by both the inline and the pipelined loops, so the two backends can
# never drift numerically.
# ---------------------------------------------------------------------------


def _unpack_dqn(packed: np.ndarray):
    """(3, B) packed (action, q_sel, q_max) -> advance arguments."""
    return (packed[0].astype(np.int64),
            dict(q_sel=packed[1], q_max=packed[2]))


class _LocalDqnEngine:
    """Fused eps-greedy forward on this process's host CPU."""

    def __init__(self, h: _ActorHarness, base_key, eps):
        import jax.numpy as jnp

        from pytorch_distributed_tpu.models.policies import build_packed_act

        self._h = h
        self._act = build_packed_act(h.model.apply)
        self._key = pin_to_cpu(base_key)
        self._eps = pin_to_cpu(jnp.asarray(eps, jnp.float32))

    def submit(self, obs, tick, reset_mask):
        out = self._act(self._h.params, obs, self._key, tick, self._eps)
        out.copy_to_host_async()  # D2H overlaps the host work too
        return out

    def collect(self, pending):
        return _unpack_dqn(np.asarray(pending))

    def close(self) -> None:
        pass


def _ou_explore(h: _ActorHarness, a: np.ndarray) -> np.ndarray:
    """Add the harness's OU exploration noise to a deterministic policy
    output and clip to the action box — ONE implementation shared by the
    local and batched DDPG engines, because both schedules' noise
    streams must stay bit-identical (the tests' oracle) and a divergence
    here would desync them silently."""
    noise = h.ou.sample().reshape(h.num_envs, h.spec.action_dim)
    return np.clip(a + noise, -1.0, 1.0).astype(np.float32)


class _LocalDdpgEngine:
    """Deterministic policy forward; OU noise stays host-side at sync
    time so the noise stream is schedule-invariant."""

    def __init__(self, h: _ActorHarness):
        from pytorch_distributed_tpu.models.policies import build_ddpg_act

        self._h = h
        self._act = build_ddpg_act(lambda p, o: h.model.apply(
            p, o, method=h.model.forward_actor))

    def submit(self, obs, tick, reset_mask):
        out = self._act(self._h.params, obs)
        out.copy_to_host_async()
        return out

    def collect(self, pending):
        return _ou_explore(self._h, np.asarray(pending)), {}

    def close(self) -> None:
        pass


class _BatchedDqnEngine:
    """Submit obs to the shared InferenceServer (agents/inference.py)."""

    def __init__(self, client, base_key, eps):
        self._client = client
        client.begin_session(base_key=np.asarray(base_key),
                             eps=np.asarray(eps, np.float32))

    def submit(self, obs, tick, reset_mask):
        return self._client.submit(obs, tick)

    def collect(self, pending):
        return _unpack_dqn(self._client.collect(pending))

    def close(self) -> None:
        pass


class _BatchedDdpgEngine:
    def __init__(self, h: _ActorHarness, client):
        self._h = h
        self._client = client
        client.begin_session()

    def submit(self, obs, tick, reset_mask):
        return self._client.submit(obs, tick)

    def collect(self, pending):
        return _ou_explore(self._h, self._client.collect(pending)), {}

    def close(self) -> None:
        pass


# ---------------------------------------------------------------------------
# The loop driver: one schedule for every family and backend.
# ---------------------------------------------------------------------------


def _drive_actor_loop(h: _ActorHarness, engine, clock: GlobalClock,
                      pipelined: bool) -> _ActorHarness:
    """Run the actor loop to the global clock's termination.

    Serial (``pipelined=False``)::

        act(k) . sync . env(k) . tick_sync . feed(k)

    Pipelined (``pipelined=True``) — the ISSUE 4 two-stage software
    pipeline; act(k+1) is IN FLIGHT on the device while the host feeds
    tick k::

        sync(k) . env(k) . tick_sync . dispatch act(k+1) . feed(k)

    Both schedules drive the same engine in the same per-tick order
    (submit once, collect once, tick_sync between env step and next
    dispatch), so their action/transition streams are bit-identical
    under a fixed seed.  Timer phases: the serial loop books ``act``;
    the pipelined loop books ``dispatch`` (issue cost), ``sync``
    (blocked-on-device time — the part overlap is hiding) and an ``act``
    aggregate of the two so dashboards compare across schedules.
    """
    timer = h.timer
    h.engine = engine  # introspection: tests read its program
    # retrace detector: the fused act program must never recompile
    # after warmup (batched engines have none — the jit lives
    # server-side and the server registers its own)
    h.perf.register_jit("act", getattr(engine, "_act", None))
    h.start()
    tick = 0
    reset_mask = np.zeros(h.num_envs, dtype=bool)
    pending = None
    if pipelined:
        t0 = time.perf_counter()
        pending = engine.submit(h._obs, 0, reset_mask)
        timer.add("dispatch", time.perf_counter() - t0)
    t_sync = 0.0
    while not clock.done(h.ap.steps):
        if pipelined:
            t0 = time.perf_counter()
            actions, extras = engine.collect(pending)
            t_sync = time.perf_counter() - t0
            timer.add("sync", t_sync)
        else:
            t0 = time.perf_counter()
            pending = engine.submit(h._obs, tick, reset_mask)
            actions, extras = engine.collect(pending)
            timer.add("act", time.perf_counter() - t0)
        with timer.phase("env"):
            next_obs, rewards, terminals, infos = h.env.step(actions)
        h.tick_sync()
        tick += 1
        if pipelined:
            t0 = time.perf_counter()
            pending = engine.submit(next_obs, tick, terminals)
            t_disp = time.perf_counter() - t0
            timer.add("dispatch", t_disp)
            timer.add("act", t_sync + t_disp)
        else:
            reset_mask = terminals
        with timer.phase("advance"):
            h.advance(actions, next_obs, rewards, terminals, infos,
                      **extras)
    h.shutdown()
    engine.close()
    return h


def fold_rollout_episode_stats(step_reward, step_terminal, episode_reward,
                               episode_steps, acc: dict) -> None:
    """Fold a fused dispatch's ``(K, N)`` per-tick env stats into the
    harness-style per-env episode accumulators and the actor stat dict
    (``ActorStats.FIELDS`` keys) — ONE implementation shared by the
    split-process device actor loop and the co-located Anakin driver
    (agents/anakin.py), so the two backends' episode curves can never
    drift.  ``episode_reward``/``episode_steps`` are mutated in place;
    an episode counts as solved when its return is positive (the
    ``_record_episode`` default for envs that report no ``solved``)."""
    K = np.asarray(step_reward).shape[0]
    for k in range(K):
        episode_reward += np.asarray(step_reward[k], np.float64)
        episode_steps += 1
        for j in np.nonzero(np.asarray(step_terminal[k]))[0]:
            j = int(j)
            acc["nepisodes"] += 1
            acc["nepisodes_solved"] += float(episode_reward[j] > 0)
            acc["total_steps"] += float(episode_steps[j])
            acc["total_reward"] += float(episode_reward[j])
            episode_steps[j] = 0
            episode_reward[j] = 0.0


def _drive_device_actor_loop(h: _ActorHarness, clock: GlobalClock,
                             base_key, eps) -> _ActorHarness:
    """The Sebulba actor loop (ISSUE 7): no per-tick host work at all.

    One fused, donated XLA program advances all N envs x K ticks —
    policy forward, row-keyed eps-greedy, env physics/render, n-step
    assembly — and the host's whole job per dispatch is ONE packed
    device->host copy of the emitted transition chunk plus the feed
    into the replay plane.  Action streams are bit-identical to the
    inline loop over the same device env (the tick_keys contract), and
    the emitted transition stream is bit-identical to what the host
    ``NStepAssembler`` would produce from those ticks
    (tests/test_device_env.py pins both).

    Cadences quantize to the dispatch: the weight-sync check, stat
    flush, watchdog liveness marks and fault frames all run once per
    K-tick dispatch instead of per tick.  Timer phases: ``rollout``
    (dispatch issue), ``emit`` (blocked on the program + the chunk
    D2H), ``advance`` (replay feed + episode accounting),
    ``param_swap`` (the prefetched weight swap)."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.models.policies import (
        build_fused_rollout, init_rollout_carry, rollout_priorities,
    )
    from pytorch_distributed_tpu.utils.experience import Transition

    ap = h.ap
    N = h.num_envs
    K = max(1, int(getattr(h.opt.env_params, "device_rollout_ticks", 8)))
    env = h.device_env
    rollout = build_fused_rollout(h.model.apply, env, nstep=ap.nstep,
                                  gamma=ap.gamma, rollout_ticks=K,
                                  emit="chunk")
    h.rollout_jit = rollout  # introspection: tests read the cache
    # perf plane: the fused rollout is a registered hot program (a
    # post-warmup recompile = a shape/dtype leak paying compile latency
    # on the hot path) and its per-frame FLOPs feed the actor-side MFU
    # on the live plane (utils/perf.py flops_per_frame)
    h.perf.register_jit("device_rollout", rollout)
    carry = init_rollout_carry(env, ap.nstep)
    eps_dev = jnp.asarray(eps, jnp.float32)
    key_dev = jnp.asarray(base_key)
    if h.perf.enabled:
        # XLA's cost analysis counts the K-tick scan body ONCE
        # (verified: totals are K-invariant, utils/perf.
        # flops_of_compiled docstring), so the per-call figure is one
        # tick of all N envs — divide by N, not K*N
        h.perf.capture_frame_flops(
            lambda: rollout.lower(h.params, carry, key_dev,
                                  jnp.int32(0), eps_dev),
            frames_per_call=N)
    timer = h.timer
    # tick0 stays DEVICE-resident and advances on device (+K is a weak
    # python constant): the audited dispatch must stage zero host
    # arrays, so the transfer audit (TPU_APEX_PERF_TRANSFER_AUDIT=1)
    # proves the hot path transfer-free instead of flagging its own
    # tick counter
    tick0 = jnp.int32(0)
    audit = h.perf.audit
    while not clock.done(ap.steps):
        t0 = time.perf_counter()
        if audit is not None:
            carry, chunk = audit.run(rollout, h.params, carry, key_dev,
                                     tick0, eps_dev)
        else:
            carry, chunk = rollout(h.params, carry, key_dev, tick0,
                                   eps_dev)
        tick0 = tick0 + K
        timer.add("rollout", time.perf_counter() - t0)
        t0 = time.perf_counter()
        ch = jax.device_get(chunk)  # the dispatch's ONE device->host copy
        timer.add("emit", time.perf_counter() - t0)
        # ---- per-dispatch cadence (the vector ticks' tick_sync) ----
        # provenance stamps quantize to the dispatch: the chunk's rows
        # carry the version that acted THIS dispatch (captured before
        # the swap cadence below) and the learner step observed at
        # fetch — windows opened in the previous dispatch inherit the
        # current stamp, a documented <=K-tick quantization
        feed_version = h.version
        birth_step = int(h.clock.learner_step.value)
        h.env_steps += K * N
        h.perf.note_frames(K * N)
        h.clock.add_actor_steps(K * N)
        # one liveness mark covering the dispatch's K vector ticks:
        # mark counts stay in tick units, so the fleet STATUS per-actor
        # frames/s (marks x num_envs / dt) is backend-invariant
        h._bump_progress(h._progress_label, n=K)
        h._faults.data_frame(())
        h._acc["total_nframes"] += K * N
        if h.env_steps >= h._next_sync:
            h._next_sync += ap.actor_sync_freq
            if h._prefetch is not None:
                t0 = time.perf_counter()
                got = h._prefetch.take()
                if got is not None:
                    h.params, h.version = got
                    timer.add("param_swap", time.perf_counter() - t0)
        with timer.phase("advance"):
            valid = np.asarray(ch.valid)
            prio = None
            if h.per_priorities:
                flat = {f: np.asarray(getattr(ch, f)).reshape(
                    (K * N,) + np.asarray(getattr(ch, f)).shape[2:])
                    for f in ("reward", "gamma_n", "terminal1",
                              "q_boot", "q_sel", "prio_ok")}
                prio = rollout_priorities(flat, True).reshape(K, N)
            for k in range(K):
                for j in range(N):
                    if not valid[k, j]:
                        continue
                    t = Transition(
                        state0=ch.state0[k, j], action=ch.action[k, j],
                        reward=ch.reward[k, j],
                        gamma_n=ch.gamma_n[k, j],
                        state1=ch.state1[k, j],
                        terminal1=ch.terminal1[k, j],
                        prov=make_prov(h.process_ind, j, feed_version,
                                       birth_step))
                    h.memory.feed(t, prio[k][j] if prio is not None
                                  else None)
            # episode accounting off the per-tick env stats (shared
            # with the Anakin driver: fold_rollout_episode_stats)
            fold_rollout_episode_stats(ch.step_reward, ch.step_terminal,
                                       h.episode_reward, h.episode_steps,
                                       h._acc)
            h._flush_cadence()
    h.shutdown()
    return h


def run_dqn_actor(opt: Options, spec: EnvSpec, process_ind: int, memory: Any,
                  param_store: ParamStore, clock: GlobalClock,
                  stats: ActorStats, inference: Any = None):
    """eps-greedy rollout worker (reference dqn_actor.py:9-192), batched
    over the actor's env vector and scheduled per ``actor_backend``."""
    from pytorch_distributed_tpu.models.policies import apex_epsilons

    backend = resolve_actor_backend(opt, inference)
    if backend == "anakin":
        # an actor PROCESS can never be the co-located loop (that loop
        # is the learner); remote hosts in a hybrid anakin fleet run
        # the split-process device schedule against the same env fleet
        backend = "device"
    h = _ActorHarness(opt, spec, process_ind, memory, param_store, clock,
                      stats, backend=backend)
    eps = apex_epsilons(process_ind, opt.num_actors, h.num_envs,
                        h.ap.eps, h.ap.eps_alpha)
    base_key = process_key(opt.seed, "actor", process_ind)
    if backend == "device":
        return _drive_device_actor_loop(h, clock, base_key, eps)
    if backend == "batched":
        engine = _BatchedDqnEngine(inference, base_key, eps)
    else:
        engine = _LocalDqnEngine(h, base_key, eps)
    return _drive_actor_loop(h, engine, clock,
                             pipelined=(backend != "inline"))


def run_ddpg_actor(opt: Options, spec: EnvSpec, process_ind: int,
                   memory: Any, param_store: ParamStore, clock: GlobalClock,
                   stats: ActorStats, inference: Any = None):
    """OU-noise rollout worker (reference ddpg_actor.py:9-172): same
    skeleton with one OrnsteinUhlenbeckProcess state per env (theta/sigma
    from AgentParams, anneal over memory_size*100 steps — reference
    ddpg_actor.py:34-35).  Rides the shared loop driver, so — unlike the
    original loop, which skipped them (ISSUE 4 satellite) — its
    act/env/advance tick breakdown reaches the metrics stream exactly
    like the DQN family's."""
    backend = resolve_actor_backend(opt, inference)

    class _DdpgHarness(_ActorHarness):
        ou: OrnsteinUhlenbeckProcess  # set right after construction

        def on_env_reset(self, j: int) -> None:
            # fresh noise path per episode, per env
            self.ou.x_prev.reshape(self.num_envs, -1)[j] = self.ou.x0

    h = _DdpgHarness(opt, spec, process_ind, memory, param_store, clock,
                     stats, backend=backend)
    h.ou = OrnsteinUhlenbeckProcess(
        size=h.num_envs * spec.action_dim,
        theta=h.ap.ou_theta,
        mu=h.ap.ou_mu,
        sigma=h.ap.ou_sigma,
        n_steps_annealing=opt.memory_params.memory_size * 100,
        seed=process_seed(opt.seed, "actor", process_ind) + 17,
    )
    if backend == "batched":
        engine = _BatchedDdpgEngine(h, inference)
    else:
        engine = _LocalDdpgEngine(h)
    return _drive_actor_loop(h, engine, clock,
                             pipelined=(backend != "inline"))


# ---------------------------------------------------------------------------
# In-process bounded runs (tests)
# ---------------------------------------------------------------------------


class _RecordingSink:
    """Memory stand-in that records every fed item in arrival order."""

    def __init__(self):
        self.items: List[tuple] = []

    def feed(self, item, priority=None) -> None:
        self.items.append((item, priority))


def bounded_actor_run(opt: Options, ticks: int, spec: EnvSpec = None,
                      process_ind: int = 0, inference: Any = None,
                      param_seed: int = 0) -> dict:
    """Run ONE actor loop in this process for exactly ``ticks`` vector
    ticks against a recording sink and a single fixed parameter snapshot.

    The harness behind the determinism tests (pipelined/batched streams
    must be bit-identical to inline, tests/test_actor_pipeline.py): no
    learner, no spawn — the param
    store is pre-published once from ``init_params(seed=param_seed)``, so
    two runs over the same opt see identical weights.  Returns
    ``{"stream": [(item, priority), ...], "timer_ms": {...},
    "harness": h}`` — the timer dict is the StepTimer drain (per-phase
    mean/max/calls in ms) accumulated over the run, provided
    ``actor_freq`` was set larger than ``ticks * num_envs`` (a mid-run
    flush would drain it early).
    """
    import threading
    import types

    from pytorch_distributed_tpu.factory import get_worker, probe_env

    spec = spec if spec is not None else probe_env(opt)
    model = build_model(opt, spec)
    flat0, _ = make_flattener(init_params(opt, spec, model,
                                          seed=param_seed))
    store = ParamStore(flat0.size)
    store.publish(flat0)

    class _BoundedClock:
        """Quacks like GlobalClock; ends the loop after ``ticks``
        iterations instead of at a learner-step horizon."""

        def __init__(self, ticks_left: int):
            self._left = ticks_left
            self.stop = threading.Event()
            self.learner_step = types.SimpleNamespace(value=0)

        def done(self, steps: int) -> bool:
            if self._left <= 0:
                return True
            self._left -= 1
            return False

        def add_actor_steps(self, n: int = 1) -> int:
            return n

    sink = _RecordingSink()
    clock = _BoundedClock(ticks)
    h = get_worker("actor", opt.agent_type)(
        opt, spec, process_ind, sink, store, clock, ActorStats(),
        inference)
    return {"stream": sink.items, "timer_ms": h.timer.drain(),
            "harness": h}
