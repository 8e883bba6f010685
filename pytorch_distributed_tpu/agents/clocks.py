"""Cross-process clocks and stat accumulators.

Equivalent of the reference's shared log-counter structs
(reference core/single_processes/logs.py): every field is a
``multiprocessing.Value`` from the spawn context, so one instance created by
the orchestrator is addressable from every worker, whether workers are OS
processes (production) or threads (tests).  As in the reference, the
**learner step is the global clock** that terminates every loop
(reference logs.py:6, dqn_actor.py:62), and actor/learner stats are
push-accumulated by workers then drained-and-reset by the logger on its
cadence (reference dqn_logger.py:34-56).
"""

from __future__ import annotations

import multiprocessing as mp

_CTX = mp.get_context("spawn")


class GlobalClock:
    """The global step counters (reference logs.py:3-6)."""

    def __init__(self):
        self.actor_step = _CTX.Value("l", 0, lock=True)
        self.learner_step = _CTX.Value("l", 0, lock=True)
        # updates whose dispatch has COMPLETED on the device, written by
        # ``run_learner``.  ``learner_step`` counts at enqueue and leads
        # the device by the dispatches in flight (seconds, for R2D2's
        # half-second dispatches); this one never leads ``learner_step``
        # and reaches it once the last dispatch is done.  Read this for a
        # rate over a window.  (The Anakin and replica drivers do not keep
        # it: they count at enqueue only.)
        self.learner_done = _CTX.Value("l", 0, lock=True)
        # Best evaluator reward so far — shared so (a) the learner can bind
        # it into every checkpoint epoch (utils/checkpoint.py save_epoch
        # extras) and (b) a resumed run's evaluator can't clobber
        # ``<refs>_best.msgpack`` with a worse policy: the learner restores
        # this from the epoch before its first publication, ahead of any
        # eval (agents/evaluator.py reads it per comparison).
        self.best_eval_reward = _CTX.Value("d", float("-inf"), lock=True)
        # Cooperative shutdown — the supervision layer the reference lacks
        # (SURVEY.md §5 "failure detection: none"): a dead learner there
        # stalls the clock and every loop spins forever; here the runtime
        # sets this flag when any worker dies or the run completes.
        self.stop = _CTX.Event()
        # Health-sentinel counters (utils/health.py): written by the
        # learner, read by the T_STATUS health plane (fleet.py
        # _health_snapshot -> tools/fleet_top.py) and by drills.
        self.skipped_steps = _CTX.Value("l", 0, lock=True)
        self.rollbacks = _CTX.Value("l", 0, lock=True)
        # Hang-watchdog progress board (utils/supervision.ProgressBoard),
        # attached by the owning Topology before workers spawn; the
        # shared Values ride the clock's spawn pickle into every child.
        self.progress = None

    def bump_progress(self, label: str, n: int = 1) -> None:
        """Stamp a liveness-progress mark for ``label`` (e.g.
        ``actor-3``); no-op when no watchdog board is attached.  ``n``
        is the number of work units the mark covers (a fused device
        dispatch marks once for its K vector ticks), so mark COUNTS
        stay in vector-tick units across backends — the fleet STATUS
        per-actor frames/s derives from them."""
        if self.progress is not None:
            self.progress.bump(label, n)

    def add_skipped_steps(self, n: int) -> None:
        with self.skipped_steps.get_lock():
            self.skipped_steps.value += n

    def add_actor_steps(self, n: int = 1) -> int:
        with self.actor_step.get_lock():
            self.actor_step.value += n
            return self.actor_step.value

    def seed_actor_steps(self, n: int) -> None:
        """Additive restore of a checkpointed actor-step count: actors may
        already be stepping when the learner restores the epoch, so the
        baseline is ADDED under the lock rather than overwriting their
        early increments."""
        with self.actor_step.get_lock():
            self.actor_step.value += n

    def set_learner_step(self, value: int) -> None:
        with self.learner_step.get_lock():
            self.learner_step.value = value

    def set_learner_done(self, value: int) -> None:
        with self.learner_done.get_lock():
            self.learner_done.value = value

    def done(self, steps: int) -> bool:
        """Termination predicate shared by every worker loop
        (reference dqn_actor.py:62 ``learner_step >= steps``)."""
        return self.stop.is_set() or self.learner_step.value >= steps


class _Accumulator:
    """A drain-and-reset float accumulator group."""

    FIELDS: tuple = ()

    def __init__(self):
        self._lock = _CTX.Lock()
        for f in self.FIELDS:
            setattr(self, f, _CTX.Value("d", 0.0, lock=False))

    def add(self, **kv: float) -> None:
        with self._lock:
            for k, v in kv.items():
                getattr(self, k).value += v

    def drain(self) -> dict:
        """Read out and zero all fields atomically
        (reference dqn_logger.py:34-55 reads then ``.value = 0``)."""
        with self._lock:
            out = {f: getattr(self, f).value for f in self.FIELDS}
            for f in self.FIELDS:
                getattr(self, f).value = 0.0
            return out


class ActorStats(_Accumulator):
    """Rollout stats accumulated by all actors (reference logs.py:8-13);
    scalar names match the reference's TensorBoard keys
    (reference dqn_logger.py:34-47)."""

    FIELDS = ("nepisodes", "nepisodes_solved", "total_steps",
              "total_reward", "total_nframes")


class LearnerStats(_Accumulator):
    """Loss accumulators (reference logs.py:15-24; DDPG adds actor_loss,
    reference ddpg_logger.py:51)."""

    # the hybrid trunks' counters (models/hybrid.py moe_stats and
    # window_applies): the step metric ``learner/<name>`` of each, 0 for
    # every model that does not report it
    MOE_FIELDS = ("moe_rows_here", "moe_rows_absent_share",
                  "moe_load_max_over_mean", "moe_rows_computed",
                  "moe_aux_loss", "gdn_decay_mean", "kda_decay_mean",
                  "kda_decay_min")
    # ``exchange_rounds``: the step metric ``learner/exchange_rounds`` of a
    # learner whose ring is row-sharded over a mesh (memory/device_replay.py
    # exchange_rounds, at least 1 there); 0 = not reported, and the logger
    # writes no row
    FIELDS = ("counter", "critic_loss", "actor_loss", "q_mean", "grad_norm",
              "steps_per_sec", "moe_aux", *MOE_FIELDS, "exchange_rounds")


class EvaluatorStats:
    """Evaluator -> logger handshake (reference logs.py:26-33): evaluator
    writes a snapshot and raises the flag; the logger consumes and lowers it
    (reference evaluators.py:90-95, dqn_logger.py:23-33)."""

    FIELDS = ("avg_steps", "avg_reward", "nepisodes", "nepisodes_solved")

    def __init__(self):
        self._lock = _CTX.Lock()
        self.flag = _CTX.Value("b", 0, lock=False)
        self.at_step = _CTX.Value("l", 0, lock=False)
        # capture wall time: the evaluator attributes each result to the
        # moment the weights were SNAPSHOTTED, not when the (possibly
        # CPU-starved) episodes finished — curve timestamps stay exact
        # under evaluator_nice (agents/evaluator.py docstring)
        self.at_wall = _CTX.Value("d", 0.0, lock=False)
        # raised when the evaluator exits (after its final eval+checkpoint)
        # so the logger drains everything before closing the run
        self.done = _CTX.Value("b", 0, lock=False)
        for f in self.FIELDS:
            setattr(self, f, _CTX.Value("d", 0.0, lock=False))

    def publish(self, learner_step: int, wall: float = 0.0,
                **kv: float) -> None:
        with self._lock:
            for k, v in kv.items():
                getattr(self, k).value = v
            self.at_step.value = learner_step
            self.at_wall.value = wall
            self.flag.value = 1

    def consume(self):
        """Returns (learner_step, wall-or-0, stats dict) or None if
        nothing new."""
        with self._lock:
            if not self.flag.value:
                return None
            out = {f: getattr(self, f).value for f in self.FIELDS}
            step, wall = self.at_step.value, self.at_wall.value
            self.flag.value = 0
            return step, wall, out
