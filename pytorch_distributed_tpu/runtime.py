"""Run topology: wiring and supervision.

Re-design of the reference orchestrator (reference main.py:12-118): allocate
the shared objects (replay plane, param store, clocks — the explicit
equivalents of the reference's shared memory at main.py:42, shared CUDA
model at :44-47, and mp.Value logs at :51-54), then run one logger,
``num_actors`` actors and one evaluator as workers, with **the learner in
the parent process** — the parent owns the TPU mesh; every child is exec'd
with ``JAX_PLATFORMS=cpu`` (``cpu_child_env``), so exactly one process
initialises the accelerator (the reference instead gives every process a
CUDA context).
Scaling learners means widening the mesh's dp axis, not adding racing
processes (agents/learner.py docstring).

Supervision — absent in the reference, where a dead worker silently stalls
or hangs the run (SURVEY.md §5 "failure detection: none"): a monitor thread
watches child liveness and trips the shared stop event if any child dies
abnormally; shutdown joins with a timeout and terminates stragglers.

Backends: ``process`` (spawn, production) and ``thread`` (in-process, the
deterministic test harness SURVEY.md §4 calls for).
"""

from __future__ import annotations

import contextlib
import multiprocessing as mp
import os
import signal
import threading
import time
from typing import Any, Dict, List, Optional

from pytorch_distributed_tpu.config import Options
from pytorch_distributed_tpu.factory import (
    EnvSpec, anakin_active, build_memory, get_worker,
    needs_inference_server, prebuild_native, probe_env,
)
from pytorch_distributed_tpu.agents.clocks import (
    ActorStats, EvaluatorStats, GlobalClock, LearnerStats,
)
from pytorch_distributed_tpu.agents.param_store import ParamStore

_CTX = mp.get_context("spawn")

# why a run ended early, when it was not the learner reaching its own end
# (Topology.stop_reason); a run stopped for one of these FAILED — main.py
# and the fleet CLI exit non-zero on them
FATAL_STOP_REASONS = ("worker-fatal", "inference-server-dead")

_SPAWN_ENV_LOCK = threading.Lock()


@contextlib.contextmanager
def cpu_child_env():
    """The environment a CPU worker is exec'd with: ``JAX_PLATFORMS=cpu``
    and no ``JAX_COMPILATION_CACHE_DIR``.  A spawn child unpickles its
    arguments — importing this package and jax — BEFORE its target
    runs, so a flip inside the child comes too late to be a guarantee:
    with libtpu on the host, a child that so much as brushes the TPU
    backend while the parent holds the chip fails or hangs.  Wrap
    ``Process.start()`` in this; the parent's own environment is
    restored on exit (jax read it at import, so the learner never
    notices the swap)."""
    with _SPAWN_ENV_LOCK:
        saved = {k: os.environ.get(k)
                 for k in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR")}
        os.environ["JAX_PLATFORMS"] = "cpu"
        # CPU-backend processes never use the persistent compile cache
        # (utils/helpers.enable_compile_cache says why)
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
        try:
            yield
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v


def _count_params(opt: Options, spec: EnvSpec) -> int:
    from pytorch_distributed_tpu.factory import build_model, init_params
    from pytorch_distributed_tpu.utils.helpers import tree_size

    model = build_model(opt, spec)
    return tree_size(init_params(opt, spec, model, seed=opt.seed))


def _child_main(role: str, agent_type: str, args: tuple) -> None:
    """Spawn trampoline: a CPU process (``cpu_child_env`` set that up
    before exec), which says so in the run's ``startup.jsonl`` and then
    dispatches to the worker function.

    Also the crash boundary for the flight recorder: an exception escaping
    the worker dumps this process's event rings to ``blackbox/`` BEFORE
    re-raising — the supervisor's restart must not erase the evidence."""
    from pytorch_distributed_tpu.utils import flight_recorder
    from pytorch_distributed_tpu.utils.helpers import record_startup

    opt = args[0]
    flight_recorder.configure(opt.log_dir, run_id=opt.refs)
    label = role
    if role in ("actor", "evaluator") and len(args) > 2:
        label = f"{role}-{args[2]}"
    rec = record_startup(opt.log_dir, label)
    if rec["platform"] != "cpu":
        raise RuntimeError(
            f"{label} initialised the {rec['platform']} backend; workers "
            f"are CPU processes (the learner owns the accelerator)")
    if role == "evaluator":
        # The evaluator's batch-1 greedy episodes are bursty CPU work
        # that matters only for reporting cadence; on an oversubscribed
        # host its bursts starved the learner (observed: the config-14
        # learner fell 2.2 -> 0.1 updates/s once eval episodes
        # lengthened, 2026-07-31).  Deprioritise it so the training
        # plane keeps the core — tunable because the flip side is a
        # starved evaluator on a 1-core host (AgentParams.evaluator_nice).
        nice = args[0].agent_params.evaluator_nice
        if nice:
            try:
                os.nice(nice)
            except OSError:  # pragma: no cover - restricted environments
                pass
    try:
        get_worker(role, agent_type)(*args)
    except BaseException as e:
        flight_recorder.get_recorder(label).record("crash", error=repr(e))
        flight_recorder.dump_all(f"{label} crashed: {e!r}")
        raise


class Topology:
    """Builds the shared plane and runs the worker topology for one
    Options."""

    def __init__(self, opt: Options, spec: Optional[EnvSpec] = None):
        self.opt = opt
        self.spec = spec if spec is not None else probe_env(opt)
        self.clock = GlobalClock()
        self.actor_stats = ActorStats()
        self.learner_stats = LearnerStats()
        self.evaluator_stats = EvaluatorStats()
        self.param_store = ParamStore(_count_params(opt, self.spec))
        self.handles = build_memory(opt, self.spec)
        # actor_backend=batched: the shared inference batcher lives HERE
        # — this process owns the accelerator (the learner runs in it),
        # so the SEED-style wide actor forward shares the device with
        # the learner's dispatches instead of burning actor-host CPUs
        # (agents/inference.py)
        self.inference_server = None
        if needs_inference_server(opt):
            from pytorch_distributed_tpu.agents.inference import (
                InferenceServer,
            )

            self.inference_server = InferenceServer(
                opt, self.spec, self.param_store)
        self._workers: List[Any] = []
        # populated by the process-backend monitor; the health plane
        # (fleet.py STATUS provider) reads per-slot budget remaining
        self._restart_budget = None
        # set when a SIGTERM (preemption notice) ended the run rather
        # than the step budget — observable by callers/tests
        self.preempted = threading.Event()
        # why the monitor stopped the run (one of FATAL_STOP_REASONS),
        # None while/when the learner ran to its own end
        self.stop_reason: Optional[str] = None
        # ---- hang watchdog (health sentinel): every supervised role
        # publishes liveness-progress marks on a shared board riding the
        # clock's spawn pickle; the monitor SIGKILLs workers whose marks
        # go stale past hang_deadline (utils/supervision.ProgressBoard).
        from pytorch_distributed_tpu.utils import flow, health, perf
        from pytorch_distributed_tpu.utils.supervision import ProgressBoard

        self.health = health.resolve(opt.health_params)
        # flow-control plane (ISSUE 11): resolved once and exported to
        # the environment so spawn children (actor feeders building
        # their shed rings, the device-ingest pending bound) resolve
        # the same policy the topology was configured with
        self.flow = flow.resolve_flow(opt.flow_params)
        flow.export_env(self.flow)
        # perf plane knobs resolved once for the topology; exported to
        # the environment so spawn children (and tools THEY fork)
        # resolve the same plane even when it was enabled
        # programmatically rather than by TPU_APEX_PERF=1
        self.perf = perf.resolve(opt.perf_params)
        if self.perf.enabled:
            perf.export_env(self.perf)
        # replica plane (ISSUE 15): resolved once + exported on the same
        # spawn-inheritance contract.  The ReplicaRegistry itself rides
        # the fleet DCN gateway (fleet.FleetTopology builds it); a plain
        # Topology with replicas > 1 has no registry and the learner
        # downgrades loudly to solo (agents/learner.py delegation gate).
        from pytorch_distributed_tpu.parallel.dcn import (
            export_gateway_env, export_replica_env, resolve_gateway,
            resolve_replica,
        )

        self.replica = resolve_replica(opt.replica_params)
        if self.replica.replicas > 1:
            export_replica_env(self.replica)
        self.replica_registry = None
        # gateway HA plane (ISSUE 16): same resolve-once + export
        # contract — spawn children (remote actor mains, the standby
        # runner) must dial the same endpoint list and lease windows
        # the topology was configured with.  Off by default: a plain
        # fleet never journals, never syncs, stays byte-compatible.
        self.gateway_ha = resolve_gateway(opt.gateway_params)
        if self.gateway_ha.enabled:
            export_gateway_env(self.gateway_ha)
        # ---- mission control (ISSUE 10): fleet metrics aggregation +
        # SLO/alert engine + opt-in OpenMetrics endpoint.  Built here
        # (unstarted) so the fleet gateway's T_METRICS sink has a
        # target from construction; run() starts/stops the poll thread.
        from pytorch_distributed_tpu.utils import telemetry

        self.metrics_params = telemetry.resolve_metrics(opt.metrics_params)
        self.mission = None
        if self.metrics_params.enabled:
            self.mission = telemetry.MissionControl(
                opt.log_dir, self.metrics_params, opt.alert_params)
        # anakin topology (ISSUE 12): NO actor workers exist — the env
        # fleet lives in the learner process, so the watchdog board
        # carries no actor slots and _worker_specs spawns none
        self.anakin = anakin_active(opt)
        labels = ["learner", "evaluator-0"] + [
            f"actor-{i}"
            for i in range(0 if self.anakin else opt.num_actors)]
        self.progress_board = ProgressBoard(labels)
        self.clock.progress = self.progress_board
        self.hang_kills = 0  # watchdog SIGKILLs (health plane counter)

    # -- worker table (reference main.py:58-106 spawn loops) ----------------

    def _worker_specs(self):
        opt, spec = self.opt, self.spec
        specs = [("logger", 0, (opt, self.clock, self.actor_stats,
                                self.learner_stats, self.evaluator_stats))]
        for i in range(0 if self.anakin else opt.num_actors):
            # per-actor feeder clone: thread workers must not share one
            # chunk buffer (process children get their own pickled copy)
            side = self.handles.actor_side
            if hasattr(side, "clone"):
                side = side.clone()
            client = (self.inference_server.make_client(i)
                      if self.inference_server is not None else None)
            specs.append(("actor", i, (
                opt, spec, i, side, self.param_store,
                self.clock, self.actor_stats, client)))
        if opt.agent_params.evaluator_nepisodes > 0:
            specs.append(("evaluator", 0, (
                opt, spec, 0, None, self.param_store, self.clock,
                self.evaluator_stats)))
        else:
            # no evaluator (time-boxed benches): mark its handshake done so
            # the logger's end-of-run drain doesn't wait the 60 s grace
            self.evaluator_stats.done.value = 1
        return specs

    # -- run ---------------------------------------------------------------

    def run(self, backend: str = "process") -> None:
        """Mode-1 training (reference main.py:34-106): start workers, run
        the learner here, supervise, join.

        SIGTERM is treated as a PREEMPTION NOTICE (what a TPU/VM
        scheduler sends before reclaiming the host, Podracer-style): trip
        the stop event so every loop drains, let the learner write its
        final checkpoint epoch (agents/learner.py end-of-loop
        ``_save_epoch``), join, and exit cleanly — the next ``--resume``
        run continues from that epoch.  Installed only when this is the
        process's main thread (signal API constraint); thread-backend
        test harnesses driving run() from a worker thread keep their
        default handling."""
        assert backend in ("process", "thread")
        opt = self.opt
        prebuild_native(opt)  # once, before N workers race the same g++
        from pytorch_distributed_tpu.utils import flight_recorder

        # the run's blackbox home; exported so spawn children inherit it
        # without plumbing (same trick the fault schedules use)
        flight_recorder.configure(opt.log_dir, export_env=True,
                                  run_id=opt.refs)
        prev_term = None
        run_over = threading.Event()
        if threading.current_thread() is threading.main_thread():
            def _on_sigterm(signum, frame):
                # handler touches ONLY self.preempted (a threading.Event
                # whose lock no other thread's hot path takes — its
                # is_set is a lockless flag read).  Promoting to the
                # shared mp stop event happens on the watcher thread
                # below, never here: mp.Event's internal lock is not
                # reentrant and the interrupted main thread — the learner
                # — polls clock.stop constantly, so a set() from the
                # handler could deadlock against the very loop it is
                # trying to stop.
                self.preempted.set()

            installed = False
            try:
                prev_term = signal.signal(signal.SIGTERM, _on_sigterm)
                installed = True
            except (ValueError, OSError):  # pragma: no cover - exotic host
                prev_term = None
            if installed:
                def _promote_preemption():
                    while not run_over.is_set():
                        if self.preempted.wait(0.2):
                            print("[runtime] SIGTERM: preemption notice "
                                  "— draining for a final checkpoint "
                                  "epoch", flush=True)
                            flight_recorder.get_recorder("runtime").record(
                                "sigterm-preemption")
                            flight_recorder.dump_all(
                                "SIGTERM preemption notice")
                            self.clock.stop.set()
                            return

                threading.Thread(target=_promote_preemption,
                                 name="preempt-watch",
                                 daemon=True).start()
        if backend == "thread":
            self._use_thread_queue()
        if backend == "process":
            self._proc_meta = []
            for role, ind, args in self._worker_specs():
                self._spawn(role, ind, args)
            monitor = threading.Thread(target=self._monitor, daemon=True)
            monitor.start()
        else:
            for role, ind, args in self._worker_specs():
                t = threading.Thread(
                    target=get_worker(role, opt.agent_type), args=args,
                    name=f"{role}-{ind}", daemon=True)
                t.start()
                self._workers.append(t)

        if self.inference_server is not None:
            # after _worker_specs wired the clients, before anyone acts
            self.inference_server.start()
        if self.mission is not None:
            # after the blackbox home is configured (alert transitions
            # record into this process's rings), before the learner
            # starts producing the rows it will aggregate
            self.mission.start()
        try:
            self.progress_board.note_start("learner")
            if self.anakin:
                # the co-located Anakin loop: this process hosts the
                # env fleet AND the learner; pass the shared ActorStats
                # so the logger's rollout curves keep flowing without
                # any actor worker existing
                from pytorch_distributed_tpu.agents.anakin import (
                    run_anakin_learner,
                )

                run_anakin_learner(
                    opt, self.spec, 0, self.handles.learner_side,
                    self.param_store, self.clock, self.learner_stats,
                    actor_stats=self.actor_stats)
            else:
                run_learner = get_worker("learner", opt.agent_type)
                run_learner(opt, self.spec, 0, self.handles.learner_side,
                            self.param_store, self.clock,
                            self.learner_stats)
        finally:
            # learner done (or dead): release every spinning loop
            self.clock.stop.set()
            run_over.set()  # parks the preemption watcher
            if prev_term is not None:
                signal.signal(signal.SIGTERM, prev_term)
            self._join_all()
            if self.inference_server is not None:
                # after the join: an actor draining its last tick may
                # still be blocked in collect()
                self.inference_server.stop()
            if self.mission is not None:
                # final tail drain + alert pass, then the writer closes;
                # before _pre_close so a last T_METRICS push racing the
                # gateway teardown still finds a live sink
                self.mission.stop()
            # transports feeding learner_side must shut before its queue
            # closes (FleetTopology stops its DCN gateway here)
            self._pre_close()
            if hasattr(self.handles.learner_side, "close"):
                self.handles.learner_side.close()

    def _pre_close(self) -> None:
        """Hook: extra transports to tear down before learner_side closes."""

    def _use_thread_queue(self) -> None:
        """In-process workers don't need the spawn-context queue: mp.Queue
        pickles every chunk (a uint8 Atari transition is ~56 KB, so a
        16-chunk put copies ~1 MB through a pipe), while queue.Queue hands
        over references.  Swap the shared queue before any worker starts;
        feeder clones made in _worker_specs pick the new queue up."""
        import queue as _q

        ls, as_ = self.handles.learner_side, self.handles.actor_side
        if hasattr(ls, "_q") and hasattr(as_, "_q") and ls._q is as_._q:
            # keep the mp queue's chunk bound: backpressure must still
            # stall producers when the learner falls behind, or drains
            # balloon into multi-GB backlog copies
            tq = _q.Queue(getattr(ls, "max_queue_chunks", 4096))
            ls._q = tq
            as_._q = tq

    def _stop_run(self, reason: str) -> None:
        """The monitor's fail-fast exit: record WHY before tripping the
        stop event, so the caller can tell a stopped run from a finished
        one (``Topology.run`` itself returns normally either way)."""
        self.stop_reason = reason
        self.clock.stop.set()

    def _spawn(self, role: str, ind: int, args: tuple) -> None:
        p = _CTX.Process(
            target=_child_main, args=(role, self.opt.agent_type, args),
            name=f"{role}-{ind}", daemon=True)
        with cpu_child_env():
            p.start()
        # restart the slot's watchdog grace window with the incarnation
        self.progress_board.note_start(f"{role}-{ind}")
        self._workers.append(p)
        self._proc_meta.append((p, role, ind, args))

    def _monitor(self, poll: float = 0.5, max_restarts: int = 3) -> None:
        """Failure detection + elastic recovery — both absent in the
        reference, where a dead actor silently reduces throughput and a
        dead learner hangs every loop (SURVEY.md §5).  A crashed ACTOR is
        restarted in place (Ape-X tolerates actor churn; its replay
        contribution just pauses), up to ``max_restarts`` per slot; any
        other abnormal child death — or an actor out of restart budget —
        trips the stop event so the run fails fast instead of degrading
        silently.  Restart/GRACE policy shared with the fleet actor-host
        supervisor via utils/supervision.RestartBudget."""
        from pytorch_distributed_tpu.utils import flight_recorder
        from pytorch_distributed_tpu.utils.supervision import (
            EXIT_HUNG, RestartBudget, describe_exit,
        )

        recorder = flight_recorder.get_recorder("runtime")
        budget = RestartBudget(max_restarts=max_restarts)
        # exposed for the health plane: the fleet gateway's STATUS verb
        # reports per-slot restart budget remaining from here
        self._restart_budget = budget
        for _p, role, ind, _args in self._proc_meta:
            # record first incarnations: the grace-period budget reset
            # only applies to slots with a KNOWN long-lived incarnation
            # (RestartBudget.request_restart no longer treats unborn
            # slots as ancient ones)
            if role == "actor":
                budget.note_birth(ind)
        while not self.clock.stop.is_set():
            srv = self.inference_server
            if srv is not None and not srv.healthy():
                # a dead inference server starves every batched actor;
                # fail the run NOW instead of letting supervised actor
                # restarts each block a full collect timeout against a
                # thread that will never answer
                print("[runtime] inference server died; stopping run")
                recorder.record("inference-server-dead")
                flight_recorder.dump_all(
                    "inference server died; run stopped")
                self._stop_run("inference-server-dead")
                return
            for i, (p, role, ind, args) in enumerate(list(self._proc_meta)):
                if p.exitcode in (None, 0):
                    continue
                if role == "actor" \
                        and budget.request_restart(ind) is not None:
                    budget.note_birth(ind)
                    print(f"[runtime] actor-{ind} died "
                          f"({describe_exit(p.exitcode)}); restart "
                          f"{budget.count(ind)}/{max_restarts}")
                    recorder.record("worker-restarted", role=role,
                                    slot=ind, exit=p.exitcode,
                                    restarts=budget.count(ind))
                    self._workers.remove(p)
                    self._proc_meta.remove((p, role, ind, args))
                    self._spawn(role, ind, args)
                else:
                    print(f"[runtime] {role}-{ind} died "
                          f"({describe_exit(p.exitcode)}); stopping run")
                    recorder.record("worker-fatal", role=role, slot=ind,
                                    exit=p.exitcode)
                    flight_recorder.dump_all(
                        f"{role}-{ind} died "
                        f"({describe_exit(p.exitcode)}); run stopped")
                    self._stop_run("worker-fatal")
                    return
            # ---- hang watchdog: an alive-but-stuck worker never
            # produces an exit code, so liveness is read off the
            # progress board instead.  Hung children are SIGKILLed
            # (flight recorder dumped first — the kill erases nothing)
            # and actors respawn through the SAME RestartBudget as a
            # crash, classified EXIT_HUNG.  Opt-in: hang_deadline=0 (the
            # default) disables the pass entirely.
            hd = self.health.hang_deadline
            if hd and hd > 0:
                hung = set(self.progress_board.hung(
                    hd, self.health.hang_grace))
                for p, role, ind, args in list(self._proc_meta):
                    label = f"{role}-{ind}"
                    if label not in hung or p.exitcode is not None:
                        continue
                    self.hang_kills += 1
                    recorder.record("worker-hung", role=role, slot=ind,
                                    age=round(self.progress_board.age(
                                        label), 1))
                    flight_recorder.dump_all(
                        f"{label} hung (> {hd:g}s without progress); "
                        f"watchdog SIGKILL")
                    p.kill()
                    p.join(5.0)
                    self._workers.remove(p)
                    self._proc_meta.remove((p, role, ind, args))
                    if role == "actor" \
                            and budget.request_restart(ind) is not None:
                        budget.note_birth(ind)
                        print(f"[runtime] {label} "
                              f"({describe_exit(EXIT_HUNG)}); restart "
                              f"{budget.count(ind)}/{max_restarts}")
                        recorder.record("worker-restarted", role=role,
                                        slot=ind, exit=EXIT_HUNG,
                                        restarts=budget.count(ind))
                        self._spawn(role, ind, args)
                    else:
                        print(f"[runtime] {label} "
                              f"({describe_exit(EXIT_HUNG)}); "
                              f"stopping run")
                        recorder.record("worker-fatal", role=role,
                                        slot=ind, exit=EXIT_HUNG)
                        self._stop_run("worker-fatal")
                        return
                if "learner" in hung:
                    # the learner runs on THIS process's main thread: a
                    # SIGKILL from here kills the whole host, which is
                    # exactly right — a stuck learner stalls every loop
                    # and only an outer orchestrator (--resume) can
                    # bring the run back.  Dump first; exit EXIT_HUNG.
                    recorder.record("learner-hung")
                    flight_recorder.dump_all(
                        f"learner hung (> {hd:g}s without progress); "
                        f"failing host fast")
                    print(f"[runtime] learner "
                          f"({describe_exit(EXIT_HUNG)}); exiting for "
                          f"the outer orchestrator", flush=True)
                    self.clock.stop.set()
                    os._exit(EXIT_HUNG)
            time.sleep(poll)

    def _join_all(self, timeout: float = 240.0) -> None:
        # generous: the evaluator's final eval (jit + greedy episodes) can
        # take minutes on a saturated host, and a thread-backend worker
        # abandoned at interpreter exit aborts the process from C++
        # teardown — waiting is the safe side
        t0 = time.monotonic()
        deadline = t0 + timeout
        hd = self.health.hang_deadline
        if hd and hd > 0:
            # watchdog-enabled shutdown: a worker whose progress mark is
            # already stale cannot drain anything — a hang that landed
            # AFTER the monitor exited (stop set) would otherwise pin
            # this join for the full timeout.  Poll-join and terminate
            # hung stragglers as their marks go stale.
            while time.monotonic() < deadline:
                alive = [w for w in self._workers if w.is_alive()]
                if not alive:
                    break
                hung = set(self.progress_board.hung(
                    hd, self.health.hang_grace))
                for w in alive:
                    if isinstance(w, _CTX.Process) and w.name in hung:
                        print(f"[runtime] {w.name} hung at shutdown; "
                              f"terminating")
                        w.terminate()
                time.sleep(0.25)
        for w in self._workers:
            w.join(max(0.1, deadline - time.monotonic()))
        if time.monotonic() - t0 > 30.0:
            slow = [w.name for w in self._workers
                    if (w.is_alive() if hasattr(w, "is_alive") else False)]
            print(f"[runtime] join took {time.monotonic() - t0:.0f}s; "
                  f"still alive: {slow or 'none'}")
        for w in self._workers:
            if isinstance(w, _CTX.Process) and w.is_alive():
                w.terminate()
                w.join(5.0)


def train(opt: Options, backend: str = "process") -> Topology:
    topo = Topology(opt)
    topo.run(backend=backend)
    return topo


def test(opt: Options) -> Dict[str, float]:
    """Mode-2 (reference main.py:107-115): run the tester inline."""
    from pytorch_distributed_tpu.agents.tester import run_tester

    return run_tester(opt, probe_env(opt))
