"""Multi-host fleet runner: one learner host + N actor hosts over DCN.

The reference runs everything on one machine through
``torch.multiprocessing`` (reference main.py:13,58-106); its topology ends
at the box.  A TPU deployment splits naturally: the host attached to the
mesh runs the learner (plus evaluator/logger and optionally some local
actors), and any number of CPU-only hosts run actor fan-out, connected by
the DCN wire protocol (parallel/dcn.py).  Fleet-wide semantics match the
single-host run:

- ``opt.num_actors`` is the TOTAL actor count across hosts — the Ape-X
  exploration schedule (reference dqn_actor.py:33-36) spans the fleet, each
  actor taking its global ``process_ind`` slot;
- the global learner clock terminates every loop on every host (reference
  dqn_actor.py:62), carried by gateway replies;
- stats aggregate into the learner host's accumulators, so the logger and
  TensorBoard streams look identical to a single-host run.

Roles (one per invocation, mirroring how NCCL/MPI launchers assign ranks):

    python -m pytorch_distributed_tpu.fleet --role learner \
        --config 4 --port 5555 --local-actors 2
    python -m pytorch_distributed_tpu.fleet --role actors \
        --config 4 --coordinator learnerhost:5555 \
        --actor-base 2 --actor-count 6

For TPU pods where multiple hosts each own chips (v4-32+), set
``parallel_params.multihost`` so the learner program itself spans hosts via
``jax.distributed`` (parallel/mesh.py init_multihost); the fleet layer here
is about scaling the *actor* side and is orthogonal.

Failure model (details in parallel/dcn.py; drills in tests/test_chaos.py,
randomized soak in tools/chaos_soak.py):

- **Survives**: a gateway/learner-host blip or restart (actors redial
  with backoff, re-claim their slots via incarnation fencing, and resend
  their one unacked experience chunk — at-least-once delivery); an actor
  crash (its slot frees on disconnect, the replacement re-claims it,
  paid from the slot's RestartBudget); a partition that heals within
  ``DCN_RECONNECT_TIMEOUT``; a half-open predecessor connection left by
  any of the above (fenced off by the reconnector's higher incarnation).
- **Lost**: stats ticks in flight when a session dies (bounded by the
  flush cadence; actor-step counts are re-queued client-side), plus the
  possibility of a duplicated chunk when an EXP ack was lost.  Tick
  retransmits are seq-deduplicated at the gateway, so step counts and
  stats do not double-count across blips (residual window: an ack lost
  across a gateway restart, which forgets the dedup map).
- **Terminal**: a partition outliving the reconnect budget, or a slot
  genuinely held by a live duplicate — the actor exits
  ``EXIT_DISCONNECTED`` (never a fake "run complete"), the supervisor
  here spends its RestartBudget, and a slot out of budget fails the host
  fast with a nonzero exit for the outer orchestrator.

Fault injection for drills rides env vars (``DCN_FAULTS_CLIENT`` /
``DCN_FAULTS_GATEWAY``, spawn children inherit them) or the
``--faults-client`` / ``--faults-gateway`` CLI knobs below; see
utils/faults.py for the spec grammar.
"""

from __future__ import annotations

import argparse
import multiprocessing as mp
import os
import signal
import sys
import threading
import time
from typing import List, Optional

from pytorch_distributed_tpu.config import Options, build_options
from pytorch_distributed_tpu.runtime import (
    FATAL_STOP_REASONS, Topology, cpu_child_env,
)

_CTX = mp.get_context("spawn")


class FleetTopology(Topology):
    """Learner-host topology: the usual local workers (minus remote actor
    slots) plus a DcnGateway bridging remote hosts into the shared plane."""

    def __init__(self, opt: Options, local_actors: int = 0, port: int = 0,
                 spec=None):
        super().__init__(opt, spec=spec)
        self.local_actors = min(local_actors, opt.num_actors)
        # learner-step-rate sampling state for the health snapshot: STATUS
        # requests land on concurrent gateway serve threads
        self._rate_lock = threading.Lock()
        self._rate_prev = None  # (monotonic, learner_step) of last probe
        # the schedule local actor slots actually run, post-downgrade
        # (resolve may warn about a downgrade — once, here, not per
        # STATUS probe)
        from pytorch_distributed_tpu.factory import resolve_actor_backend

        self._actor_backend = resolve_actor_backend(
            opt, self.inference_server)
        # elastic multi-learner plane (ISSUE 15): the lease-fenced
        # membership registry + round coordinator rides THIS gateway;
        # the lead learner (replica 0, this process) joins through the
        # module-local handle instead of dialling loopback
        if self.replica.replicas > 1:
            from pytorch_distributed_tpu.parallel.dcn import (
                ReplicaRegistry, set_local_registry,
            )

            self.replica_registry = ReplicaRegistry(
                self.replica,
                writer=(self.mission._writer
                        if self.mission is not None else None))
            set_local_registry(self.replica_registry)
        self.gateway = self._make_gateway(port)
        self.port = self.gateway.port
        if self.perf.enabled:
            # warm the profiler's one-time session init NOW, while the
            # learner is still compiling (GIL mostly released), so the
            # first T_PROFILE answers at window speed — cold, it can
            # take a minute+ on a saturated small host (utils/perf.
            # prewarm_profiler has the measurement)
            from pytorch_distributed_tpu.utils import perf

            perf.prewarm_profiler()

    def _make_gateway(self, port: int):
        """Single construction point, shared with restart_gateway — a
        post-restart gateway must be configured identically to the
        original or recovery behaviour silently diverges mid-run."""
        from pytorch_distributed_tpu.parallel.dcn import (
            DcnGateway, feed_queue_of,
        )

        return DcnGateway(
            self.param_store, self.clock, self.actor_stats,
            put_chunk=feed_queue_of(self.handles), port=port,
            local_actors=self.local_actors,
            health=self._health_snapshot,
            profiler=self._profile_request,
            metrics_sink=self._metrics_sink,
            flow_params=self.flow,
            pressure=self._flow_pressure,
            # overload transitions land in the run's scalar stream so
            # the DEFAULT_RULES ``overload_shed`` alert (and the
            # incident timeline) can see them; mission-off runs keep
            # the flight-recorder leg only
            flow_writer=(self.mission._writer
                         if self.mission is not None else None),
            replicas=self.replica_registry,
            # gateway HA plane (ISSUE 16): resolved by Topology.__init__
            # (and exported to spawn children); with the plane off the
            # extra kwargs are inert and the gateway is byte-identical
            gateway_params=self.gateway_ha,
            log_dir=(self.opt.log_dir if self.gateway_ha.enabled
                     else None),
            ha_writer=(self.mission._writer
                       if self.mission is not None else None))

    def _flow_pressure(self) -> float:
        """The overload governor's input signal: ingest-queue
        utilization of the learner-side memory (the exact backlog a
        slow learner grows), 0.0 when the queue is unreadable —
        unknown pressure must read healthy, never shedding."""
        ls = self.handles.learner_side
        q = getattr(ls, "_q", None)
        bound = int(getattr(ls, "max_queue_chunks", 0) or 0)
        if q is None or bound <= 0 or not hasattr(q, "qsize"):
            return 0.0
        try:
            return min(1.0, q.qsize() / bound)
        except (NotImplementedError, OSError):
            return 0.0  # macOS mp queues have no qsize

    def _metrics_sink(self, payload: dict) -> int:
        """T_METRICS provider: remote hosts' scalar batches land in the
        mission-control aggregator (utils/telemetry.py).  Plane
        disabled -> absorb nothing (the gateway replies accepted:0; the
        pusher side only runs when ITS plane is enabled, so this is the
        mixed-config case, not the steady state)."""
        if self.mission is None:
            return 0
        return self.mission.ingest_remote(payload)

    def _profile_request(self, msg: dict) -> dict:
        """T_PROFILE provider (parallel/dcn.py): a bounded
        ``utils/profiling.trace`` window captured from THIS process —
        the learner host parent, which owns the accelerator, so the
        trace shows the real XLA activity of the running learner (and
        the co-located inference server / gateway threads).  Other
        roles run in other processes (often other hosts) with no
        profiler listener; asking for them is a clean error, not a
        silently-wrong trace of the wrong process."""
        from pytorch_distributed_tpu.utils import perf

        role = str(msg.get("role", "learner"))
        if role != "learner":
            return {"error": f"role {role!r} not profilable over "
                             f"T_PROFILE: only the learner host process "
                             f"(the accelerator owner) captures XLA "
                             f"traces"}
        label = msg.get("label") or time.strftime("tprofile_%H%M%S")
        return perf.run_profile_window(
            os.path.join(self.opt.log_dir, "profiles"),
            label=str(label), seconds=msg.get("seconds", 3.0),
            max_seconds=self.perf.profile_window_max)

    def _health_snapshot(self) -> dict:
        """Topology-level fields for the gateway's STATUS verb: the parts
        of the health plane only the learner-host wiring can see.  Reads
        are best-effort snapshots of live structures (sizes, counters) —
        racing the learner by one step is fine, blocking it is not."""
        h: dict = {"run_id": self.opt.refs}
        ls = self.handles.learner_side
        try:  # size/capacity are properties; a device ring raises
            size = int(ls.size)  # pre-attach — skip, don't crash STATUS
            h["replay_size"] = size
            cap = int(getattr(ls, "capacity", 0))
            if cap:
                h["replay_capacity"] = cap
                h["replay_fill"] = round(size / cap, 4)
        except Exception:  # noqa: BLE001
            pass
        q = getattr(ls, "_q", None)
        if q is not None and hasattr(q, "qsize"):
            try:
                h["ingest_queue_depth"] = int(q.qsize())
                h["ingest_queue_bound"] = int(
                    getattr(ls, "max_queue_chunks", 0))
            except (NotImplementedError, OSError):
                pass  # macOS mp queues have no qsize
        now = time.monotonic()
        step = int(self.clock.learner_step.value)
        astep = int(self.clock.actor_step.value)
        # per-LOCAL-actor vector-tick marks off the watchdog's progress
        # board (each actor bumps once per tick / per fused dispatch's
        # K ticks), so the panel can attribute the fleet rate to slots
        n_envs = max(1, self.opt.env_params.num_envs_per_actor)
        marks = {i: self.progress_board.marks(f"actor-{i}")
                 for i in range(self.local_actors)}
        with self._rate_lock:
            prev = self._rate_prev
            # advance the window anchor only after it has real width:
            # concurrent probers (a fleet_top refresh loop + a CI probe)
            # would otherwise shrink each other's windows to a few ms,
            # quantizing the rate into 0-or-thousands flapping
            if prev is None or now - prev[0] >= 0.5:
                self._rate_prev = (now, step, astep, marks)
        if prev is not None and now > prev[0]:
            h["learner_steps_per_sec"] = round(
                (step - prev[1]) / (now - prev[0]), 3)
            # the fleet-wide env-frames rate off the same window: the
            # shared actor clock sums every host's ticks, so this is
            # the live Ape-X actor/learner balance read (per-process
            # actor/env_frames_per_s rows live in each actor's metrics
            # stream; remote processes can't reach this registry)
            h["actor_frames_per_sec"] = round(
                (astep - prev[2]) / (now - prev[0]), 3)
            prev_marks = prev[3] if len(prev) > 3 else {}
            if marks:
                # ISSUE-7 satellite: per-actor env frames/s + the
                # schedule each slot actually runs (post-downgrade),
                # rendered by fleet_top's perf panel.  A respawned
                # slot's marks reset (note_start) — clamp at 0 rather
                # than report a negative rate for that window.
                h["actors"] = {
                    str(i): {
                        "env_frames_per_sec": round(max(
                            0.0, (m - prev_marks.get(i, 0)) * n_envs
                            / (now - prev[0])), 1),
                        "backend": self._actor_backend,
                    } for i, m in marks.items()}
        # health-sentinel counters (utils/health.py): learner-side guard
        # skips and rollbacks ride the shared clock; quarantine counts
        # come from this process's registry (the learner-side ingest
        # boundaries — the gateway's own per-slot counts are already in
        # the base snapshot); hang kills from the runtime watchdog
        from pytorch_distributed_tpu.utils import health

        h["health_sentinel"] = {
            "skipped_steps": int(self.clock.skipped_steps.value),
            "rollbacks": int(self.clock.rollbacks.value),
            "hang_kills": int(self.hang_kills),
            # gateway-* sources are excluded: the gateway's own per-slot
            # dict (base snapshot "quarantined") already carries them
            "quarantined_local": {
                s: n for s, n in health.quarantine_counts().items()
                if not s.startswith("gateway-")},
        }
        budget = self._restart_budget
        if budget is not None:
            # scope is honest in the name: the runtime monitor only
            # supervises the learner host's LOCAL actor slots
            # (ind < local_actors); remote slots are supervised by their
            # own actor host's RestartBudget, which never reaches here
            h["local_restart_budget_remaining"] = {
                str(s): r for s, r in budget.remaining().items()}
        # perf plane (utils/perf.py, TPU_APEX_PERF=1): last-drained
        # MFU/rate/watermark values of every monitor in THIS process
        # (learner, thread-backend local actors, inference server) —
        # fleet_top's live perf read
        from pytorch_distributed_tpu.utils import perf

        psnap = perf.status_snapshot()
        if psnap:
            h["perf"] = psnap
        # anakin panel block (ISSUE 12): the co-located loop's vitals —
        # duty cycle / rollout rate off the learner monitor's gauges
        # (present when the perf plane is on), ring fill off the host
        # accounting either way; fleet_top renders it and the ``--json``
        # consumers read it verbatim
        if getattr(self, "anakin", False):
            snap = (psnap or {}).get("learner", {})
            h["anakin"] = {
                "backend": "anakin",
                "duty_cycle": snap.get("anakin/duty_cycle"),
                "rollout_frames_per_s":
                    snap.get("anakin/rollout_frames_per_s"),
                "replay_fill": snap.get("anakin/replay_fill",
                                        h.get("replay_fill")),
                "mfu": snap.get("learner/mfu"),
            }
        # mission control (ISSUE 10): per-rule alert states + recent
        # fleet series — fleet_top's alert panel/sparklines and the
        # ``--json`` blocks CI asserts on come from HERE, not from the
        # probe re-tailing metrics files itself
        if self.mission is not None:
            h.update(self.mission.status_block())
        return h

    def _worker_specs(self):
        # local actor slots are [0, local_actors); remote hosts take the
        # higher process_inds (flatter Ape-X epsilons, the exploratory end)
        specs = [s for s in super()._worker_specs()
                 if s[0] != "actor" or s[1] < self.local_actors]
        return specs

    def _pre_close(self) -> None:
        # stop accepting/serving before the learner-side queue closes:
        # an in-flight EXP put on a closed queue would kill a serve thread
        self.gateway.close()
        if self.replica_registry is not None:
            # drop the module-local handle: a LATER topology in this
            # process (test suites, embedders) must not silently wire
            # its lead learner to this closed run's registry
            from pytorch_distributed_tpu.parallel.dcn import (
                local_registry, set_local_registry,
            )

            if local_registry() is self.replica_registry:
                set_local_registry(None)

    def restart_gateway(self) -> None:
        """Tear the gateway down and rebind on the same port — the
        recovery drill for a learner-host network blip (and the chaos
        harness's kill-gateway lever).  Remote actors ride through it:
        their clients redial, re-HELLO with bumped incarnations, and
        resend their unacked chunks (parallel/dcn.py failure model)."""
        port = self.gateway.port
        self.gateway.close()
        self.gateway = self._make_gateway(port)

    def run(self, backend: str = "process") -> None:
        try:
            super().run(backend=backend)
        finally:
            self.gateway.close()  # idempotent; covers pre-run failures


def run_fleet_learner(opt: Options, local_actors: int = 0, port: int = 5555,
                      backend: str = "process") -> FleetTopology:
    topo = FleetTopology(opt, local_actors=local_actors, port=port)
    print(f"[fleet] learner host up: gateway on port {topo.port}, "
          f"{topo.local_actors}/{opt.num_actors} actors local")
    topo.run(backend=backend)
    return topo


# ---------------------------------------------------------------------------
# replica learner host (ISSUE 15)
# ---------------------------------------------------------------------------

def run_replica_host(opt: Options, coordinator: str,
                     replica_id: int) -> None:
    """One remote learner replica: dials the lead gateway's replica
    plane (lease + generation-stamped rounds) and trains the shared
    model data-parallel (agents/learner.py run_replica_learner).  Exit
    codes mirror the actor host contract: run complete exits 0; a
    terminal fence whose rejoin failed exits EXIT_DISCONNECTED so an
    outer supervisor can respawn the replica — which will re-lease at a
    new generation and sync from the join-barrier epoch."""
    from pytorch_distributed_tpu.factory import probe_env
    from pytorch_distributed_tpu.agents.clocks import (
        GlobalClock, LearnerStats,
    )
    from pytorch_distributed_tpu.agents.learner import run_replica_learner
    from pytorch_distributed_tpu.agents.param_store import ParamStore
    from pytorch_distributed_tpu.parallel.dcn import ReplicaFenced
    from pytorch_distributed_tpu.utils import flight_recorder
    from pytorch_distributed_tpu.utils.helpers import tree_size
    from pytorch_distributed_tpu.utils.supervision import EXIT_DISCONNECTED

    opt.replica_params.coordinator = coordinator
    flight_recorder.configure(opt.log_dir, run_id=opt.refs)
    spec = probe_env(opt)
    from pytorch_distributed_tpu.factory import build_model, init_params

    store = ParamStore(tree_size(init_params(
        opt, spec, build_model(opt, spec), seed=opt.seed)))
    clock = GlobalClock()
    # SIGTERM = preemption notice, same contract as every other host
    # (runtime.py / run_fleet_actors): drain the round loop, publish +
    # commit, release the lease, exit 0 — the next incarnation rejoins
    # through the epoch barrier
    if threading.current_thread() is threading.main_thread():
        try:
            signal.signal(signal.SIGTERM,
                          lambda s, f: clock.stop.set())
        except (ValueError, OSError):  # pragma: no cover
            pass
    print(f"[fleet] replica host up: replica {replica_id} -> "
          f"{coordinator}")
    try:
        run_replica_learner(opt, spec, replica_id, None, store,
                            clock, LearnerStats(),
                            replica_id=replica_id)
    except (ReplicaFenced, ConnectionError, OSError) as e:
        print(f"[fleet] replica-{replica_id} lost its lease/session "
              f"({e}); exiting {EXIT_DISCONNECTED} for the supervisor")
        flight_recorder.dump_all(
            f"replica-{replica_id} fenced/disconnected")
        sys.exit(EXIT_DISCONNECTED)


# ---------------------------------------------------------------------------
# gateway standby host (ISSUE 16)
# ---------------------------------------------------------------------------

def run_gateway_standby(opt: Options, coordinator: str,
                        port: int = 0) -> None:
    """``--role gateway-standby``: a warm standby gateway for the HA
    plane (parallel/dcn.py, GatewayParams).  It pulls the primary's
    journaled control plane over sessionless T_SYNC, refuses session
    verbs (counted) until the primary's lease expires, then PROMOTES:
    CAS-bumps the term on the SHARED ``{log_dir}/gateway/`` dir — the
    same shared-storage requirement checkpoint resume already has — and
    starts serving, fencing any resurrected predecessor.

    The standby hosts its own param store/clock/stats and spools
    promoted-era experience into a bounded drop-oldest buffer (counted)
    — control-plane continuity that keeps actors alive and accounted
    while an orchestrator restarts a full learner host against the
    checkpoint store; it does not itself train.  SIGTERM drains and
    exits 0 like every other host role."""
    import collections

    from pytorch_distributed_tpu.factory import (
        build_model, init_params, probe_env,
    )
    from pytorch_distributed_tpu.agents.clocks import (
        ActorStats, GlobalClock,
    )
    from pytorch_distributed_tpu.agents.param_store import ParamStore
    from pytorch_distributed_tpu.parallel.dcn import (
        DcnGateway, parse_endpoints, resolve_gateway,
    )
    from pytorch_distributed_tpu.utils import flight_recorder
    from pytorch_distributed_tpu.utils.helpers import tree_size

    gp = resolve_gateway(opt.gateway_params)
    if not gp.enabled:
        raise SystemExit(
            "--role gateway-standby needs the HA plane on: set "
            "TPU_APEX_GATEWAY_ENABLED=1 (or opt.gateway_params.enabled)")
    flight_recorder.configure(opt.log_dir, run_id=opt.refs)
    spec = probe_env(opt)
    store = ParamStore(tree_size(init_params(
        opt, spec, build_model(opt, spec), seed=opt.seed)))
    clock = GlobalClock()
    spool: collections.deque = collections.deque(maxlen=4096)
    spooled = [0]

    def _spool(items: list) -> None:
        spool.append(items)
        spooled[0] += len(items)

    bind_host, bind_port = "0.0.0.0", port
    if gp.standby:
        eps = parse_endpoints(gp.standby)
        if eps:
            bind_host, bind_port = eps[0]
    primary = parse_endpoints(coordinator)[0]
    gw = DcnGateway(store, clock, ActorStats(), put_chunk=_spool,
                    host=bind_host, port=bind_port,
                    gateway_params=gp, log_dir=opt.log_dir,
                    ha_role="standby", sync_from=primary)
    # SIGTERM drain flag: a plain threading.Event polled around an
    # interruptible sleep (the run_fleet_actors pattern) — the handler
    # must NOT take the mp clock lock the main thread would be parked
    # on inside ``clock.stop.wait`` (signal-handler self-deadlock)
    host_stop = threading.Event()
    if threading.current_thread() is threading.main_thread():
        try:
            signal.signal(signal.SIGTERM,
                          lambda s, f: host_stop.set())
        except (ValueError, OSError):  # pragma: no cover
            pass
    print(f"[fleet] gateway standby up on port {gw.port}, syncing "
          f"{primary[0]}:{primary[1]} (lease {gp.lease_s:g}s)")
    try:
        while not host_stop.is_set() and not clock.stop.is_set():
            time.sleep(0.5)
    finally:
        role = gw.status_snapshot().get("gateway", {})
        gw.close()
        print(f"[fleet] gateway standby exiting: role "
              f"{role.get('role')!r} term {role.get('term')} "
              f"(spooled {spooled[0]} rows post-promotion)")


def run_replay_shard_host(opt: Options, coordinator: str,
                          shard_id: int, port: int = 0) -> None:
    """``--role replay-shard``: one replay ring shard of the sharded
    priority plane (ISSUE 20, memory/shard_plane.py).  The host owns a
    whole ``PrioritizedReplay`` and serves the two-level sample's
    shard-local leg over T_SSAMPLE/T_SPRIO on its own gateway; actors
    stream T_EXP chunks AT this host (experience samples where it
    LANDS — the INES topology), and every ingest ack renews the shard's
    coordinator lease with the updated cumulative ingest report, so the
    registry's conservation ledger is exact at every chunk boundary: a
    crash loses only unacked — hence actor-counted — rows.

    A restarted shard id re-leases at a fresh generation in ``joining``
    (routed ingest, no sample mass) and activates once its ring is
    warm — the rejoin barrier.  SIGTERM releases the lease (rows move
    to the ``shard_lost`` bucket, counted) and exits 0."""
    import numpy as np

    from pytorch_distributed_tpu.factory import probe_env
    from pytorch_distributed_tpu.agents.clocks import (
        ActorStats, GlobalClock,
    )
    from pytorch_distributed_tpu.agents.param_store import ParamStore
    from pytorch_distributed_tpu.memory.prioritized import (
        PrioritizedReplay,
    )
    from pytorch_distributed_tpu.memory.shard_plane import (
        LocalShard, ShardLease, resolve_shard,
    )
    from pytorch_distributed_tpu.parallel.dcn import (
        DcnGateway, parse_endpoints,
    )
    from pytorch_distributed_tpu.utils import flight_recorder

    sp = resolve_shard(opt.shard_params)
    if sp.shards <= 1:
        raise SystemExit(
            "--role replay-shard needs the shard plane on: set "
            "TPU_APEX_SHARD_SHARDS >= 2 (or opt.shard_params.shards)")
    flight_recorder.configure(opt.log_dir, run_id=opt.refs)
    spec = probe_env(opt)
    mp_ = opt.memory_params
    state_dtype = np.uint8 if mp_.state_dtype == "uint8" else np.float32
    shard_capacity = max(1, -(-int(mp_.memory_size) // sp.shards))
    shard = LocalShard(shard_id, PrioritizedReplay(
        capacity=shard_capacity,
        state_shape=spec.state_shape,
        action_shape=spec.action_shape,
        state_dtype=state_dtype,
        action_dtype=spec.action_dtype,
        priority_exponent=mp_.priority_exponent,
        importance_weight=mp_.priority_weight,
        importance_anneal_steps=opt.agent_params.steps))
    lease = ShardLease(
        parse_endpoints(coordinator or sp.coordinator)[0],
        shard_id, incarnation=int(time.time() * 1000) & 0x7FFFFFFF,
        capacity=shard_capacity)
    lease.acquire()
    shard.generation = lease.generation

    def _report() -> dict:
        rep = shard.mass()
        rep["mass"] = rep["total"]
        rep["fill"] = (rep["size"] / shard_capacity
                       if shard_capacity else 0.0)
        return rep

    def _ingest(items: list) -> None:
        # renew-WITH-updated-ingest before the gateway acks the chunk:
        # the registry ledger moves in the same step the rows become
        # ours, so a crash between acks is exactly the unacked chunk
        for tr, pr in items:
            shard.feed(tr, pr)
        if lease.joining and shard.ingested_rows > 0:
            lease.activate()  # ring is warm: cross the rejoin barrier
        lease.renew(_report())

    gw = DcnGateway(ParamStore(4), GlobalClock(), ActorStats(),
                    put_chunk=_ingest, port=port, shards=shard)
    host_stop = threading.Event()
    if threading.current_thread() is threading.main_thread():
        try:
            signal.signal(signal.SIGTERM, lambda s, f: host_stop.set())
        except (ValueError, OSError):  # pragma: no cover
            pass
    renew_s = sp.renew_s if sp.renew_s > 0 else max(0.05, sp.lease_s / 3)
    print(f"[fleet] replay shard {shard_id} up on port {gw.port} "
          f"(generation {lease.generation}, capacity {shard_capacity}, "
          f"{'joining' if lease.joining else 'member'}, lease "
          f"{sp.lease_s:g}s)")
    try:
        while not host_stop.is_set():
            if host_stop.wait(renew_s):
                break
            try:
                if lease.joining and shard.ingested_rows > 0:
                    lease.activate()
                if not lease.renew(_report()):
                    # expired under us (partition outlived the lease):
                    # re-lease at a fresh generation and rejoin
                    lease.acquire()
                    shard.generation = lease.generation
                    print(f"[fleet] shard {shard_id} lease expired; "
                          f"rejoined at generation {lease.generation} "
                          f"(joining={lease.joining})", flush=True)
            except (ConnectionError, OSError) as e:
                print(f"[fleet] shard {shard_id} coordinator "
                      f"unreachable: {e!r}", flush=True)
    finally:
        shard.alive = False  # drain: answer SSTAT_DEAD, never silence
        try:
            lease.release()
        except (ConnectionError, OSError):
            pass
        gw.close()
        print(f"[fleet] replay shard {shard_id} exiting: "
              f"{shard.ingested_rows} rows ingested, "
              f"{shard.stale_rejected} stale write-backs rejected")


# ---------------------------------------------------------------------------
# actor host
# ---------------------------------------------------------------------------

def _remote_actor_main(opt: Options, coordinator: str, process_ind: int,
                       progress=None) -> None:
    """One remote rollout worker: DCN adapters in place of the shared-memory
    plane, then the standard actor loop (agents/actor.py) unmodified.

    Exit code reflects WHAT ended the loop (utils/supervision.py codes):
    the learner's stop flag exits 0 (run complete — the supervisor frees
    the slot for good), a terminal session loss exits EXIT_DISCONNECTED
    (the supervisor respawns the slot from its RestartBudget).  Before
    the stop/disconnected split, a gateway blip read as "run complete"
    and silently drained the whole remote fleet with zero restarts
    consumed."""
    from pytorch_distributed_tpu.factory import get_worker, probe_env
    from pytorch_distributed_tpu.parallel.dcn import (
        DcnClient, DcnRefused, RemoteClock, RemoteMemory, RemoteParamStore,
        RemoteStats,
    )
    from pytorch_distributed_tpu.utils import flight_recorder
    from pytorch_distributed_tpu.utils.supervision import EXIT_DISCONNECTED

    flight_recorder.configure(opt.log_dir, run_id=opt.refs)
    recorder = flight_recorder.get_recorder(f"actor-{process_ind}")
    # ``--coordinator`` accepts an ORDERED endpoint list
    # ("primary:5555,standby:5556") when the gateway HA plane is on
    # (ISSUE 16): the client dials in order and fails over to the
    # promoted standby on terminal disconnect.  A plain host:port is
    # the unchanged single-gateway contract.
    from pytorch_distributed_tpu.parallel.dcn import parse_endpoints

    endpoints = parse_endpoints(coordinator)
    recorder.record("session-start", coordinator=coordinator)
    try:
        client = DcnClient(endpoints, process_ind=process_ind)
    except (ConnectionError, OSError, DcnRefused) as e:
        # no session was ever established (gateway unreachable, or the
        # HELLO was refused — slot conflict): still a network/learner-host
        # condition, not an actor-code crash, so classify it the same
        # way; anything else (an InjectedCrash drill, a setup bug)
        # propagates as the crash it is
        print(f"[fleet] actor-{process_ind} could not establish its DCN "
              f"session ({e}); exiting {EXIT_DISCONNECTED}")
        recorder.record("session-refused", error=repr(e))
        flight_recorder.dump_all(
            f"actor-{process_ind} could not establish DCN session")
        sys.exit(EXIT_DISCONNECTED)
    memory = RemoteMemory(client)
    clock = RemoteClock(client)
    # hang-watchdog liveness: the actor harness bumps
    # clock.bump_progress per vector tick; the shared board's marks are
    # read by run_fleet_actors' supervisor (utils/supervision.py)
    clock.progress = progress
    try:
        spec = probe_env(opt)
        get_worker("actor", opt.agent_type)(
            opt, spec, process_ind, memory, RemoteParamStore(client), clock,
            RemoteStats(client))
    except (ConnectionError, OSError):
        # a terminal DcnDisconnected escapes the actor loop through its
        # highest-frequency RPC (send_chunk) — swallow it iff the client
        # latched the loss, so the exit-code split below classifies it
        # as EXIT_DISCONNECTED, not an anonymous crash; anything else is
        # a genuine transport bug and must crash loudly
        if not client.disconnected.is_set():
            raise
    finally:
        try:
            memory.flush()
            clock.flush()
        except (ConnectionError, OSError):
            pass
        client.close()
    if client.disconnected.is_set() and not client.stop.is_set():
        print(f"[fleet] actor-{process_ind} lost its DCN session; "
              f"exiting {EXIT_DISCONNECTED} for the supervisor")
        # the client already dumped when it latched the loss
        # (DcnClient._terminal); this records how the ROLE ended
        recorder.record("session-lost", reconnects=client.reconnects)
        flight_recorder.dump_all(
            f"actor-{process_ind} DCN session lost")
        sys.exit(EXIT_DISCONNECTED)
    recorder.record("run-complete", reconnects=client.reconnects)


def run_fleet_actors(opt: Options, coordinator: str, actor_base: int,
                     actor_count: int, backend: str = "process",
                     max_restarts: int = 3) -> List[int]:
    """Run ``actor_count`` rollout workers holding global process_inds
    ``[actor_base, actor_base + actor_count)``.

    Process backend supervises with the same RestartBudget policy as the
    learner host's runtime monitor (utils/supervision.py): a crashed actor
    respawns in place — its gateway slot frees when its connection drops,
    so the replacement re-claims it — up to ``max_restarts`` per slot;
    clean exits (the run finished) are final.  Returns the list of slots
    abandoned with their budget exhausted (empty = clean host run; the
    CLI exits nonzero otherwise so an outer orchestrator sees the
    failure instead of a learner silently training with a reduced
    fleet)."""
    assert actor_base + actor_count <= opt.num_actors, (
        f"actor slots [{actor_base}, {actor_base + actor_count}) exceed "
        f"fleet num_actors={opt.num_actors}")

    from pytorch_distributed_tpu.factory import prebuild_native
    from pytorch_distributed_tpu.utils import health, telemetry
    from pytorch_distributed_tpu.utils.supervision import ProgressBoard

    prebuild_native(opt)  # once, before N workers race the same g++

    # mission-control push leg (ISSUE 10): this host's actors write
    # their scalar rows to the LOCAL log dir; when the metrics plane is
    # on, a MetricsPusher tails that stream and ships scalar-window
    # deltas to the learner-host aggregator over the sessionless
    # T_METRICS verb, clock-offset-aligned — the fleet-level series
    # cover remote hosts, not just the gateway host.
    pusher = None
    mparams = telemetry.resolve_metrics(opt.metrics_params)
    if mparams.enabled:
        from pytorch_distributed_tpu.parallel.dcn import parse_endpoints

        # the pusher pins the FIRST endpoint; its sessionless push has
        # per-call timeouts + a single retry (parallel/dcn.py), so a
        # promotion window costs dropped batches, not a wedged thread
        pusher = telemetry.MetricsPusher(parse_endpoints(coordinator)[0],
                                         opt.log_dir, mparams)
        pusher.start()

    # hang watchdog (health sentinel): per-slot liveness marks bumped by
    # the remote actors' RemoteClock; stale marks past hang_deadline get
    # the worker SIGKILLed and respawned as EXIT_HUNG from the same
    # RestartBudget as a crash.  Process backend only (threads cannot be
    # killed); hang_deadline=0 (default) disables the pass.
    hp = health.resolve(opt.health_params)
    board = ProgressBoard([f"actor-{actor_base + i}"
                           for i in range(actor_count)])

    thread_exits: dict = {}  # slot -> nonzero exit (thread backend only)

    def spawn(ind: int):
        board.note_start(f"actor-{ind}")
        if backend == "process":
            w = _CTX.Process(target=_remote_actor_main,
                             args=(opt, coordinator, ind, board),
                             name=f"fleet-actor-{ind}", daemon=True)
        else:
            def _thread_main(ind=ind):
                from pytorch_distributed_tpu.utils.supervision import (
                    EXIT_CRASH,
                )

                try:
                    _remote_actor_main(opt, coordinator, ind)
                except SystemExit as e:
                    # threading machinery swallows SystemExit, which
                    # would let a session-loss exit read as a clean run
                    # — record it so the join loop can fail loudly
                    thread_exits[ind] = int(e.code or 0)
                except BaseException:
                    # a genuine crash (incl. an InjectedCrash drill) must
                    # not vanish into a dead thread's stderr either
                    thread_exits[ind] = EXIT_CRASH
                    raise

            w = threading.Thread(target=_thread_main,
                                 name=f"fleet-actor-{ind}", daemon=True)
        if backend == "process":
            # rollout workers are CPU processes wherever they run (an
            # actor host may sit beside a chip another process owns)
            with cpu_child_env():
                w.start()
        else:
            w.start()
        return w

    workers = {actor_base + i: spawn(actor_base + i)
               for i in range(actor_count)}
    print(f"[fleet] actor host up: {actor_count} actors "
          f"(slots {actor_base}..{actor_base + actor_count - 1}) -> "
          f"{coordinator}")
    if backend != "process":
        for w in workers.values():
            w.join()
        bad = {ind: code for ind, code in thread_exits.items() if code}
        if pusher is not None:
            pusher.stop()  # final tail drain rides the stop
        if bad:
            raise RuntimeError(
                f"actor host FAILED (thread backend): worker exit codes "
                f"{bad} — see utils/supervision.describe_exit")
        return []

    from pytorch_distributed_tpu.utils import flight_recorder
    from pytorch_distributed_tpu.utils.supervision import (
        EXIT_HUNG, RestartBudget, describe_exit,
    )

    flight_recorder.configure(opt.log_dir, export_env=True,
                              run_id=opt.refs)
    host_recorder = flight_recorder.get_recorder("fleet-host")
    budget = RestartBudget(max_restarts=max_restarts, backoff=True)
    for ind in workers:
        budget.note_birth(ind)
    # SIGTERM = the host is being preempted: actor hosts hold no
    # checkpointable state (the learner host owns the epoch store), so
    # the right drain is to stop respawning and terminate the rollout
    # workers promptly — their unflushed chunks are the bounded loss the
    # failure model already declares (parallel/dcn.py "Lost").
    host_stop = threading.Event()
    prev_term = None
    if threading.current_thread() is threading.main_thread():
        try:
            prev_term = signal.signal(
                signal.SIGTERM, lambda s, f: host_stop.set())
        except (ValueError, OSError):  # pragma: no cover
            prev_term = None
    pending: dict = {}  # slot -> respawn-at deadline (crash backoff)
    abandoned: List[int] = []
    while (workers or pending) and not host_stop.is_set():
        time.sleep(0.5)
        now = time.monotonic()
        for ind, at in list(pending.items()):
            if now >= at:
                del pending[ind]
                workers[ind] = spawn(ind)
                budget.note_birth(ind)
        for ind, w in list(workers.items()):
            if w.is_alive():
                continue
            if w.exitcode == 0:
                del workers[ind]  # run complete for this slot
                continue
            delay = budget.request_restart(ind)
            if delay is not None:
                print(f"[fleet] actor-{ind} died "
                      f"({describe_exit(w.exitcode)}); "
                      f"restart {budget.count(ind)}/{max_restarts} "
                      f"in {delay:.0f}s")
                host_recorder.record("worker-restarted", slot=ind,
                                     exit=w.exitcode,
                                     restarts=budget.count(ind),
                                     delay=delay)
                del workers[ind]
                pending[ind] = now + delay
            else:
                print(f"[fleet] actor-{ind} out of restart budget; "
                      f"abandoning slot")
                host_recorder.record("slot-abandoned", slot=ind,
                                     exit=w.exitcode)
                del workers[ind]
                abandoned.append(ind)
        # ---- hang watchdog: SIGKILL alive-but-stuck actors (no
        # progress mark within hang_deadline; compile grace respected)
        # and respawn them through the RestartBudget as EXIT_HUNG
        if hp.hang_deadline > 0:
            hung = set(board.hung(hp.hang_deadline, hp.hang_grace,
                                  only=[f"actor-{i}" for i in workers]))
            for ind, w in list(workers.items()):
                if f"actor-{ind}" not in hung or not w.is_alive():
                    continue
                host_recorder.record(
                    "worker-hung", slot=ind,
                    age=round(board.age(f"actor-{ind}"), 1))
                flight_recorder.dump_all(
                    f"actor-{ind} hung (> {hp.hang_deadline:g}s without "
                    f"progress); watchdog SIGKILL")
                w.kill()
                w.join(10.0)
                delay = budget.request_restart(ind)
                if delay is not None:
                    print(f"[fleet] actor-{ind} "
                          f"({describe_exit(EXIT_HUNG)}); restart "
                          f"{budget.count(ind)}/{max_restarts} "
                          f"in {delay:.0f}s")
                    host_recorder.record("worker-restarted", slot=ind,
                                         exit=EXIT_HUNG,
                                         restarts=budget.count(ind),
                                         delay=delay)
                    del workers[ind]
                    pending[ind] = now + delay
                else:
                    print(f"[fleet] actor-{ind} out of restart budget "
                          f"(hung); abandoning slot")
                    host_recorder.record("slot-abandoned", slot=ind,
                                         exit=EXIT_HUNG)
                    del workers[ind]
                    abandoned.append(ind)
        if abandoned:
            # fail fast like the single-host monitor (runtime._monitor
            # trips the stop event on the same condition): a host running
            # a reduced fleet for the rest of a long run is the silent
            # degradation this supervision exists to prevent.  Terminate
            # the survivors and surface the failure NOW — the outer
            # orchestrator restarts the whole host with a fresh budget.
            flight_recorder.dump_all(
                f"actor host failing fast: slots {abandoned} out of "
                f"restart budget")
            for ind, w in list(workers.items()):
                print(f"[fleet] terminating healthy actor-{ind} "
                      "(host failing fast)")
                w.terminate()
                w.join(10.0)
            workers.clear()
            pending.clear()
            break
    if host_stop.is_set():
        print(f"[fleet] SIGTERM: preemption notice — terminating "
              f"{len(workers)} actors on this host")
        host_recorder.record("sigterm-preemption", live=len(workers))
        flight_recorder.dump_all("SIGTERM preemption notice (actor host)")
        for ind, w in list(workers.items()):
            w.terminate()
            w.join(10.0)
        workers.clear()
        pending.clear()
    if prev_term is not None:
        signal.signal(signal.SIGTERM, prev_term)
    if pusher is not None:
        pusher.stop()  # final tail drain rides the stop
    return abandoned


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def main(argv: Optional[List[str]] = None) -> None:
    ap = argparse.ArgumentParser(
        prog="pytorch_distributed_tpu.fleet",
        description="multi-host Ape-X fleet launcher")
    ap.add_argument("--role",
                    choices=("learner", "actors", "learner-replica",
                             "gateway-standby", "replay-shard"),
                    required=True)
    ap.add_argument("--replica-id", type=int, default=1,
                    help="[learner-replica] this host's replica id "
                         "(replica 0 is the lead learner host; ids "
                         "must be unique across the fleet)")
    ap.add_argument("--shard-id", type=int, default=0,
                    help="[replay-shard] this host's replay shard id "
                         "(ids must be unique across the fleet; "
                         "ISSUE 20, memory/shard_plane.py)")
    ap.add_argument("--config", type=int, default=1)
    ap.add_argument("--num-actors", type=int, default=None,
                    help="TOTAL fleet actor count (defaults to config)")
    ap.add_argument("--port", type=int, default=5555)
    ap.add_argument("--local-actors", type=int, default=0,
                    help="[learner] actors co-located on the learner host")
    ap.add_argument("--coordinator", type=str, default=None,
                    help="[actors|gateway-standby] learner host as "
                         "host:port; actor hosts may give a comma list "
                         "'h1:p1,h2:p2' (primary first, standby after) "
                         "and fail over between them (ISSUE 16)")
    ap.add_argument("--actor-base", type=int, default=0,
                    help="[actors] first global actor slot on this host")
    ap.add_argument("--actor-count", type=int, default=8,
                    help="[actors] actors to run on this host")
    ap.add_argument("--seed", type=int, default=None)
    ap.add_argument("--actor-backend", type=str, default=None,
                    choices=("inline", "pipelined", "batched", "device",
                             "anakin"),
                    help="actor hot-loop schedule (config.py EnvParams."
                         "actor_backend): pipelined = overlapped "
                         "two-stage loop (default), inline = serial "
                         "fallback, batched = SEED-style shared "
                         "inference on the learner host — applies to "
                         "that host's LOCAL actors; remote actor hosts "
                         "have no co-located server and auto-downgrade "
                         "to pipelined; device = Sebulba on-device env "
                         "fleet (pure-JAX envs fused with the policy "
                         "into one scan, envs/device_env.py — dqn + "
                         "device-implemented envs only, others "
                         "downgrade); anakin = the CLOSED loop (ISSUE "
                         "12): env fleet + learner in ONE process, no "
                         "actor workers on the learner host at all "
                         "(agents/anakin.py — remote actor hosts in a "
                         "hybrid fleet run the device schedule) "
                         "(factory.resolve_actor_backend)")
    ap.add_argument("--resume", type=str, default=None, metavar="REFS",
                    help="[learner] resume run REFS from its newest "
                         "complete checkpoint epoch (models/REFS_ckpt — "
                         "written on the checkpoint_freq cadence and on "
                         "SIGTERM preemption); fails fast if none exists. "
                         "Remote actor hosts need no flag: their slots "
                         "re-attach through the DCN session layer's "
                         "incarnation fencing as on any learner restart.")
    ap.add_argument("--set", action="append", default=[], metavar="K=V",
                    help="Options override, e.g. --set steps=2000 "
                         "--set batch_size=32 (repeatable; int/float/str "
                         "auto-typed). Must match on every host.")
    ap.add_argument("--faults-client", type=str, default=None,
                    metavar="SPEC",
                    help="fault-injection spec for DCN clients on this "
                         "host (utils/faults.py grammar, e.g. "
                         "'sever@40,corrupt@90' or 'random:7'); exported "
                         "as DCN_FAULTS_CLIENT so spawn children inherit")
    ap.add_argument("--faults-gateway", type=str, default=None,
                    metavar="SPEC",
                    help="[learner] fault-injection spec for the gateway "
                         "(DCN_FAULTS_GATEWAY)")
    ap.add_argument("--reconnect-timeout", type=float, default=None,
                    help="seconds a disconnected actor redials before "
                         "declaring its session lost (DCN_RECONNECT_TIMEOUT)")
    ap.add_argument("--heartbeat", type=float, default=None,
                    help="idle seconds between client heartbeat pings "
                         "(DCN_HEARTBEAT_INTERVAL; <=0 disables)")
    args = ap.parse_args(argv)

    for env, val in (("DCN_FAULTS_CLIENT", args.faults_client),
                     ("DCN_FAULTS_GATEWAY", args.faults_gateway),
                     ("DCN_RECONNECT_TIMEOUT", args.reconnect_timeout),
                     ("DCN_HEARTBEAT_INTERVAL", args.heartbeat)):
        if val is not None:
            os.environ[env] = str(val)

    from pytorch_distributed_tpu.config import parse_set_overrides

    overrides = parse_set_overrides(args.set)
    if args.num_actors is not None:
        overrides["num_actors"] = args.num_actors
    if args.seed is not None:
        overrides["seed"] = args.seed
    if args.actor_backend is not None:
        overrides["actor_backend"] = args.actor_backend
    if args.resume is not None:
        if args.role != "learner":
            ap.error("--resume applies to the learner host (actor hosts "
                     "re-attach through DCN incarnation fencing)")
        overrides["refs"] = args.resume
        overrides["resume"] = "must"
    opt = build_options(args.config, **overrides)

    if args.role in ("learner", "learner-replica"):
        # the roles that own an accelerator share main.py's cache rule
        from pytorch_distributed_tpu.utils.helpers import (
            enable_compile_cache,
        )

        enable_compile_cache()
    if args.role == "learner":
        topo = run_fleet_learner(opt, local_actors=args.local_actors,
                                 port=args.port)
        if topo.stop_reason in FATAL_STOP_REASONS:
            sys.exit(f"[fleet] run FAILED: stopped by {topo.stop_reason}")
    elif args.role == "learner-replica":
        assert args.coordinator, "--coordinator host:port required"
        run_replica_host(opt, args.coordinator, args.replica_id)
    elif args.role == "gateway-standby":
        assert args.coordinator, "--coordinator host:port required"
        run_gateway_standby(opt, args.coordinator, args.port)
    elif args.role == "replay-shard":
        assert args.coordinator, "--coordinator host:port required"
        run_replay_shard_host(opt, args.coordinator, args.shard_id,
                              args.port)
    else:
        assert args.coordinator, "--coordinator host:port required"
        abandoned = run_fleet_actors(opt, args.coordinator, args.actor_base,
                                     args.actor_count)
        if abandoned:
            print(f"[fleet] actor host FAILED: slots {abandoned} out of "
                  "restart budget")
            sys.exit(1)


if __name__ == "__main__":
    main()
