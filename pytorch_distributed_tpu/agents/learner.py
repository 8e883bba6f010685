"""Learner process: the compute-critical update loop.

Re-design of reference core/single_processes/dqn_learner.py:50-95 /
ddpg_learner.py:50-106.  Same cadence contract — gate on
``memory.size > learn_start`` with a sleep spin (reference dqn_learner.py:
51,102-103), one sampled minibatch per step, target-net update folded into
the step, global learner clock increment (reference :94-95), loss stats on
the ``learner_freq`` cadence (reference :99-101) — but the update itself is
one pure jitted XLA program (ops/losses.py) dispatched through
``ShardedLearner``: batch dp-sharded over the mesh, gradients all-reduced
over ICI, params/opt-state donated so the TrainState updates in place in
HBM.  Where the reference's Adam writes become instantly visible through
shared CUDA storage (reference :87), here the learner explicitly publishes
versioned parameter snapshots every ``param_publish_freq`` steps.

A single learner process drives the whole mesh; the reference's
``num_learners > 1`` hogwild hook (unsynchronized racing Adam steps,
SURVEY.md "known quirks") maps to widening the mesh's dp axis instead.

PER additions (the reference's TODO): queue-fed single-owner buffer
(memory/feeder.py) drained each step, |TD| priority write-back after every
update.
"""

from __future__ import annotations

import collections
import time
from typing import Any

import numpy as np

from pytorch_distributed_tpu.config import Options
from pytorch_distributed_tpu.factory import (
    EnvSpec, build_learner_core, build_learner_dispatch, build_model,
    init_params, published_params,
)
from pytorch_distributed_tpu.agents.clocks import GlobalClock, LearnerStats
from pytorch_distributed_tpu.agents.param_store import ParamStore
from pytorch_distributed_tpu.memory.feeder import QueueOwner
from pytorch_distributed_tpu.utils import checkpoint as ckpt
from pytorch_distributed_tpu.utils import (
    bandwidth, flight_recorder, health, perf, tracing,
)
from pytorch_distributed_tpu.utils.faults import FaultInjector
from pytorch_distributed_tpu.utils.metrics import MetricsWriter
from pytorch_distributed_tpu.utils.profiling import StepTimer, report_setup
from pytorch_distributed_tpu.utils.rngs import np_rng, process_seed


def announce_startup(opt: Options, *, mesh, steps_per_dispatch: int,
                     replay: Any = None, publish: str = "inline") -> dict:
    """The learner's ONE start-up line: what the chip path resolved to —
    platform, device kind and count, mesh axes, ``steps_per_dispatch``,
    which PER sampler and which torso were selected, how the fused step's
    batch lies on the chips (``batch_rows=128x4dp``: rows a chip trains x
    chips, read off the ring like the sampler; ``512x1`` on one device),
    how parameters are published, the format an HBM ring keeps its
    observation rows in with the bytes it holds (``replay/hbm_bytes``),
    and the HBM bytes each
    device holds once the ring is attached.  Printed once and appended
    to ``<log_dir>/startup.jsonl`` (utils/helpers.record_startup), so no
    branch the learner takes on the backend it found is silent;
    ``chip_smoke.py`` asserts on it."""
    import jax

    from pytorch_distributed_tpu.factory import select_torso
    from pytorch_distributed_tpu.utils.bandwidth import replay_nbytes
    from pytorch_distributed_tpu.utils.helpers import record_startup

    axes = ({a: int(n) for a, n in mesh.shape.items() if n > 1} or {"dp": 1}
            if mesh is not None else None)
    hbm = [(d.memory_stats() or {}).get("bytes_in_use")
           for d in jax.local_devices()]
    rec = record_startup(
        opt.log_dir, "learner", mesh=axes,
        steps_per_dispatch=int(steps_per_dispatch),
        per_sampler=getattr(replay, "sampler", "n/a"),
        batch_rows=(replay.batch_rows(opt.agent_params.batch_size)
                    if hasattr(replay, "batch_rows") else "n/a"),
        torso=select_torso(opt) if opt.agent_type == "dqn" else "xla",
        publish=publish, ring_rows=getattr(replay, "stored_rows", "n/a"),
        replay_hbm_bytes=replay_nbytes(getattr(replay, "state", None)),
        hbm_bytes_in_use=hbm)
    mesh_s = ("none" if axes is None
              else "x".join(f"{a}{n}" for a, n in axes.items()))
    print(f"[learner] start-up: platform={rec['platform']} "
          f"device_kind={rec['device_kind']!r} "
          f"devices={rec['device_count']} mesh={mesh_s} "
          f"steps_per_dispatch={rec['steps_per_dispatch']} "
          f"per_sampler={rec['per_sampler']} "
          f"batch_rows={rec['batch_rows']} torso={rec['torso']} "
          f"publish={publish} ring_rows={rec['ring_rows']} "
          f"replay/hbm_bytes={rec['replay_hbm_bytes']} "
          f"hbm_bytes_in_use={hbm}", flush=True)
    return rec


def run_learner(opt: Options, spec: EnvSpec, process_ind: int, memory: Any,
                param_store: ParamStore, clock: GlobalClock,
                stats: LearnerStats) -> None:
    from pytorch_distributed_tpu.factory import anakin_active

    if anakin_active(opt):
        # the co-located Anakin topology (ISSUE 12): this process IS
        # the actor fleet too — delegate to the duty-cycle driver.
        # Direct callers land here; the runtime dispatches earlier so
        # it can hand the shared ActorStats in (runtime.Topology.run).
        from pytorch_distributed_tpu.agents.anakin import (
            run_anakin_learner,
        )

        return run_anakin_learner(opt, spec, process_ind, memory,
                                  param_store, clock, stats)
    from pytorch_distributed_tpu.factory import replica_active

    if replica_active(opt):
        # the elastic multi-learner plane (ISSUE 15): N data-parallel
        # replicas over DCN, lease-fenced membership, generation-stamped
        # allreduce.  Delegation is gated the same LOUD-downgrade way as
        # megabatch: an unsupported family or a topology without a
        # registry/coordinator runs the solo loop and says so.
        from pytorch_distributed_tpu.parallel import dcn as dcn_mod

        rp = dcn_mod.resolve_replica(opt.replica_params)
        if opt.agent_type != "dqn":
            print(f"[learner] replicas={rp.replicas} is only supported "
                  f"for agent_type=dqn (got {opt.agent_type}); running "
                  f"the solo learner", flush=True)
        elif dcn_mod.local_registry() is None and not rp.coordinator:
            print(f"[learner] replicas={rp.replicas} needs the fleet "
                  f"gateway's ReplicaRegistry (fleet.py --role learner) "
                  f"or replica_params.coordinator; running the solo "
                  f"learner", flush=True)
        else:
            return run_replica_learner(opt, spec, process_ind, memory,
                                       param_store, clock, stats)
    import jax
    import jax.numpy as jnp
    from jax.flatten_util import ravel_pytree

    ap = opt.agent_params

    # ---- mesh, model, train state, placement (reference
    # dqn_learner.py:21-39): the factory's one assembly ----
    core, state = build_learner_core(opt, spec)
    mesh, model, learner = core.mesh, core.model, core.learner

    # ---- resume: newest complete checkpoint epoch, else the legacy
    # single snapshot (utils/checkpoint.py docstring).  Epoch extras
    # (clock counters, evaluator best-score) restore BEFORE the first
    # publication so no worker ever observes pre-resume values.
    assert opt.resume in ("auto", "must", "never"), (
        f"unknown resume mode {opt.resume!r}")
    epoch = None
    if opt.resume != "never":
        epoch = ckpt.resolve_epoch(opt.model_name)
        if epoch is not None:
            state = learner.place(
                ckpt.load_epoch_state(epoch, jax.device_get(state)))
            clock.seed_actor_steps(int(epoch.extras.get("actor_step", 0)))
            # the sidecar (written WITH every best-params file) can be
            # ahead of the epoch's score when the record fell between
            # two commits — take the max so a resumed run never lets a
            # worse policy overwrite <refs>_best.msgpack
            best = max(float(epoch.extras.get("best_eval_reward",
                                              float("-inf"))),
                       ckpt.load_best_score(opt.model_name))
            clock.best_eval_reward.value = best
            print(f"[learner] resumed epoch {epoch.epoch} "
                  f"(step {epoch.learner_step}, "
                  f"actor_step +{int(epoch.extras.get('actor_step', 0))}, "
                  f"best_eval {best:g})")
        else:
            restored = ckpt.restore_train_state(opt.model_name,
                                                jax.device_get(state))
            if restored is not None:
                state = learner.place(restored)
                clock.best_eval_reward.value = ckpt.load_best_score(
                    opt.model_name)
                print("[learner] resumed legacy single-snapshot state")
            elif opt.resume == "must":
                raise RuntimeError(
                    f"resume='must' but no complete checkpoint epoch "
                    f"under {ckpt.ckpt_root(opt.model_name)} and no "
                    f"legacy snapshot at {ckpt.state_dir(opt.model_name)}")

    # ---- initial publication: actors block on version 1 ----
    def _publish(st) -> None:
        flat, _ = ravel_pytree(jax.device_get(published_params(opt, st)))
        param_store.publish(np.asarray(flat, dtype=np.float32))

    _publish(state)

    # Async publication path: a publish crossing only enqueues a cheap
    # on-device copy of the param tree (jit outputs never alias
    # non-donated inputs, so the copy survives later donating
    # dispatches); a worker thread fetches + publishes in the background,
    # always taking the freshest snapshot (an in-flight fetch absorbs any
    # newer requests - actors only ever want the latest version anyway),
    # so the device->host fetch never sits inside the learner hot loop.
    # TPU only: a concurrent device_get against in-flight multi-device
    # programs deadlocks the CPU backend's collective rendezvous (see
    # ShardedLearner.host_params), so the CPU path publishes inline —
    # the start-up line says which (publish=async|inline).
    import threading

    _pub_thread = None
    _pub_error: list = []  # the exception that killed the publisher
    if jax.devices()[0].platform == "tpu":
        _copy_tree = jax.jit(
            lambda p: jax.tree_util.tree_map(jnp.copy, p))
        _pub_lock = threading.Lock()
        _pub_box: list = [None]
        _pub_event = threading.Event()
        _pub_stop = threading.Event()

        def _pub_worker() -> None:
            while True:
                _pub_event.wait()
                if _pub_stop.is_set():
                    return
                with _pub_lock:
                    snap, _pub_box[0] = _pub_box[0], None
                    _pub_event.clear()
                if snap is None:
                    continue
                try:
                    flat, _ = ravel_pytree(jax.device_get(snap))
                    param_store.publish(np.asarray(flat, dtype=np.float32))
                except Exception as e:  # noqa: BLE001 - thread boundary
                    # a failed fetch from a directly attached chip is a
                    # fault, not weather: actors must not keep acting on
                    # frozen weights while the run reports success.
                    # Stop the run; the learner re-raises after its loop.
                    _pub_error.append(e)
                    clock.stop.set()
                    return

        _pub_thread = threading.Thread(target=_pub_worker,
                                       name="param-pub", daemon=True)
        _pub_thread.start()

        def _publish_async(st) -> None:
            with _pub_lock:
                _pub_box[0] = _copy_tree(published_params(opt, st))
                _pub_event.set()
    else:
        _publish_async = _publish

    is_per = isinstance(memory, QueueOwner)
    # the three HBM rings (uniform, PER, segments) present one surface:
    # attach / drain; which fused program and which call signature they
    # get is the factory's to say (build_learner_dispatch)
    on_device = hasattr(memory, "attach")
    # perf plane monitor (utils/perf.py, TPU_APEX_PERF=1): created for
    # every memory path — rates/watermarks/gauges work everywhere; the
    # FLOPs capture below is device-path only (the host path's step
    # runs through ShardedLearner, whose per-update FLOPs nobody
    # dispatch-amortizes)
    perf_mon = perf.get_monitor("learner", opt.perf_params)
    if perf_mon.enabled:
        # the MFU denominator scales by the dtype the model actually
        # computes in (ISSUE-13 satellite: an fp32 run scored against
        # the bf16 peak under-reports MFU 2x)
        _cd = getattr(model, "compute_dtype", None)
        if _cd is not None:
            perf_mon.set_compute_dtype(jnp.dtype(_cd).name)
    if not on_device:
        # megabatch serves the fused device-replay dispatch only — a
        # host-replay config with the knob set must say so LOUDLY (the
        # same downgrade convention as the unsupported-family case
        # below), not silently benchmark an unengaged lever
        from pytorch_distributed_tpu.utils.perf import resolve_mxu

        _m_req = resolve_mxu(opt.learner_perf_params).megabatch
        if _m_req > 1:
            print(f"[learner] megabatch={_m_req} requires a device "
                  f"replay (memory_type device/device-per; got "
                  f"{opt.memory_type}); host-path learner runs "
                  f"unbatched", flush=True)
    if on_device:
        # Attach the HBM ring on the learner's mesh and fuse sampling (and
        # for PER: priority write-back) into the train step — one XLA
        # program per DISPATCH, which covers ``steps_per_dispatch`` scanned
        # update steps, amortising launch latency K-fold
        # (memory/device_replay.py build_uniform_fused_step docstring).
        replay = memory.attach(mesh=mesh)
        beta_dev = None
        prog = build_learner_dispatch(core, replay, opt, role="learner")
        K, fused = prog.K, prog.fused

        def device_step(keys):
            nonlocal state
            out = dict(zip(prog.returns, fused(
                state, replay.state, keys,
                *((beta_dev,) if prog.takes_beta else ()))))
            state = out["state"]
            if "ring" in out:
                replay.state = out["ring"]
            return out["metrics"]

        # Capture the fused program's per-update FLOPs off its cost
        # analysis ONCE at startup — the same executable the loop
        # dispatches (the AOT lower/compile below dedups through the
        # persistent compile cache on TPU) — so live MFU is one
        # multiply per stats window.  The retrace detector watches the
        # program: it must never recompile after warmup.
        if perf_mon.enabled:
            perf_mon.register_jit("fused_step", fused)
            # seed-derived even though these keys only feed .lower()
            # for the FLOP capture (apexlint rng-key-reuse: no literal-
            # seed streams outside utils.rngs)
            _pkeys = jax.random.split(
                jax.random.PRNGKey(process_seed(opt.seed, "learner",
                                                process_ind)),
                K + 1)[1:]
            _pkeys = (_pkeys.reshape(K, *_pkeys.shape[1:]) if K > 1
                      else _pkeys[0])
            _pbeta = ((jax.device_put(np.float32(replay.beta(0))),)
                      if prog.takes_beta else ())
            perf_mon.capture_flops(
                lambda: fused.lower(state, replay.state, _pkeys, *_pbeta))
        if perf_mon.audit is not None:
            # transfer audit (opt-in): the fused dispatch is transfer-
            # free by construction — state, ring and keys are all
            # device-resident — so ANY implicit transfer it stages is a
            # regression; the audit attributes it to its call site and
            # retries with transfers allowed (utils/perf.TransferAudit)
            _unaudited_step = device_step

            def device_step(keys):  # noqa: F811 - deliberate rebind
                return perf_mon.audit.run(_unaudited_step, keys)

        # data-plane telemetry programs (ISSUE 8): a bounded provenance
        # gather and — for the PER ring — the in-jit priority X-ray;
        # each is ONE small D2H on the stats cadence, never per step
        from pytorch_distributed_tpu.memory.device_replay import (
            provenance_sample,
        )

        _prov_sample = (jax.jit(provenance_sample, static_argnames="n")
                        if getattr(replay.state, "prov", None) is not None
                        else None)
        _xray_dev = None
        if getattr(replay.state, "priority", None) is not None:
            from pytorch_distributed_tpu.memory.device_per import (
                priority_xray_device,
            )

            _xray_dev = jax.jit(priority_xray_device,
                                static_argnames="bins")
        # telemetry's own key stream, decoupled from the sampling
        # stream by a fold — never a draw from device_key's chain
        _tel_key = jax.random.fold_in(
            jax.random.PRNGKey(np_rng(opt.seed, "learner",
                                      process_ind).integers(2 ** 31)),
            0x7e1)

        device_key = jax.random.PRNGKey(
            np_rng(opt.seed, "learner", process_ind).integers(2 ** 31))
        saved_key = (epoch.extras.get("rng", {}).get("learner_device")
                     if epoch is not None else None)
        if saved_key:
            # resume the device sampling stream where the epoch froze it
            # (keys pre-split after the save are re-drawn — a bounded
            # overlap, not a reuse of the whole stream)
            device_key = ckpt.deserialize_prng_key(saved_key, device_key)
        key_buf: list = []  # pre-split sampling keys, one split per 64
        # the CPU backend's collective rendezvous needs per-step blocking
        # (see ShardedLearner.step)
        block_each_step = (mesh is not None
                           and mesh.devices.flat[0].platform == "cpu")

    announce_startup(opt, mesh=mesh,
                     steps_per_dispatch=K if on_device else 1,
                     replay=replay if on_device else memory,
                     publish="async" if _pub_thread is not None else "inline")

    # warm-start the replay from the SAME epoch the train state came from
    # (after attach, so device rings land in HBM) — state, replay and
    # counters are one digest-verified triple, never a mixed resume.  A
    # geometry change between runs fails loudly here (CheckpointMismatch)
    # instead of as a broadcast error deep in the first train step.
    if epoch is not None and opt.memory_params.checkpoint_replay:
        # the flag gates the restore leg exactly like the save leg (and
        # the legacy branch below): a user resuming with
        # checkpoint_replay=false has asked for a cold replay — e.g.
        # after a deliberate memory-geometry change — and must not trip
        # CheckpointMismatch on an artifact they opted out of
        rows = ckpt.load_epoch_replay(epoch, memory)
        if rows:
            print(f"[learner] replay restored from epoch {epoch.epoch}: "
                  f"{rows} rows")
    elif epoch is None and opt.memory_params.checkpoint_replay:
        if ckpt.load_replay(opt.model_name, memory):
            print(f"[learner] replay restored: {memory_size(memory)} rows")

    rng = np_rng(opt.seed, "learner", process_ind)
    lstep = int(jax.device_get(state.step))
    lstep0 = lstep  # checkpoint-resumed steps; pacing baselines on THIS run
    if epoch is not None:
        # the epoch binds the pacing baseline and host RNG to the counters
        # restored above: replay-ratio throttling continues on cumulative
        # (lstep - lstep0) vs the restored actor clock instead of
        # resetting every resume (and the sampling stream continues
        # instead of replaying itself)
        lstep0 = int(epoch.extras.get("lstep0", lstep0))
        ckpt.restore_np_rng(
            rng, epoch.extras.get("rng", {}).get("learner_host"))
    clock.set_learner_step(lstep)

    # ---- gate until the replay warms up (reference dqn_learner.py:51) ----
    # clamped to the actual buffer capacity (segments for sequence replay,
    # transitions elsewhere): a learn_start >= capacity would otherwise
    # spin forever since a full ring's size never exceeds its capacity
    cap = getattr(memory, "capacity", opt.memory_params.memory_size)
    learn_start = min(ap.learn_start, cap - 1)
    deadline = (time.monotonic() + ap.max_seconds) if ap.max_seconds > 0 \
        else float("inf")
    while not clock.done(ap.steps) and memory_size(memory) <= learn_start \
            and time.monotonic() < deadline:
        # replay starvation is a LEGITIMATE wait: keep the liveness mark
        # fresh so the hang watchdog never reads warmup as a hang
        clock.bump_progress("learner")
        time.sleep(0.05)

    # the latest step's metric refs, fetched to host only on the
    # learner_freq cadence (one device_get per window — a per-step fetch
    # would serialise the host with the device on every dispatch)
    last_metrics = None
    t_cadence = time.monotonic()
    last_stats_lstep = lstep
    # host phases of the loop (README "Observability"): pace, drain, keys,
    # dispatch, sample, priorities, publish, checkpoint, stats; every one
    # is also a ``learner/<phase>`` span in a profiler trace.  ``step`` is
    # not a stretch of the host's time and has no span: it runs from a
    # dispatch's enqueue to its COMPLETION (``_reap`` below), the same
    # reading the ``learn`` trace span records.
    timer = StepTimer("learner")
    # per-phase timings go straight to the run's JSONL stream (appends are
    # atomic line writes; the logger process keeps the aggregated scalars)
    timing_writer = MetricsWriter(opt.log_dir, enable_tensorboard=False,
                                  role="learner", run_id=opt.refs)
    # distributed-trace tail: sample/learn spans attach to the most recent
    # trace id the replay drain observed (utils/tracing.py), closing the
    # actor→gateway→feed→sample→learn chain; the learner also flushes the
    # in-process "feeder" and "gateway" tracers — both record on threads
    # of THIS process (the drain path and the DCN serve threads)
    tracer = tracing.get_tracer("learner")

    def _flush_traces(step: int) -> None:
        for t in (tracer, tracing.get_tracer("feeder"),
                  tracing.get_tracer("gateway")):
            t.flush_to(timing_writer, step=step)

    # dispatches enqueued and not yet seen complete, oldest first: (one
    # of the dispatch's metric refs, perf_counter at enqueue, updates it
    # holds, trace id).  ``clock.learner_step`` counts at enqueue;
    # ``clock.learner_done`` counts here, so it never leads and reaches
    # ``learner_step`` once the last dispatch is done.
    in_flight: collections.deque = collections.deque()
    ldone = ldone_at_entry = lstep
    clock.set_learner_done(ldone)

    def _reap() -> None:
        """Book every dispatch that has completed since the last loop turn:
        one non-blocking ``is_ready()`` per turn and per completion, no
        thread and no wait, so the runtime alone bounds what is in flight.
        The interval is read when the loop comes by, one turn late at
        most."""
        nonlocal ldone
        seen = ldone
        while in_flight and in_flight[0][0].is_ready():
            _ref, t_enqueue, updates, trace_id = in_flight.popleft()
            seconds = time.perf_counter() - t_enqueue
            timer.add("step", seconds)
            tracer.record("learn", seconds * 1e3, trace_id=trace_id)
            ldone += updates
        if ldone != seen:
            clock.set_learner_done(ldone)

    def _save_epoch() -> None:
        """One coordinated checkpoint epoch: train state + replay +
        clocks/counters/best-score/RNG, captured NOW and committed by the
        atomic manifest rename (utils/checkpoint.py save_epoch) — the
        crash-consistent replacement for the old separate
        save_train_state/save_replay writes."""
        extras = dict(
            learner_step=lstep,
            lstep0=lstep0,
            actor_step=int(clock.actor_step.value),
            best_eval_reward=float(clock.best_eval_reward.value),
            replay_size=int(getattr(memory, "size", 0)),
            # sentinel provenance: how many rollbacks/skips preceded
            # this epoch (ckpt_fsck context for post-rollback roots)
            rollbacks=int(clock.rollbacks.value),
            skipped_steps=int(clock.skipped_steps.value),
            rng=dict(
                learner_host=ckpt.serialize_np_rng(rng),
                learner_device=(ckpt.serialize_prng_key(device_key)
                                if on_device else None),
            ),
        )
        ckpt.save_epoch(
            opt.model_name, state=state,
            memory=memory if opt.memory_params.checkpoint_replay else None,
            extras=extras, retain=ap.checkpoint_retain)

    # ---- training health sentinel (utils/health.py): the in-jit guard
    # already skips non-finite steps inside the train program; here the
    # host side watches the metrics stream for SUSTAINED divergence
    # (consecutive anomalous stats windows) and rolls the whole triple —
    # params, opt state, replay, clocks, RNG — back to the last good
    # checkpoint epoch in-process, bounded by ``max_rollbacks`` before
    # failing fast.  ``LEARNER_FAULTS`` (poison_grad@N / hang@N) drills
    # the ladder deterministically (utils/faults.py).
    hp = health.resolve(opt.health_params)
    detector = health.AnomalyDetector(zmax=hp.anomaly_zmax,
                                      grad_spike=hp.grad_spike,
                                      threshold=hp.anomaly_threshold,
                                      ess_floor=hp.ess_floor)
    recorder = flight_recorder.get_recorder("learner")
    _linj = FaultInjector.from_env("learner")
    _poison = [False]   # a pending poison_grad verb (next host batch)
    _win_skips = [0]    # exact skip count this stats window (host paths)
    _last_td = [None]   # mean |TD| of the last applied host-PER step
    _last_idx = [None]  # last sampled host-batch indices (provenance)
    _rb = {"used": 0, "before": None}  # rollback budget + ladder position

    def _fatal_divergence(msg: str) -> None:
        recorder.record("divergence-fatal", step=lstep, detail=msg)
        flight_recorder.dump_all(f"learner divergence: {msg}")
        raise RuntimeError(f"[health] {msg}")

    def _rollback(reason: str) -> None:
        """Restore the last good epoch in-process and resume.  Each
        successive rollback targets an epoch strictly OLDER than the
        previous restore point (the newest epoch may itself hold
        already-diverged params), and every committed epoch newer than
        the target is fenced with a ROLLED_BACK marker so neither this
        run nor a later --resume can step back onto it."""
        nonlocal state, lstep, lstep0, device_key, key_buf, ldone
        if _rb["used"] >= hp.max_rollbacks:
            _fatal_divergence(
                f"divergence persists after {_rb['used']} rollback(s) "
                f"(max_rollbacks={hp.max_rollbacks}): {reason}")
        target = ckpt.resolve_epoch(opt.model_name, before=_rb["before"])
        if target is None:
            _fatal_divergence(
                f"sustained divergence ({reason}) with no resumable "
                f"checkpoint epoch to roll back to "
                f"(checkpoint_freq=0 or all epochs spent)")
        ckpt.fence_epochs_after(opt.model_name, target.epoch,
                                reason=reason)
        state = learner.place(
            ckpt.load_epoch_state(target, jax.device_get(state)))
        if opt.memory_params.checkpoint_replay and target.has_replay:
            rows = ckpt.load_epoch_replay(target, memory)
            if rows:
                print(f"[health] replay rolled back with the epoch: "
                      f"{rows} rows")
        lstep = (target.learner_step if target.learner_step >= 0
                 else int(jax.device_get(state.step)))
        lstep0 = int(target.extras.get("lstep0", lstep))
        ckpt.restore_np_rng(rng,
                            target.extras.get("rng", {}).get("learner_host"))
        if on_device:
            saved = target.extras.get("rng", {}).get("learner_device")
            if saved:
                device_key = ckpt.deserialize_prng_key(saved, device_key)
            key_buf.clear()  # pre-split keys belong to the abandoned tail
        clock.set_learner_step(lstep)
        in_flight.clear()  # the abandoned tail completes unobserved
        ldone = lstep
        clock.set_learner_done(ldone)
        with clock.rollbacks.get_lock():
            clock.rollbacks.value += 1
        _rb["used"] += 1
        _rb["before"] = target.epoch
        detector.reset()
        _win_skips[0] = 0  # pre-rollback skips belong to the dead tail
        recorder.record("rollback", epoch=target.epoch, step=lstep,
                        reason=reason, used=_rb["used"])
        flight_recorder.dump_all(
            f"health rollback #{_rb['used']} to epoch {target.epoch} "
            f"({reason})")
        print(f"[health] rolled back to epoch {target.epoch} "
              f"(step {lstep}) after {reason}; "
              f"{hp.max_rollbacks - _rb['used']} rollback(s) left",
              flush=True)

    def _throttled() -> bool:
        """Is the learner ahead of ``max_replay_ratio`` samples per
        collected transition?  Baselined on THIS run's steps (lstep -
        lstep0): a resumed checkpoint's cumulative count against a fresh
        actor clock would stall the learner for hours."""
        return (not clock.stop.is_set()
                and time.monotonic() < deadline
                and (lstep - lstep0 + 1) * ap.batch_size
                > ap.max_replay_ratio * max(clock.actor_step.value, 1))

    # anchor the first rate window at loop entry (not process start:
    # warmup compiles must not dilute it); the anchor drain carries the
    # one-time flops_per_update row + startup watermarks, so write it
    if perf_mon.enabled:
        timing_writer.scalars(perf_mon.drain(step=lstep), step=lstep)
    while lstep < ap.steps and not clock.stop.is_set() \
            and time.monotonic() < deadline:
        clock.bump_progress("learner")
        for _action, _arg in _linj.data_frame(("poison_grad",)):
            _poison[0] = True
        if ap.max_replay_ratio > 0 and _throttled():
            # pacing gate: don't draw more than max_replay_ratio samples
            # per collected transition (config.py AgentParams docstring).
            # Queue-backed memories keep draining while throttled — a
            # full ingest queue blocks actors before they can advance the
            # clock (deadlock).
            with timer.phase("pace"):
                while _throttled():
                    if hasattr(memory, "drain"):
                        memory.drain()
                    # pacing throttle = flow control, not a hang
                    clock.bump_progress("learner")
                    time.sleep(0.002)
            if clock.stop.is_set():
                break
        if on_device:
            if _poison[0]:
                _poison[0] = False
                print("[faults:learner] poison_grad targets the "
                      "host-sampled batch; inert on the fused device "
                      "path (drill with poison_chunk instead)",
                      flush=True)
            with timer.phase("drain"):
                memory.drain()
            if not key_buf:
                # one split dispatch amortised over 64 dispatches
                # instead of one tiny program per step; beta (PER)
                # anneals slowly and refreshes on the same cadence
                with timer.phase("keys"):
                    keys = jax.random.split(device_key, 64 * K + 1)
                    device_key = keys[0]
                    rest = keys[1:]
                    # typed PRNG keys are (n,)-shaped, raw keys (n, 2) —
                    # group into 64 dispatches of K either way
                    key_buf = (list(rest.reshape(64, K, *rest.shape[1:]))
                               if K > 1 else list(rest))
                    if prog.takes_beta:
                        beta_dev = jax.device_put(
                            np.float32(replay.beta(lstep)))
            t_enqueue = time.perf_counter()
            with timer.phase("dispatch"):
                metrics = device_step(key_buf.pop())
                if block_each_step:
                    jax.block_until_ready(state.params)
        else:
            if is_per:
                with timer.phase("drain"):
                    memory.drain()
            with timer.phase("sample"):
                batch = memory.sample(ap.batch_size, rng)
            tracer.record("sample", timer.last_s * 1e3,
                          trace_id=tracing.current_trace())
            _last_idx[0] = np.asarray(batch.index)
            if _poison[0]:
                # poison_grad drill: a non-finite loss injected into
                # THIS update — the in-jit guard must skip it with
                # params provably unchanged (tests/test_health.py)
                _poison[0] = False
                batch = batch._replace(reward=np.full_like(
                    np.asarray(batch.reward), np.nan))
                print("[faults:learner] poison_grad: NaN rewards "
                      "injected into this update's batch", flush=True)
            t_enqueue = time.perf_counter()
            with timer.phase("dispatch"):
                state, metrics, td_abs = learner.step(state, batch)
            skipped_now = 0.0
            if is_per and isinstance(metrics, dict) \
                    and health.SKIPPED_KEY in metrics:
                # the PER path must know NOW (write-back suppression)
                # and already syncs td_abs to host — one extra scalar
                # rides the same sync, giving exact per-step skip
                # accounting.  Uniform paths keep full async dispatch
                # and sample the flag on the stats cadence instead.
                skipped_now = float(jax.device_get(
                    metrics[health.SKIPPED_KEY]))
                if skipped_now >= 0.5:
                    _win_skips[0] += 1
            if is_per:
                with timer.phase("priorities"):
                    if skipped_now < 0.5:
                        td_np = np.asarray(td_abs)
                        # |TD| scale feeds the anomaly detector's
                        # td_explosion signal on the stats cadence
                        _last_td[0] = float(np.mean(np.abs(td_np)))
                        memory.update_priorities(np.asarray(batch.index),
                                                 td_np)
                    # skipped step: the guard zeroed td_abs — writing it
                    # back would crush real priorities to epsilon
        stride = K if on_device else 1
        prev = lstep
        lstep += stride
        clock.set_learner_step(lstep)  # reference dqn_learner.py:94-95
        perf_mon.note_updates(stride)  # one int add; no-op when disabled
        last_metrics = metrics
        in_flight.append((jax.tree_util.tree_leaves(metrics)[0], t_enqueue,
                          stride, tracing.current_trace()))
        _reap()
        if ldone_at_entry is not None and ldone > ldone_at_entry:
            # the first dispatch is done, so its program is traced, lowered
            # and compiled or loaded: set-up as the compile record saw it
            ldone_at_entry = None
            report_setup(timing_writer, lstep)

        # cadences fire on boundary crossings so a multi-step dispatch
        # (stride > 1) never skips them
        crossed = lambda freq: freq and lstep // freq != prev // freq
        if crossed(ap.param_publish_freq):
            with timer.phase("publish"):
                _publish_async(state)
        if crossed(ap.checkpoint_freq):
            with timer.phase("checkpoint"):
                _save_epoch()

        if crossed(ap.learner_freq):  # reference dqn_learner.py:99-101
            # the window's bookkeeping, one host fetch included: the
            # phase's own row lands in the NEXT window's drain
            with timer.phase("stats"):
                now = time.monotonic()
                # sampled (not averaged) losses: the window's last step stands
                # in for the window, one host fetch total
                vals = {k: float(v)
                        for k, v in jax.device_get(last_metrics).items()}
                stats.add(
                    counter=1,
                    critic_loss=vals.get("learner/critic_loss", 0.0),
                    actor_loss=vals.get("learner/actor_loss", 0.0),
                    q_mean=vals.get("learner/q_mean", 0.0),
                    grad_norm=vals.get("learner/grad_norm", 0.0),
                    moe_aux=vals.get("learner/moe_aux", 0.0),
                    **{k: vals.get(f"learner/{k}", 0.0)
                       for k in stats.MOE_FIELDS},
                    exchange_rounds=vals.get(health.EXCHANGE_ROUNDS_KEY,
                                             0.0),
                    steps_per_sec=(lstep - last_stats_lstep)
                    / max(now - t_cadence, 1e-9),
                )
                # ---- sentinel window: guard skips + rolling anomalies ----
                # host PER counted every step (_win_skips); other paths read
                # the sampled flag of the window's last step/dispatch (the
                # fused path's flag already sums over its K substeps,
                # utils/health.reduce_scan_metrics)
                skipped_w = float(_win_skips[0]) or vals.get(
                    health.SKIPPED_KEY, 0.0)
                _win_skips[0] = 0
                if skipped_w:
                    clock.add_skipped_steps(int(round(skipped_w)))
                # ---- data-plane X-ray (ISSUE 8): provenance of what the
                # learner is actually consuming + the PER priority
                # distribution, exported on this cadence and fed to the
                # detector.  Host paths read their sidecars directly; the
                # device paths pay ONE bounded D2H each (a 256-row
                # provenance gather / the in-jit bucket histogram).
                prov = None
                prov_fn = getattr(memory, "provenance_of", None)
                if prov_fn is not None and _last_idx[0] is not None:
                    prov = prov_fn(_last_idx[0])
                    prov = None if prov is None else np.asarray(prov)
                elif on_device and _prov_sample is not None:
                    pr_dev, _ = _prov_sample(
                        replay.state, jax.random.fold_in(_tel_key, lstep),
                        n=256)
                    prov = np.asarray(pr_dev)
                cur_version = int(getattr(param_store, "version", 0) or 0)
                ds = (health.provenance_stats(prov, cur_version, lstep)
                      if prov is not None else None)
                if ds is not None:
                    timing_writer.histogram("learner/staleness",
                                            ds["staleness"].tolist(),
                                            step=lstep)
                    timing_writer.histogram("learner/sample_age",
                                            ds["age"].tolist(), step=lstep)
                    timing_writer.histogram("replay/actor_share",
                                            ds["shares"].tolist(),
                                            step=lstep)
                    perf_mon.set_gauge("data/staleness_p50",
                                       float(np.median(ds["staleness"])))
                    perf_mon.set_gauge("data/sample_age_p95",
                                       float(np.percentile(ds["age"], 95)))
                    perf_mon.set_gauge("data/top_actor_share",
                                       float(ds["shares"].max()))
                xray = None
                # mass/rows kept SEPARATE from the X-ray: an all-zero leaf
                # set yields xray=None, and the detector must still see
                # (mass ~0, rows > 0) — the degenerate collapse the signal
                # was originally built for
                p_mass, p_rows = None, 0
                leaves_fn = getattr(memory, "priority_leaves", None)
                leaves = leaves_fn() if leaves_fn is not None else None
                if leaves is not None and len(leaves):
                    p_mass = float(np.sum(leaves))
                    p_rows = int(len(leaves))
                    xray = health.priority_xray(leaves)
                elif on_device and _xray_dev is not None:
                    counts, ess, rows_d, mass = jax.device_get(
                        _xray_dev(replay.state))
                    rows_d = int(rows_d)
                    p_mass, p_rows = float(mass), rows_d
                    if rows_d:
                        xray = {"rows": rows_d, "mass": float(mass),
                                "ess": float(ess),
                                "ess_frac": float(ess) / rows_d,
                                "counts": np.asarray(counts),
                                "log10_lo": health.PRIORITY_XRAY_LOG10_LO,
                                "log10_hi": health.PRIORITY_XRAY_LOG10_HI}
                if xray is not None:
                    timing_writer.bucket_histogram(
                        "replay/priority", xray["counts"],
                        log10_lo=xray["log10_lo"], log10_hi=xray["log10_hi"],
                        step=lstep,
                        extra={"ess": xray["ess"],
                               "ess_frac": xray["ess_frac"],
                               "mass": xray["mass"], "rows": xray["rows"]})
                    timing_writer.scalars({
                        "replay/priority_ess": xray["ess"],
                        "replay/priority_ess_frac": xray["ess_frac"],
                    }, step=lstep)
                    perf_mon.set_gauge("data/priority_ess",
                                       xray["ess_frac"])
                anomalies = detector.observe(
                    loss=vals.get("learner/critic_loss"),
                    grad_norm=vals.get("learner/grad_norm"),
                    td_mean=_last_td[0],
                    priority_mass=p_mass,
                    replay_rows=p_rows,
                    skipped=skipped_w,
                    priority_ess=xray["ess_frac"] if xray else None)
                if anomalies:
                    recorder.record("anomaly", step=lstep, kinds=anomalies,
                                    streak=detector.streak)
                    print(f"[health] anomaly at step {lstep}: "
                          f"{'+'.join(anomalies)} (streak {detector.streak}"
                          f"/{hp.anomaly_threshold})", flush=True)
                timing_writer.scalars({
                    "health/skipped_steps": float(clock.skipped_steps.value),
                    "health/rollbacks": float(clock.rollbacks.value),
                    "health/anomaly_streak": float(detector.streak),
                }, step=lstep)
                if hp.rollback and detector.should_rollback():
                    _rollback("+".join(anomalies) if anomalies
                              else "anomaly streak")
                if perf_mon.enabled:
                    # throughput-attribution gauges the monitor can't see
                    # from inside: replay ratio on THIS run's steps (the
                    # pacing gate's own accounting) and how full the ingest
                    # queue is (1.0 = actors blocked on backpressure)
                    perf_mon.set_gauge(
                        "learner/replay_ratio",
                        (lstep - lstep0) * ap.batch_size
                        / max(int(clock.actor_step.value), 1))
                    _q = getattr(memory, "_q", None)
                    if _q is not None and hasattr(_q, "qsize"):
                        try:
                            depth = int(_q.qsize())
                            bound = int(getattr(memory, "max_queue_chunks",
                                                0))
                            perf_mon.set_gauge("learner/ingest_queue_depth",
                                               depth)
                            if bound:
                                perf_mon.set_gauge(
                                    "learner/ingest_queue_util",
                                    depth / bound)
                        except (NotImplementedError, OSError):
                            pass  # macOS mp queues have no qsize
                    timing_writer.scalars(perf_mon.drain(step=lstep),
                                          step=lstep)
                # bandwidth X-ray (ISSUE 18): the headline wire/replay/ckpt
                # series on the same stats cadence — wire/<link>/bytes_per_s
                # rates come from deltas against the previous emit
                wire_series = bandwidth.emit_scalars()
                if wire_series:
                    timing_writer.scalars(wire_series, step=lstep)
                timing_writer.scalars(timer.drain(), step=lstep)
                _flush_traces(lstep)
                t_cadence = now
                last_stats_lstep = lstep

    # nothing is enqueued any more: wait for the tail, so that
    # ``learner_done`` reaches ``learner_step`` (the final publication
    # below would wait for it anyway)
    jax.block_until_ready([entry[0] for entry in in_flight])
    _reap()
    # final publication + final checkpoint epoch so a next run can resume
    # — this is also the preemption path: a SIGTERM (runtime.py) trips
    # clock.stop, the loop above drains out, and the run's last complete
    # state is committed here before exit
    if _pub_thread is not None:
        _pub_stop.set()
        _pub_event.set()
        _pub_thread.join(timeout=120)
    if _pub_error:
        raise RuntimeError(
            f"parameter publication failed at learner step <= {lstep}; "
            f"run stopped") from _pub_error[0]
    _publish(state)
    _save_epoch()
    if perf_mon.enabled:
        # final partial window: short runs must still export their rates
        timing_writer.scalars(perf_mon.drain(step=lstep), step=lstep)
    # tail phases and spans of the final partial window
    timing_writer.scalars(timer.drain(), step=lstep)
    _flush_traces(lstep)
    timing_writer.close()


def memory_size(memory: Any) -> int:
    if hasattr(memory, "drain"):
        memory.drain()
    return memory.size


# ---------------------------------------------------------------------------
# elastic multi-learner replica plane (ISSUE 15)
# ---------------------------------------------------------------------------

def _key_data(key) -> np.ndarray:
    """Raw uint32 view of a PRNG key (typed or raw) — the key-stream
    schedule the parity oracle compares bit-for-bit."""
    import jax

    try:
        return np.asarray(jax.random.key_data(key)).copy()
    except (TypeError, AttributeError):  # raw uint32 keys
        return np.asarray(key).copy()


class ReplicaLearnerDriver:
    """One data-parallel learner replica of the elastic plane
    (ISSUE 15): the composition of the grad/apply split
    (factory.build_replica_grad_apply), a LOCAL HBM-style PER ring
    (memory/device_per.DevicePerReplay — every replica holds the full
    ring; the merged write-backs keep the N rings ONE logical priority
    plane), and the lease-fenced, generation-stamped gradient exchange
    through the gateway registry (parallel/dcn.py).

    Determinism contract (the degraded-parity oracle's substrate):

    - **Params** are initialised from ``opt.seed`` identically on every
      replica; every applied update is the registry's reduced mean, so
      the N TrainStates can never diverge while membership is stable.
    - **Experience** is the deterministic shared stream: ingest rows are
      minted from a counter-keyed RNG (``np_rng(seed, "replica-ingest",
      counter)``) every replica advances identically, so the N rings
      hold the same rows.  (Sharding the gateway ingest across replicas
      is the named next ROADMAP step; this plane is the fault-tolerance
      composition it will ride on.)
    - **Keys**: round ``r``'s sample key is ``fold_in(fold_in(base, r),
      rank)`` with ``rank`` = this replica's index in the SORTED live
      membership of the previous completed round.  Rank folding — not
      world-size folding — is what makes degradation seamless: when N
      shrinks to 1, the survivor at rank 0 draws the EXACT key stream a
      solo driver draws, so from the degradation round onward it is
      bit-identical to the solo learner (tests/test_replicas.py).
    - **Priorities**: each round's |TD| write-back rides the round
      submission; the registry's reply carries every survivor's
      write-back in ascending-replica order and each replica applies
      ALL of them sequentially — identical scatter sequence, identical
      rings.  A fenced (stale-generation) write-back is a counted
      reject at the registry and never reaches any ring.

    Faults: the ``REPLICA_FAULTS`` env plane (utils/faults.py) is
    consulted once per round — ``kill@N`` / ``hang@N[:S]`` / ``crash@N``
    are the production drill verbs (tools/chaos_soak.py --kill-replica /
    --hang-replica)."""

    def __init__(self, opt: Options, spec: EnvSpec, replica_id: int,
                 channel, writer=None,
                 ingest_rows_per_round: int = 0):
        import jax

        from pytorch_distributed_tpu.factory import (
            build_replica_grad_apply, build_train_state_and_step,
        )
        from pytorch_distributed_tpu.memory.device_per import (
            DevicePerReplay,
        )

        self.opt = opt
        self.spec = spec
        self.replica = replica_id
        self.channel = channel
        self.writer = writer
        self.ingest_rows_per_round = ingest_rows_per_round
        ap = opt.agent_params
        mp_ = opt.memory_params
        model = build_model(opt, spec)
        params = init_params(opt, spec, model, seed=opt.seed)
        # state construction shared with the solo learner (identical
        # optimizer chain -> checkpoint-interchangeable TrainStates);
        # the returned fused step is discarded — replicas train through
        # the split halves
        state, _ = build_train_state_and_step(opt, spec, model, params)
        pair = build_replica_grad_apply(opt, model)
        assert pair is not None, (
            f"replica plane does not support agent_type={opt.agent_type}")
        grad_fn, apply_fn = pair
        self._grad = jax.jit(grad_fn)
        self._apply = jax.jit(apply_fn, donate_argnums=0)
        self.state = jax.device_put(state)
        self.replay = DevicePerReplay(
            mp_.memory_size, spec.state_shape, spec.action_shape,
            state_dtype=np.dtype(mp_.state_dtype),
            action_dtype=spec.action_dtype,
            priority_exponent=mp_.priority_exponent,
            importance_weight=mp_.priority_weight,
            importance_anneal_steps=ap.steps)
        # ONE base key stream shared by every replica (index 0 on
        # purpose: rank folding differentiates replicas, the stream
        # itself must be common property)
        self._base_key = jax.random.PRNGKey(
            process_seed(opt.seed, "replica-plane", 0))
        self.round = 0
        self.members: list = []
        self.key_log: list = []      # (round, raw key bytes)
        self.fence_events = 0
        self.rejoins = 0
        self._ingest_counter = 0
        self._recorder = flight_recorder.get_recorder(
            f"replica-{replica_id}")

    # -- deterministic shared ingest ----------------------------------------

    def _synth_chunk(self, rows: int) -> Any:
        """``rows`` transitions minted from the counter-keyed shared
        stream — identical bytes on every replica at the same counter."""
        from pytorch_distributed_tpu.utils.experience import Transition

        ap = self.opt.agent_params
        rng = np_rng(self.opt.seed, "replica-ingest",
                     self._ingest_counter)
        self._ingest_counter += 1
        shape = (rows,) + tuple(self.spec.state_shape)
        sdt = np.dtype(self.opt.memory_params.state_dtype)
        if sdt.kind == "u":
            s0 = rng.integers(0, 256, size=shape).astype(sdt)
            s1 = rng.integers(0, 256, size=shape).astype(sdt)
        else:
            s0 = rng.standard_normal(shape).astype(sdt)
            s1 = rng.standard_normal(shape).astype(sdt)
        if self.spec.discrete:
            action = rng.integers(
                0, max(1, self.spec.num_actions),
                size=(rows,)).astype(np.int32)
        else:
            action = rng.standard_normal(
                (rows, self.spec.action_dim)).astype(np.float32)
        return Transition(
            state0=s0,
            action=action,
            reward=rng.standard_normal(rows).astype(np.float32),
            gamma_n=np.full(rows, ap.gamma ** ap.nstep, np.float32),
            state1=s1,
            terminal1=(rng.random(rows) < 0.05).astype(np.float32),
        )

    def prefill(self, rows: int) -> None:
        self.replay.feed_chunk(self._synth_chunk(rows))

    # -- state capture / restore (the oracle + the rejoin leg) ---------------

    def snapshot(self) -> dict:
        import jax

        return {
            "state": jax.device_get(self.state),
            "ring": jax.device_get(self.replay.state),
            "round": self.round,
            "ingest_counter": self._ingest_counter,
        }

    def load_snapshot(self, snap: dict) -> None:
        import jax

        self.state = jax.device_put(snap["state"])
        self.replay.state = jax.device_put(snap["ring"])
        self.round = snap["round"]
        self._ingest_counter = snap["ingest_counter"]

    @property
    def lstep(self) -> int:
        import jax

        return int(jax.device_get(self.state.step))

    def _commit_epoch(self) -> int:
        extras = dict(
            learner_step=self.lstep,
            replica_round=self.round,
            replica_ingest_counter=self._ingest_counter,
        )
        ckpt.save_epoch(
            self.opt.model_name, state=self.state, memory=self.replay,
            extras=extras, retain=self.opt.agent_params.checkpoint_retain)
        return self.lstep

    # -- the round loop ------------------------------------------------------

    def _rank(self) -> int:
        if not self.members:
            return 0
        try:
            return sorted(self.members).index(self.replica)
        except ValueError:
            return 0

    def run_rounds(self, until_round: int, *, stop=None, faults=None,
                   capture=None, on_round=None, rejoin: bool = False,
                   stats_every: int = 0) -> None:
        """Drive rounds ``[self.round, until_round)``.  ``capture(r,
        driver)`` fires after round ``r`` is fully applied (state,
        ring, key log current).  ``rejoin=True`` turns a fence into the
        epoch-barrier rejoin path instead of an exception."""
        import jax

        from pytorch_distributed_tpu.parallel.dcn import (
            RSTAT_OK, ReplicaFenced,
        )
        from pytorch_distributed_tpu.parallel.learner import (
            ReplicaExchange,
        )

        inj = faults if faults is not None \
            else FaultInjector.from_env("replica")
        exchange = ReplicaExchange(self.channel)
        t_win = time.monotonic()
        r_win = self.round
        while self.round < until_round:
            if stop is not None and stop.is_set():
                return
            r = self.round
            # the production fault plane: kill@N / hang@N / crash@N /
            # delay@N:S fire HERE, once per round
            inj.frame(b"")
            if self.ingest_rows_per_round > 0:
                self.prefill(self.ingest_rows_per_round)
            rank = self._rank()
            key = jax.random.fold_in(
                jax.random.fold_in(self._base_key, r), rank)
            self.key_log.append((r, _key_data(key)))
            beta = self.replay.beta(self.lstep)
            batch = self.replay.sample(
                self.opt.agent_params.batch_size, key, beta=beta)
            grads, ok, _metrics, td_abs = self._grad(self.state, batch)
            pidx = np.asarray(jax.device_get(batch.index), np.int32)
            ptd = np.abs(np.asarray(jax.device_get(td_abs), np.float32))
            try:
                reply, reduced = exchange.exchange(
                    r, grads, ok=bool(float(jax.device_get(ok)) > 0),
                    pidx=pidx, ptd=ptd)
            except (ConnectionError, OSError) as e:
                raise ReplicaFenced(
                    f"replica {self.replica} lost the registry: {e}")
            if reply["status"] != RSTAT_OK:
                self.fence_events += 1
                self._recorder.record("replica-fenced", round=r,
                                      status=reply["status"])
                if rejoin:
                    self.rejoin()
                    continue
                raise ReplicaFenced(
                    f"replica {self.replica} fenced at round {r} "
                    f"(status {reply['status']})")
            self.members = list(reply["members"])
            if reduced is not None:
                self.state = self._apply(self.state, reduced,
                                         np.float32(1.0))
            # merged |TD| write-backs, applied in the reply's
            # deterministic order on EVERY replica — one logical
            # priority plane across N rings
            # (memory/device_per.per_apply_writeback_groups)
            from pytorch_distributed_tpu.memory.device_per import (
                per_apply_writeback_groups,
            )

            self.replay.state = per_apply_writeback_groups(
                self.replay.state,
                [(w[1], w[2]) for w in reply["writebacks"]],
                alpha=self.replay.alpha)
            self.round = r + 1
            if reply.get("epoch_due") and self._rank() == 0:
                step = self._commit_epoch()
                self.channel.note_epoch(r, step)
            if capture is not None:
                capture(r, self)
            if on_round is not None:
                on_round(r, reply)
            if stats_every and (r + 1) % stats_every == 0 \
                    and self.writer is not None:
                now = time.monotonic()
                self.writer.scalar(
                    "learner/updates_per_s",
                    (self.round - r_win) / max(now - t_win, 1e-9),
                    step=self.lstep)
                self.writer.scalar("replica/round", float(self.round),
                                   step=self.lstep)
                self.writer.flush()
                t_win, r_win = now, self.round

    # -- elastic rejoin ------------------------------------------------------

    def rejoin(self, timeout: float = 60.0) -> None:
        """Rejoin at a NEW generation: re-lease, wait for the join
        barrier's committed epoch, load that exact state (params, opt
        state, ring, counters), fast-forward to the join round, and
        activate — the survivors held the entry round for us."""
        from pytorch_distributed_tpu.parallel.dcn import ReplicaFenced

        reply = self.channel.acquire()
        self.rejoins += 1
        self.members = list(reply.get("members", []))
        self.channel.start_renewer()
        barrier = reply.get("epoch_barrier")
        self._recorder.record("rejoin", generation=reply["generation"],
                              barrier=barrier)
        if barrier is None:
            # no live peers = a fresh plane OR a whole-fleet restart
            # behind a fresh registry.  "Rejoin = fetch the latest
            # committed epoch": restore it exactly as the solo learner
            # would (resume="never" opts out, same contract), so a
            # supervisor-restarted replicated fleet never silently
            # retrains from seed-initialised params
            self.round = int(reply.get("round", 0))
            if self.opt.resume != "never":
                info = ckpt.resolve_epoch(self.opt.model_name)
                if info is not None:
                    import jax

                    self.state = jax.device_put(ckpt.load_epoch_state(
                        info, jax.device_get(self.state)))
                    if info.has_replay:
                        ckpt.load_epoch_replay(info, self.replay)
                    self.round = max(self.round, int(
                        info.extras.get("replica_round", 0)))
                    self._ingest_counter = int(info.extras.get(
                        "replica_ingest_counter",
                        self._ingest_counter))
                    print(f"[replica] {self.replica} resumed epoch "
                          f"{info.epoch} (step {info.learner_step}, "
                          f"round {self.round})", flush=True)
            return
        deadline = time.monotonic() + timeout
        epoch_step = None
        while time.monotonic() < deadline:
            j = self.channel.poll_join()
            if j is None:
                # join cancelled (timeout server-side): fenced again
                raise ReplicaFenced(
                    f"replica {self.replica} join cancelled")
            if j.get("epoch_step") is not None:
                epoch_step = int(j["epoch_step"])
                break
            time.sleep(0.05)
        if epoch_step is None:
            raise ReplicaFenced(
                f"replica {self.replica} barrier epoch never committed")
        info = ckpt.await_epoch(self.opt.model_name, epoch_step,
                                timeout=max(5.0, deadline
                                            - time.monotonic()))
        if info is None:
            raise ReplicaFenced(
                f"replica {self.replica} could not resolve the barrier "
                f"epoch (step >= {epoch_step})")
        import jax

        self.state = jax.device_put(ckpt.load_epoch_state(
            info, jax.device_get(self.state)))
        if info.has_replay:
            ckpt.load_epoch_replay(info, self.replay)
        self.round = int(reply.get("round",
                                   info.extras.get("replica_round", 0)))
        self._ingest_counter = int(info.extras.get(
            "replica_ingest_counter", self._ingest_counter))
        act = self.channel.activate(epoch_step)
        self.members = list(act.get("members", self.members))
        print(f"[replica] {self.replica} rejoined at generation "
              f"{self.channel.generation}, round {self.round} "
              f"(epoch step {epoch_step})", flush=True)


def run_replica_learner(opt: Options, spec: EnvSpec, process_ind: int,
                        memory: Any, param_store: ParamStore,
                        clock: GlobalClock, stats: LearnerStats,
                        replica_id: Optional[int] = None) -> None:
    """Production wrapper around ``ReplicaLearnerDriver``: the learner
    role of a replicated fleet.  Replica 0 is the LEAD — it runs in the
    gateway's own process and joins through a LocalReplicaChannel
    against the in-process registry (fleet.FleetTopology wires it);
    replicas >= 1 run on other hosts (``fleet.py --role
    learner-replica``) and dial ``replica_params.coordinator``.  The
    ``memory`` handle of the solo learner is not consumed — the replica
    plane's experience is the deterministic shared stream (driver
    docstring); a loud note says so once."""
    from pytorch_distributed_tpu.parallel import dcn as dcn_mod

    rp = dcn_mod.resolve_replica(opt.replica_params)
    rid = int(replica_id if replica_id is not None else process_ind)
    registry = dcn_mod.local_registry()
    if registry is not None:
        channel = dcn_mod.LocalReplicaChannel(registry, rid)
    else:
        host, _, port = rp.coordinator.rpartition(":")
        channel = dcn_mod.ReplicaClient((host, int(port)), rid,
                                        params=rp)
    ap = opt.agent_params
    timing_writer = MetricsWriter(opt.log_dir, enable_tensorboard=False,
                                  role="learner", run_id=opt.refs)
    driver = ReplicaLearnerDriver(opt, spec, rid, channel,
                                  writer=timing_writer,
                                  ingest_rows_per_round=0)
    if memory is not None:
        print(f"[replica] {rid}: the replica plane trains from the "
              f"deterministic shared stream; the local ingest queue is "
              f"drained but not consumed (sharded gateway ingest is the "
              f"next ROADMAP step)", flush=True)
    # the initial lease goes through the REJOIN path: a fresh plane
    # grants round 0 and falls through; a replacement process entering
    # mid-training gets the join barrier and syncs from the committed
    # epoch instead of bouncing a stale round 0 off the registry
    driver.rejoin(timeout=max(60.0, 4.0 * rp.join_timeout_s))
    channel.wait_members(rp.replicas,
                         timeout=4.0 * max(rp.lease_s, 0.5))
    driver.members = channel.members()
    if driver.round == 0:
        driver.prefill(min(max(ap.learn_start, ap.batch_size),
                           opt.memory_params.memory_size))

    import jax
    from jax.flatten_util import ravel_pytree

    from pytorch_distributed_tpu.factory import published_params

    def _publish() -> None:
        flat, _ = ravel_pytree(jax.device_get(
            published_params(opt, driver.state)))
        param_store.publish(np.asarray(flat, dtype=np.float32))

    _publish()

    def _on_round(r: int, reply: dict) -> None:
        clock.bump_progress("learner")
        clock.set_learner_step(driver.lstep)
        if ap.param_publish_freq and \
                (r + 1) % ap.param_publish_freq == 0:
            _publish()
        if ap.checkpoint_freq and (r + 1) % ap.checkpoint_freq == 0 \
                and driver._rank() == 0:
            driver._commit_epoch()
        if memory is not None and hasattr(memory, "drain"):
            # keep a hybrid topology's ingest queue from backing up
            # while the replica plane trains from the shared stream
            memory.drain()

    try:
        driver.run_rounds(ap.steps, stop=clock.stop,
                          on_round=_on_round, rejoin=(rid != 0),
                          stats_every=max(1, ap.learner_freq))
    finally:
        _publish()
        if driver._rank() == 0 and driver.round > 0:
            driver._commit_epoch()
        channel.release()
        channel.close()
        timing_writer.close()
