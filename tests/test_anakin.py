"""The closed Anakin loop (ISSUE 12): co-located env fleet + learner.

The headline is the parity oracle: under a fixed seed and the
strict-alternation schedule, a co-located ``AnakinDriver`` run must be
bit-identical to the split-process ``actor_backend="device"`` path —
ring contents, PER priorities, and learner params after N steps —
because every XLA program involved is the SAME program the split path
dispatches (the fused rollout and the fused learner step); only the
host plumbing between them (spawn queue, pickle, chunk D2H/H2D)
vanishes.  The split leg here IS that plumbing: the chunk-emit rollout,
the real ``QueueFeeder`` -> mp queue -> ``DevicePerIngest.drain``
chain, and the learner's exact fused-step construction and key-stream
schedule, driven to the schedule the driver itself chose.

Geometry note: the split drain feeds the ring in ``chunk_sizes`` preset
multiples (smallest = 64) and parks the remainder pending — so the
parity geometry makes every dispatch's emission count a multiple of 64
((K - nstep) * N = 64, then K * N = 128); otherwise the split ring
would lag the co-located ring by the pending tail at each learn and
the sampled batches (hence params) would diverge for a reason that is
queue cadence, not semantics.

Satellites covered here: the duty-cycle scheduler + double-buffer swap
protocol (host logic, no dispatches), the no-actor-workers topology
contract, the transfer-audit-clean experience path, and the fleet
STATUS ``anakin`` panel block.
"""

import json
import threading
import time

import numpy as np
import pytest

from pytorch_distributed_tpu.config import build_options
from pytorch_distributed_tpu.utils.experience import REPLAY_FIELDS


def _anakin_opts(tmp_path, **overrides):
    """Config-12 (pong-sim + device-per HBM ring) shrunk for CPU: the
    mlp head keeps compiles in seconds while exercising the real env
    fleet, ring scatter, PER write-back and fused learner step."""
    base = dict(
        root_dir=str(tmp_path), refs="anakin_t", num_actors=1,
        num_envs_per_actor=16, actor_backend="anakin", visualize=False,
        # dqn-mlp keeps compiles fast, but the mlp default ring schema
        # is float32 while the pong-sim device env emits uint8 frames —
        # pin the ring to uint8 (the config-12 cnn default) so the
        # split leg's ingest quarantine accepts the rollout's rows
        model_type="dqn-mlp", state_dtype="uint8",
        nstep=4, memory_size=256, learn_start=64,
        batch_size=32, steps=10 ** 6, early_stop=50,
        actor_freq=10 ** 9, learner_freq=10 ** 9,
        param_publish_freq=10 ** 9, checkpoint_freq=10 ** 9)
    base.update(overrides)
    opt = build_options(config=12, **base)
    opt.env_params.device_rollout_ticks = 8
    return opt


def _make_driver(opt):
    from pytorch_distributed_tpu.agents.anakin import AnakinDriver
    from pytorch_distributed_tpu.agents.clocks import (
        ActorStats, GlobalClock, LearnerStats,
    )
    from pytorch_distributed_tpu.agents.param_store import (
        ParamStore, make_flattener,
    )
    from pytorch_distributed_tpu.factory import (
        build_memory, build_model, init_params, probe_env,
    )

    spec = probe_env(opt)
    handles = build_memory(opt, spec)
    model = build_model(opt, spec)
    flat0, _ = make_flattener(init_params(opt, spec, model,
                                          seed=opt.seed))
    store = ParamStore(flat0.size)
    drv = AnakinDriver(opt, spec, handles.learner_side, store,
                       GlobalClock(), LearnerStats(),
                       actor_stats=ActorStats())
    return drv, handles, spec


class TestBackendGate:
    def test_eligible_config_resolves_anakin(self, tmp_path):
        from pytorch_distributed_tpu.factory import (
            anakin_active, resolve_actor_backend,
        )

        opt = _anakin_opts(tmp_path)
        assert resolve_actor_backend(opt) == "anakin"
        assert anakin_active(opt)

    def test_host_memory_downgrades_to_device(self, tmp_path):
        """anakin needs the HBM ring for the in-graph scatter; host
        replay falls back to the split-process device schedule."""
        from pytorch_distributed_tpu.factory import (
            anakin_active, resolve_actor_backend,
        )

        opt = build_options(
            config=4, root_dir=str(tmp_path), num_actors=1,
            actor_backend="anakin", visualize=False)
        with pytest.warns(UserWarning, match="anakin"):
            assert resolve_actor_backend(opt) == "device"
        assert not anakin_active(opt)

    def test_no_device_env_downgrades_all_the_way(self, tmp_path):
        """fake env has no device implementation: anakin -> device ->
        pipelined, warning at each gate."""
        from pytorch_distributed_tpu.factory import (
            anakin_active, resolve_actor_backend,
        )

        opt = build_options(
            config=1, root_dir=str(tmp_path), num_actors=1,
            memory_type="device", actor_backend="anakin",
            visualize=False)
        with pytest.warns(UserWarning):
            assert resolve_actor_backend(opt) == "pipelined"
        assert not anakin_active(opt)


class TestParityOracle:
    """Co-located vs split-process, one shared two-leg run."""

    DISPATCHES = 8  # strict alternation: 4 rollouts + 4 learner steps

    @pytest.fixture(scope="class")
    def run(self, tmp_path_factory):
        import jax

        from pytorch_distributed_tpu.agents.param_store import (
            make_flattener,
        )

        tmp = tmp_path_factory.mktemp("anakin_parity")

        # ---- leg A: the co-located driver, recording its schedule ----
        opt_a = _anakin_opts(tmp / "a")
        drv, handles_a, _spec = _make_driver(opt_a)
        assert drv.is_per and len(drv.rings) == 1
        schedule, fed_rows = [], 0
        for _ in range(self.DISPATCHES):
            if drv.want_rollout():
                st = drv.dispatch_rollout()
                fed_rows += int(st.fed)
                schedule.append("R")
            else:
                drv.dispatch_learn()
                schedule.append("L")
        ring_a = jax.device_get(drv.rings[0].state)
        flat_a, _ = make_flattener(jax.device_get(drv.state.params))
        handles_a.learner_side.close()

        # ---- leg B: the split-process path's exact pieces, driven to
        # the same schedule ----
        opt_b = _anakin_opts(tmp / "b", actor_backend="device")
        ring_b, flat_b, chunks = self._split_leg(opt_b, schedule)
        return dict(schedule=schedule, ring_a=ring_a, flat_a=flat_a,
                    ring_b=ring_b, flat_b=flat_b, chunks=chunks,
                    fed_rows=fed_rows)

    def _split_leg(self, opt, schedule):
        """The split-process ``actor_backend="device"`` path in one
        process: chunk-emit rollout -> QueueFeeder -> mp queue ->
        DevicePerIngest.drain -> the learner's fused step, with the
        actor acting on the train state's params each dispatch (the
        zero-staleness sync anakin gives by construction)."""
        import jax
        import jax.numpy as jnp

        from pytorch_distributed_tpu.agents.param_store import (
            make_flattener,
        )
        from pytorch_distributed_tpu.factory import (
            build_device_env, build_memory, build_model, init_params,
            build_train_state_and_step, probe_env,
        )
        from pytorch_distributed_tpu.models.policies import (
            apex_epsilons, build_fused_rollout, init_rollout_carry,
        )
        from pytorch_distributed_tpu.parallel.learner import (
            ShardedLearner,
        )
        from pytorch_distributed_tpu.parallel.mesh import make_mesh
        from pytorch_distributed_tpu.utils.experience import (
            Transition, make_prov,
        )
        from pytorch_distributed_tpu.utils.rngs import (
            np_rng, process_key,
        )

        ap = opt.agent_params
        pp = opt.parallel_params
        spec = probe_env(opt)
        ingest = build_memory(opt, spec).learner_side
        mesh = None
        if len(jax.devices()) > 1:
            mesh = make_mesh(pp.dp_size, pp.mp_size, pp.sp_size,
                             pp.ep_size, pp.pp_size)
        model = build_model(opt, spec)
        params = init_params(opt, spec, model, seed=opt.seed)
        state, step_fn = build_train_state_and_step(opt, spec, model,
                                                    params, mesh=mesh)
        learner = ShardedLearner(step_fn, mesh, donate=pp.donate)
        state = learner.place(state)
        ring = ingest.attach(mesh=mesh)
        fused = ring.build_fused_step(step_fn, ap.batch_size,
                                      donate=pp.donate,
                                      steps_per_call=1)
        device_key = jax.random.PRNGKey(
            np_rng(opt.seed, "learner", 0).integers(2 ** 31))
        key_buf, beta_dev, lstep = [], None, 0

        N = opt.env_params.num_envs_per_actor
        K = opt.env_params.device_rollout_ticks
        env = build_device_env(opt, 0, N)
        roll = build_fused_rollout(model.apply, env, nstep=ap.nstep,
                                   gamma=ap.gamma, rollout_ticks=K,
                                   emit="chunk")
        carry = init_rollout_carry(env, ap.nstep)
        base_key = jnp.asarray(process_key(opt.seed, "actor", 0))
        eps = jnp.asarray(apex_epsilons(0, 1, N, ap.eps, ap.eps_alpha),
                          jnp.float32)
        feeder = ingest.make_feeder()
        tick0 = jnp.int32(0)
        fed_expected, chunks = 0, []
        for kind in schedule:
            if kind == "R":
                carry, chunk = roll(state.params, carry, base_key,
                                    tick0, eps)
                tick0 = tick0 + K
                ch = jax.device_get(chunk)
                chunks.append(ch)
                valid = np.asarray(ch.valid)
                for k in range(K):
                    for j in range(N):
                        if not valid[k, j]:
                            continue
                        feeder.feed(Transition(
                            state0=ch.state0[k, j],
                            action=ch.action[k, j],
                            reward=ch.reward[k, j],
                            gamma_n=ch.gamma_n[k, j],
                            state1=ch.state1[k, j],
                            terminal1=ch.terminal1[k, j],
                            prov=make_prov(0, j, 0, lstep)), None)
                        fed_expected += 1
                feeder.flush()
            else:
                # the learner loop's drain cadence, held until the
                # queue's feeder thread has landed everything (in the
                # real topology the next loop iteration retries)
                deadline = time.monotonic() + 30.0
                while (ingest._fed_total < fed_expected
                       and time.monotonic() < deadline):
                    ingest.drain()
                    time.sleep(0.002)
                assert ingest._fed_total == fed_expected, \
                    "split drain never caught up — queue stall"
                if not key_buf:
                    keys = jax.random.split(device_key, 64 + 1)
                    device_key = keys[0]
                    key_buf = list(keys[1:])
                    beta_dev = jax.device_put(
                        np.float32(ring.beta(lstep)))
                state, ring.state, _m = fused(state, ring.state,
                                              key_buf.pop(), beta_dev)
                lstep += 1
        ring_b = jax.device_get(ring.state)
        flat_b, _ = make_flattener(jax.device_get(state.params))
        ingest.close()
        return ring_b, flat_b, chunks

    def test_schedule_is_strict_alternation_after_warmup(self, run):
        sched = "".join(run["schedule"])
        # min_fill = learn_start = 64 = the first dispatch's emissions
        assert sched == "RLRLRLRL"

    def test_ring_contents_bit_identical(self, run):
        a, b = run["ring_a"], run["ring_b"]
        for f in REPLAY_FIELDS:
            np.testing.assert_array_equal(
                np.asarray(getattr(a, f)), np.asarray(getattr(b, f)),
                err_msg=f"ring field {f} diverged")
        assert int(a.pos) == int(b.pos)
        assert int(a.fill) == int(b.fill)

    def test_per_priorities_bit_identical(self, run):
        a, b = run["ring_a"], run["ring_b"]
        np.testing.assert_array_equal(np.asarray(a.priority),
                                      np.asarray(b.priority))
        assert float(a.max_priority) == float(b.max_priority)

    def test_learner_params_bit_identical(self, run):
        np.testing.assert_array_equal(run["flat_a"], run["flat_b"])

    def test_actions_bit_identical(self, run):
        """The split leg's chunk actions (every tick, valid or not)
        against the co-located ring's action column: emitted actions
        land row-for-row, so equality of the ring column + the env
        closure over actions covers the action stream."""
        acts = []
        for ch in run["chunks"]:
            valid = np.asarray(ch.valid)
            K, N = valid.shape
            for k in range(K):
                for j in range(N):
                    if valid[k, j]:
                        acts.append(np.asarray(ch.action[k, j]))
        assert len(acts) == run["fed_rows"]
        ring_act = np.asarray(run["ring_a"].action)
        cap = ring_act.shape[0]
        assert len(acts) >= cap  # the run wraps: every slot rewritten
        exp = np.zeros_like(ring_act)
        for i, a in enumerate(acts):  # later writes win, like the ring
            exp[i % cap] = a
        np.testing.assert_array_equal(ring_act, exp)
        assert int(run["ring_a"].fill) == cap

    def test_provenance_scattered_in_graph(self, run):
        """Written rows carry in-graph stamps (actor 0, their env
        slot), not the -1 sentinel — the ISSUE-8 columns survive the
        co-located scatter."""
        prov = np.asarray(run["ring_a"].prov)
        fill = int(run["ring_a"].fill)
        assert (prov[:fill, 0] == 0).all()          # actor_id
        assert (prov[:fill, 1] >= 0).all()          # env_slot
        assert (prov[:fill, 1] < 16).all()


class TestDutyCycleScheduler:
    """Host-side scheduler logic: no dispatches, just the driver's
    bookkeeping — constructing a driver compiles nothing (the jit
    wrappers trace on first call and the perf plane is off)."""

    @pytest.fixture(scope="class")
    def drv(self, tmp_path_factory):
        opt = _anakin_opts(tmp_path_factory.mktemp("anakin_sched"),
                           double_buffer=True, learn_start=32)
        d, handles, _ = _make_driver(opt)
        yield d
        handles.learner_side.close()

    def _reset(self, d):
        d._fill = [0 for _ in d.rings]
        d._fresh = 0
        d.sample_ix = d.write_ix = 0
        d.frames = 0
        d.lstep = d.lstep0 = 0
        d._last_was_rollout = False

    def test_double_buffer_geometry(self, drv):
        assert len(drv.rings) == 2
        assert drv.rings[0].capacity == drv.rings[1].capacity == 128
        assert drv.min_fill == 32

    def test_warmup_forces_rollouts(self, drv):
        self._reset(drv)
        assert drv.want_rollout()
        drv._fill[0] = drv.min_fill - 1
        assert drv.want_rollout()

    def test_cold_start_split_then_swap_on_fresh(self, drv):
        self._reset(drv)
        # cold start: write half detaches once it holds min_fill
        drv._fill[0] = drv.min_fill
        drv._maybe_swap()
        assert (drv.sample_ix, drv.write_ix) == (0, 1)
        # fresh rows below the bar: no swap
        drv._fresh = drv.min_fill - 1
        drv._maybe_swap()
        assert (drv.sample_ix, drv.write_ix) == (0, 1)
        # bar reached: halves swap and the fresh counter re-arms
        drv._fresh = drv.min_fill
        drv._maybe_swap()
        assert (drv.sample_ix, drv.write_ix) == (1, 0)
        assert drv._fresh == 0

    def test_sample_half_never_the_write_half_after_detach(self, drv):
        self._reset(drv)
        drv._fill[0] = drv.min_fill
        for _ in range(8):
            drv._fresh = drv.min_fill
            drv._maybe_swap()
            assert drv.sample_ix != drv.write_ix

    def test_strict_alternation_when_ratio_zero(self, drv):
        self._reset(drv)
        drv._fill[0] = drv.min_fill
        drv._maybe_swap()
        assert drv.an.rollout_ratio == 0
        drv._last_was_rollout = True
        assert not drv.want_rollout()
        drv._last_was_rollout = False
        assert drv.want_rollout()

    def test_rollout_ratio_setpoint(self, drv):
        import dataclasses

        self._reset(drv)
        drv._fill[0] = drv.min_fill
        drv._maybe_swap()
        drv.an = dataclasses.replace(drv.an, rollout_ratio=128.0)
        try:
            drv.lstep = drv.lstep0 + 2  # 2 updates -> setpoint 256
            drv.frames = 255
            assert drv.want_rollout()
            drv.frames = 256
            assert not drv.want_rollout()
        finally:
            drv.an = dataclasses.replace(drv.an, rollout_ratio=0.0)

    def test_env_knob_override(self, monkeypatch):
        from pytorch_distributed_tpu.agents.anakin import resolve_anakin
        from pytorch_distributed_tpu.config import AnakinParams

        monkeypatch.setenv("TPU_APEX_ANAKIN_ROLLOUT_RATIO", "64")
        monkeypatch.setenv("TPU_APEX_ANAKIN_DOUBLE_BUFFER", "1")
        monkeypatch.setenv("TPU_APEX_ANAKIN_MIN_FILL", "7")
        ap = AnakinParams()
        out = resolve_anakin(ap)
        assert (out.rollout_ratio, out.double_buffer, out.min_fill) \
            == (64.0, True, 7)
        assert ap.rollout_ratio == 0.0  # input never mutated


class TestResume:
    def test_resume_seeds_cumulative_frames(self, tmp_path):
        """Duty-cycle counters ride the checkpoint: a resumed driver
        restores the CUMULATIVE frames count next to the restored
        lstep/lstep0 — a zeroed counter would read as a frames deficit
        of (lstep - lstep0) * rollout_ratio and flood rollout-only
        (zero updates, zero stats cadences) until it caught up."""
        opt = _anakin_opts(tmp_path, num_envs_per_actor=4,
                           learn_start=8, batch_size=8,
                           rollout_ratio=64.0)
        opt.env_params.device_rollout_ticks = 8
        drv, handles, _ = _make_driver(opt)
        try:
            for _ in range(4):
                if drv.want_rollout():
                    drv.dispatch_rollout()
                else:
                    drv.dispatch_learn()
            frames, lstep = drv.frames, drv.lstep
            assert frames > 0 and lstep > drv.lstep0
            deficit = (lstep - drv.lstep0) * drv.an.rollout_ratio \
                - frames
            drv._save_epoch()
        finally:
            drv.writer.close()
            handles.learner_side.close()

        drv2, handles2, _ = _make_driver(opt)
        try:
            assert drv2.lstep == lstep
            assert drv2.frames == frames, \
                "resume zeroed the duty-cycle frames counter"
            # the setpoint deficit survives the restart unchanged — a
            # zeroed counter would inflate it by every frame ever
            # collected (the rollout-only flood)
            assert (drv2.lstep - drv2.lstep0) * drv2.an.rollout_ratio \
                - drv2.frames == deficit
        finally:
            drv2.writer.close()
            handles2.learner_side.close()


class TestTopologyContract:
    def test_no_actor_workers_spawn(self, tmp_path):
        """anakin_active topologies carry zero actor worker specs and
        no actor slots on the watchdog board — the learner IS the
        fleet."""
        from pytorch_distributed_tpu.runtime import Topology

        opt = _anakin_opts(tmp_path, num_actors=4)
        topo = Topology(opt)
        try:
            assert topo.anakin
            roles = [s[0] for s in topo._worker_specs()]
            assert "actor" not in roles
            assert "logger" in roles
        finally:
            topo.handles.learner_side.close()

    def test_split_topology_keeps_actor_workers(self, tmp_path):
        from pytorch_distributed_tpu.runtime import Topology

        opt = _anakin_opts(tmp_path, num_actors=2,
                           actor_backend="device")
        topo = Topology(opt)
        try:
            assert not topo.anakin
            roles = [s[0] for s in topo._worker_specs()]
            assert roles.count("actor") == 2
        finally:
            topo.handles.learner_side.close()


class TestAuditAndPerfPlane:
    def test_dispatches_transfer_free_and_mfu_combined(self, tmp_path,
                                                       monkeypatch):
        """The acceptance bar's transfer claim, in-process: with the
        perf plane + transfer audit on, a rollout->learn->rollout
        cycle stages ZERO implicit host->device transfers (the
        explicit 12-byte prov device_put is control plane and passes
        by definition), and the drained MFU sums the update- and
        frame-denominated programs."""
        monkeypatch.setenv("TPU_APEX_PERF", "1")
        monkeypatch.setenv("TPU_APEX_PERF_TRANSFER_AUDIT", "1")
        from pytorch_distributed_tpu.utils import perf

        perf.reset()
        try:
            opt = _anakin_opts(tmp_path, num_envs_per_actor=4,
                               learn_start=8, batch_size=8)
            opt.env_params.device_rollout_ticks = 4
            drv, handles, _ = _make_driver(opt)
            assert drv.audit is not None
            drv.perf.drain()  # anchor the rate window
            for _ in range(6):
                if drv.want_rollout():
                    drv.dispatch_rollout()
                else:
                    drv.dispatch_learn()
            assert drv.audit.total == 0, \
                f"implicit transfers on the experience path: " \
                f"{drv.audit.sites}"
            # the zero-copy scatter shows up in the ingest's host
            # accounting (fleet STATUS replay_size/fill would read a
            # busy ring as empty otherwise)
            assert handles.learner_side.size > 0
            assert drv.replay_fill() > 0
            assert drv.perf.flops_per_update and \
                drv.perf.flops_per_update > 0
            assert drv.perf.flops_per_frame and \
                drv.perf.flops_per_frame > 0
            rows = drv.perf.drain(step=drv.lstep)
            assert rows["learner/achieved_flops_per_s"] == pytest.approx(
                rows["learner/updates_per_s"]
                * drv.perf.flops_per_update
                + rows["learner/env_frames_per_s"]
                * drv.perf.flops_per_frame, rel=1e-6)
            assert "anakin_rollout" in drv.perf.retraces._names
            handles.learner_side.close()
        finally:
            perf.reset()


class TestFleetStatusAnakinBlock:
    def test_health_snapshot_carries_anakin_block(self, tmp_path,
                                                  monkeypatch):
        """ISSUE 12 satellite: the gateway STATUS payload carries the
        co-located loop's vitals — fleet_top renders them and the
        --json consumers read them verbatim."""
        import json as _json

        monkeypatch.setenv("TPU_APEX_PERF", "1")
        from pytorch_distributed_tpu.fleet import FleetTopology
        from pytorch_distributed_tpu.utils import perf

        perf.reset()
        try:
            opt = _anakin_opts(tmp_path)
            topo = FleetTopology(opt, local_actors=0, port=0)
            try:
                assert topo.anakin
                mon = perf.get_monitor("learner")
                mon.note_updates(10)
                mon.drain()
                mon.set_gauge("anakin/duty_cycle", 0.44)
                mon.set_gauge("anakin/rollout_frames_per_s", 1234.0)
                mon.set_gauge("anakin/replay_fill", 0.5)
                mon.drain()
                h = topo._health_snapshot()
                blk = h["anakin"]
                assert blk["backend"] == "anakin"
                assert blk["duty_cycle"] == pytest.approx(0.44)
                assert blk["rollout_frames_per_s"] == pytest.approx(
                    1234.0)
                assert blk["replay_fill"] == pytest.approx(0.5)
                assert "actors" not in h or not h.get("actors")
                _json.dumps(h)  # the --json path must serialize
                from tools.fleet_top import anakin_line, render

                line = anakin_line(h)
                assert line and "duty 44%" in line
                assert "anakin:" in render(h)
            finally:
                topo.gateway.close()
        finally:
            perf.reset()


# ---------------------------------------------------------------------------
# acceptance: the full co-located topology, live (slow tier)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.timeout(420)
class TestAnakinTopologyAcceptance:
    def test_full_topology_closed_loop(self, tmp_path, monkeypatch):
        """ISSUE 12 acceptance drill: the REAL anakin topology — fleet
        gateway + logger + the co-located learner/env-fleet loop — runs
        a bounded training session end to end.  Verified live: the
        STATUS ``anakin`` block appears mid-run with a real duty cycle
        and zero actor slots; verified post-run: the duty-cycle
        telemetry landed in the metrics stream, the logger's actor
        curves flowed without any actor worker existing, and a complete
        checkpoint epoch committed (the preemption/resume surface the
        driver shares with the split learner)."""
        monkeypatch.setenv("TPU_APEX_PERF", "1")
        monkeypatch.setenv("TPU_APEX_PERF_PEAK_FLOPS", "1e12")
        from pytorch_distributed_tpu.fleet import FleetTopology
        from pytorch_distributed_tpu.parallel.dcn import fetch_status
        from pytorch_distributed_tpu.utils import perf
        from pytorch_distributed_tpu.utils.checkpoint import resolve_epoch
        from pytorch_distributed_tpu.utils.metrics import read_scalars

        perf.reset()
        try:
            opt = _anakin_opts(
                tmp_path, steps=160, max_seconds=240.0,
                learner_freq=10, actor_freq=64, logger_freq=1,
                checkpoint_freq=50, param_publish_freq=40,
                evaluator_nepisodes=0)
            topo = FleetTopology(opt, local_actors=0, port=0)
            done = threading.Event()

            def run():
                try:
                    topo.run(backend="thread")
                finally:
                    done.set()

            t = threading.Thread(target=run, daemon=True)
            t.start()
            addr = ("127.0.0.1", topo.port)
            try:
                status, blk = None, None
                deadline = time.monotonic() + 300
                while time.monotonic() < deadline and not done.is_set():
                    try:
                        status = fetch_status(addr, timeout=5.0)
                    except (ConnectionError, OSError):
                        status = None
                    blk = (status or {}).get("anakin")
                    if blk and blk.get("duty_cycle") is not None:
                        break
                    time.sleep(0.25)
                assert blk, "anakin block never appeared in STATUS"
                assert blk["backend"] == "anakin"
                assert 0.0 < blk["duty_cycle"] < 1.0
                assert blk["rollout_frames_per_s"] > 0
                assert not status.get("actors"), \
                    "actor slots exist on an anakin topology"
                json.dumps(status)
            finally:
                t.join(360)
            assert not t.is_alive()

            rows = read_scalars(opt.log_dir)
            by_tag = {}
            for r in rows:
                if "value" in r:
                    by_tag.setdefault(r["tag"], []).append(r["value"])
            for tag in ("anakin/duty_cycle", "anakin/rollout_frames_per_s",
                        "anakin/replay_fill", "learner/updates_per_s"):
                assert tag in by_tag, \
                    f"{tag} missing (have {sorted(by_tag)[:30]}...)"
            assert any(0.0 < v < 1.0 for v in by_tag["anakin/duty_cycle"])
            assert max(by_tag["anakin/replay_fill"]) > 0
            # the logger's rollout curves flowed from the co-located
            # fleet (no actor worker exists to push them)
            assert "actor/total_nframes" in by_tag
            assert sum(by_tag["actor/total_nframes"]) > 0
            # a complete epoch committed on the checkpoint cadence
            epoch = resolve_epoch(opt.model_name)
            assert epoch is not None and epoch.learner_step >= 50
        finally:
            perf.reset()
