"""Performance observability plane (ISSUE 6): FLOPs capture, MFU math,
retrace detection, transfer-audit attribution, the T_PROFILE verb over a
real gateway, the incremental metrics tail reader, the profiling
label/nesting satellites — and the acceptance drill: a short CPU run
with TPU_APEX_PERF=1 exports learner/mfu, learner/updates_per_s,
actor/env_frames_per_s and per-role memory watermarks as metrics rows,
live-readable through fleet_top while a T_PROFILE window captures a
real trace from the running topology."""

import json
import os
import queue
import subprocess
import sys
import threading
import time
import warnings

import numpy as np
import pytest

from pytorch_distributed_tpu.agents.clocks import ActorStats, GlobalClock
from pytorch_distributed_tpu.agents.param_store import ParamStore
from pytorch_distributed_tpu.config import PerfParams, build_options
from pytorch_distributed_tpu.parallel.dcn import (
    DcnGateway, fetch_profile, fetch_status,
)
from pytorch_distributed_tpu.utils import perf, profiling, tracing
from pytorch_distributed_tpu.utils import flight_recorder
from pytorch_distributed_tpu.utils.metrics import (
    MetricsWriter, ScalarsTail, read_scalars,
)
from pytorch_distributed_tpu.utils.profiling import StepTimer

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module", autouse=True)
def _warm_profiler(tmp_path_factory):
    """Pay the XLA profiler's one-time session init (it lazily imports
    the whole tensorflow tree, ~35 s on this image) ONCE, idle, before
    any test here opens a trace window — otherwise whichever test
    captures first pays it mid-drill, GIL-starved behind a busy
    topology, and times out order-dependently.  Production fleets
    amortize the same cost via perf.prewarm_profiler at startup."""
    with profiling.trace("warm", log_dir=str(
            tmp_path_factory.mktemp("profiler_warm"))):
        pass


@pytest.fixture(autouse=True)
def _fresh_perf(monkeypatch):
    """Monitors are a per-process registry (like tracers); isolate each
    test, and strip any perf env an earlier topology exported."""
    for var in list(os.environ):
        if var == "TPU_APEX_PERF" or var.startswith("TPU_APEX_PERF_"):
            monkeypatch.delenv(var, raising=False)
    perf.reset()
    tracing.reset()
    flight_recorder.reset()
    yield
    perf.reset()
    tracing.reset()
    flight_recorder.reset()


# ---------------------------------------------------------------------------
# FLOPs capture + MFU math (tentpole part 1)
# ---------------------------------------------------------------------------

class TestFlopsCapture:
    def test_monitor_captures_flops_at_compile_time(self):
        import jax
        import jax.numpy as jnp

        m = perf.PerfMonitor("learner", PerfParams(enabled=True))
        f = jax.jit(lambda x: jnp.dot(x, x))
        flops = m.capture_flops(lambda: f.lower(jnp.ones((16, 16))))
        assert flops and flops > 0
        assert m.flops_per_update == flops

    def test_disabled_monitor_is_inert(self):
        m = perf.PerfMonitor("learner", PerfParams(enabled=False))
        assert m.capture_flops(lambda: 1 / 0) is None  # thunk never runs
        m.note_updates(5)
        m.note_frames(5)
        assert m.drain() == {}

    def test_peak_flops_table(self):
        class _Dev:
            device_kind = "TPU v5 lite"

        assert perf.peak_flops_of(_Dev()) == 197e12

        class _Cpu:
            device_kind = "cpu"

        assert perf.peak_flops_of(_Cpu()) is None

        class _NewTpu:
            platform = "tpu"
            device_kind = "TPU v9 mega"

        # the chip path must know its device: no default, no None
        with pytest.raises(ValueError, match="TPU v9 mega"):
            perf.peak_flops_of(_NewTpu())

    def test_peak_flops_scales_by_compute_dtype(self):
        """ISSUE-13 satellite: the MFU denominator is dtype-aware — an
        fp32 run scores against the fp32 MXU peak (half the bf16
        table), never the bf16 one."""
        class _Dev:
            device_kind = "TPU v5 lite"

        assert perf.peak_flops_of(_Dev(), "float32") == 197e12 / 2
        assert perf.peak_flops_of(_Dev(), "bfloat16") == 197e12
        # unknown dtypes keep the bf16 figure rather than guessing
        assert perf.peak_flops_of(_Dev(), "int8") == 197e12

        class _Cpu:
            device_kind = "cpu"

        assert perf.peak_flops_of(_Cpu(), "float32") is None

    def test_monitor_mfu_uses_dtype_scaled_peak(self, monkeypatch):
        """A monitor told its role computes in fp32 resolves half the
        bf16 peak; an explicit peak_flops knob is never scaled (the
        operator named the denominator)."""
        class _Dev:
            device_kind = "TPU v5 lite"

        import jax

        monkeypatch.setattr(jax, "devices", lambda: [_Dev()])
        m = perf.PerfMonitor(
            "learner",
            PerfParams(enabled=True, memory_watermarks=False))
        m.enabled = True
        m.set_compute_dtype("float32")
        assert m._peak_flops() == 197e12 / 2
        m2 = perf.PerfMonitor(
            "learner",
            PerfParams(enabled=True, peak_flops=123.0,
                       memory_watermarks=False))
        m2.enabled = True
        m2.set_compute_dtype("float32")
        assert m2._peak_flops() == 123.0


class TestMfuMath:
    def test_rates_and_mfu_units(self):
        """mfu = updates/s * flops/update / peak — pinned with injected
        clocks so the math (not the scheduler) is under test."""
        m = perf.PerfMonitor(
            "learner",
            PerfParams(enabled=True, peak_flops=200.0,
                       memory_watermarks=False))
        m.flops_per_update = 10.0
        first = m.drain(now=100.0)  # anchor; one-time flops row rides it
        assert first.get("learner/flops_per_update") == 10.0
        m.note_updates(50)
        out = m.drain(now=105.0)
        assert out["learner/updates_per_s"] == pytest.approx(10.0)
        assert out["learner/achieved_flops_per_s"] == pytest.approx(100.0)
        assert out["learner/mfu"] == pytest.approx(0.5)

    def test_frames_rate_and_gauges(self):
        m = perf.PerfMonitor("actor-0", PerfParams(
            enabled=True, memory_watermarks=False), prefix="actor")
        m.drain(now=0.0)
        m.note_frames(400)
        m.set_gauge("actor/custom_gauge", 3.5)
        out = m.drain(now=2.0)
        assert out["actor/env_frames_per_s"] == pytest.approx(200.0)
        assert out["actor/custom_gauge"] == 3.5

    def test_watermarks_present_on_cpu_host(self):
        """On CPU device.memory_stats() is None — the host RSS rows
        carry the per-role watermark (the acceptance's CPU leg)."""
        m = perf.PerfMonitor("learner", PerfParams(enabled=True))
        out = m.drain()
        assert out["perf/learner/rss_bytes"] > 0
        assert out["perf/learner/rss_peak_bytes"] >= \
            out["perf/learner/rss_bytes"] * 0.5  # peak is lifetime-wide

    def test_env_resolution_and_status_snapshot(self, monkeypatch):
        monkeypatch.setenv("TPU_APEX_PERF", "1")
        monkeypatch.setenv("TPU_APEX_PERF_PEAK_FLOPS", "123.0")
        pp = perf.resolve(PerfParams())
        assert pp.enabled and pp.peak_flops == 123.0
        m = perf.get_monitor("learner")  # params from env alone
        assert m.enabled
        m.note_updates(3)
        m.drain(now=1.0)
        snap = perf.status_snapshot()
        assert snap["learner"]["updates_total"] == 3.0


# ---------------------------------------------------------------------------
# retrace detector (tentpole part 2)
# ---------------------------------------------------------------------------

class TestRetraceDetector:
    def test_fires_on_forced_recompile_and_stays_silent_warm(self):
        import jax
        import jax.numpy as jnp

        def _drill_double(x):   # a name no other program of the process has
            return x * 2

        f = jax.jit(_drill_double)
        det = perf.RetraceDetector()
        det.register("act", f)
        f(jnp.ones(8))
        assert det.check() == []  # first check IS the warmup mark
        for _ in range(3):
            f(jnp.ones(8))  # warm replays: same shape, no compile
        assert det.check() == []
        assert det.retraces == 0
        f(jnp.ones(4))  # shape leak -> forced recompile
        assert det.check() == ["act"]
        assert det.retraces == 1 and det.fired == {"act": 1}
        assert det.check() == []  # counted once; high-water advanced

    def test_none_and_unbuilt_programs_are_skipped(self):
        det = perf.RetraceDetector()
        det.register("server-side", None)
        det.register("never-built", "_drill_no_such_program")
        assert det.check() == [] and det.check() == []
        assert det.retraces == 0

    def test_a_program_registered_by_name_and_a_late_register(self):
        import jax
        import jax.numpy as jnp

        def _drill_by_name(x):
            return x - 1

        f = jax.jit(_drill_by_name)
        det = perf.RetraceDetector()
        det.check()                          # warmup mark, nothing yet
        det.register("late", "_drill_by_name")
        f(jnp.ones(2))
        assert det.check() == []             # a late register: new warmup
        f(jnp.ones(5))
        assert det.check() == ["late"] and det.fired == {"late": 1}

    def test_same_named_programs_of_two_threads_fire_apart(self):
        """A thread fleet: each actor builds its own ``act`` and watches it
        from its own thread.  An actor that compiles after the other's
        warmup mark, or recompiles, fires its own detector only."""
        import threading

        import jax
        import jax.numpy as jnp

        def _drill_twin():
            def _drill_twin_act(x):   # one name, two programs
                return x * 3
            return jax.jit(_drill_twin_act)

        dets = [perf.RetraceDetector(), perf.RetraceDetector()]
        fns = [_drill_twin(), _drill_twin()]
        # two live actor threads, each running what it is handed
        inbox = [queue.Queue(), queue.Queue()]
        outbox = queue.Queue()

        def actor(i):
            while (size := inbox[i].get()) is not None:
                dets[i].register("act", fns[i])
                fns[i](jnp.ones(size))
                outbox.put(dets[i].check())

        threads = [threading.Thread(target=actor, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()

        def step(i, size):
            inbox[i].put(size)
            return outbox.get(timeout=60)

        try:
            assert step(0, 8) == []               # actor 0 warms
            assert step(1, 8) == []               # actor 1 compiles after
            assert step(0, 8) == []               # ... not actor 0's
            assert step(1, 6) == ["act"]          # actor 1 recompiles
            assert step(0, 8) == [] and dets[0].retraces == 0
            assert dets[1].fired == {"act": 1}
        finally:
            for q in inbox:
                q.put(None)
            for t in threads:
                t.join()

    def test_monitor_exports_retrace_count(self):
        import jax
        import jax.numpy as jnp

        m = perf.PerfMonitor("actor-0", PerfParams(
            enabled=True, memory_watermarks=False), prefix="actor")
        def _drill_inc(x):
            return x + 1

        f = jax.jit(_drill_inc)
        m.register_jit("act", f)
        f(jnp.ones(8))
        m.note_frames(1)
        m.drain(now=1.0)  # warmup mark (gated on work having happened)
        f(jnp.ones(3))
        m.note_frames(1)
        out = m.drain(now=2.0)
        assert out["perf/actor/retraces"] == 1.0


# ---------------------------------------------------------------------------
# transfer audit (tentpole part 2)
# ---------------------------------------------------------------------------

class TestTransferAudit:
    def test_attributes_deliberate_host_array_on_hot_path(self):
        """The audit's target class of bug: a host numpy array smuggled
        into a jitted dispatch (an implicit H2D transfer per call).  It
        must be flagged, attributed to THIS file, and the call must
        still return the right answer (retried under allow)."""
        import jax
        import jax.numpy as jnp

        aud = perf.TransferAudit()
        g = jax.jit(lambda a, b: a + b)
        xdev = jax.device_put(jnp.ones(3))
        host = np.ones(3, np.float32)  # the deliberate host sync
        out = aud.run(g, xdev, host)
        np.testing.assert_allclose(np.asarray(out), np.full(3, 2.0))
        assert aud.total == 1
        (site,) = aud.sites
        assert "test_perf.py" in site
        assert "transfer" in aud.last_error.lower()

    def test_clean_calls_pass_unflagged(self):
        import jax
        import jax.numpy as jnp

        aud = perf.TransferAudit()
        g = jax.jit(lambda a: a * 2)
        xdev = jax.device_put(jnp.ones(3))
        aud.run(g, xdev)
        assert aud.total == 0 and aud.sites == {}

    def test_non_transfer_errors_propagate(self):
        aud = perf.TransferAudit()
        with pytest.raises(ZeroDivisionError):
            aud.run(lambda: 1 / 0)
        assert aud.total == 0


# ---------------------------------------------------------------------------
# profiling satellites: label sanitization + nested no-op + totals
# ---------------------------------------------------------------------------

class TestProfilingSatellites:
    def test_label_is_sanitized_into_the_trace_path(self, tmp_path):
        assert profiling.sanitize_label("../../etc/passwd") == \
            "etc-passwd"
        assert profiling.sanitize_label("fused step @K=32") == \
            "fused-step-K-32"
        assert profiling.sanitize_label("...") == "trace"
        with profiling.trace("../evil lab",
                             log_dir=str(tmp_path)) as path:
            pass
        assert path == str(tmp_path / "evil-lab")
        assert os.path.realpath(path).startswith(
            os.path.realpath(str(tmp_path)))

    def test_nested_capture_is_warning_plus_noop(self, tmp_path):
        with profiling.trace("outer", log_dir=str(tmp_path)) as outer:
            assert outer is not None
            with pytest.warns(UserWarning, match="already active"):
                with profiling.trace("inner",
                                     log_dir=str(tmp_path)) as inner:
                    assert inner is None  # no-op, outer keeps recording
                    # doubly-nested same-thread capture: must be
                    # another no-op, not a re-acquire deadlock on the
                    # module lock
                    with profiling.trace(
                            "inner2", log_dir=str(tmp_path)) as i2:
                        assert i2 is None
        # the outer window closed cleanly; a fresh capture works again
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            with profiling.trace("after", log_dir=str(tmp_path)) as p:
                assert p is not None

    def test_disabled_trace_yields_none(self, monkeypatch):
        monkeypatch.delenv("TPU_APEX_PROFILE", raising=False)
        with profiling.trace("anything") as path:
            assert path is None

    def test_steptimer_drain_reports_totals(self):
        t = StepTimer("actor")
        t.add("act", 0.010)
        t.add("act", 0.030)
        t.add("env", 0.005)
        out = t.drain()
        assert out["actor/time_act_total_ms"] == pytest.approx(40.0)
        assert out["actor/time_env_total_ms"] == pytest.approx(5.0)
        # totals == mean * calls (the stackable identity)
        assert out["actor/time_act_total_ms"] == pytest.approx(
            out["actor/time_act_ms"] * out["actor/time_act_calls"])
        assert t.drain() == {}


# ---------------------------------------------------------------------------
# incremental metrics tail (fleet_top satellite)
# ---------------------------------------------------------------------------

class TestScalarsTail:
    def test_incremental_reads_remember_offset(self, tmp_path):
        path = tmp_path / "scalars.jsonl"
        tail = ScalarsTail(str(tmp_path))
        assert tail.poll() == []  # no file yet
        with open(path, "w") as f:
            f.write(json.dumps({"tag": "a", "value": 1.0}) + "\n")
        assert [r["tag"] for r in tail.poll()] == ["a"]
        assert tail.poll() == []  # nothing new
        with open(path, "a") as f:
            f.write(json.dumps({"tag": "b", "value": 2.0}) + "\n")
            f.write(json.dumps({"tag": "c", "value": 3.0}) + "\n")
        assert [r["tag"] for r in tail.poll()] == ["b", "c"]

    def test_unterminated_tail_is_not_consumed(self, tmp_path):
        """A writer mid-append leaves a torn trailing line; the tail
        reader must wait for the newline and then deliver the COMPLETE
        row — never half-consume it."""
        path = tmp_path / "scalars.jsonl"
        tail = ScalarsTail(str(tmp_path))
        with open(path, "w") as f:
            f.write(json.dumps({"tag": "a", "value": 1.0}) + "\n")
            f.write('{"tag": "b", "val')  # mid-append
        assert [r["tag"] for r in tail.poll()] == ["a"]
        with open(path, "a") as f:
            f.write('ue": 2.0}\n')
        assert tail.poll() == [{"tag": "b", "value": 2.0}]

    def test_kill_torn_terminated_line_is_skipped(self, tmp_path):
        """A newline-terminated but undecodable line (SIGKILL tore the
        payload, a later writer appended past it) is skipped for good —
        the read_scalars torn-artifact philosophy."""
        path = tmp_path / "scalars.jsonl"
        tail = ScalarsTail(str(tmp_path))
        with open(path, "w") as f:
            f.write('{"tag": "torn", "val\n')
            f.write(json.dumps({"tag": "good", "value": 1.0}) + "\n")
        assert [r["tag"] for r in tail.poll()] == ["good"]

    def test_truncated_file_resets_cursor(self, tmp_path):
        path = tmp_path / "scalars.jsonl"
        tail = ScalarsTail(str(tmp_path))
        with open(path, "w") as f:
            f.write(json.dumps({"tag": "old", "value": 1.0}) + "\n")
            f.write(json.dumps({"tag": "old2", "value": 2.0}) + "\n")
        assert len(tail.poll()) == 2
        with open(path, "w") as f:  # rotation: fresh, shorter file
            f.write(json.dumps({"tag": "fresh", "value": 9.0}) + "\n")
        assert [r["tag"] for r in tail.poll()] == ["fresh"]


# ---------------------------------------------------------------------------
# T_PROFILE verb over a real gateway (tentpole part 3)
# ---------------------------------------------------------------------------

class TestTProfile:
    def _gateway(self, tmp_path, wire_profiler=True):
        clock, stats = GlobalClock(), ActorStats()
        store = ParamStore(8)
        store.publish(np.zeros(8, dtype=np.float32))
        profiler = None
        if wire_profiler:
            profiler = lambda msg: perf.run_profile_window(  # noqa: E731
                str(tmp_path / "profiles"),
                label=msg.get("label", "t"),
                seconds=msg.get("seconds", 0.2), max_seconds=1.0)
        gw = DcnGateway(store, clock, stats, put_chunk=lambda i: None,
                        host="127.0.0.1", port=0, profiler=profiler)
        return gw

    def test_round_trip_captures_real_trace(self, tmp_path):
        gw = self._gateway(tmp_path)
        try:
            reply = fetch_profile(("127.0.0.1", gw.port), seconds=0.2,
                                  label="accept test")
            assert "error" not in reply, reply
            assert reply["seconds"] == pytest.approx(0.2)
            # the label was sanitized into the path, inside the dir
            assert reply["trace_dir"] == str(
                tmp_path / "profiles" / "accept-test")
            # a REAL xplane landed (jax profiler works on CPU)
            found = []
            for root, _dirs, files in os.walk(reply["trace_dir"]):
                found += [f for f in files if f.endswith(".xplane.pb")]
            assert found, f"no xplane.pb under {reply['trace_dir']}"
            assert gw.profiles_served == 1
            # STATUS stays live on the same gateway
            assert fetch_status(("127.0.0.1", gw.port))["uptime"] >= 0
        finally:
            gw.close()

    def test_seconds_clamped_by_server(self, tmp_path):
        gw = self._gateway(tmp_path)
        try:
            t0 = time.monotonic()
            reply = fetch_profile(("127.0.0.1", gw.port), seconds=300.0)
            assert time.monotonic() - t0 < 30.0  # clamped to max 1.0s
            assert reply["seconds"] == pytest.approx(1.0)
        finally:
            gw.close()

    def test_unwired_gateway_replies_error_not_crash(self, tmp_path):
        gw = self._gateway(tmp_path, wire_profiler=False)
        try:
            reply = fetch_profile(("127.0.0.1", gw.port), seconds=0.1)
            assert "no profiler wired" in reply["error"]
            # the session plane is unharmed
            assert fetch_status(("127.0.0.1", gw.port))["uptime"] >= 0
        finally:
            gw.close()


# ---------------------------------------------------------------------------
# acceptance: live perf plane on a short CPU run
# ---------------------------------------------------------------------------

class TestPerfPlaneAcceptance:
    def test_short_cpu_run_exports_live_perf_plane(self, tmp_path,
                                                   monkeypatch):
        """ISSUE 6 acceptance: with TPU_APEX_PERF=1, a short CPU run
        exports learner/mfu, learner/updates_per_s,
        actor/env_frames_per_s and per-role memory watermarks as
        metrics rows; fleet_top --json surfaces them live; and
        T_PROFILE captures a real trace from the RUNNING topology."""
        monkeypatch.setenv("TPU_APEX_PERF", "1")
        # CPU has no table peak: the documented override supplies one
        # so the mfu row exists (value = achieved / this peak)
        monkeypatch.setenv("TPU_APEX_PERF_PEAK_FLOPS", "1e12")
        from pytorch_distributed_tpu.fleet import FleetTopology

        opt = build_options(
            1, memory_type="device", root_dir=str(tmp_path),
            refs="perfrun", num_actors=1, seed=3,
            # the test ends the run itself (stop event in the finally)
            # once every probe landed; max_seconds is the backstop.
            # Replay-ratio pacing keeps the learner from churning the
            # GIL at full speed, so the profiler prewarm thread
            # finishes during the run instead of starving behind it.
            steps=10 ** 9, max_seconds=150.0, max_replay_ratio=8.0,
            learn_start=16, memory_size=512, batch_size=16,
            actor_freq=25, actor_sync_freq=100, param_publish_freq=50,
            learner_freq=50, logger_freq=2, evaluator_nepisodes=0,
            early_stop=50, checkpoint_freq=0)
        topo = FleetTopology(opt, local_actors=1, port=0)
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
            # 1) the live plane: STATUS grows a perf block once the
            # learner's first stats window drains
            status = None
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline and not done.is_set():
                try:
                    status = fetch_status(addr, timeout=5.0)
                except (ConnectionError, OSError):
                    status = None
                if status and "learner/updates_per_s" in (
                        status.get("perf") or {}).get("learner", {}):
                    break
                time.sleep(0.25)
            assert status is not None and "perf" in status, \
                "perf block never appeared in STATUS"
            lsnap = status["perf"]["learner"]
            assert lsnap["learner/updates_per_s"] > 0
            assert lsnap["learner/mfu"] > 0
            assert lsnap["perf/learner/rss_bytes"] > 0
            assert "actor_frames_per_sec" in status

            # 2) fleet_top --json surfaces the same live block
            proc = subprocess.run(
                [sys.executable,
                 os.path.join(_REPO, "tools", "fleet_top.py"),
                 f"127.0.0.1:{topo.port}", "--json"],
                capture_output=True, text=True, timeout=60,
                env={**os.environ, "JAX_PLATFORMS": "cpu"})
            assert proc.returncode == 0, proc.stderr
            seen = json.loads(proc.stdout)
            assert seen["perf"]["learner"]["learner/updates_per_s"] > 0

            # 3) T_PROFILE captures a real trace from the running
            # topology, no restart.  The startup prewarm window
            # (perf.prewarm_profiler) may still hold the one-window
            # lock — a transient busy reply, retried
            deadline = time.monotonic() + 120
            while True:
                reply = fetch_profile(addr, seconds=0.3, label="live")
                if "error" not in reply:
                    break
                assert ("already active" in reply["error"]
                        or "unavailable" in reply["error"]), reply
                assert time.monotonic() < deadline, reply
                time.sleep(0.5)
            found = []
            for root, _d, files in os.walk(reply["trace_dir"]):
                found += [f for f in files if f.endswith(".xplane.pb")]
            assert found, f"no xplane under {reply['trace_dir']}"
        finally:
            topo.clock.stop.set()
            t.join(120)
        assert not t.is_alive()

        # 4) the exported rows: every acceptance tag is a scalar row in
        # the run's metrics stream, role-stamped
        rows = read_scalars(opt.log_dir)
        by_tag = {}
        for r in rows:
            if "value" in r:
                by_tag.setdefault(r["tag"], []).append(r)
        for tag in ("learner/mfu", "learner/updates_per_s",
                    "learner/flops_per_update", "learner/replay_ratio",
                    "actor/env_frames_per_s",
                    "perf/learner/rss_bytes", "perf/learner/rss_peak_bytes",
                    "perf/actor/rss_bytes",
                    # the compile record, written once after warm-up
                    "learner/setup_trace_s", "learner/setup_traces"):
            assert tag in by_tag, \
                f"{tag} missing (have {sorted(by_tag)[:40]}...)"
        assert any(r["value"] > 0 for r in by_tag["learner/mfu"])
        assert any(r["value"] > 0
                   for r in by_tag["actor/env_frames_per_s"])
        assert by_tag["learner/mfu"][0]["role"] == "learner"
        assert by_tag["perf/actor/rss_bytes"][0]["role"] == "actor-0"
        # the retrace watch ran and stayed silent (no shape leaks in
        # the production hot loops)
        assert all(r["value"] == 0.0
                   for r in by_tag.get("perf/learner/retraces", []))

    def test_fleet_top_metrics_overlay_tails_incrementally(self,
                                                           tmp_path):
        """fleet_top --json --metrics overlays the newest perf rows via
        the incremental tail reader (no gateway-side perf needed)."""
        clock, stats = GlobalClock(), ActorStats()
        store = ParamStore(8)
        store.publish(np.zeros(8, dtype=np.float32))
        gw = DcnGateway(store, clock, stats, put_chunk=lambda i: None,
                        host="127.0.0.1", port=0)
        writer = MetricsWriter(str(tmp_path), enable_tensorboard=False,
                               role="learner", run_id="x")
        writer.scalars({"learner/mfu": 0.17,
                        "perf/learner/rss_bytes": 1e9}, step=5)
        writer.close()
        try:
            proc = subprocess.run(
                [sys.executable,
                 os.path.join(_REPO, "tools", "fleet_top.py"),
                 f"127.0.0.1:{gw.port}", "--json",
                 "--metrics", str(tmp_path)],
                capture_output=True, text=True, timeout=60,
                env={**os.environ, "JAX_PLATFORMS": "cpu"})
            assert proc.returncode == 0, proc.stderr
            status = json.loads(proc.stdout)
            assert status["metrics_latest"]["learner/mfu"] == 0.17
        finally:
            gw.close()


# ---------------------------------------------------------------------------
# plot_run phase breakdown (StepTimer totals satellite)
# ---------------------------------------------------------------------------

class TestPhaseBreakdownPlot:
    def test_stacked_phase_plot_from_totals(self, tmp_path):
        pytest.importorskip("matplotlib")
        writer = MetricsWriter(str(tmp_path), enable_tensorboard=False,
                               role="actor-0", run_id="x")
        wall = time.time()
        for i in range(4):
            writer.scalars({"actor/time_act_total_ms": 100.0 + i,
                            "actor/time_env_total_ms": 40.0,
                            "actor/time_advance_total_ms": 20.0},
                           step=i, wall=wall + 10 * i)
        writer.close()
        out = tmp_path / "phases.png"
        proc = subprocess.run(
            [sys.executable, os.path.join(_REPO, "tools", "plot_run.py"),
             str(tmp_path), "--phase-breakdown", "actor",
             "--out", str(out)],
            capture_output=True, text=True, timeout=180,
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 "MPLBACKEND": "Agg"})
        assert proc.returncode == 0, proc.stderr
        assert out.exists() and out.stat().st_size > 0

    def test_multi_process_roles_need_an_exact_role(self, tmp_path):
        """Two actor processes share the ``actor/`` tag prefix; a bare
        --phase-breakdown actor would interleave their unrelated drain
        windows, so it must refuse and name them — an exact role plots
        that process only."""
        wall = time.time()
        for role in ("actor-0", "actor-1"):
            writer = MetricsWriter(str(tmp_path),
                                   enable_tensorboard=False, role=role,
                                   run_id="x")
            for i in range(3):
                writer.scalars({"actor/time_act_total_ms": 50.0,
                                "actor/time_env_total_ms": 10.0},
                               step=i, wall=wall + 10 * i + 0.1)
            writer.close()
        from tools import plot_run

        with pytest.raises(SystemExit, match="actor-0, actor-1"):
            plot_run.load_phase_windows(str(tmp_path), "actor")
        walls, phases = plot_run.load_phase_windows(str(tmp_path),
                                                    "actor-1")
        assert len(walls) == 3 and set(phases) == {"act", "env"}

    def test_missing_rows_fail_loudly(self, tmp_path):
        pytest.importorskip("matplotlib")
        writer = MetricsWriter(str(tmp_path), enable_tensorboard=False)
        writer.scalar("learner/critic_loss", 1.0, step=0)
        writer.close()
        proc = subprocess.run(
            [sys.executable, os.path.join(_REPO, "tools", "plot_run.py"),
             str(tmp_path), "--phase-breakdown", "actor"],
            capture_output=True, text=True, timeout=180,
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 "MPLBACKEND": "Agg"})
        assert proc.returncode != 0
        assert "time_*_total_ms" in proc.stderr


# ---------------------------------------------------------------------------
# ISSUE 7: the fused device rollout on the perf plane — retrace
# fire/silent drill + transfer-audit coverage of the new hot program,
# and the fleet_top per-actor panel line
# ---------------------------------------------------------------------------

class TestDeviceRolloutPerfPlane:
    @pytest.fixture(scope="class")
    def rollout(self):
        """A tiny fused rollout (linear policy, 2 device Pong envs)
        shared by the drills — the registration surface is identical
        to the production CNN one."""
        import jax.numpy as jnp

        from pytorch_distributed_tpu.envs.device_env import (
            build_device_env,
        )
        from pytorch_distributed_tpu.models.policies import (
            build_fused_rollout, init_rollout_carry,
        )

        opt = build_options(4)
        env = build_device_env(opt.env_params, 0, 2)
        dim = int(np.prod(env.state_shape))
        w = jnp.asarray(np.zeros((dim, 6), np.float32))

        def apply_fn(params, obs):
            return obs.reshape((obs.shape[0], -1)).astype(
                jnp.float32) @ params

        roll = build_fused_rollout(apply_fn, env, nstep=2, gamma=0.99,
                                   rollout_ticks=2, emit="chunk")
        return dict(roll=roll, w=w, env=env,
                    carry=lambda: init_rollout_carry(env, 2))

    def test_rollout_retrace_drill_silent_then_fires(self, rollout):
        """The registered rollout program must stay silent across
        same-shape dispatches (the production stream: tick0 is traced,
        so consecutive dispatches share one compile) and FIRE when a
        dtype leak forces a recompile."""
        import jax.numpy as jnp

        roll, w = rollout["roll"], rollout["w"]
        m = perf.PerfMonitor("actor-drill", PerfParams(
            enabled=True, memory_watermarks=False), prefix="actor")
        m.register_jit("device_rollout", roll)
        key = jnp.asarray(np.zeros(2, np.uint32))
        eps = jnp.zeros((2,), jnp.float32)
        carry, _ = roll(w, rollout["carry"](), key, jnp.int32(0), eps)
        m.note_frames(4)
        m.drain(now=1.0)  # warmup mark
        for d in range(1, 4):  # production stream: traced tick0 only
            carry, _ = roll(w, carry, key, jnp.int32(d * 2), eps)
        m.note_frames(12)
        out = m.drain(now=2.0)
        assert out["perf/actor/retraces"] == 0.0
        # the leak class the detector exists for: a raw python int
        # tick0 (weak-typed i32) instead of the driver's device-
        # resident strong i32 — new aval, fresh trace
        carry, _ = roll(w, carry, key, 8, eps)
        m.note_frames(4)
        out = m.drain(now=3.0)
        assert out["perf/actor/retraces"] == 1.0

    def test_rollout_transfer_audit_clean_and_flagged(self, rollout):
        """The device actor's dispatch is transfer-free by
        construction (device-resident key/eps/tick0/carry): the audit
        must pass it clean, and must flag + attribute + survive a
        smuggled host array."""
        import jax.numpy as jnp

        roll, w = rollout["roll"], rollout["w"]
        aud = perf.TransferAudit()
        key = jnp.asarray(np.zeros(2, np.uint32))
        eps = jnp.zeros((2,), jnp.float32)
        tick0 = jnp.int32(0)
        carry, _ = roll(w, rollout["carry"](), key, tick0, eps)
        carry, _ = aud.run(roll, w, carry, key, tick0 + 2, eps)
        assert aud.total == 0
        # a host numpy eps is an implicit H2D on the audited path
        carry, chunk = aud.run(roll, w, carry, key, tick0 + 4,
                               np.zeros(2, np.float32))
        assert aud.total == 1 and len(aud.sites) == 1
        assert chunk.valid.shape == (2, 2)

    def test_fleet_top_renders_per_actor_backend_line(self):
        """ISSUE 7 satellite: the STATUS ``actors`` block (per-slot env
        frames/s + active backend) renders in the panel and survives
        --json serialization."""
        from tools import fleet_top

        status = {
            "wall": 0.0, "learner_step": 10, "actor_step": 400,
            "slots": {},
            "actors": {
                "0": {"env_frames_per_sec": 512.5, "backend": "device"},
                "1": {"env_frames_per_sec": 100.0, "backend": "device"},
            },
        }
        line = fleet_top.actor_line(status)
        assert "actors[device]" in line
        assert "a0 512.5 f/s" in line and "a1 100 f/s" in line
        panel = fleet_top.render(status)
        assert "actors[device]" in panel
        json.loads(json.dumps(status))  # --json path serializes
        # mixed backends are labelled, absent block renders nothing
        status["actors"]["1"]["backend"] = "pipelined"
        assert "actors[mixed]" in fleet_top.actor_line(status)
        assert fleet_top.actor_line({"slots": {}}) is None
