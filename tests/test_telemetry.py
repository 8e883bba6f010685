"""Mission control (ISSUE 10): fleet metrics aggregation, the
SLO/alert state machine, OpenMetrics exposition, the T_METRICS push
path with clock-offset alignment, and the end-to-end acceptance drill
— a seeded chaos_soak learner stall whose absence alert fires, shows
in ``fleet_top --json``, lands on the ``tools/timeline.py`` incident
timeline, and resolves after recovery."""

import io
import json
import os
import socket
import subprocess
import sys
import threading
import time
from contextlib import redirect_stdout

import pytest

from pytorch_distributed_tpu.agents.clocks import ActorStats, GlobalClock
from pytorch_distributed_tpu.agents.param_store import ParamStore
from pytorch_distributed_tpu.config import AlertParams, MetricsParams
from pytorch_distributed_tpu.parallel.dcn import (
    DcnGateway, fetch_status, push_metrics,
)
from pytorch_distributed_tpu.utils import flight_recorder, telemetry
from pytorch_distributed_tpu.utils.metrics import (
    MetricsWriter, ScalarsTail, is_scalar_row, read_scalars,
)
from pytorch_distributed_tpu.utils.telemetry import (
    AlertEngine, FleetMetrics, MetricsPusher, MissionControl,
    OpenMetricsServer, SeriesRing, openmetrics_text, parse_rule,
    parse_rules,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "tools"))


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    for var in ("TPU_APEX_METRICS", "TPU_APEX_ALERT_RULES"):
        monkeypatch.delenv(var, raising=False)
    flight_recorder.reset()
    yield
    flight_recorder.reset()


def _row(tag, value, wall, role="learner", step=0):
    return {"tag": tag, "value": float(value), "wall": float(wall),
            "step": step, "role": role}


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# ---------------------------------------------------------------------------
# the series ring: bounded retention tiers
# ---------------------------------------------------------------------------

class TestSeriesRing:
    def test_raw_ring_evicts_by_span_and_count(self):
        ring = SeriesRing(raw_span=10.0, raw_points=64)
        t0 = 1000.0
        for i in range(200):
            ring.append(t0 + i * 0.5, float(i))
        pts = ring.recent(500)
        assert len(pts) <= 64
        newest = pts[-1][0]
        assert all(newest - w <= 10.0 for w, _v in pts)
        assert ring.latest() == (t0 + 199 * 0.5, 199.0)
        assert ring.appended == 200

    def test_downsample_tiers_extend_past_raw(self):
        """History older than the raw span survives as bucket means —
        the memory stays O(tier spans) while a window query still
        reaches back hours."""
        ring = SeriesRing(raw_span=30.0, raw_points=64,
                          tiers=((10.0, 3600.0),))
        t0 = 5000.0
        for i in range(120):  # 10 minutes of 5 s-spaced points
            ring.append(t0 + i * 5.0, float(i))
        # raw only covers the last 30 s; a 10-minute window must reach
        # into the 10 s-bucket tier
        win = ring.window(600.0, now=t0 + 600.0)
        assert len(win) > 7  # far more than the raw tail alone
        walls = [w for w, _v in win]
        assert walls == sorted(walls)
        assert min(walls) < t0 + 595.0 - 30.0  # pre-raw history present

    def test_out_of_order_append_folds_not_crashes(self):
        ring = SeriesRing(raw_span=60.0)
        ring.append(100.0, 1.0)
        ring.append(90.0, 2.0)  # merged-role interleave
        assert ring.appended == 2


# ---------------------------------------------------------------------------
# aggregator
# ---------------------------------------------------------------------------

class TestFleetMetrics:
    def test_ingest_filters_non_scalar_rows(self):
        m = FleetMetrics(MetricsParams(enabled=True))
        now = time.time()
        n = m.ingest([
            _row("a/b", 1.0, now),
            {"tag": "h", "kind": "histogram", "p50": 1.0, "wall": now},
            {"tag": "s", "kind": "span", "value": 2.0, "wall": now},
            {"no": "tag"},
            _row("a/b", 2.0, now + 1),
        ])
        assert n == 2
        assert m.latest("a/b") == (now + 1, 2.0)
        assert m.tags() == ["a/b"]

    def test_per_role_series_merge_on_read(self):
        m = FleetMetrics(MetricsParams(enabled=True))
        now = time.time()
        m.ingest([_row("t", 1.0, now, role="actor-0"),
                  _row("t", 2.0, now + 1, role="actor-1")])
        assert m.latest("t") == (now + 1, 2.0)
        assert len(m.window("t", 60.0, now=now + 2)) == 2
        blk = m.series_block(["t"])
        assert blk["t"]["latest"] == 2.0
        assert len(blk["t"]["points"]) == 2

    def test_series_cap_counts_dropped_never_silent(self):
        m = FleetMetrics(MetricsParams(enabled=True, max_series=2))
        now = time.time()
        m.ingest([_row(f"tag{i}", 1.0, now) for i in range(5)])
        assert len(m.tags()) == 2
        assert m.series_dropped == 3

    def test_remote_offset_shifts_walls(self):
        m = FleetMetrics(MetricsParams(enabled=True))
        now = time.time()
        m.ingest([_row("t", 1.0, now - 2.5)], offset=2.5)
        wall, _v = m.latest("t")
        assert wall == pytest.approx(now, abs=1e-6)


# ---------------------------------------------------------------------------
# rule DSL
# ---------------------------------------------------------------------------

class TestRuleParsing:
    def test_threshold_with_dwell(self):
        r = parse_rule("slow: learner/updates_per_s < 0.5 for 30s")
        assert (r.name, r.kind, r.op, r.value, r.for_s) == (
            "slow", "threshold", "<", 0.5, 30.0)

    def test_absence_and_duration_units(self):
        r = parse_rule("stall: learner/updates_per_s absent 2m")
        assert r.kind == "absence" and r.window_s == 120.0
        assert parse_rule("x: t absent 500ms").window_s == 0.5
        assert parse_rule("x: t absent 45").window_s == 45.0

    def test_burn_rate(self):
        r = parse_rule("burn: data/staleness_p50 > 100 frac 0.5 "
                       "over 300s")
        assert (r.kind, r.frac, r.window_s, r.value) == (
            "burn_rate", 0.5, 300.0, 100.0)

    def test_name_defaults_from_tag(self):
        assert parse_rule("replay/priority_ess_frac < 0.02").name == \
            "replay_priority_ess_frac"

    def test_semicolon_string_and_duplicates(self):
        rules = parse_rules("a: t absent 1s; b: t > 5 for 2s")
        assert [r.name for r in rules] == ["a", "b"]
        with pytest.raises(ValueError, match="duplicate"):
            parse_rules("a: t absent 1s; a: t > 5")

    def test_garbage_raises(self):
        with pytest.raises(ValueError, match="unparseable"):
            parse_rule("what even is this")
        with pytest.raises(ValueError, match="frac"):
            parse_rule("x: t > 1 frac 7 over 10s")
        with pytest.raises(ValueError, match="unparseable"):
            parse_rule("x: t > +e+.")  # float-shaped garbage

    def test_scientific_notation_values(self):
        assert parse_rule("x: t < 2e-2").value == pytest.approx(0.02)
        assert parse_rule("x: t > 1.5E+3 for 10s").value == 1500.0
        assert parse_rule("x: t >= -3e-1").value == pytest.approx(-0.3)

    def test_default_rules_parse(self):
        rules = parse_rules(telemetry.DEFAULT_RULES)
        assert {r.kind for r in rules} == {"absence", "burn_rate",
                                           "threshold"}


# ---------------------------------------------------------------------------
# the alert state machine
# ---------------------------------------------------------------------------

class TestAlertEngine:
    def _engine(self, rules, resolve_s=0.0):
        m = FleetMetrics(MetricsParams(enabled=True))
        rec = flight_recorder.get_recorder("missionctl-test")
        return m, rec, AlertEngine(parse_rules(rules), m,
                                   resolve_s=resolve_s, recorder=rec)

    def test_threshold_pending_dwell_firing_resolved(self):
        m, rec, e = self._engine("hot: t > 10 for 5s")
        t0 = 1000.0
        m.ingest([_row("t", 20.0, t0)])
        tr = e.evaluate(now=t0 + 1)
        assert [x["state"] for x in tr] == ["pending"]
        # dwell not yet served: still pending, no new transition
        assert e.evaluate(now=t0 + 3) == []
        tr = e.evaluate(now=t0 + 7)
        assert [x["state"] for x in tr] == ["firing"]
        assert e.firing() == ["hot"]
        # recovery
        m.ingest([_row("t", 1.0, t0 + 8)])
        tr = e.evaluate(now=t0 + 9)
        assert [x["state"] for x in tr] == ["resolved"]
        snap = {a["rule"]: a for a in e.snapshot()}
        assert snap["hot"]["fired_total"] == 1
        assert snap["hot"]["resolved_total"] == 1
        # resolved relaxes to ok on the next pass
        e.evaluate(now=t0 + 10)
        assert {a["state"] for a in e.snapshot()} == {"ok"}
        kinds = [ev["kind"] for ev in rec.snapshot()]
        assert kinds.count("alert") >= 3  # pending, firing, resolved

    def test_pending_clears_quietly_without_firing(self):
        m, _rec, e = self._engine("hot: t > 10 for 60s")
        t0 = 1000.0
        m.ingest([_row("t", 20.0, t0)])
        e.evaluate(now=t0 + 1)
        m.ingest([_row("t", 1.0, t0 + 2)])
        tr = e.evaluate(now=t0 + 3)
        assert [x["state"] for x in tr] == ["ok"]
        snap = e.snapshot()[0]
        assert snap["fired_total"] == 0 and snap["resolved_total"] == 0

    def test_absence_never_seen_does_not_fire(self):
        """A series that never reported is absent by CONFIGURATION —
        firing on it would page every fleet that runs without the perf
        plane enabled."""
        _m, _rec, e = self._engine("stall: ghost/tag absent 0.1s")
        for dt in (0.0, 10.0, 100.0):
            assert e.evaluate(now=1000.0 + dt) == []
        assert e.snapshot()[0]["state"] == "ok"

    def test_absence_fires_and_resolves(self):
        m, _rec, e = self._engine("stall: t absent 2s")
        t0 = 1000.0
        m.ingest([_row("t", 5.0, t0)])
        assert e.evaluate(now=t0 + 1) == []
        tr = e.evaluate(now=t0 + 3)
        assert [x["state"] for x in tr] == ["pending", "firing"]
        m.ingest([_row("t", 5.0, t0 + 4)])
        tr = e.evaluate(now=t0 + 4.5)
        assert [x["state"] for x in tr] == ["resolved"]

    def test_burn_rate_counts_window_fraction(self):
        m, _rec, e = self._engine("burn: t > 10 frac 0.5 over 60s")
        t0 = 1000.0
        # 3 of 10 samples violating: under budget
        m.ingest([_row("t", 20.0 if i < 3 else 1.0, t0 + i)
                  for i in range(10)])
        assert e.evaluate(now=t0 + 10) == []
        # 8 of 12: over budget -> pending + firing (for_s 0)
        m.ingest([_row("t", 20.0, t0 + 10 + i) for i in range(5)])
        tr = e.evaluate(now=t0 + 15)
        assert [x["state"] for x in tr] == ["pending", "firing"]

    def test_resolve_hysteresis(self):
        m, _rec, e = self._engine("hot: t > 10", resolve_s=5.0)
        t0 = 1000.0
        m.ingest([_row("t", 20.0, t0)])
        e.evaluate(now=t0 + 1)
        m.ingest([_row("t", 1.0, t0 + 2)])
        assert e.evaluate(now=t0 + 3) == []       # clean, inside window
        assert e.snapshot()[0]["state"] == "firing"
        tr = e.evaluate(now=t0 + 9)               # 5 s clean served
        assert [x["state"] for x in tr] == ["resolved"]

    def test_transitions_land_in_scalar_stream(self, tmp_path):
        m = FleetMetrics(MetricsParams(enabled=True))
        writer = MetricsWriter(str(tmp_path), enable_tensorboard=False,
                               role="missionctl")
        e = AlertEngine(parse_rules("hot: t > 10"), m, writer=writer)
        t0 = 1000.0
        m.ingest([_row("t", 20.0, t0)])
        e.evaluate(now=t0 + 1)
        writer.close()
        rows = [r for r in read_scalars(str(tmp_path))
                if r.get("tag", "").startswith("alert/")]
        assert [r["value"] for r in rows] == [1.0, 2.0]  # pending, firing


# ---------------------------------------------------------------------------
# OpenMetrics exposition
# ---------------------------------------------------------------------------

class TestOpenMetrics:
    def test_text_format(self):
        m = FleetMetrics(MetricsParams(enabled=True))
        now = time.time()
        m.ingest([_row("learner/updates_per_s", 123.4, now),
                  _row("actor/env_frames_per_s", 9.0, now,
                       role="actor-1")])
        e = AlertEngine(parse_rules("stall: learner/updates_per_s "
                                    "absent 0.001s"), m)
        e.evaluate(now=now + 10)  # absent -> firing
        text = openmetrics_text(m, e)
        assert "# TYPE tpu_apex_learner_updates_per_s gauge" in text
        assert 'tpu_apex_learner_updates_per_s{role="learner"} 123.4' \
            in text
        assert 'tpu_apex_alert_state{rule="stall",' in text
        assert "tpu_apex_alerts_firing 1" in text
        assert text.rstrip().endswith("# EOF")
        # every non-comment line: name{labels} value [timestamp]
        for line in text.splitlines():
            if line.startswith("#") or not line:
                continue
            assert line.split(" ")[0][0].isalpha()

    def test_label_values_are_escaped(self):
        """A pusher-controlled role/host string with quotes/newlines
        must not make the whole /metrics page unparseable."""
        m = FleetMetrics(MetricsParams(enabled=True))
        m.ingest([_row("t", 1.0, time.time(),
                       role='evil"role\nwith\\stuff')])
        text = openmetrics_text(m)
        line = next(ln for ln in text.splitlines()
                    if ln.startswith("tpu_apex_t{"))
        assert "\n" not in line  # by construction of splitlines
        assert '\\"' in line and "\\n" in line and "\\\\" in line

    def test_http_scrape(self):
        import urllib.request

        m = FleetMetrics(MetricsParams(enabled=True))
        m.ingest([_row("learner/updates_per_s", 7.0, time.time())])
        srv = OpenMetricsServer(lambda: openmetrics_text(m),
                                host="127.0.0.1", port=0)
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/metrics",
                    timeout=10) as resp:
                assert resp.status == 200
                assert "version=0.0.4" in resp.headers["Content-Type"]
                body = resp.read().decode()
            assert "tpu_apex_learner_updates_per_s" in body
            assert srv.scrapes == 1
            with pytest.raises(Exception):  # noqa: PT011 - 404 surface
                urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/nope", timeout=10)
        finally:
            srv.close()

    def test_mission_control_serves_openmetrics(self, tmp_path):
        import urllib.request

        mission = MissionControl(
            str(tmp_path),
            MetricsParams(enabled=True, openmetrics=True,
                          openmetrics_port=0),
            AlertParams(rules="stall: t absent 60s"))
        try:
            w = MetricsWriter(str(tmp_path), enable_tensorboard=False,
                              role="learner")
            w.scalar("t", 1.5, step=0)
            w.close()
            mission.poll()
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{mission.exporter.port}/metrics",
                    timeout=10) as resp:
                body = resp.read().decode()
            assert 'tpu_apex_t{role="learner"} 1.5' in body
            assert "tpu_apex_alert_state" in body
        finally:
            mission.stop()


# ---------------------------------------------------------------------------
# T_METRICS push + clock-offset alignment (ISSUE 10 satellite)
# ---------------------------------------------------------------------------

class _GatewayFixture:
    def __init__(self, mission=None, health=None):
        sink = mission.ingest_remote if mission is not None else None
        self.gw = DcnGateway(
            ParamStore(4), GlobalClock(), ActorStats(),
            put_chunk=lambda items: None, host="127.0.0.1", port=0,
            health=health, metrics_sink=sink)
        self.addr = ("127.0.0.1", self.gw.port)

    def close(self):
        self.gw.close()


class TestTMetricsPush:
    def test_push_round_trip_counts(self):
        mission = MissionControl(None, MetricsParams(enabled=True),
                                 AlertParams(enabled=False))
        fx = _GatewayFixture(mission)
        try:
            reply = push_metrics(fx.addr, [
                _row("t", 1.0, time.time()),
                {"tag": "h", "kind": "histogram", "wall": 0.0},
            ])
            assert reply["accepted"] == 1  # non-scalar rows filtered
            assert isinstance(reply["wall"], float)
            assert mission.metrics.remote_batches == 1
            st = fetch_status(fx.addr)
            assert st["metrics_batches"] == 1
            assert st["metrics_rows"] == 1
        finally:
            fx.close()

    def test_push_without_sink_is_counted_error(self):
        fx = _GatewayFixture(mission=None)
        try:
            reply = push_metrics(fx.addr, [_row("t", 1.0, 0.0)])
            assert reply["accepted"] == 0
            assert "no metrics sink" in reply["error"]
            assert "wall" in reply  # offset estimation still works
        finally:
            fx.close()

    def test_skewed_host_lands_on_gateway_clock(self, tmp_path):
        """The ISSUE-10 satellite: a fleet-host scalar pushed with a
        SKEWED wall clock must land on the gateway's timeline within
        the offset tolerance.  Same 2.5 s skew convention as the
        test_timeline.py offset fixtures: the remote host's clock runs
        2.5 s BEHIND the gateway's."""
        skew = -2.5
        skewed_clock = lambda: time.time() + skew  # noqa: E731
        mission = MissionControl(None, MetricsParams(enabled=True),
                                 AlertParams(enabled=False))
        fx = _GatewayFixture(mission)
        try:
            # the remote host's writer stamps walls with ITS clock
            w = MetricsWriter(str(tmp_path), enable_tensorboard=False,
                              role="actor-7")
            w.scalar("actor/env_frames_per_s", 1000.0, step=1,
                     wall=skewed_clock())
            w.close()
            pusher = MetricsPusher(fx.addr, str(tmp_path),
                                   MetricsParams(enabled=True),
                                   clock=skewed_clock)
            n = pusher.push_once()
            assert n == 1
            assert pusher.offset == pytest.approx(-skew, abs=0.5)
            wall, value = mission.metrics.latest(
                "actor/env_frames_per_s")
            assert value == 1000.0
            # aligned onto the gateway clock: ~now, not ~now-2.5
            assert abs(wall - time.time()) < 0.5
        finally:
            fx.close()

    def test_pusher_handshakes_before_first_rows(self, tmp_path):
        """No rows travel before an offset estimate exists — a skewed
        host must never pollute the fleet series with unaligned
        points."""
        mission = MissionControl(None, MetricsParams(enabled=True),
                                 AlertParams(enabled=False))
        fx = _GatewayFixture(mission)
        try:
            w = MetricsWriter(str(tmp_path), enable_tensorboard=False,
                              role="actor-0")
            w.scalar("t", 1.0, step=0)
            w.close()
            pusher = MetricsPusher(fx.addr, str(tmp_path),
                                   MetricsParams(enabled=True))
            assert pusher.offset is None
            pusher.push_once()
            assert pusher.offset is not None
            assert mission.metrics.ingested_rows == 1
            assert mission.metrics.remote_batches == 2  # handshake+rows
        finally:
            fx.close()

    def test_push_failure_is_counted_and_rows_retained(self, tmp_path):
        w = MetricsWriter(str(tmp_path), enable_tensorboard=False,
                          role="actor-0")
        w.scalar("t", 1.0, step=0)
        w.close()
        pusher = MetricsPusher(("127.0.0.1", _free_port()),
                               str(tmp_path),
                               MetricsParams(enabled=True))
        assert pusher.push_once() == 0
        assert pusher.push_errors == 1
        assert len(pusher._pending) == 1  # retried next cadence

    def test_post_handshake_failure_retains_batch_in_order(self,
                                                           tmp_path):
        """The gateway-restart scenario: the pusher already has an
        offset, pops its batch, and the push RPC dies mid-blip — the
        batch must be RE-PREPENDED (order kept) and delivered whole
        once the gateway is back."""
        mission = MissionControl(None, MetricsParams(enabled=True),
                                 AlertParams(enabled=False))
        fx = _GatewayFixture(mission)
        port = fx.gw.port
        w = MetricsWriter(str(tmp_path), enable_tensorboard=False,
                          role="actor-0")
        w.scalar("t", 1.0, step=0)
        w.close()
        pusher = MetricsPusher(("127.0.0.1", port), str(tmp_path),
                               MetricsParams(enabled=True))
        assert pusher.push_once() == 1  # handshake + delivery
        fx.close()  # the blip
        w2 = MetricsWriter(str(tmp_path), enable_tensorboard=False,
                          role="actor-0")
        w2.scalar("t", 2.0, step=1)
        w2.close()
        assert pusher.push_once() == 0
        assert pusher.push_errors == 1
        assert [r["value"] for r in pusher._pending] == [2.0]
        mission2 = MissionControl(None, MetricsParams(enabled=True),
                                  AlertParams(enabled=False))
        gw2 = DcnGateway(ParamStore(4), GlobalClock(), ActorStats(),
                         put_chunk=lambda items: None,
                         host="127.0.0.1", port=port,
                         metrics_sink=mission2.ingest_remote)
        try:
            assert pusher.push_once() == 1  # the retained row lands
            assert mission2.metrics.latest("t")[1] == 2.0
        finally:
            gw2.close()

    def test_pending_backlog_is_capped_and_counted(self, tmp_path):
        w = MetricsWriter(str(tmp_path), enable_tensorboard=False,
                          role="actor-0")
        for i in range(30):
            w.scalar("t", float(i), step=i)
        w.close()
        pusher = MetricsPusher(("127.0.0.1", _free_port()),
                               str(tmp_path),
                               MetricsParams(enabled=True))
        pusher.MAX_PENDING = 10
        pusher.push_once()  # dead gateway: rows buffer, oldest shed
        assert len(pusher._pending) == 10
        assert pusher.dropped_rows == 20
        assert [r["value"] for r in pusher._pending][0] == 20.0


class TestScalarsTailBound:
    def test_bounded_poll_catches_up_across_polls(self, tmp_path):
        w = MetricsWriter(str(tmp_path), enable_tensorboard=False,
                          role="r")
        for i in range(50):
            w.scalar("t", float(i), step=i)
        w.close()
        tail = ScalarsTail(str(tmp_path), max_bytes=1024)
        rows = []
        for _ in range(100):
            got = tail.poll()
            if not got:
                break
            rows.extend(got)
        assert [r["value"] for r in rows] == [float(i)
                                              for i in range(50)]

    def test_is_scalar_row(self):
        assert is_scalar_row({"tag": "t", "value": 1.0})
        assert not is_scalar_row({"tag": "t", "value": 1.0,
                                  "kind": "histogram"})
        assert not is_scalar_row({"tag": "t", "value": "NaN-string"})
        assert not is_scalar_row({"value": 1.0})


# ---------------------------------------------------------------------------
# fleet_top --json alerts/series blocks (ISSUE 10 satellite; the
# tier-1 smoke alongside test_observability's existing --json smoke)
# ---------------------------------------------------------------------------

class TestFleetTopJson:
    def test_json_gains_alert_and_series_blocks(self):
        mission = MissionControl(
            None, MetricsParams(enabled=True),
            AlertParams(rules="stall: learner/updates_per_s "
                              "absent 0.2s"))
        fx = _GatewayFixture(mission,
                             health=lambda: mission.status_block())
        try:
            push_metrics(fx.addr, [
                _row("learner/updates_per_s", 11.0, time.time())])
            time.sleep(0.3)
            mission.poll()  # absence window served -> firing
            proc = subprocess.run(
                [sys.executable,
                 os.path.join(_REPO, "tools", "fleet_top.py"),
                 f"127.0.0.1:{fx.gw.port}", "--json"],
                capture_output=True, text=True, timeout=120,
                env={**os.environ, "JAX_PLATFORMS": "cpu"})
            assert proc.returncode == 0, proc.stderr
            status = json.loads(proc.stdout)
            assert status["alerts"][0]["rule"] == "stall"
            assert status["alerts"][0]["state"] == "firing"
            series = status["series"]["learner/updates_per_s"]
            assert series["latest"] == 11.0
            assert series["points"]
            assert status["telemetry"]["remote_batches"] == 1
        finally:
            fx.close()

    def test_selftest_passes(self):
        proc = subprocess.run(
            [sys.executable,
             os.path.join(_REPO, "tools", "fleet_top.py"),
             "--selftest"],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert proc.returncode == 0, proc.stderr
        assert "PASS" in proc.stderr

    def test_render_shows_alert_panel_and_sparklines(self):
        from tools import fleet_top

        status = {
            "learner_step": 5, "wall": time.time(),
            "alerts": [{"rule": "stall", "tag": "t", "state": "firing",
                        "age": 4.0, "detail": "last sample 9s ago",
                        "fired_total": 1}],
            "series": {"learner/updates_per_s": {
                "points": [[1.0, 1.0], [2.0, 8.0], [3.0, 3.0]],
                "latest": 3.0}},
        }
        panel = fleet_top.render(status)
        assert "alerts: stall FIRING" in panel
        assert "learner/updates_per_s" in panel
        assert any(ch in panel for ch in fleet_top._SPARK)
        ok = dict(status, alerts=[dict(status["alerts"][0], state="ok",
                                       fired_total=2)])
        assert "alerts: ok (1 rule(s), 2 fired lifetime)" \
            in fleet_top.render(ok)


# ---------------------------------------------------------------------------
# config/knob plumbing
# ---------------------------------------------------------------------------

class TestKnobs:
    def test_env_overrides(self, monkeypatch):
        monkeypatch.setenv("TPU_APEX_METRICS", "1")
        monkeypatch.setenv("TPU_APEX_METRICS_POLL_S", "0.5")
        monkeypatch.setenv("TPU_APEX_METRICS_OPENMETRICS", "1")
        mp = telemetry.resolve_metrics()
        assert mp.enabled and mp.poll_s == 0.5 and mp.openmetrics
        monkeypatch.setenv("TPU_APEX_ALERT_RULES", "a: t absent 9s")
        monkeypatch.setenv("TPU_APEX_ALERT_RESOLVE_S", "3")
        ap = telemetry.resolve_alerts()
        assert ap.rules == "a: t absent 9s" and ap.resolve_s == 3.0
        assert parse_rules(ap.rules)[0].window_s == 9.0

    def test_options_route_overrides(self):
        from pytorch_distributed_tpu.config import build_options

        opt = build_options(1, poll_s=0.7,
                            rules="a: t absent 1s", resolve_s=2.0)
        assert opt.metrics_params.poll_s == 0.7
        assert opt.alert_params.rules == "a: t absent 1s"
        assert opt.alert_params.resolve_s == 2.0

    def test_ambiguous_override_refused(self):
        """``enabled`` lives on the perf/metrics/alert planes: a bare
        override must refuse loudly instead of flipping all three."""
        from pytorch_distributed_tpu.config import build_options

        with pytest.raises(ValueError, match="ambiguous"):
            build_options(1, enabled=True)


# ---------------------------------------------------------------------------
# the acceptance drill: seeded chaos_soak learner stall, end to end
# ---------------------------------------------------------------------------

class TestAcceptanceDrill:
    def test_learner_stall_fires_shows_and_resolves(self, tmp_path):
        """ISSUE 10 acceptance: a seeded ``chaos_soak`` run with an
        injected learner stall raises a ``learner/updates_per_s``
        absence alert that (1) FIRES, (2) is visible in ``fleet_top
        --json`` while firing, (3) appears as transition events on the
        ``tools/timeline.py`` incident timeline, and (4) RESOLVES
        after recovery — through the production components only: the
        soak's simulated learner writes real scalar rows, mission
        control tails them, the gateway serves the alert block over
        the real wire, and the blackbox rings land on disk."""
        import chaos_soak
        import timeline

        port = _free_port()
        box = {}

        def _run():
            box["report"] = chaos_soak.soak(
                seconds=9.0, actors=1, seed=7, restart_every=None,
                poison_every=0, learner_stall=2.5, learner_stall_at=2.0,
                log_dir=str(tmp_path), port=port, verbose=False)

        th = threading.Thread(target=_run, daemon=True)
        th.start()
        # ---- (2) visible in fleet_top --json mid-run, while firing.
        # In-process main(): a subprocess interpreter per poll would
        # outlast the firing window on a slow host.
        from tools import fleet_top

        firing_status = None
        deadline = time.monotonic() + 8.0
        while time.monotonic() < deadline:
            buf = io.StringIO()
            try:
                with redirect_stdout(buf):
                    rc = fleet_top.main([f"127.0.0.1:{port}", "--json"])
            except SystemExit:  # argparse never exits here; belt+braces
                rc = 1
            if rc == 0:
                status = json.loads(buf.getvalue())
                firing = [a for a in status.get("alerts", [])
                          if a["state"] == "firing"
                          and a["rule"] == "learner_stall"]
                if firing:
                    firing_status = status
                    break
            time.sleep(0.25)
        th.join(30.0)
        assert not th.is_alive(), "soak did not finish"
        report = box["report"]
        assert firing_status is not None, \
            f"alert never visible over fleet_top --json; " \
            f"report={report.get('alerts')}"
        assert "learner/updates_per_s" in firing_status["series"]
        # ---- (1) fired + (4) resolved, and nothing unexpected
        assert report["violations"] == []
        assert report["alerts"]["fired"] == ["learner_stall"]
        assert report["alerts"]["unexpected"] == []
        assert report["alerts"]["unresolved"] == []
        assert report["alerts"]["resolved_total"] >= 1
        # ---- (3) the incident timeline reconstructs the transitions
        events = timeline.build_timeline(str(tmp_path))
        alert_ev = [e for e in events if e["kind"] == "alert"]
        states = [e["data"].get("state") for e in alert_ev]
        assert "firing" in states and "resolved" in states
        assert states.index("firing") < states.index("resolved")
        assert all(e["role"] == "missionctl" for e in alert_ev)
        # the alert/* scalar rows ride the default timeline view too
        assert any(e["kind"] == "scalar"
                   and str(e.get("tag", "")).startswith("alert/")
                   for e in events)

    def test_soak_without_stall_keeps_alert_plane_quiet(self, tmp_path):
        """The negative leg: the same rule set over a HEALTHY simulated
        learner fires nothing — the unexpected-alert invariant the
        chaos gate enforces."""
        import chaos_soak

        report = chaos_soak.soak(
            seconds=4.0, actors=1, seed=3, restart_every=None,
            poison_every=0, learner_stall=0.0,
            alert_rules=chaos_soak.SOAK_ALERT_RULES,
            log_dir=str(tmp_path), verbose=False)
        assert report["violations"] == []
        assert report["alerts"]["fired"] == []
        assert report["alerts"]["stall_injected"] is False


# ---------------------------------------------------------------------------
# topology wiring: the mission rides a real (thread-backend) run
# ---------------------------------------------------------------------------

class TestTopologyWiring:
    @pytest.mark.slow
    @pytest.mark.timeout(300)
    def test_fleet_topology_serves_alert_blocks_live(self, tmp_path,
                                                     monkeypatch):
        """A real FleetTopology with the metrics plane enabled serves
        ``alerts``/``series`` on its gateway STATUS verb while the run
        is still alive, and the aggregator has absorbed the run's own
        scalar stream by the end.  (Slow tier since ISSUE 12's budget
        thinning: ~70s of live-topology wall on this image — the wiring
        itself is smoke-covered by fleet_top --selftest in check.sh and
        the anakin acceptance drill exercises the same STATUS plane.)"""
        from pytorch_distributed_tpu.config import build_options
        from pytorch_distributed_tpu.fleet import FleetTopology

        # another suite's perf-enabled topology may have exported
        # TPU_APEX_PERF via perf.export_env — with it on, this run pays
        # the flops AOT compile + profiler prewarm and the learner's
        # first stats window outlives the probe budget on this host
        for k in list(os.environ):
            if k.startswith("TPU_APEX_PERF"):
                monkeypatch.delenv(k, raising=False)

        opt = build_options(
            1, root_dir=str(tmp_path), refs="telemetry-accept",
            num_actors=1, seed=3,
            # the test ends the run itself (stop event in the finally)
            # once the probe landed; max_seconds is the backstop
            steps=10 ** 9, max_seconds=90.0, learn_start=16,
            memory_size=512, batch_size=16, actor_freq=25,
            learner_freq=50, logger_freq=1, evaluator_nepisodes=0,
            early_stop=50, checkpoint_freq=0)
        opt.metrics_params.enabled = True
        opt.metrics_params.poll_s = 0.2
        opt.alert_params.rules = (
            "stall: learner/critic_loss absent 300s; "
            "quiet: learner/critic_loss > 1e12 for 5s")
        topo = FleetTopology(opt, local_actors=1, port=0)
        assert topo.mission is not None
        done = threading.Event()

        def run():
            try:
                topo.run(backend="thread")
            finally:
                done.set()

        t = threading.Thread(target=run, daemon=True)
        t.start()
        seen = {}
        try:
            deadline = time.monotonic() + 75.0
            while time.monotonic() < deadline and not done.is_set():
                try:
                    st = fetch_status(("127.0.0.1", topo.port),
                                      timeout=5.0)
                except (ConnectionError, OSError):
                    st = None
                # wait for the RULE tag specifically: other suites may
                # leave the perf plane's env on, whose tags fill the
                # series block before the logger's first drain lands
                if st and "alerts" in st and "learner/critic_loss" in (
                        st.get("series") or {}):
                    seen.update(st)
                    break
                time.sleep(0.3)
        finally:
            topo.clock.stop.set()
            t.join(120)
        assert not t.is_alive()
        assert "alerts" in seen, "STATUS never carried the alert block"
        assert {a["rule"] for a in seen["alerts"]} == {"stall", "quiet"}
        assert all(a["state"] == "ok" for a in seen["alerts"])
        # a rule tag that reported rides the series block
        assert "learner/critic_loss" in seen["series"]
        # the aggregator tailed the run's own stream
        assert topo.mission.metrics.ingested_rows > 0
        assert "learner/critic_loss" in topo.mission.metrics.tags()
