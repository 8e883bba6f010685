"""The bandwidth X-ray (ISSUE 18, utils/bandwidth.py): byte-exact
accounting for every wire, ring, and checkpoint plane.

Four depths, mirroring the flow suite's layering:

- units: the LinkAccountant's counter table (link x verb x slot x
  direction), the socket side-table, payload sizing, the headline
  ratios, emit/status shapes, and resolve_bandwidth's env contract;
- the wire: a real DcnClient <-> DcnGateway pair — per-frame byte
  equality across the loopback, the byte conservation ledger's three
  gateway buckets (ingested / rejected / shed), and EXACT equality
  under injected corruption and severs (a frame that dies mid-wire is
  counted by NEITHER side; the clean retransmit is counted once);
- the journal: the gateway byte legs ride the ISSUE-16 HA state
  records — absolute-cumulative, double-apply idempotent, carried
  across a warm restart;
- acceptance: a short CPU topology exports every ``wire/*`` headline
  tag as role-stamped metrics rows, live-readable through T_STATUS's
  ``wire`` block.

The randomized end-to-end versions are ``tools/chaos_soak.py --flood``
(byte ledger exact under brownout) and ``--kill-gateway`` (journaled
byte carry across a promotion).
"""

import io
import json
import os
import socket
import threading
import time

import numpy as np
import pytest

from pytorch_distributed_tpu.agents.clocks import ActorStats, GlobalClock
from pytorch_distributed_tpu.agents.param_store import ParamStore
from pytorch_distributed_tpu.config import (
    BandwidthParams, FlowParams, GatewayParams, build_options,
)
from pytorch_distributed_tpu.parallel.dcn import (
    T_CLOCK, T_EXP, T_HELLO, T_PING, DcnClient, DcnGateway, _recv_frame,
    _send_frame, encode_chunk, fetch_status,
)
from pytorch_distributed_tpu.utils import bandwidth
from pytorch_distributed_tpu.utils.experience import Transition
from pytorch_distributed_tpu.utils.faults import FaultInjector
from pytorch_distributed_tpu.utils.metrics import read_scalars
from tools.chaos_soak import ChunkLog, tagged_transition

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_wire(monkeypatch):
    """The accountant is a per-process lazy singleton (like perf
    monitors and tracers): isolate each test and strip any wire env an
    earlier topology exported."""
    for var in list(os.environ):
        if var == "TPU_APEX_WIRE" or var.startswith("TPU_APEX_WIRE_"):
            monkeypatch.delenv(var, raising=False)
    bandwidth.reset_for_tests()
    yield
    bandwidth.reset_for_tests()


def _tr():
    return Transition(
        state0=np.zeros(4, dtype=np.float32), action=np.int32(1),
        reward=np.float32(0.5), gamma_n=np.float32(0.99),
        state1=np.zeros(4, dtype=np.float32),
        terminal1=np.float32(0.0), prov=None)


def _chunk(tag=0, n=1):
    return [(tagged_transition(tag + i), None) for i in range(n)]


# ---------------------------------------------------------------------------
# units
# ---------------------------------------------------------------------------


class TestResolveBandwidth:
    def test_defaults_on(self):
        bp = bandwidth.resolve_bandwidth()
        assert bp.enabled and bp.spawn

    def test_bare_switch_and_field_overrides(self, monkeypatch):
        monkeypatch.setenv("TPU_APEX_WIRE", "0")
        assert not bandwidth.resolve_bandwidth().enabled
        monkeypatch.setenv("TPU_APEX_WIRE", "1")
        monkeypatch.setenv("TPU_APEX_WIRE_SPAWN", "0")
        monkeypatch.setenv("TPU_APEX_WIRE_RATE_FLOOR_S", "0.5")
        bp = bandwidth.resolve_bandwidth()
        assert (bp.enabled, bp.spawn, bp.rate_floor_s) == (True, False, 0.5)

    def test_input_never_mutated(self, monkeypatch):
        monkeypatch.setenv("TPU_APEX_WIRE_SPAWN", "0")
        src = BandwidthParams()
        out = bandwidth.resolve_bandwidth(src)
        assert src.spawn is True
        assert out.spawn is False

    def test_export_env_round_trip(self, monkeypatch):
        bp = BandwidthParams(spawn=False, rate_floor_s=0.25)
        bandwidth.export_env(bp)
        try:
            child = bandwidth.resolve_bandwidth()
            assert child.spawn is False
            assert child.rate_floor_s == 0.25
        finally:
            os.environ.pop("TPU_APEX_WIRE_SPAWN", None)
            os.environ.pop("TPU_APEX_WIRE_RATE_FLOOR_S", None)


class TestPayloadNbytes:
    def test_arrays_scalars_bytes(self):
        assert bandwidth.payload_nbytes(
            np.zeros((4,), dtype=np.float32)) == 16
        assert bandwidth.payload_nbytes(np.int32(0)) == 4
        assert bandwidth.payload_nbytes(b"abcd") == 4
        assert bandwidth.payload_nbytes(None) == 0
        assert bandwidth.payload_nbytes(object()) == 0

    def test_transition_and_chunk(self):
        # 2 x f32[4] + 3 scalar f32 + 1 i32 = 16+16+12+4
        t = _tr()
        assert bandwidth.payload_nbytes(t) == 48
        assert bandwidth.chunk_nbytes([(t, None), (t, None)]) == 96

    def test_nested_dicts_and_depth_guard(self):
        assert bandwidth.payload_nbytes(
            {"a": np.zeros(2, np.float32), "b": [np.int32(0)]}) == 12
        deep = np.zeros(2, np.float32)
        for _ in range(10):
            deep = [deep]
        assert bandwidth.payload_nbytes(deep) == 0  # past the guard


class TestLinkAccountant:
    def _acct(self):
        return bandwidth.LinkAccountant(BandwidthParams())

    def test_note_totals_and_filters(self):
        a = self._acct()
        a.note("client", "exp", 100, "tx", slot=0)
        a.note("client", "exp", 50, "tx", slot=1)
        a.note("client", "tick", 10, "tx", slot=0)
        a.note("gateway", "exp", 150, "rx")
        assert a.totals() == (310, 4)
        assert a.totals(link="client") == (160, 3)
        assert a.totals(link="client", verb="exp") == (150, 2)
        assert a.totals(direction="rx") == (150, 1)

    def test_snapshot_folds_slots(self):
        a = self._acct()
        a.note("client", "exp", 100, "tx", slot=0)
        a.note("client", "exp", 50, "tx", slot=1)
        snap = a.snapshot()
        assert snap == {"client": {"exp": {"tx": [150, 2]}}}

    def test_socket_side_table(self):
        a = self._acct()
        s1, s2 = socket.socketpair()
        try:
            a.register_socket(s1, "client", slot=3)
            a.note_frame(s1, 2, 64, "tx")       # T_EXP
            a.note_frame(s2, 2, 64, "rx")       # unregistered -> anon
            assert a.totals(link="client") == (64, 1)
            assert a.totals(link="anon") == (64, 1)
            # unweakrefable doubles are accepted, accounted anon
            a.register_socket(object(), "gateway")
        finally:
            s1.close()
            s2.close()

    def test_bytes_per_transition_rx_only(self):
        """Loopback topologies (every test) count the SAME exp frame
        tx on the client link and rx on the gateway link; the headline
        ratio divides the rx side only — no double-count."""
        a = self._acct()
        a.note("client", "exp", 400, "tx")
        a.note("gateway", "exp", 400, "rx")
        a.note("gateway", "exp", 100, "tx")     # acks don't count
        a.note_transitions(4)
        assert a.bytes_per_transition() == pytest.approx(100.0)

    def test_replica_bytes_per_round(self):
        a = self._acct()
        a.note("gateway", "rlease", 30, "rx")
        a.note("gateway", "rgrad", 50, "rx")
        a.note("gateway", "rgrad", 10, "tx")
        a.note("gateway", "rprio", 10, "rx")
        a.note("gateway", "exp", 999, "rx")     # not replica plane
        a.note_round()
        a.note_round()
        assert a.replica_bytes_per_round() == pytest.approx(50.0)
        assert bandwidth.LinkAccountant(
            BandwidthParams()).replica_bytes_per_round() == 0.0

    def test_emit_scalars_rates_ratios_gauges(self):
        a = self._acct()
        a.note("client", "exp", 1000, "tx")
        first = a.emit_scalars(now=100.0)       # primes the baseline
        assert "wire/client/bytes_per_s" not in first
        a.note("client", "exp", 500, "tx")
        a.note("gateway", "exp", 1500, "rx")
        a.note_transitions(10)
        a.set_gauge("replay/hbm_bytes", 4096.0)
        out = a.emit_scalars(now=102.0)
        assert out["wire/client/bytes_per_s"] == pytest.approx(250.0)
        assert out["wire/bytes_per_transition"] == pytest.approx(150.0)
        assert "wire/replica_bytes_per_round" not in out  # no rounds
        assert out["replay/hbm_bytes"] == 4096.0

    def test_emit_respects_rate_floor(self):
        a = bandwidth.LinkAccountant(BandwidthParams(rate_floor_s=1.0))
        a.note("client", "exp", 100, "tx")
        a.emit_scalars(now=100.0)
        a.note("client", "exp", 100, "tx")
        # a sub-floor window would divide noise by ~0: suppressed
        assert "wire/client/bytes_per_s" not in a.emit_scalars(now=100.01)

    def test_status_block_shape(self):
        a = self._acct()
        a.note("gateway", "exp", 300, "rx", slot=0)
        a.note("gateway", "clock", 30, "tx", slot=0)
        a.note_transitions(3)
        blk = a.status_block()
        g = blk["links"]["gateway"]
        assert (g["bytes"], g["frames"]) == (330, 2)
        assert (g["rx_bytes"], g["tx_bytes"]) == (300, 30)
        assert blk["transitions"] == 3
        assert blk["bytes_per_transition"] == pytest.approx(100.0)


class TestPlaneSwitch:
    def test_disabled_plane_hooks_are_noops(self, monkeypatch):
        monkeypatch.setenv("TPU_APEX_WIRE", "0")
        bandwidth.reset_for_tests()
        assert bandwidth.get_accountant() is None
        assert not bandwidth.enabled()
        # every module hook degrades to a flag check, never a crash
        bandwidth.note("client", "exp", 10, "tx")
        bandwidth.note_frame(None, 2, 10, "tx")
        bandwidth.note_spawn("mint", _chunk())
        bandwidth.note_transitions(5)
        bandwidth.note_round()
        bandwidth.set_gauge("replay/hbm_bytes", 1.0)
        assert bandwidth.emit_scalars() == {}
        assert bandwidth.status_block() is None

    def test_spawn_accounting_and_gate(self, monkeypatch):
        chunk = [(_tr(), None)]
        bandwidth.note_spawn("mint", chunk)
        bandwidth.note_spawn("drain", chunk, frames=1)
        acct = bandwidth.get_accountant()
        assert acct.totals(link="spawn", verb="mint") == (48, 1)
        assert acct.totals(link="spawn", direction="rx") == (48, 1)
        monkeypatch.setenv("TPU_APEX_WIRE_SPAWN", "0")
        bandwidth.reset_for_tests()
        bandwidth.note_spawn("mint", chunk)
        assert bandwidth.get_accountant().totals(link="spawn") == (0, 0)

    def test_replay_gauges(self):
        class _Mem:
            state0 = np.zeros((8, 4), dtype=np.float32)
            action = np.zeros((8,), dtype=np.int32)

        bandwidth.note_host_replay(_Mem())
        out = bandwidth.get_accountant().emit_scalars()
        assert out["replay/host_bytes"] == 128 + 32
        assert out["replay/host_bytes/state0"] == 128.0

    def test_device_replay_gauge_sums_fields(self):
        from pytorch_distributed_tpu.memory.device_replay import (
            DeviceReplayIngest,
        )

        ing = DeviceReplayIngest(16, (4,), state_dtype=np.float32)
        ing.attach()
        out = bandwidth.get_accountant().emit_scalars()
        assert out["replay/hbm_bytes"] > 0
        assert out["replay/hbm_bytes/state0"] >= 16 * 4 * 4


# ---------------------------------------------------------------------------
# the wire: byte equality + the conservation ledger's three buckets
# ---------------------------------------------------------------------------


@pytest.fixture()
def plane():
    clock = GlobalClock()
    stats = ActorStats()
    store = ParamStore(8)
    store.publish(np.zeros(8, dtype=np.float32))
    log = ChunkLog()
    gw = DcnGateway(store, clock, stats, put_chunk=log,
                    host="127.0.0.1", port=0, idle_deadline=30.0,
                    flow_params=FlowParams(dwell_s=0.0, recover_s=0.0),
                    pressure=lambda: 0.0)
    gw.flow._next_update = time.monotonic() + 3600  # tests drive it
    holder = {"gw": gw}
    yield holder, log
    holder["gw"].close()


def _client(gw, slot=0, **kw):
    kw.setdefault("heartbeat_interval", 0)
    kw.setdefault("reconnect_timeout", 10.0)
    return DcnClient(("127.0.0.1", gw.port), process_ind=slot, **kw)


class TestWireByteEquality:
    def test_round_trip_frame_and_ledger_equality(self, plane):
        """Clean run: every exp frame's bytes land once on each side of
        the loopback (client tx == gateway rx, header included), and
        the payload-level ledger balances EXACTLY."""
        holder, log = plane
        gw = holder["gw"]
        client = _client(gw)
        for i in range(3):
            client.send_chunk(_chunk(i * 10, n=2))
        client.tick()                             # ships the byte report
        acct = bandwidth.get_accountant()
        tx_b, tx_f = acct.totals(link="client", verb="exp",
                                 direction="tx")
        rx_b, rx_f = acct.totals(link="gateway", verb="exp",
                                 direction="rx")
        assert tx_f == rx_f == 3
        assert tx_b == rx_b > 0
        assert client.flow_acked_bytes == gw.flow.ingested_bytes > 0
        cons = gw.flow.conservation()
        assert cons["bytes_balanced"], cons
        assert cons["acked_bytes"] == cons["accounted_bytes"]
        assert cons["rejected_bytes"] == cons["shed_bytes"] == 0
        assert acct.bytes_per_transition() > 0
        client.close()

    def test_status_wire_block_over_the_wire(self, plane):
        holder, log = plane
        gw = holder["gw"]
        client = _client(gw)
        client.send_chunk(_chunk(0, n=4))
        client.tick()
        status = fetch_status(("127.0.0.1", gw.port))
        wire = status["wire"]
        assert wire["links"]["gateway"]["rx_bytes"] > 0
        assert wire["transitions"] == 4
        assert wire["bytes_per_transition"] > 0
        led = wire["ledger"]
        assert led["bytes_balanced"]
        assert led["acked_bytes"] == led["accounted_bytes"] > 0
        # the probe link itself is accounted (fetch_status is
        # sessionless): fleet_top polls are not invisible traffic
        acct = bandwidth.get_accountant()
        assert acct.totals(link="probe")[0] > 0
        client.close()

    def test_rejected_frame_bytes_bucketed(self, plane):
        """A well-framed, schema-invalid EXP frame is acked and its
        bytes land in the rejected bucket — frame-granular, exact."""
        holder, log = plane
        gw = holder["gw"]
        sock = socket.create_connection(("127.0.0.1", gw.port),
                                        timeout=5.0)
        sock.settimeout(5.0)
        _send_frame(sock, T_HELLO, json.dumps(
            {"role": "actor", "process_ind": 0,
             "incarnation": 1}).encode())
        assert _recv_frame(sock)[0] == T_CLOCK
        payload = encode_chunk([(tagged_transition(1), None),
                                (tagged_transition(2), None)])
        with np.load(io.BytesIO(payload)) as z:
            cols = {k: z[k] for k in z.files}
        cols["priority"] = cols["priority"][:1]   # truncated column
        buf = io.BytesIO()
        np.savez(buf, **cols)
        bad = buf.getvalue()
        _send_frame(sock, T_EXP, bad)
        assert _recv_frame(sock)[0] == T_CLOCK    # acked, not dropped
        assert gw.flow.rejected_bytes == len(bad)
        assert gw.flow.ingested_bytes == 0
        assert log.tags == []
        sock.close()

    def test_shed_frame_bytes_bucketed_per_tier(self):
        """Brownout tier 3 with a dry bucket sheds the frame: its
        bytes land in shed_bytes (and the per-tier map), and the
        ledger still balances exactly — shed, never silently lost."""
        clock = GlobalClock()
        stats = ActorStats()
        store = ParamStore(8)
        store.publish(np.zeros(8, dtype=np.float32))
        log = ChunkLog()
        gw = DcnGateway(store, clock, stats, put_chunk=log,
                        host="127.0.0.1", port=0, idle_deadline=30.0,
                        flow_params=FlowParams(dwell_s=0.0, recover_s=0.0,
                                               bucket_rate=0.0,
                                               bucket_burst=0.0),
                        pressure=lambda: 0.0)
        gw.flow._next_update = time.monotonic() + 3600
        client = _client(gw)
        try:
            client.send_chunk(_chunk(0))          # tier < 3: admitted
            gov = gw.flow.governor
            gov.update(1.0)
            gov.update(1.0)                       # -> shedding
            gov.tier = 3                          # the brownout rung
            client.send_chunk(_chunk(5))          # shed: bucket is dry
            client.tick()
            assert gw.flow.shed_chunks == 1
            assert gw.flow.shed_bytes > 0
            assert gw.flow.shed_bytes_by_tier == {3: gw.flow.shed_bytes}
            cons = gw.flow.conservation()
            assert cons["acked_bytes"] == cons["accounted_bytes"], cons
            assert cons["acked_bytes"] == (gw.flow.ingested_bytes
                                           + gw.flow.shed_bytes)
            assert cons["bytes_balanced"]
            assert log.tags == [0]                # the shed never landed
        finally:
            client.close()
            gw.close()

    def test_ledger_exact_under_corrupt_retransmit(self, plane):
        """A corrupted frame dies mid-wire (decode ConnectionError,
        conn dropped): NEITHER side counts it; the clean retransmit is
        counted ONCE on each — the ledger stays exact, not one-sided."""
        holder, log = plane
        gw = holder["gw"]
        client = _client(gw, faults=FaultInjector.scripted("corrupt@1"))
        client.send_chunk(_chunk(7))
        client.send_chunk(_chunk(8))
        client.tick()
        assert sorted(log.tags) == [7, 8]
        assert client.reconnects == 1
        cons = gw.flow.conservation()
        assert cons["acked_bytes"] == cons["accounted_bytes"] > 0, cons
        assert gw.flow.ingested_bytes == client.flow_acked_bytes
        client.close()

    def test_ledger_exact_under_sever(self, plane):
        holder, log = plane
        gw = holder["gw"]
        client = _client(gw, faults=FaultInjector.scripted("sever@1"))
        client.send_chunk(_chunk(3))
        client.tick()
        assert log.tags == [3]
        cons = gw.flow.conservation()
        assert cons["acked_bytes"] == cons["accounted_bytes"] > 0, cons
        assert cons["bytes_balanced"]

    def test_fleet_top_wire_panel(self, plane):
        from tools.fleet_top import render, wire_line

        holder, log = plane
        gw = holder["gw"]
        client = _client(gw)
        client.send_chunk(_chunk(0, n=2))
        client.tick()
        status = fetch_status(("127.0.0.1", gw.port))
        line = wire_line(status)
        assert line and "gateway" in line and "B/transition" in line
        assert "IMBALANCED" not in line
        assert "wire:" in render(status)
        # a cooked imbalance (more acked than accounted) goes LOUD
        status["wire"]["ledger"] = {"acked_bytes": 100,
                                    "accounted_bytes": 40,
                                    "bytes_balanced": False}
        assert "IMBALANCED" in wire_line(status)
        client.close()

    def test_panel_absent_without_plane(self):
        from tools.fleet_top import wire_line

        assert wire_line({"learner_step": 0}) is None


# ---------------------------------------------------------------------------
# the journal: byte legs ride the HA state records
# ---------------------------------------------------------------------------


GP = GatewayParams(enabled=True, lease_s=0.4, sync_s=0.05)


def make_gateway(tmp, log, role="primary", gp=GP):
    clock = GlobalClock()
    store = ParamStore(8)
    store.publish(np.zeros(8, dtype=np.float32))
    return DcnGateway(store, clock, ActorStats(), put_chunk=log,
                      host="127.0.0.1", port=0, idle_deadline=30.0,
                      gateway_params=gp, log_dir=str(tmp), ha_role=role)


class TestByteCarryJournal:
    def test_seed_records_byte_legs_idempotent(self, tmp_path):
        log = ChunkLog()
        gw = make_gateway(tmp_path, log)
        try:
            recs = [{"seq": 1, "kind": "state",
                     "data": {"tick_seq": {}, "chunks_in": 4, "lost": 0,
                              "ledger": {"ingested": 10, "shed": 0,
                                         "quarantined": 0,
                                         "ingested_bytes": 4096,
                                         "rejected_bytes": 128,
                                         "shed_bytes": 256}}}]
            gw._seed_records(recs)
            first = dict(gw._ha_carry)
            gw._seed_records(recs)      # replay: absolute, max-applied
            assert gw._ha_carry == first
            assert gw._ha_carry["ingested_bytes"] == 4096
            assert gw._ha_carry["rejected_bytes"] == 128
            assert gw._ha_carry["shed_bytes"] == 256
            # the live ledger = carry + this term's own flow counters
            gw.flow.note_ingested_bytes(1000)
            led = gw._ha_ledger()
            assert led["ingested_bytes"] == 5096
            assert led["shed_bytes"] == 256
        finally:
            gw.close()

    def test_warm_restart_carries_byte_ledger(self, tmp_path):
        log = ChunkLog()
        gw = make_gateway(tmp_path, log)
        gw._ha_append("state", {
            "tick_seq": {}, "chunks_in": 2, "lost": 0,
            "ledger": {"ingested": 5, "shed": 0, "quarantined": 0,
                       "ingested_bytes": 7777, "rejected_bytes": 0,
                       "shed_bytes": 33}})
        gw.close()
        gw2 = make_gateway(tmp_path, log)
        try:
            snap = gw2.status_snapshot()["gateway"]
            assert snap["carry"]["ingested_bytes"] == 7777
            assert snap["carry"]["shed_bytes"] == 33
            # and the promoted ledger REPORTS the carried bytes
            assert gw2._ha_ledger()["ingested_bytes"] == 7777
        finally:
            gw2.close()


# ---------------------------------------------------------------------------
# acceptance: a live CPU topology exports the wire plane
# ---------------------------------------------------------------------------


class TestBandwidthAcceptance:
    @pytest.mark.timeout(240)
    def test_short_cpu_run_exports_wire_series(self, tmp_path):
        """ISSUE 18 acceptance: an unmodified short CPU run (the plane
        is ON by default) exports wire/<link>/bytes_per_s,
        wire/bytes_per_transition and the replay occupancy gauges as
        role-stamped metrics rows, live-readable through the STATUS
        ``wire`` block with a balanced byte ledger.  The actor joins
        over the REAL DCN session (a remote host in thread clothing) —
        local queue-fed actors never touch the wire, so they cannot
        exercise the exp byte path this plane exists to meter."""
        from pytorch_distributed_tpu.fleet import (
            FleetTopology, _remote_actor_main,
        )

        opt = build_options(
            1, memory_type="device", root_dir=str(tmp_path),
            refs="wirerun", num_actors=1, seed=5,
            steps=10 ** 9, max_seconds=120.0, max_replay_ratio=8.0,
            learn_start=16, memory_size=512, batch_size=16,
            actor_freq=25, actor_sync_freq=100, param_publish_freq=50,
            learner_freq=10, logger_freq=2, evaluator_nepisodes=0,
            early_stop=60, checkpoint_freq=0)
        topo = FleetTopology(opt, local_actors=0, port=0)
        done = threading.Event()

        def run():
            try:
                topo.run(backend="thread")
            finally:
                done.set()

        t = threading.Thread(target=run, daemon=True)
        t.start()
        actor = threading.Thread(
            target=_remote_actor_main,
            args=(opt, f"127.0.0.1:{topo.port}", 0), daemon=True)
        actor.start()
        addr = ("127.0.0.1", topo.port)
        try:
            status = None
            deadline = time.monotonic() + 100
            while time.monotonic() < deadline and not done.is_set():
                try:
                    status = fetch_status(addr, timeout=5.0)
                except (ConnectionError, OSError):
                    status = None
                wire = (status or {}).get("wire") or {}
                # the byte-ledger verdict only exists once a client has
                # reported its counters (tick cadence) — bytes can flow
                # a moment before that, so wait for both
                if wire.get("bytes_per_transition", 0) > 0 \
                        and "bytes_balanced" in wire.get("ledger", {}):
                    break
                time.sleep(0.25)
            assert status is not None and "wire" in status, \
                "wire block never appeared in STATUS"
            wire = status["wire"]
            assert wire["bytes_per_transition"] > 0
            assert wire["links"]["gateway"]["rx_bytes"] > 0
            assert wire["links"]["client"]["tx_bytes"] > 0
            assert wire["ledger"]["bytes_balanced"], wire["ledger"]
            # hold the run until the learner's stats cadence has
            # emitted the headline series at least twice (rates need a
            # delta window) and the rows reached the metrics stream
            want = {"wire/bytes_per_transition",
                    "wire/client/bytes_per_s",
                    "wire/gateway/bytes_per_s", "replay/hbm_bytes"}
            deadline = time.monotonic() + 90
            while time.monotonic() < deadline and not done.is_set():
                tags = {r.get("tag") for r in read_scalars(opt.log_dir)}
                if want <= tags:
                    break
                time.sleep(0.5)
        finally:
            topo.clock.stop.set()
            t.join(120)
            actor.join(60)
        assert not t.is_alive()

        rows = read_scalars(opt.log_dir)
        by_tag = {}
        for r in rows:
            if "value" in r:
                by_tag.setdefault(r["tag"], []).append(r)
        assert "wire/bytes_per_transition" in by_tag, sorted(by_tag)[:40]
        assert any(r["value"] > 0
                   for r in by_tag["wire/bytes_per_transition"])
        rate_tags = [tg for tg in by_tag
                     if tg.startswith("wire/") and
                     tg.endswith("/bytes_per_s")]
        assert rate_tags, sorted(by_tag)[:40]
        assert {"wire/client/bytes_per_s",
                "wire/gateway/bytes_per_s"} <= set(rate_tags)
        assert "replay/hbm_bytes" in by_tag
        assert any(r["value"] > 0 for r in by_tag["replay/hbm_bytes"])
        assert by_tag["wire/bytes_per_transition"][0]["role"] == "learner"
