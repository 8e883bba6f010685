"""Data-plane X-ray (ISSUE 8): transition provenance round-trips across
every hop (assembler, spawn-queue pickling, DCN wire, host sidecars,
device ring columns, checkpoint snapshots), staleness math under
ParamPrefetcher version bumps, the priority X-ray (host/device bucket
parity + the detector's ESS-collapse signal), quarantine correlation
keys, and the acceptance drill: a CPU PER topology with TPU_APEX_PERF=1
exports learner/staleness, learner/sample_age, replay/actor_share and
the priority histogram live (scalars.jsonl + fleet STATUS data
gauges)."""

import json
import os
import pickle
import sys
import threading
import time

import numpy as np
import pytest

from pytorch_distributed_tpu.config import build_options
from pytorch_distributed_tpu.memory.feeder import QueueOwner
from pytorch_distributed_tpu.memory.prioritized import PrioritizedReplay
from pytorch_distributed_tpu.ops.nstep import NStepAssembler
from pytorch_distributed_tpu.parallel.dcn import (
    decode_chunk, encode_chunk, fetch_status,
)
from pytorch_distributed_tpu.utils import (
    flight_recorder, health, perf, tracing,
)
from pytorch_distributed_tpu.utils.experience import (
    PROV_FIELDS, Transition, make_prov, stack_prov,
)
from pytorch_distributed_tpu.utils.metrics import read_scalars

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_registries(monkeypatch):
    for var in list(os.environ):
        if var == "TPU_APEX_PERF" or var.startswith("TPU_APEX_PERF_"):
            monkeypatch.delenv(var, raising=False)
    perf.reset()
    tracing.reset()
    flight_recorder.reset()
    health.reset()
    yield
    perf.reset()
    tracing.reset()
    flight_recorder.reset()
    health.reset()


def _mk_transition(v: float, prov=None) -> Transition:
    return Transition(
        state0=np.full((4,), v, np.float32), action=np.int32(int(v) % 3),
        reward=np.float32(v), gamma_n=np.float32(0.99),
        state1=np.full((4,), v + 1, np.float32),
        terminal1=np.float32(0.0), prov=prov)


# ---------------------------------------------------------------------------
# minting + transport
# ---------------------------------------------------------------------------

class TestMintingAndTransport:
    def test_assembler_prov_rides_the_window_fifo(self):
        """Provenance is minted at ACTION time and emitted with the
        window that opened on that action — including the shrinking
        terminal tail, where several windows (each with its own birth
        tick) flush at once."""
        a = NStepAssembler(3, 0.99)
        out = []
        for t in range(6):
            out += a.feed(np.zeros(2), np.int32(0), 1.0, np.ones(2),
                          t == 5, prov=make_prov(4, 1, 7, 100 + t))
        assert len(out) == 6
        assert [int(tr.prov[3]) for tr in out] == [100 + i
                                                   for i in range(6)]
        assert all(tuple(tr.prov[:3]) == (4, 1, 7) for tr in out)

    def test_spawn_queue_pickling_preserves_prov(self):
        chunk = tracing.TracedChunk(
            [(_mk_transition(i, make_prov(2, i, 5, 10 + i)), 0.5)
             for i in range(4)])
        clone = pickle.loads(pickle.dumps(chunk))  # the spawn-queue hop
        assert isinstance(clone, tracing.TracedChunk)
        assert clone.trace_id == chunk.trace_id
        for i, (t, _p) in enumerate(clone):
            assert tuple(t.prov) == (2, i, 5, 10 + i)

    def test_dcn_wire_round_trip_mixed_rows(self):
        """The savez wire carries provenance as an (n, 4) int64 column;
        rows minted without provenance survive as None, and a chunk
        with NO provenance at all ships byte-compatible (no column)."""
        items = [(_mk_transition(0, make_prov(1, 0, 3, 50)), 1.0),
                 (_mk_transition(1, None), None),
                 (_mk_transition(2, make_prov(1, 2, 3, 52)), 0.25)]
        dec = decode_chunk(encode_chunk(items))
        assert tuple(dec[0][0].prov) == (1, 0, 3, 50)
        assert dec[1][0].prov is None
        assert tuple(dec[2][0].prov) == (1, 2, 3, 52)
        legacy = [(_mk_transition(9, None), None)]
        import io

        with np.load(io.BytesIO(encode_chunk(legacy))) as z:
            assert "prov" not in z.files  # legacy wire bytes unchanged

    def test_malformed_prov_column_is_rejected(self):
        items = [(_mk_transition(0, make_prov(1, 0, 3, 50)), 1.0)]
        payload = encode_chunk(items)
        import io

        with np.load(io.BytesIO(payload)) as z:
            cols = {k: z[k] for k in z.files}
        cols["prov"] = cols["prov"][:, :2]  # wrong width
        out = io.BytesIO()
        np.savez(out, **cols)
        with pytest.raises(ValueError, match="prov"):
            decode_chunk(out.getvalue())


# ---------------------------------------------------------------------------
# storage sidecars + checkpoints
# ---------------------------------------------------------------------------

class TestHostSidecars:
    def test_prioritized_sidecar_sample_and_snapshot_round_trip(self):
        mem = PrioritizedReplay(capacity=16, state_shape=(4,),
                                state_dtype=np.float32)
        for i in range(10):
            mem.feed(_mk_transition(i, make_prov(i % 3, i, 2, 100 + i)),
                     0.5)
        rng = np.random.default_rng(0)
        batch = mem.sample(8, rng)
        prov = mem.provenance_of(batch.index)
        assert prov.shape == (8, len(PROV_FIELDS))
        for row, idx in zip(prov, batch.index):
            assert tuple(row) == (idx % 3, idx, 2, 100 + idx)
        # checkpoint epoch leg: snapshot -> (savez round trip) -> restore
        snap = mem.snapshot()
        assert snap["prov"].shape == (10, 4)
        import io

        buf = io.BytesIO()
        np.savez(buf, **snap)
        buf.seek(0)
        with np.load(buf) as z:
            data = {k: z[k] for k in z.files}
        fresh = PrioritizedReplay(capacity=16, state_shape=(4,),
                                  state_dtype=np.float32)
        fresh.restore(data)
        np.testing.assert_array_equal(fresh.provenance_of(np.arange(10)),
                                      mem.provenance_of(np.arange(10)))
        # a pre-provenance snapshot restores to the -1 sentinel
        legacy = {k: v for k, v in data.items() if k != "prov"}
        fresh2 = PrioritizedReplay(capacity=16, state_shape=(4,),
                                   state_dtype=np.float32)
        fresh2.restore(legacy)
        assert (fresh2.provenance_of(np.arange(10)) == -1).all()

    def test_queue_owner_delegates_provenance(self):
        owner = QueueOwner(PrioritizedReplay(capacity=8, state_shape=(4,),
                                             state_dtype=np.float32))
        f = owner.make_feeder(chunk=2)
        for i in range(4):
            f.feed(_mk_transition(i, make_prov(0, i, 1, i)), 0.5)
        f.flush()
        # mp.Queue hands chunks to its feeder thread asynchronously: a
        # single drain-until-empty pass can land BETWEEN two chunks'
        # visibility and under-read the queue (observed on this image:
        # rows [2, 3] still in flight -> -1 provenance sentinels), so
        # poll until every row has arrived
        drained = 0
        deadline = time.monotonic() + 10.0
        while drained < 4:
            drained += owner.drain()
            if drained < 4:
                assert time.monotonic() < deadline, \
                    f"only {drained}/4 rows drained"
                time.sleep(0.01)
        np.testing.assert_array_equal(
            owner.provenance_of(np.arange(4))[:, 3], np.arange(4))
        assert owner.priority_leaves() is not None

    def test_sequence_replay_sidecar(self):
        from pytorch_distributed_tpu.memory.sequence_replay import (
            Segment, SequenceReplay,
        )

        rep = SequenceReplay(capacity=4, seq_len=5, state_shape=(3,),
                             lstm_dim=2, priority_exponent=0.9)
        seg = Segment(obs=np.zeros((6, 3), np.float32),
                      action=np.zeros(5, np.int32),
                      reward=np.zeros(5, np.float32),
                      terminal=np.zeros(5, np.float32),
                      mask=np.ones(5, np.float32),
                      c0=np.zeros(2, np.float32),
                      h0=np.zeros(2, np.float32),
                      prov=make_prov(3, 1, 9, 77))
        rep.feed(seg, 0.5)
        assert tuple(rep.provenance_of([0])[0]) == (3, 1, 9, 77)
        snap = rep.snapshot()
        fresh = SequenceReplay(capacity=4, seq_len=5, state_shape=(3,),
                               lstm_dim=2, priority_exponent=0.9)
        fresh.restore(snap)
        assert tuple(fresh.provenance_of([0])[0]) == (3, 1, 9, 77)


class TestDeviceRingColumns:
    def test_ring_columns_feed_sample_snapshot_restore(self):
        from pytorch_distributed_tpu.memory.device_replay import (
            DeviceReplay, provenance_sample,
        )
        import jax

        ring = DeviceReplay(capacity=32, state_shape=(4,),
                            state_dtype=np.float32)
        n = 12
        prov = np.stack([make_prov(i % 2, i, 3, 200 + i)
                         for i in range(n)]).astype(np.int32)
        chunk = Transition(
            state0=np.zeros((n, 4), np.float32),
            action=np.zeros((n,), np.int32),
            reward=np.arange(n, dtype=np.float32),
            gamma_n=np.full((n,), 0.9, np.float32),
            state1=np.zeros((n, 4), np.float32),
            terminal1=np.zeros((n,), np.float32),
            prov=prov)
        ring.feed_chunk(chunk)
        got, fill = provenance_sample(ring.state, jax.random.PRNGKey(0),
                                      n=64)
        got = np.asarray(got)
        assert int(fill) == n
        assert (got[:, 0] >= 0).all()  # every drawn row was stamped
        assert set(got[:, 3].tolist()) <= set((200 + np.arange(n))
                                              .tolist())
        snap = ring.snapshot()
        np.testing.assert_array_equal(snap["prov"], prov.astype(np.int64))
        fresh = DeviceReplay(capacity=32, state_shape=(4,),
                             state_dtype=np.float32)
        fresh.restore(snap)
        np.testing.assert_array_equal(fresh.snapshot()["prov"],
                                      prov.astype(np.int64))
        # a legacy chunk (no prov) recycles slots back to the sentinel
        ring.feed_chunk(chunk._replace(prov=None))
        snap2 = ring.snapshot()
        assert (snap2["prov"][-n:] == -1).all()

    def test_fused_replay_rollout_stamps_ring_columns(self):
        """The emit="replay" fused rollout scatters (actor_id, env_slot,
        param_version, birth_step) alongside each emitted row; env_slot
        is the env's row index."""
        import jax
        import jax.numpy as jnp

        from pytorch_distributed_tpu.envs.device_env import (
            build_device_env,
        )
        from pytorch_distributed_tpu.memory.device_replay import (
            DeviceReplay,
        )
        from pytorch_distributed_tpu.models.policies import (
            build_fused_rollout, init_rollout_carry,
        )

        opt = build_options(4, visualize=False)
        N, K, NSTEP = 4, 8, 5
        env = build_device_env(opt.env_params, 0, N)

        def linear_apply(params, obs):
            x = obs.reshape(obs.shape[0], -1).astype(jnp.float32)
            return x @ params["w"]

        params = {"w": jnp.zeros((4 * 84 * 84, 6), jnp.float32)}
        ring = DeviceReplay(capacity=256, state_shape=env.state_shape,
                            state_dtype=np.uint8)
        roll = build_fused_rollout(linear_apply, env, nstep=NSTEP,
                                   gamma=0.99, rollout_ticks=K,
                                   emit="replay")
        carry = init_rollout_carry(env, NSTEP)
        eps = jnp.full((N,), 0.1, jnp.float32)
        key = jnp.asarray(jax.random.PRNGKey(0))
        prov3 = jnp.asarray(np.array([2, 41, 1234], np.int32))
        carry, rs, stats = roll(params, carry, ring.state, key,
                                jnp.int32(0), eps, prov3)
        fed = int(jax.device_get(stats.fed))
        assert fed == (K - NSTEP) * N
        pv = np.asarray(jax.device_get(rs.prov))[:fed]
        assert (pv[:, 0] == 2).all()
        assert (pv[:, 2] == 41).all()
        assert (pv[:, 3] == 1234).all()
        # rows land tick-major: env_slot cycles 0..N-1 per tick
        np.testing.assert_array_equal(
            pv[:, 1], np.tile(np.arange(N), K - NSTEP))


class TestHostVsDeviceEmitParity:
    @pytest.mark.slow
    def test_device_and_host_emit_mint_identical_provenance(
            self, tmp_path):
        """ISSUE 8 satellite: under a fixed param version and learner
        clock, the fused device rollout path and the host
        NStepAssembler emit path mint BIT-IDENTICAL provenance for the
        same (actor, env-slot) stream positions.  The transition
        streams themselves are pinned identical by the PR-7 parity
        chain (tests/test_device_env.py — the inline leg there steps a
        CounterRng-patched twin env, which bounded runs don't), so the
        provenance claim reduces to both paths minting the same
        deterministic (actor_id, env_slot, version, birth) pattern
        over their emission order — asserted against the closed-form
        expectation on a REAL device bounded run (dqn-cnn fused
        rollout) and a REAL inline bounded run (host assembler path,
        fake-env geometry where it is cheap)."""
        from pytorch_distributed_tpu.agents.actor import (
            bounded_actor_run,
        )

        N = 4
        # device leg: the fused rollout driver's per-dispatch stamps
        opt = build_options(
            4, root_dir=str(tmp_path), refs="prov_dev", num_actors=1,
            num_envs_per_actor=N, actor_backend="device",
            visualize=False, actor_freq=10 ** 9,
            actor_sync_freq=10 ** 9)
        opt.env_params.device_rollout_ticks = 4
        dev = bounded_actor_run(opt, ticks=3, param_seed=0)["stream"]
        # inline leg: the host assembler's per-tick mints over the same
        # game (no episode boundary falls inside 12 Pong ticks, so both
        # paths sit in pure steady state)
        opt2 = build_options(
            4, root_dir=str(tmp_path), refs="prov_inl", num_actors=1,
            num_envs_per_actor=N, actor_backend="inline",
            visualize=False, actor_freq=10 ** 9,
            actor_sync_freq=10 ** 9)
        inl = bounded_actor_run(opt2, ticks=12, param_seed=0)["stream"]
        assert len(dev) >= 20 and len(inl) >= 20

        def expected(stream):
            # post-warmup every tick emits one row per env, env-slot
            # cycling 0..N-1; version is the single published snapshot
            # (1), birth the frozen learner clock (0)
            return [make_prov(0, i % N, 1, 0)
                    for i in range(len(stream))]

        for stream in (dev, inl):
            for (t, _p), want in zip(stream, expected(stream)):
                assert t.prov is not None
                np.testing.assert_array_equal(np.asarray(t.prov), want)


# ---------------------------------------------------------------------------
# staleness math + priority X-ray + detector
# ---------------------------------------------------------------------------

class TestStalenessAndXray:
    def test_staleness_under_prefetcher_version_bumps(self):
        from pytorch_distributed_tpu.agents.param_store import (
            ParamPrefetcher, ParamStore,
        )

        store = ParamStore(4)
        v1 = store.publish(np.zeros(4, np.float32))
        pf = ParamPrefetcher(store, lambda flat: flat,
                             start_version=v1, poll_secs=0.01)
        try:
            v2 = store.publish(np.ones(4, np.float32))
            deadline = time.monotonic() + 5.0
            got = None
            while got is None and time.monotonic() < deadline:
                got = pf.take()
                time.sleep(0.01)
            assert got is not None
            _tree, version = got
            assert version == v2
        finally:
            pf.close()
        # the learner-side subtraction: rows minted pre-bump read as
        # one version stale, post-bump rows as fresh
        prov = np.stack([make_prov(0, 0, v1, 10),
                         make_prov(0, 1, v2, 20)])
        staleness = np.maximum(store.version - prov[:, 2], 0)
        np.testing.assert_array_equal(staleness, [1, 0])

    def test_priority_xray_host_math(self):
        uniform = health.priority_xray(np.full(100, 0.5))
        assert uniform["rows"] == 100
        assert uniform["ess"] == pytest.approx(100.0)
        assert uniform["ess_frac"] == pytest.approx(1.0)
        assert uniform["counts"].sum() == 100
        spiked = health.priority_xray(
            np.concatenate([np.full(99, 1e-6), [100.0]]))
        assert spiked["ess_frac"] < 0.05  # one row dominates
        assert health.priority_xray(np.zeros(8)) is None

    def test_priority_xray_device_matches_host_buckets(self):
        import jax

        from pytorch_distributed_tpu.memory.device_per import (
            DevicePerReplay, priority_xray_device,
        )

        mem = DevicePerReplay(capacity=32, state_shape=(4,),
                              state_dtype=np.float32)
        n = 16
        mem.feed_chunk(Transition(
            state0=np.zeros((n, 4), np.float32),
            action=np.zeros((n,), np.int32),
            reward=np.zeros((n,), np.float32),
            gamma_n=np.full((n,), 0.9, np.float32),
            state1=np.zeros((n, 4), np.float32),
            terminal1=np.zeros((n,), np.float32)))
        leaves = np.asarray(jax.device_get(mem.state.priority))
        counts, ess, rows, mass = jax.device_get(
            priority_xray_device(mem.state))
        host = health.priority_xray(leaves[leaves > 0])
        assert int(rows) == host["rows"] == n
        assert float(ess) == pytest.approx(host["ess"], rel=1e-5)
        assert float(mass) == pytest.approx(host["mass"], rel=1e-5)
        np.testing.assert_array_equal(np.asarray(counts),
                                      host["counts"])

    def test_detector_fires_on_ess_collapse(self):
        det = health.AnomalyDetector(threshold=1, ess_floor=0.05)
        assert "priority_collapse" not in det.observe(
            priority_mass=10.0, replay_rows=100, priority_ess=0.5)
        out = det.observe(priority_mass=10.0, replay_rows=100,
                          priority_ess=0.01)
        assert "priority_collapse" in out  # healthy mass, collapsed ESS


# ---------------------------------------------------------------------------
# quarantine correlation keys (satellite 6)
# ---------------------------------------------------------------------------

class TestQuarantineCorrelation:
    def test_quarantine_file_carries_run_id_wall_and_prov(self, tmp_path):
        flight_recorder.configure(str(tmp_path), run_id="drill_run_7")
        store = health.QuarantineStore("test-src")
        bad = [(_mk_transition(0, make_prov(5, 2, 3, 99)), float("nan"),
                "non-finite reward")]
        path = store.put(bad, trace_id=0xabc)
        assert path is not None
        with np.load(path, allow_pickle=False) as z:
            cols = {k: z[k] for k in z.files}
        assert str(cols["run_id"][0]) == "drill_run_7"
        assert cols["wall"][0] > 0
        np.testing.assert_array_equal(cols["prov"][0], [5, 2, 3, 99])

    def test_stack_prov_mixed(self):
        rows = stack_prov([(_mk_transition(0, make_prov(1, 2, 3, 4)), 0.1),
                           (_mk_transition(1, None), None)])
        np.testing.assert_array_equal(rows,
                                      [[1, 2, 3, 4], [-1, -1, -1, -1]])

    def test_stack_prov_accepts_bare_transitions(self):
        """Transition IS a NamedTuple (a tuple): stack_prov must not
        unwrap it as an (item, priority) pair — that would read state0
        and silently sentinel every stamped row (the review-caught bug
        that killed provenance on the device-ring ingest path)."""
        rows = stack_prov([_mk_transition(0, make_prov(9, 8, 7, 6)),
                           _mk_transition(1, None)])
        np.testing.assert_array_equal(rows,
                                      [[9, 8, 7, 6], [-1, -1, -1, -1]])

    def test_device_ingest_drain_stamps_ring_columns(self):
        """End to end over the host-actor -> device-ring path: a
        QueueFeeder chunk of stamped transitions drained by
        DeviceReplayIngest must land in the HBM ring's provenance
        columns, not as sentinels."""
        import jax

        from pytorch_distributed_tpu.memory.device_replay import (
            DeviceReplayIngest, provenance_sample,
        )

        ing = DeviceReplayIngest(capacity=64, state_shape=(4,),
                                 state_dtype=np.float32, chunk_size=4)
        feeder = ing.make_feeder(chunk=4)
        ing.attach(mesh=None)
        for i in range(8):
            feeder.feed(_mk_transition(i, make_prov(1, i % 4, 2, 30 + i)),
                        None)
        feeder.flush()
        deadline = time.monotonic() + 10.0
        while ing.size < 8 and time.monotonic() < deadline:
            ing.drain()
            time.sleep(0.02)
        assert ing.size == 8
        pv, _fill = provenance_sample(ing.replay.state,
                                      jax.random.PRNGKey(0), n=32)
        pv = np.asarray(pv)
        assert (pv[:, 0] == 1).all()      # no sentinels: stamps survived
        assert (pv[:, 2] == 2).all()
        assert set(pv[:, 3].tolist()) <= set(range(30, 38))

    def test_shared_replay_unwritten_rows_read_unknown(self):
        from pytorch_distributed_tpu.memory.shared_replay import (
            SharedReplay,
        )

        mem = SharedReplay(capacity=8, state_shape=(4,),
                           state_dtype=np.float32)
        mem.feed(_mk_transition(0, make_prov(1, 2, 3, 4)))
        mem.feed(_mk_transition(1, None))
        got = mem.provenance_of(np.arange(8))
        np.testing.assert_array_equal(got[0], [1, 2, 3, 4])
        # unwritten pages are zeroed mp.Arrays — they must still read
        # as the -1 sentinel, never as "actor 0, version 0"
        assert (got[1:] == -1).all()


# ---------------------------------------------------------------------------
# fleet_top data line
# ---------------------------------------------------------------------------

class TestFleetTopDataLine:
    def test_data_line_renders_from_perf_gauges(self):
        sys.path.insert(0, os.path.join(_REPO, "tools"))
        import fleet_top

        status = {"perf": {"learner": {
            "data/staleness_p50": 2.0, "data/sample_age_p95": 140.0,
            "data/priority_ess": 0.42, "data/top_actor_share": 0.3}}}
        line = fleet_top.data_line(status)
        assert "staleness p50 2" in line
        assert "sample age p95 140" in line
        assert "priority ESS 42%" in line
        assert "top actor 30%" in line
        vals = fleet_top.data_values(status)
        assert vals["data/priority_ess"] == 0.42
        assert fleet_top.data_line({}) is None


# ---------------------------------------------------------------------------
# acceptance: live data plane on a short CPU PER topology
# ---------------------------------------------------------------------------

class TestDataPlaneAcceptance:
    def test_cpu_per_topology_exports_data_plane_live(self, tmp_path,
                                                      monkeypatch):
        """ISSUE 8 acceptance: a CPU topology run with TPU_APEX_PERF=1
        exports learner/staleness, learner/sample_age,
        replay/actor_share histogram rows and the priority X-ray
        (buckets row + replay/priority_ess) to the metrics stream, and
        the STATUS perf block carries the live data/* gauges fleet_top
        renders."""
        monkeypatch.setenv("TPU_APEX_PERF", "1")
        from pytorch_distributed_tpu.fleet import FleetTopology

        opt = build_options(
            1, memory_type="prioritized", root_dir=str(tmp_path),
            refs="provrun", num_actors=1, seed=5,
            steps=10 ** 9, max_seconds=120.0, max_replay_ratio=16.0,
            learn_start=32, memory_size=512, batch_size=16,
            actor_freq=25, actor_sync_freq=50, param_publish_freq=25,
            learner_freq=25, logger_freq=2, evaluator_nepisodes=0,
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
        status = None
        try:
            deadline = time.monotonic() + 100
            while time.monotonic() < deadline and not done.is_set():
                try:
                    status = fetch_status(addr, timeout=5.0)
                except (ConnectionError, OSError):
                    status = None
                lsnap = (status or {}).get("perf", {}).get("learner", {})
                if "data/staleness_p50" in lsnap:
                    break
                time.sleep(0.25)
        finally:
            topo.clock.stop.set()
            t.join(120)
        assert not t.is_alive()
        lsnap = (status or {}).get("perf", {}).get("learner", {})
        assert "data/staleness_p50" in lsnap, \
            f"data gauges never reached STATUS (have {sorted(lsnap)})"
        assert "data/priority_ess" in lsnap
        assert 0 < lsnap["data/priority_ess"] <= 1.0
        assert "data/top_actor_share" in lsnap

        rows = read_scalars(opt.log_dir)
        hists = {r["tag"] for r in rows if r.get("kind") == "histogram"}
        for tag in ("learner/staleness", "learner/sample_age",
                    "replay/actor_share"):
            assert tag in hists, f"{tag} histogram missing"
        buckets = [r for r in rows if r.get("kind") == "buckets"
                   and r["tag"] == "replay/priority"]
        assert buckets, "priority X-ray buckets row missing"
        assert sum(buckets[-1]["counts"]) == buckets[-1]["rows"]
        ess_rows = [r for r in rows
                    if r.get("tag") == "replay/priority_ess_frac"]
        assert ess_rows and all(0 < r["value"] <= 1.0 for r in ess_rows)
        # staleness is version-denominated and sane: p50 gauge is a
        # small non-negative number (actors lag by at most a few
        # publishes at these cadences)
        assert lsnap["data/staleness_p50"] >= 0
