"""Training health sentinel drills (utils/health.py + the ladder wiring).

Fast tier: unit drills for every rung — the in-jit finite guard (params
provably bit-unchanged across a skipped step), the PER write-back
suppression, the anomaly detector, ingest validation/quarantine on all
three boundaries (QueueOwner, DeviceReplayIngest, DcnGateway), the
NaN-vs-None priority wire fix, malformed-frame rejection, the rollback
checkpoint machinery, the ProgressBoard, and an in-process learner run
that diverges, rolls back to its last good epoch and completes.

Slow tier (excluded from tier-1): full process-topology drills — a hung
actor SIGKILLed and respawned by the watchdog, and the end-to-end chaos
acceptance run mixing poison_chunk / poison_grad / hang in one topology.
"""

from __future__ import annotations

import json
import io
import os
import time

import numpy as np
import pytest

from pytorch_distributed_tpu.utils import flight_recorder, health, tracing
from pytorch_distributed_tpu.utils.experience import Batch, Transition


@pytest.fixture(autouse=True)
def _isolate(tmp_path, monkeypatch):
    """Each test gets its own quarantine/blackbox home and a clean
    registry; fault-plane envs never leak between tests."""
    health.reset()
    flight_recorder.reset()
    flight_recorder.configure(str(tmp_path))
    for var in ("FEEDER_FAULTS", "LEARNER_FAULTS", "ACTOR_FAULTS",
                "TPU_APEX_QUARANTINE"):
        monkeypatch.delenv(var, raising=False)
    yield
    health.reset()
    flight_recorder.reset()


def _transition(reward=0.5, state=None, action=0, priority=None,
                dtype=np.float32, shape=(4,)):
    s = (np.zeros(shape, dtype) if state is None
         else np.asarray(state, dtype))
    return (Transition(state0=s, action=np.int32(action),
                       reward=np.float32(reward),
                       gamma_n=np.float32(0.99),
                       state1=s.copy(), terminal1=np.float32(0.0)),
            priority)


# ---------------------------------------------------------------------------
# in-jit finite guard
# ---------------------------------------------------------------------------

class TestFiniteGuard:
    def _setup(self):
        import jax

        from pytorch_distributed_tpu.models import DqnMlpModel
        from pytorch_distributed_tpu.ops.losses import (
            build_dqn_train_step, init_train_state, make_optimizer,
        )

        model = DqnMlpModel(action_space=3, hidden_dim=16)
        params = model.init(jax.random.PRNGKey(0),
                            np.zeros((1, 4), np.float32))
        tx = make_optimizer(1e-3)
        state = init_train_state(params, tx)
        step = jax.jit(build_dqn_train_step(model.apply, tx))
        return state, step

    def _batch(self, reward):
        B = 4
        rng = np.random.default_rng(0)
        return Batch(
            state0=rng.normal(size=(B, 4)).astype(np.float32),
            action=rng.integers(0, 3, B).astype(np.int32),
            reward=np.full(B, reward, np.float32),
            gamma_n=np.full(B, 0.99, np.float32),
            state1=rng.normal(size=(B, 4)).astype(np.float32),
            terminal1=np.zeros(B, np.float32),
            weight=np.ones(B, np.float32),
            index=np.arange(B, dtype=np.int32))

    def test_nonfinite_step_skipped_params_bit_unchanged(self):
        import jax

        state, step = self._setup()
        state, m, _ = step(state, self._batch(1.0))
        assert float(m[health.SKIPPED_KEY]) == 0.0
        before = [np.asarray(x) for x in jax.tree_util.tree_leaves(state)]
        state2, m2, td2 = step(state, self._batch(np.nan))
        assert float(m2[health.SKIPPED_KEY]) == 1.0
        # the raw loss stays visible (the anomaly detector wants it)...
        assert not np.isfinite(float(m2["learner/critic_loss"]))
        # ...but params, opt state AND the step counter are bit-unchanged
        after = [np.asarray(x) for x in jax.tree_util.tree_leaves(state2)]
        for a, b in zip(before, after):
            assert np.array_equal(a, b, equal_nan=True)
        # TD zeroed so an unaware write-back can't scatter NaN priorities
        assert float(np.abs(np.asarray(td2)).sum()) == 0.0

    def test_recovers_after_skip(self):
        state, step = self._setup()
        state, _, _ = step(state, self._batch(1.0))
        state, _, _ = step(state, self._batch(np.nan))
        state, m, _ = step(state, self._batch(1.0))
        assert float(m[health.SKIPPED_KEY]) == 0.0
        assert int(state.step) == 2  # skipped step never counted

    def test_guard_off_passes_nan_through(self):
        import jax

        from pytorch_distributed_tpu.models import DqnMlpModel
        from pytorch_distributed_tpu.ops.losses import (
            build_dqn_train_step, init_train_state, make_optimizer,
        )

        model = DqnMlpModel(action_space=3, hidden_dim=16)
        params = model.init(jax.random.PRNGKey(0),
                            np.zeros((1, 4), np.float32))
        tx = make_optimizer(1e-3)
        state = init_train_state(params, tx)
        step = jax.jit(build_dqn_train_step(model.apply, tx, guard=False))
        state, m, _ = step(state, self._batch(np.nan))
        assert health.SKIPPED_KEY not in m
        leaves = jax.tree_util.tree_leaves(state.params)
        assert not all(np.isfinite(np.asarray(x)).all() for x in leaves)

    def test_reduce_scan_metrics_sums_skip_counter(self):
        import jax.numpy as jnp

        stacked = {"learner/critic_loss": jnp.asarray([1.0, 2.0, 3.0]),
                   health.SKIPPED_KEY: jnp.asarray([1.0, 0.0, 1.0])}
        out = health.reduce_scan_metrics(stacked)
        assert float(out["learner/critic_loss"]) == 3.0
        assert float(out[health.SKIPPED_KEY]) == 2.0

    def test_reduce_scan_metrics_averages_the_exchange_rounds(self):
        import jax.numpy as jnp

        stacked = {"learner/critic_loss": jnp.asarray([1.0, 2.0, 3.0, 4.0]),
                   health.EXCHANGE_ROUNDS_KEY: jnp.asarray([1., 2., 2., 1.])}
        out = health.reduce_scan_metrics(stacked)
        assert float(out["learner/critic_loss"]) == 4.0
        assert float(out[health.EXCHANGE_ROUNDS_KEY]) == 1.5

    def test_per_writeback_suppressed_on_skip(self):
        """A guarded step that skips must leave the fused PER ring's
        priorities bit-unchanged (its zeroed TD would otherwise crush
        every sampled row to epsilon priority)."""
        import jax

        from pytorch_distributed_tpu.memory.device_per import (
            DevicePerReplay,
        )

        ring = DevicePerReplay(capacity=32, state_shape=(4,),
                               state_dtype=np.float32)
        rng = np.random.default_rng(1)
        C = 32
        ring.feed_chunk(Transition(
            state0=rng.normal(size=(C, 4)).astype(np.float32),
            action=rng.integers(0, 3, C).astype(np.int32),
            reward=rng.normal(size=C).astype(np.float32),
            gamma_n=np.full(C, 0.99, np.float32),
            state1=rng.normal(size=(C, 4)).astype(np.float32),
            terminal1=np.zeros(C, np.float32)))

        def raw_step(bad):
            def step(ts, batch):
                td = jnp_full = np.nan if bad else 1.0
                import jax.numpy as jnp

                td_abs = jnp.full(batch.reward.shape[0], jnp_full,
                                  jnp.float32)
                metrics = {"learner/critic_loss": jnp.sum(td_abs)}
                return {"w": ts["w"] + 1.0}, metrics, td_abs
            return health.finite_guard(step)

        ts = {"w": np.float32(0.0)}
        fused_bad = ring.build_fused_step(raw_step(bad=True), 8,
                                          donate=False)
        before = np.asarray(jax.device_get(ring.state.priority))
        key = jax.random.PRNGKey(0)
        ts2, rs2, m = fused_bad(ts, ring.state, key, np.float32(0.4))
        assert float(m[health.SKIPPED_KEY]) == 1.0
        assert np.array_equal(np.asarray(jax.device_get(rs2.priority)),
                              before)
        assert float(ts2["w"]) == 0.0  # train state passed through too
        fused_ok = ring.build_fused_step(raw_step(bad=False), 8,
                                         donate=False)
        ts3, rs3, m3 = fused_ok(ts, ring.state, key, np.float32(0.4))
        assert float(m3[health.SKIPPED_KEY]) == 0.0
        assert not np.array_equal(
            np.asarray(jax.device_get(rs3.priority)), before)


# ---------------------------------------------------------------------------
# anomaly detector
# ---------------------------------------------------------------------------

class TestAnomalyDetector:
    def test_steady_loss_never_trips(self):
        d = health.AnomalyDetector(zmax=6.0, threshold=2)
        rng = np.random.default_rng(0)
        for _ in range(50):
            assert d.observe(loss=1.0 + 0.01 * rng.normal(),
                             grad_norm=0.5) == []
        assert not d.should_rollback()

    def test_loss_spike_and_streak(self):
        d = health.AnomalyDetector(zmax=6.0, threshold=2)
        for _ in range(20):
            d.observe(loss=1.0, grad_norm=0.5)
        # EWMA variance of a constant is ~0; floor makes any jump trip
        assert "loss_spike" in d.observe(loss=500.0, grad_norm=0.5)
        assert not d.should_rollback()  # streak 1 < threshold 2
        d.observe(loss=500.0, grad_norm=0.5)
        assert d.should_rollback()
        d.observe(loss=1.0, grad_norm=0.5)  # healthy window resets
        assert not d.should_rollback()

    def test_grad_spike_and_nonfinite(self):
        d = health.AnomalyDetector(grad_spike=10.0, threshold=1)
        for _ in range(20):
            d.observe(loss=1.0, grad_norm=1.0)
        assert "grad_spike" in d.observe(loss=1.0, grad_norm=100.0)
        assert "nonfinite" in d.observe(loss=float("nan"), grad_norm=1.0)
        assert "skipped" in d.observe(loss=1.0, grad_norm=1.0, skipped=3)

    def test_spikes_do_not_poison_baseline(self):
        d = health.AnomalyDetector(grad_spike=10.0, threshold=99)
        for _ in range(20):
            d.observe(grad_norm=1.0)
        for _ in range(5):  # a sustained spike keeps tripping: the
            # anomalous readings never fold into their own baseline
            assert "grad_spike" in d.observe(grad_norm=100.0)

    def test_priority_collapse_and_reset(self):
        d = health.AnomalyDetector(threshold=1)
        assert "priority_collapse" in d.observe(priority_mass=0.0,
                                                replay_rows=100)
        assert d.should_rollback()
        d.reset()
        assert not d.should_rollback()
        assert d.observe(priority_mass=5.0, replay_rows=100) == []


# ---------------------------------------------------------------------------
# ingest validation + quarantine stores
# ---------------------------------------------------------------------------

class TestChunkValidator:
    def test_clean_items_pass_as_same_object(self):
        v = health.ChunkValidator()
        items = tracing.TracedChunk([_transition(), _transition(1.0, priority=2.0)])
        out, bad = v.filter(items)
        assert out is items and bad == []

    def test_nonfinite_scalars_rejected(self):
        v = health.ChunkValidator()
        out, bad = v.filter([_transition(), _transition(np.nan)])
        assert len(out) == 1 and len(bad) == 1
        assert "reward" in bad[0][2]

    def test_nan_obs_rejected_for_float_states(self):
        v = health.ChunkValidator()
        s = np.array([1.0, np.nan, 0.0, 0.0], np.float32)
        out, bad = v.filter([_transition(state=s)])
        assert not out and "state0" in bad[0][2]

    def test_uint8_states_skip_the_scan(self):
        v = health.ChunkValidator()
        out, bad = v.filter(
            [_transition(state=np.zeros((2, 2), np.uint8),
                         dtype=np.uint8, shape=(2, 2))])
        assert out and not bad

    def test_priority_garbage_rejected(self):
        v = health.ChunkValidator()
        out, bad = v.filter([_transition(priority=float("nan")),
                             _transition(priority=-1.0),
                             _transition(priority=3.0)])
        assert len(out) == 1 and len(bad) == 2

    def test_shape_and_dtype_drift_rejected(self):
        v = health.ChunkValidator(state_shape=(4,), state_dtype=np.float32)
        out, bad = v.filter([
            _transition(),
            _transition(shape=(5,)),                      # shape drift
            _transition(dtype=np.float64),                # dtype drift
        ])
        assert len(out) == 1 and len(bad) == 2
        assert "shape" in bad[0][2] and "dtype" in bad[1][2]

    def test_first_seen_schema_latches(self):
        v = health.ChunkValidator()
        out, bad = v.filter([_transition(shape=(4,))])
        assert not bad
        out, bad = v.filter([_transition(shape=(8,))])
        assert bad and "shape" in bad[0][2]

    def test_action_range(self):
        v = health.ChunkValidator(num_actions=4)
        out, bad = v.filter([_transition(action=3), _transition(action=7)])
        assert len(out) == 1 and "range" in bad[0][2]

    # -- Segment rows (R2D2 sequence topologies) ------------------------

    def _segment(self, T=4, reward_nan_at=None, obs_shape=(5, 3)):
        from pytorch_distributed_tpu.memory.sequence_replay import Segment

        reward = np.zeros(T, np.float32)
        if reward_nan_at is not None:
            reward[reward_nan_at] = np.nan
        return Segment(
            obs=np.zeros(obs_shape, np.float32),
            action=np.zeros(T, np.int32), reward=reward,
            terminal=np.zeros(T, np.float32),
            mask=np.ones(T, np.float32),
            c0=np.zeros(2, np.float32), h0=np.zeros(2, np.float32))

    def test_segment_rows_validate_instead_of_crashing(self):
        """Regression (found driving config 13 under ISSUE 9): the
        validator scalar-checked Segment.reward — a (T,) array — and
        raised ValueError on the learner's FIRST drain of every
        sequence topology with quarantine active.  The per-step
        state_shape a SequenceReplay advertises must also never be
        compared against the segment's whole-window obs."""
        v = health.ChunkValidator(state_shape=(3,),
                                  state_dtype=np.float32)
        out, bad = v.filter([(self._segment(), 1.0),
                             (self._segment(), None)])
        assert len(out) == 2 and bad == []

    def test_segment_nonfinite_and_drift_rejected(self):
        v = health.ChunkValidator()
        out, bad = v.filter([
            (self._segment(), 1.0),
            (self._segment(reward_nan_at=2), 1.0),   # NaN reward step
            (self._segment(obs_shape=(6, 3)), 1.0),  # window drift
        ])
        assert len(out) == 1 and len(bad) == 2
        assert "reward" in bad[0][2] and "shape" in bad[1][2]


class TestQuarantineStore:
    def test_writes_npz_with_reason_and_trace(self, tmp_path):
        st = health.get_quarantine("test-src")
        t, p = _transition(np.nan)
        path = st.put([(t, p, "non-finite reward")], trace_id=0xabc)
        assert path and os.path.exists(path)
        with np.load(path) as z:
            assert "non-finite reward" in str(z["reason"][0])
            assert z["trace_id"][0] == tracing.format_trace_id(0xabc)
            assert np.isnan(z["reward"][0])
        assert health.quarantine_counts() == {"test-src": 1}

    def test_file_budget_bounds_disk_not_counting(self):
        st = health.QuarantineStore("bounded", max_files=2)
        for _ in range(5):
            st.put([(*_transition(np.nan), "r")])
        assert st.files == 2 and st.count == 5

    def test_segment_rows_quarantine_without_crashing(self):
        """Companion to the validator segment fix: put() must dump the
        SEGMENT schema, not getattr the six transition columns (that
        crashed the drain on the first rejected segment)."""
        from pytorch_distributed_tpu.memory.sequence_replay import (
            Segment,
        )

        seg = Segment(obs=np.zeros((5, 3), np.float32),
                      action=np.zeros(4, np.int32),
                      reward=np.full(4, np.nan, np.float32),
                      terminal=np.zeros(4, np.float32),
                      mask=np.ones(4, np.float32),
                      c0=np.zeros(2, np.float32),
                      h0=np.zeros(2, np.float32))
        st = health.get_quarantine("seq-src")
        path = st.put([(seg, 1.0, "non-finite reward")])
        assert path and os.path.exists(path)
        with np.load(path) as z:
            assert z["obs"].shape == (1, 5, 3)
            assert np.isnan(z["reward"]).any()
            assert "state0" not in z.files

    def test_shape_drifted_offenders_still_quarantine(self):
        st = health.get_quarantine("drift")
        bad = [( _transition(shape=(3,))[0], None, "shape drift"),
               (_transition(shape=(9,))[0], None, "shape drift")]
        path = st.put(bad)
        assert path and os.path.exists(path)


class TestIngestBoundaries:
    def _owner(self):
        from pytorch_distributed_tpu.memory.feeder import QueueOwner

        class Rec:
            def __init__(self):
                self.items = []

            def feed(self, t, p):
                self.items.append((t, p))

        rec = Rec()
        return QueueOwner(rec), rec

    def test_queue_owner_drain_quarantines(self):
        owner, rec = self._owner()
        f = owner.make_feeder(chunk=2)
        f.feed(*_transition(0.1))
        f.feed(*_transition(0.2))          # clean chunk latches schema
        f.feed(*_transition(np.nan))
        f.feed(*_transition(0.3))          # mixed chunk: 1 bad, 1 good
        time.sleep(0.2)  # spawn queue feeder thread latency
        while owner.drain():
            pass
        assert len(rec.items) == 3
        assert all(np.isfinite(t.reward) for t, _ in rec.items)
        assert health.quarantine_counts() == {"feeder-local": 1}

    def test_quarantine_kill_switch(self, monkeypatch):
        monkeypatch.setenv("TPU_APEX_QUARANTINE", "0")
        owner, rec = self._owner()
        f = owner.make_feeder(chunk=1)
        f.feed(*_transition(np.nan))
        time.sleep(0.2)
        while owner.drain():
            pass
        assert len(rec.items) == 1  # pre-sentinel behaviour restored
        assert health.quarantine_counts() == {}

    def test_poison_chunk_verb_poisons_then_quarantined(self, monkeypatch):
        monkeypatch.setenv("FEEDER_FAULTS", "poison_chunk@1")
        owner, rec = self._owner()
        f = owner.make_feeder(chunk=2)
        for i in range(4):  # flush 0 clean, flush 1 poisoned
            f.feed(*_transition(0.1 * (i + 1)))
        time.sleep(0.2)
        while owner.drain():
            pass
        assert len(rec.items) == 2
        assert health.quarantine_counts() == {"feeder-local": 2}

    def test_device_ingest_quarantines_shape_drift(self):
        from pytorch_distributed_tpu.memory.device_replay import (
            DeviceReplayIngest,
        )

        ing = DeviceReplayIngest(capacity=64, state_shape=(4,),
                                 state_dtype=np.float32, chunk_size=2)
        ing.attach(mesh=None)
        f = ing.make_feeder(chunk=2)
        f.feed(*_transition(0.1))
        f.feed(*_transition(np.nan))       # caught by finiteness
        f.feed(*_transition(0.2, shape=(7,)))  # would crash np.stack
        f.feed(*_transition(0.3))
        time.sleep(0.2)
        ing.drain()
        snap = ing.snapshot()
        assert len(snap["reward"]) == 2
        assert np.isfinite(snap["reward"]).all()
        assert health.quarantine_counts() == {"feeder-device": 2}


# ---------------------------------------------------------------------------
# DCN wire: priority validity, malformed frames, gateway quarantine
# ---------------------------------------------------------------------------

class TestWirePriorityValidity:
    def test_none_vs_nan_round_trip(self):
        from pytorch_distributed_tpu.parallel.dcn import (
            decode_chunk, encode_chunk,
        )

        items = [_transition(priority=None), _transition(priority=1.5),
                 _transition(priority=float("nan"))]
        out = decode_chunk(encode_chunk(items))
        assert out[0][1] is None
        assert out[1][1] == 1.5
        # the regression this satellite fixes: a genuine NaN priority
        # must survive as NaN (to be quarantined), never decode as None
        assert out[2][1] is not None and np.isnan(out[2][1])

    def test_sentinel_era_frames_still_decode(self):
        """Old peers without the validity column: NaN meant None."""
        from pytorch_distributed_tpu.parallel.dcn import (
            _FIELDS, decode_chunk, encode_chunk,
        )

        payload = encode_chunk([_transition(priority=None),
                                _transition(priority=2.0)])
        with np.load(io.BytesIO(payload)) as z:
            cols = {k: z[k] for k in z.files if k != "priority_ok"}
        buf = io.BytesIO()
        np.savez(buf, **cols)
        out = decode_chunk(buf.getvalue())
        assert out[0][1] is None and out[1][1] == 2.0
        assert set(_FIELDS) <= set(cols)


class TestMalformedFrames:
    def _payload(self, mutate):
        from pytorch_distributed_tpu.parallel.dcn import encode_chunk

        payload = encode_chunk([_transition(0.1), _transition(0.2)])
        with np.load(io.BytesIO(payload)) as z:
            cols = {k: z[k] for k in z.files}
        mutate(cols)
        buf = io.BytesIO()
        np.savez(buf, **cols)
        return buf.getvalue()

    def test_truncated_column_rejected(self):
        from pytorch_distributed_tpu.parallel.dcn import decode_chunk

        def truncate(cols):
            cols["reward"] = cols["reward"][:1]
        with pytest.raises(ValueError, match="length"):
            decode_chunk(self._payload(truncate))

    def test_missing_column_rejected(self):
        from pytorch_distributed_tpu.parallel.dcn import decode_chunk

        def drop(cols):
            del cols["gamma_n"]
        with pytest.raises(ValueError, match="missing"):
            decode_chunk(self._payload(drop))

    def test_wrong_dtype_rejected(self):
        from pytorch_distributed_tpu.parallel.dcn import decode_chunk

        def stringify(cols):
            cols["reward"] = np.array(["a", "b"])
        with pytest.raises(ValueError, match="not numeric"):
            decode_chunk(self._payload(stringify))

    def test_garbage_bytes_stay_on_connection_path(self):
        from pytorch_distributed_tpu.parallel.dcn import decode_chunk

        with pytest.raises(ConnectionError):
            decode_chunk(b"\x00garbage-not-a-zip")


class _GatewayPlane:
    """Minimal live gateway + sink, no jax/topology."""

    def __init__(self):
        from pytorch_distributed_tpu.agents.clocks import (
            ActorStats, GlobalClock,
        )
        from pytorch_distributed_tpu.agents.param_store import ParamStore
        from pytorch_distributed_tpu.parallel.dcn import DcnGateway

        self.delivered = []
        self.clock = GlobalClock()
        store = ParamStore(4)
        store.publish(np.zeros(4, np.float32))
        self.gw = DcnGateway(store, self.clock, ActorStats(),
                             put_chunk=self.delivered.append,
                             host="127.0.0.1", port=0)

    def close(self):
        self.gw.close()


class TestGatewayIngest:
    def test_poisoned_chunk_quarantined_per_slot(self):
        from pytorch_distributed_tpu.parallel.dcn import DcnClient

        plane = _GatewayPlane()
        try:
            client = DcnClient(("127.0.0.1", plane.gw.port),
                               process_ind=2, heartbeat_interval=0.0)
            client.send_chunk([_transition(0.5)])
            client.send_chunk([_transition(np.nan),
                               _transition(0.7)])
            flat = [t for chunk in plane.delivered for t, _p in chunk]
            assert len(flat) == 2
            assert all(np.isfinite(t.reward) for t in flat)
            snap = plane.gw.status_snapshot()
            assert snap["quarantined"] == {"slot2": 1}
            assert plane.gw.chunks_in == 2  # session never dropped
            client.close()
        finally:
            plane.close()

    def test_malformed_frame_rejected_with_ack_session_survives(self):
        import socket
        import struct

        from pytorch_distributed_tpu.parallel.dcn import (
            T_CLOCK, T_EXP, T_HELLO, T_PING, _recv_frame, _send_frame,
            encode_chunk,
        )

        plane = _GatewayPlane()
        try:
            sock = socket.create_connection(("127.0.0.1", plane.gw.port),
                                            timeout=5.0)
            sock.settimeout(5.0)
            _send_frame(sock, T_HELLO, json.dumps(
                {"role": "actor", "process_ind": 0,
                 "incarnation": 1}).encode())
            assert _recv_frame(sock)[0] == T_CLOCK
            # well-framed savez with a truncated column: schema reject
            payload = encode_chunk([_transition(0.1), _transition(0.2)])
            with np.load(io.BytesIO(payload)) as z:
                cols = {k: z[k] for k in z.files}
            cols["priority"] = cols["priority"][:1]
            buf = io.BytesIO()
            np.savez(buf, **cols)
            _send_frame(sock, T_EXP, buf.getvalue())
            rtype, _ = _recv_frame(sock)  # acked, NOT disconnected
            assert rtype == T_CLOCK
            _send_frame(sock, T_PING, b"")
            assert _recv_frame(sock)[0] == T_CLOCK  # session alive
            assert plane.gw.frames_rejected == 1
            assert plane.delivered == []
            snap = plane.gw.status_snapshot()
            assert snap["frames_rejected"] == 1
            sock.close()
        finally:
            plane.close()


# ---------------------------------------------------------------------------
# rollback machinery (checkpoint tier)
# ---------------------------------------------------------------------------

class TestRollbackCheckpointMachinery:
    def _save(self, model_name, step, extras=None):
        from pytorch_distributed_tpu.utils import checkpoint as ckpt

        return ckpt.save_epoch(model_name, state=None,
                               extras=dict(learner_step=step,
                                           **(extras or {})),
                               retain=10)

    def test_resolve_skips_rolled_back_and_respects_before(self, tmp_path):
        from pytorch_distributed_tpu.utils import checkpoint as ckpt

        name = str(tmp_path / "run")
        for step in (10, 20, 30):
            self._save(name, step)
        info = ckpt.resolve_epoch(name)
        assert info.epoch == 2 and info.learner_step == 30
        ckpt.mark_rolled_back(info.path, to_epoch=1, reason="drill")
        info = ckpt.resolve_epoch(name)
        assert info.epoch == 1 and info.learner_step == 20
        info = ckpt.resolve_epoch(name, before=1)
        assert info.epoch == 0
        assert ckpt.resolve_epoch(name, before=0) is None

    def test_fsck_reports_rolled_back_cleanly(self, tmp_path):
        from pytorch_distributed_tpu.utils import checkpoint as ckpt

        name = str(tmp_path / "run")
        for step in (10, 20, 30):
            self._save(name, step)
        root = ckpt.ckpt_root(name)
        # a rollback to epoch 0 fences epochs 1 and 2; the run then
        # saves epoch 3 with a REGRESSED learner_step — legal, because
        # the overtaken epochs are marked
        for k in (1, 2):
            ckpt.mark_rolled_back(os.path.join(root, f"epoch_{k}"),
                                  to_epoch=0, reason="drill")
        self._save(name, 15, extras={"rollbacks": 1})
        rep = ckpt.fsck(root)
        assert rep["violations"] == []
        assert rep["rolled_back"] == 2
        assert rep["newest_complete"] == 3

    def test_fsck_flags_unmarked_step_regression(self, tmp_path):
        from pytorch_distributed_tpu.utils import checkpoint as ckpt

        name = str(tmp_path / "run")
        self._save(name, 30)
        self._save(name, 10)  # regression with NO rollback marker: a lie
        rep = ckpt.fsck(ckpt.ckpt_root(name))
        assert any("regressed" in v for v in rep["violations"])

    def test_gc_never_lets_rolled_back_crowd_out_good(self, tmp_path):
        from pytorch_distributed_tpu.utils import checkpoint as ckpt

        name = str(tmp_path / "run")
        for step in (10, 20, 30):
            self._save(name, step)
        root = ckpt.ckpt_root(name)
        for k in (1, 2):
            ckpt.mark_rolled_back(os.path.join(root, f"epoch_{k}"))
        ckpt.gc_epochs(root, retain=1)
        # the only GOOD epoch (0) must survive retain=1 even though two
        # newer (fenced) epochs exist
        info = ckpt.resolve_epoch(name)
        assert info is not None and info.epoch == 0

    def test_ckpt_fsck_cli_exits_clean_on_rollback_root(self, tmp_path):
        import importlib

        from pytorch_distributed_tpu.utils import checkpoint as ckpt

        fsck_cli = importlib.import_module("tools.ckpt_fsck")
        name = str(tmp_path / "run")
        for step in (10, 20):
            self._save(name, step)
        root = ckpt.ckpt_root(name)
        ckpt.mark_rolled_back(os.path.join(root, "epoch_1"), to_epoch=0)
        self._save(name, 12, extras={"rollbacks": 1})
        assert fsck_cli.main([root]) == 0


# ---------------------------------------------------------------------------
# progress board (hang watchdog core)
# ---------------------------------------------------------------------------

class TestProgressBoard:
    def test_never_started_is_never_hung(self):
        from pytorch_distributed_tpu.utils.supervision import ProgressBoard

        b = ProgressBoard(["actor-0"])
        assert b.hung(0.001) == []

    def test_grace_covers_first_compile_then_deadline_applies(self):
        from pytorch_distributed_tpu.utils.supervision import ProgressBoard

        b = ProgressBoard(["actor-0", "actor-1"])
        b.note_start("actor-0")
        b.note_start("actor-1")
        b.bump("actor-1")
        now = time.time() + 0.5
        # 0 never bumped: deadline+grace (0.3+1.0) not yet reached;
        # 1 bumped: plain deadline 0.3 exceeded
        assert b.hung(0.3, grace=1.0, now=now) == ["actor-1"]
        now = time.time() + 2.0
        assert set(b.hung(0.3, grace=1.0, now=now)) == {"actor-0",
                                                        "actor-1"}

    def test_bump_clears_and_respawn_restarts_grace(self):
        from pytorch_distributed_tpu.utils.supervision import ProgressBoard

        b = ProgressBoard(["w"])
        b.note_start("w")
        b.bump("w", 3)
        assert b.marks("w") == 3
        assert b.hung(10.0) == []
        b.note_start("w")  # respawn: marks reset, grace window restarts
        assert b.marks("w") == 0

    def test_disabled_deadline(self):
        from pytorch_distributed_tpu.utils.supervision import ProgressBoard

        b = ProgressBoard(["w"])
        b.note_start("w")
        assert b.hung(0.0, now=time.time() + 999) == []


# ---------------------------------------------------------------------------
# the full detection -> containment -> recovery ladder, in process
# ---------------------------------------------------------------------------

class TestLearnerSentinel:
    @pytest.mark.timeout(240)
    def test_divergence_rolls_back_to_last_good_epoch(self, tmp_path,
                                                      monkeypatch):
        """Thread-backend topology on the chain MDP: poison_grad NaNs
        every update for several stats windows; the guard skips them
        all (no NaN ever reaches Adam), the anomaly streak trips, the
        learner rolls back to its last committed epoch in-process and
        the run completes with exit 0 semantics — final params finite,
        exactly one rollback consumed, blackbox stamped."""
        from pytorch_distributed_tpu import runtime
        from pytorch_distributed_tpu.config import build_options
        from pytorch_distributed_tpu.utils import checkpoint as ckpt

        spec = ",".join(f"poison_grad@{i}" for i in range(30, 54))
        monkeypatch.setenv("LEARNER_FAULTS", spec)
        opt = build_options(
            1, root_dir=str(tmp_path), refs="health_rb", seed=7,
            num_actors=1, steps=90, learn_start=16, batch_size=8,
            checkpoint_freq=25, learner_freq=8, evaluator_nepisodes=0,
            visualize=False, anomaly_threshold=2, max_rollbacks=3)
        topo = runtime.train(opt, backend="thread")
        assert topo.clock.rollbacks.value == 1
        assert topo.clock.skipped_steps.value >= 1
        # the fenced (overtaken) epochs carry markers; the root fscks
        # clean — a resumed run can never step back onto diverged params
        rep = ckpt.fsck(ckpt.ckpt_root(opt.model_name))
        assert rep["violations"] == []
        # blackbox records the rollback event
        bb = os.path.join(opt.log_dir, "blackbox", "learner.jsonl")
        assert os.path.exists(bb)
        with open(bb) as f:
            kinds = [json.loads(line).get("kind") for line in f]
        assert "rollback" in kinds

    @pytest.mark.timeout(240)
    def test_rollback_budget_exhaustion_is_fatal(self, tmp_path,
                                                 monkeypatch):
        """Sustained divergence with max_rollbacks=0 must escalate to a
        fatal learner exit, never loop forever."""
        from pytorch_distributed_tpu import runtime
        from pytorch_distributed_tpu.config import build_options

        spec = ",".join(f"poison_grad@{i}" for i in range(30, 90))
        monkeypatch.setenv("LEARNER_FAULTS", spec)
        opt = build_options(
            1, root_dir=str(tmp_path), refs="health_fatal", seed=7,
            num_actors=1, steps=200, learn_start=16, batch_size=8,
            checkpoint_freq=25, learner_freq=8, evaluator_nepisodes=0,
            visualize=False, anomaly_threshold=2, max_rollbacks=0)
        with pytest.raises(RuntimeError, match="health"):
            runtime.train(opt, backend="thread")


# ---------------------------------------------------------------------------
# slow full-topology drills (excluded from tier-1)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.timeout(420)
def test_hang_watchdog_kills_and_respawns_actor(tmp_path, monkeypatch):
    """Process topology: actor-0 stops progressing at tick 40 without
    exiting (hang@40); the watchdog must SIGKILL it, classify EXIT_HUNG,
    respawn from the RestartBudget, and the run completes.

    Every respawned incarnation re-fires its deterministic hang@40 (the
    schedule is per-process), exactly like a worker with a deterministic
    stall bug — so the run is sized to finish on the LAST incarnation
    before it reaches tick 40 again: replay-ratio pacing needs
    2*steps = 96 actor ticks = 40 + 40 + 16, i.e. two watchdog kills
    inside a 3-restart budget."""
    from pytorch_distributed_tpu import runtime
    from pytorch_distributed_tpu.config import build_options

    monkeypatch.setenv("ACTOR_FAULTS", "hang@40")
    monkeypatch.setenv("TPU_APEX_HEALTH_HANG_DEADLINE", "5")
    monkeypatch.setenv("TPU_APEX_HEALTH_HANG_GRACE", "120")
    opt = build_options(
        1, root_dir=str(tmp_path), refs="health_hang", seed=3,
        num_actors=1, steps=48, learn_start=16, batch_size=8,
        learner_freq=16, evaluator_nepisodes=0, visualize=False,
        max_replay_ratio=4.0)
    topo = runtime.train(opt, backend="process")
    assert 1 <= topo.hang_kills <= 3
    assert int(topo.clock.learner_step.value) >= 48
    bb = os.path.join(opt.log_dir, "blackbox")
    assert os.path.isdir(bb)  # the kill dumped post-mortems first


@pytest.mark.slow
@pytest.mark.timeout(600)
def test_e2e_chaos_drill_poison_and_hang(tmp_path, monkeypatch):
    """The acceptance drill: one process-backend PER run with
    poison_chunk@N (feeder), poison_grad@M (learner) and hang@K (actor)
    all scripted.  The run must complete cleanly with: quarantine files
    written, replay verifiably free of non-finite values, the poisoned
    update skipped, at most one rollback consumed, and the hung actor
    respawned within its RestartBudget."""
    from pytorch_distributed_tpu import runtime
    from pytorch_distributed_tpu.config import build_options

    # flush 1 = the actor's second 16-transition chunk (~tick 37) —
    # safely before its hang@60 stops the feed
    monkeypatch.setenv("FEEDER_FAULTS", "poison_chunk@1")
    monkeypatch.setenv("LEARNER_FAULTS", "poison_grad@60")
    monkeypatch.setenv("ACTOR_FAULTS", "hang@60")
    monkeypatch.setenv("TPU_APEX_HEALTH_HANG_DEADLINE", "5")
    monkeypatch.setenv("TPU_APEX_HEALTH_HANG_GRACE", "120")
    # sized like the hang drill: pacing needs 2*steps = 160 actor ticks
    # = 60 + 60 + 40, so the final incarnation finishes the run before
    # re-firing ITS hang@60 — two watchdog kills inside the budget
    opt = build_options(
        1, root_dir=str(tmp_path), refs="health_chaos", seed=11,
        memory_type="prioritized",
        num_actors=1, steps=80, learn_start=16, batch_size=8,
        learner_freq=16, evaluator_nepisodes=0, visualize=False,
        max_replay_ratio=4.0)
    topo = runtime.train(opt, backend="process")
    # run completed (exit-0 semantics): the clock reached the budget
    assert int(topo.clock.learner_step.value) >= 80
    # hung actor detected, killed, respawned within budget
    assert 1 <= topo.hang_kills <= 3
    # the poisoned update was skipped in-graph
    assert int(topo.clock.skipped_steps.value) >= 1
    # at most one rollback consumed (none expected: one skip is not a
    # sustained anomaly)
    assert int(topo.clock.rollbacks.value) <= 1
    # quarantine file written (learner-side ingest boundary)
    qdir = os.path.join(opt.log_dir, "quarantine")
    files = os.listdir(qdir)
    assert any(f.startswith("feeder-local") for f in files)
    with np.load(os.path.join(qdir, sorted(files)[0])) as z:
        assert "reason" in z and "trace_id" in z
    # replay is bit-clean: no non-finite value anywhere (the wrapped
    # memory directly — the owner's ingest queue is closed post-run)
    snap = topo.handles.learner_side.memory.snapshot()
    assert len(snap["reward"]) > 0
    for key in ("state0", "reward", "gamma_n", "state1", "terminal1"):
        assert np.isfinite(np.asarray(snap[key], np.float64)).all(), key
    assert np.isfinite(np.asarray(snap["leaf_priority"])).all()
