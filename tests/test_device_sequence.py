"""HBM segment ring (memory/device_sequence.py): ring semantics,
proportional sampling, fused burn-in/train/write-back, ingest, resume."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pytorch_distributed_tpu.memory.device_sequence import (
    DeviceSequenceIngest, DeviceSequenceReplay, SegmentChunk,
    seq_update_priorities,
)
from pytorch_distributed_tpu.memory.sequence_replay import Segment

T, S, L = 4, (3,), 4  # seq_len, state_shape, lstm_dim


def _seg(v: float) -> Segment:
    return Segment(
        obs=np.full((T + 1, *S), v, np.float32),
        action=(np.arange(T) % 2).astype(np.int32),
        reward=np.full(T, v, np.float32),
        terminal=np.zeros(T, np.float32),
        mask=np.ones(T, np.float32),
        c0=np.full(L, v, np.float32),
        h0=np.full(L, -v, np.float32))


def _chunk(vals) -> SegmentChunk:
    segs = [_seg(float(v)) for v in vals]
    return SegmentChunk(*(np.stack([getattr(s, f) for s in segs])
                          for f in SegmentChunk._fields))


def _mk(capacity=8, alpha=1.0):
    m = DeviceSequenceReplay(capacity, T, S, L, state_dtype=np.float32,
                             priority_exponent=alpha,
                             importance_weight=0.5,
                             importance_anneal_steps=100)
    m.feed_chunk(_chunk(range(4)))
    return m


def test_ring_write_wraps_and_tracks_fill():
    m = _mk(capacity=8)
    assert m.size == 4 and int(m.state.pos) == 4
    m.feed_chunk(_chunk(range(4, 10)))  # 6 more: wraps past capacity
    assert m.size == 8 and int(m.state.pos) == 2
    # rows 8, 9 overwrote slots 0, 1; row 2 survives
    np.testing.assert_allclose(np.asarray(m.state.reward)[0], 8.0)
    np.testing.assert_allclose(np.asarray(m.state.reward)[1], 9.0)
    np.testing.assert_allclose(np.asarray(m.state.reward)[2], 2.0)


def test_sampling_proportional_and_skips_empty():
    m = _mk(capacity=8)
    m.state = m.state._replace(
        priority=jnp.asarray([10, 1, 1, 1, 0, 0, 0, 0], jnp.float32))
    b = m.sample(4096, jax.random.PRNGKey(0), beta=1.0)
    idx = np.asarray(b.index)
    assert idx.max() <= 3  # empty rows never drawn
    np.testing.assert_allclose((idx == 0).mean(), 10 / 13, atol=0.03)
    # IS weights at beta=1 fully counteract: rarest row normalised to 1
    w = np.asarray(b.weight)
    np.testing.assert_allclose(w[idx == 1], 1.0, rtol=1e-5)
    np.testing.assert_allclose(w[idx == 0], 0.1, rtol=1e-5)
    # sampled segment fields gather the right rows
    np.testing.assert_allclose(np.asarray(b.reward)[idx == 2][:, 0], 2.0)
    np.testing.assert_allclose(np.asarray(b.c0)[idx == 3][:, 0], 3.0)


def test_writeback_then_new_rows_enter_at_max():
    m = _mk(capacity=8)
    m.state = seq_update_priorities(m.state, jnp.asarray([0, 1]),
                                    jnp.asarray([2.0, 0.5]), alpha=1.0)
    np.testing.assert_allclose(float(m.state.priority[0]), 2.0, atol=1e-5)
    assert float(m.state.max_priority) >= 2.0
    m.feed_chunk(_chunk([42]))  # lands at slot 4
    np.testing.assert_allclose(float(m.state.priority[4]),
                               float(m.state.max_priority), rtol=1e-6)


def _drqn_setup(lstm=8):
    from pytorch_distributed_tpu.models.drqn import DrqnMlpModel, halves
    from pytorch_distributed_tpu.ops.losses import (
        init_train_state, make_optimizer,
    )
    from pytorch_distributed_tpu.ops.sequence_losses import (
        build_drqn_train_step,
    )

    model = DrqnMlpModel(action_space=2, hidden_dim=16, lstm_dim=L)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, *S)))
    tx = make_optimizer(1e-3)
    ts = init_train_state(params, tx)
    step = build_drqn_train_step(*halves(model), tx, burn_in=1, nstep=2,
                                 target_model_update=100)
    return ts, step


def test_fused_step_trains_and_writes_back():
    ts, step = _drqn_setup()
    m = _mk(capacity=8)
    fused = m.build_fused_step(step, batch_size=4, donate=False)
    pr_before = np.asarray(m.state.priority).copy()
    ts2, rs2, metrics = fused(ts, m.state, jax.random.PRNGKey(2),
                              jnp.asarray(0.5, jnp.float32))
    assert int(ts2.step) == 1
    assert np.isfinite(float(metrics["learner/critic_loss"]))
    assert not np.allclose(np.asarray(rs2.priority), pr_before)


def test_fused_multi_step_scans_k_updates():
    ts, step = _drqn_setup()
    m = _mk(capacity=8)
    K = 3
    fused = m.build_fused_step(step, batch_size=4, donate=False,
                               steps_per_call=K)
    keys = jax.random.split(jax.random.PRNGKey(3), K)
    ts2, rs2, metrics = fused(ts, m.state, keys,
                              jnp.asarray(0.5, jnp.float32))
    assert int(ts2.step) == K
    assert np.isfinite(float(metrics["learner/critic_loss"]))


def test_snapshot_restore_roundtrip():
    m = _mk(capacity=8)
    m.feed_chunk(_chunk(range(4, 10)))  # wrapped ring: age-order matters
    m.state = seq_update_priorities(m.state, jnp.asarray([2, 3]),
                                    jnp.asarray([7.0, 3.0]), alpha=1.0)
    snap = m.snapshot()
    assert snap["reward"].shape[0] == 8
    # oldest-first: the wrapped ring's oldest surviving row is 2
    np.testing.assert_allclose(snap["reward"][0, 0], 2.0)

    m2 = DeviceSequenceReplay(8, T, S, L, state_dtype=np.float32,
                              priority_exponent=1.0)
    assert m2.restore(snap) == 8
    # restore re-bases the ring at slot 0; AGE-ordered contents (a second
    # snapshot) must match the original exactly, leaves included
    snap2 = m2.snapshot()
    for k, v in snap.items():
        np.testing.assert_allclose(np.asarray(snap2[k]), np.asarray(v),
                                   rtol=1e-6, err_msg=k)


def test_ingest_drains_feeder_chunks():
    ing = DeviceSequenceIngest(16, T, S, L, state_dtype=np.float32,
                               chunk_size=4)
    feeder = ing.make_feeder(chunk=2)
    ing.attach(mesh=None)
    for i in range(9):
        feeder.feed(_seg(float(i)), None)
    feeder.flush()
    # mp.Queue's feeder thread makes puts visible asynchronously; drain
    # until the data lands (the learner loop drains every step anyway)
    import time

    deadline = time.monotonic() + 5.0
    while (ing.size + len(ing._pending) < 9
           and time.monotonic() < deadline):
        ing.drain()
        time.sleep(0.01)
    # 9 segments: two chunks of 4 land, 1 stays pending below chunk_size
    assert ing.size == 8
    snap = ing.snapshot()  # snapshot flushes the remainder
    assert snap["reward"].shape[0] == 9
    np.testing.assert_allclose(snap["c0"][:, 0], np.arange(9.0))
    ing.close()


def test_packed_ring_shape_matches_builder_format():
    # frame-packed pixel rows: (T+C, H, W) — the SegmentBuilder wire format
    m = DeviceSequenceReplay(4, 6, (4, 8, 8), 8, state_dtype=np.uint8,
                             pack_frames=4)
    assert m.state.obs.shape == (4, 10, 8, 8)


@pytest.mark.slow
@pytest.mark.timeout(1200)
def test_device_sequence_chain_topology_learns(tmp_path):
    """The config-13 chain R2D2 bar, on the HBM segment ring: the fused
    sample->train->write-back plane must LEARN, not just run."""
    from pytorch_distributed_tpu import runtime
    from pytorch_distributed_tpu.config import build_options

    opt = build_options(
        13, memory_type="device-sequence", root_dir=str(tmp_path),
        num_actors=2, steps=1200, learn_start=8, batch_size=16,
        memory_size=4096, seq_len=16, seq_overlap=8, burn_in=4, nstep=3,
        actor_sync_freq=20, param_publish_freq=5, learner_freq=50,
        evaluator_freq=1, max_replay_ratio=64.0, lr=2e-3,
        target_model_update=100, steps_per_dispatch=4)
    runtime.train(opt, backend="thread")
    opt2 = build_options(13, root_dir=str(tmp_path), mode=2,
                         tester_nepisodes=5, seq_len=16,
                         model_file=opt.model_name)
    out = runtime.test(opt2)
    assert out["avg_reward"] >= 0.9
    assert out["avg_steps"] <= 10
