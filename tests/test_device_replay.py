import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_tpu.memory import DeviceReplay
from pytorch_distributed_tpu.utils.experience import Transition


def _chunk(start, n, state_shape=(4,)):
    i = np.arange(start, start + n, dtype=np.float32)
    return Transition(
        state0=np.broadcast_to(i[:, None], (n, *state_shape)).astype(np.float32),
        action=(i % 2).astype(np.int32),
        reward=i.astype(np.float32),
        gamma_n=np.full(n, 0.99, dtype=np.float32),
        state1=np.broadcast_to(i[:, None] + 1, (n, *state_shape)).astype(np.float32),
        terminal1=np.zeros(n, dtype=np.float32),
    )


def test_device_replay_roundtrip():
    m = DeviceReplay(capacity=16, state_shape=(4,), state_dtype=np.float32)
    m.feed_chunk(_chunk(0, 8))
    assert m.size == 8
    b = m.sample(32, jax.random.PRNGKey(0))
    b = jax.tree_util.tree_map(np.asarray, b)
    np.testing.assert_allclose(b.state1[:, 0], b.state0[:, 0] + 1)
    np.testing.assert_allclose(b.reward, b.state0[:, 0])
    assert set(np.unique(b.index)) <= set(range(8))


def test_device_replay_wraparound():
    m = DeviceReplay(capacity=8, state_shape=(2,), state_dtype=np.float32)
    m.feed_chunk(_chunk(0, 6, (2,)))
    m.feed_chunk(_chunk(6, 6, (2,)))  # wraps: slots hold 8..11, 4..7... etc
    assert m.size == 8
    b = jax.tree_util.tree_map(
        np.asarray, m.sample(128, jax.random.PRNGKey(1)))
    present = set(np.unique(b.reward).tolist())
    assert present <= set(float(x) for x in range(4, 12))


def test_device_replay_sharded_over_mesh():
    devs = jax.devices()
    assert len(devs) == 8, "conftest must force 8 cpu devices"
    mesh = jax.sharding.Mesh(np.array(devs), ("dp",))
    m = DeviceReplay(capacity=32, state_shape=(4,), state_dtype=np.float32,
                     mesh=mesh, axis="dp")
    m.feed_chunk(_chunk(0, 16))
    b = jax.tree_util.tree_map(
        np.asarray, m.sample(64, jax.random.PRNGKey(0)))
    np.testing.assert_allclose(b.state1[:, 0], b.state0[:, 0] + 1)
    # buffer rows really are sharded across the mesh
    shard_devs = {s.device for s in m.state.state0.addressable_shards}
    assert len(shard_devs) == 8


def test_device_replay_uint8():
    m = DeviceReplay(capacity=8, state_shape=(4, 84, 84), state_dtype=np.uint8)
    n = 4
    chunk = Transition(
        state0=np.full((n, 4, 84, 84), 200, dtype=np.uint8),
        action=np.zeros(n, dtype=np.int32),
        reward=np.ones(n, dtype=np.float32),
        gamma_n=np.full(n, 0.95, dtype=np.float32),
        state1=np.full((n, 4, 84, 84), 90, dtype=np.uint8),
        terminal1=np.zeros(n, dtype=np.float32))
    m.feed_chunk(chunk)
    b = m.sample(4, jax.random.PRNGKey(0))
    assert b.state0.dtype == jnp.uint8
    assert int(b.state0[0, 0, 0, 0]) == 200


def test_device_ingest_chunks_and_feeds():
    from pytorch_distributed_tpu.memory.device_replay import DeviceReplayIngest

    ing = DeviceReplayIngest(capacity=16, state_shape=(3,),
                             state_dtype=np.float32, chunk_size=4)
    ing.attach()
    feeder = ing.make_feeder(chunk=2)
    for i in range(7):
        feeder.feed(Transition(
            state0=np.full(3, i, np.float32), action=np.int32(i % 2),
            reward=np.float32(i), gamma_n=np.float32(0.9),
            state1=np.full(3, i + 1, np.float32),
            terminal1=np.float32(0.0)))
    feeder.flush()
    # mp.Queue's feeder thread makes puts visible asynchronously; drain
    # until the data lands (the learner loop drains every step anyway)
    import time

    deadline = time.monotonic() + 5.0
    while (ing.size + len(ing._pending) < 7
           and time.monotonic() < deadline):
        ing.drain()
        time.sleep(0.01)
    # 7 fed -> one full chunk of 4 ingested, 3 pending
    assert ing.size == 4
    assert len(ing._pending) == 3
    b = ing.replay.sample(8, jax.random.PRNGKey(1))
    assert np.all(np.asarray(b.index) < 4)


@pytest.mark.parametrize("devices", [8, 1])
def test_multi_step_dispatch_topology(tmp_path, monkeypatch, devices):
    """steps_per_dispatch > 1: K scanned updates per dispatched program;
    clocks/cadences still line up.  With more than one device visible the
    learner builds a mesh and shards the ring's rows over it: that run's
    ``scalars.jsonl`` carries the row exchange's ``learner/exchange_rounds``
    (1: two rows a chip, one round carries any draw); a one-device run has
    no exchange and writes no such row."""
    from pytorch_distributed_tpu import runtime
    from pytorch_distributed_tpu.config import build_options
    from pytorch_distributed_tpu.utils.metrics import read_scalars

    seen = jax.devices()[:devices]
    monkeypatch.setattr(jax, "devices", lambda *a, **kw: seen)
    opt = build_options(
        1, memory_type="device", root_dir=str(tmp_path), num_actors=1,
        steps=60, learn_start=16, batch_size=16, memory_size=1024,
        actor_sync_freq=20, param_publish_freq=10, learner_freq=20,
        evaluator_freq=30, early_stop=60, steps_per_dispatch=4,
        visualize=False)
    topo = runtime.train(opt, backend="thread")
    assert topo.clock.learner_step.value >= 60
    rows = read_scalars(opt.log_dir)
    assert "learner/critic_loss" in {r["tag"] for r in rows}
    rounds = [r["value"] for r in rows
              if r["tag"] == "learner/exchange_rounds"]
    assert rounds == ([] if devices == 1 else [1.0] * len(rounds))
    assert bool(rounds) == (devices > 1)


# ---------------------------------------------------------------------------
# the stored row format (RowCodec): exact, and invisible from outside
# ---------------------------------------------------------------------------

def _frames(rng, n, shape=(4, 12, 12), dtype=np.uint8):
    if np.dtype(dtype).kind == "f":
        return rng.standard_normal((n, *shape)).astype(dtype)
    return rng.integers(0, 256, (n, *shape)).astype(dtype)


def _frame_chunk(rng, start, n, shape=(4, 12, 12), dtype=np.uint8):
    return Transition(
        state0=_frames(rng, n, shape, dtype),
        action=np.zeros(n, np.int32),
        reward=np.arange(start, start + n, dtype=np.float32),
        gamma_n=np.full(n, 0.99, np.float32),
        state1=_frames(rng, n, shape, dtype),
        terminal1=np.zeros(n, np.float32))


def _ring(kind, capacity, shape=(4, 12, 12), dtype=np.uint8, **kw):
    if kind == "per":
        from pytorch_distributed_tpu.memory.device_per import DevicePerReplay
        return DevicePerReplay(capacity, shape, state_dtype=dtype, **kw)
    return DeviceReplay(capacity, shape, state_dtype=dtype, **kw)


# the two row kinds RowCodec adapts to from what it sees (PERF.md section
# 6, PR 25): pixel rows packed into padded uint32 lines, and the float32
# control-task rows stored as they are.  (shape, dtype, stored column of
# an 8-row ring)
ROWS = {
    "pixels-u8": ((4, 12, 12), np.uint8, (np.uint32, (8, 256))),
    "vector-f32": ((4,), np.float32, (np.float32, (8, 4))),
}


@pytest.mark.parametrize("shape,dtype,words", [
    ((4, 84, 84), np.uint8, 7056),
    ((84, 84, 4), np.uint8, 7056),
    ((4, 12, 12), np.int8, 144),
    ((4,), np.float32, 0),          # flat 32-bit rows: stored as they are
    ((28224,), np.uint8, 0),        # already flat
    ((3, 5, 5), np.uint8, 0),       # 75 bytes: no whole words
])
def test_codec_round_trip_is_exact(shape, dtype, words):
    from pytorch_distributed_tpu.memory.device_replay import LANES, RowCodec

    codec = RowCodec(shape, np.dtype(dtype))
    assert codec.words == words
    rng = np.random.default_rng(0)
    x = (rng.standard_normal((5, *shape)).astype(dtype) if words == 0
         and dtype == np.float32 else
         rng.integers(-128, 256, (5, *shape)).astype(dtype))
    stored = codec.pack(jnp.asarray(x))
    if words:
        assert stored.dtype == np.uint32
        assert stored.shape == (5, -(-words // LANES) * LANES)
        assert not np.asarray(stored)[:, words:].any()
    else:
        assert stored.dtype == x.dtype and stored.shape == x.shape
    for s in (stored, np.asarray(stored)):    # device, and host (snapshot)
        back = codec.unpack(s)
        assert isinstance(back, type(s))
        assert back.dtype == x.dtype and back.shape == x.shape
        np.testing.assert_array_equal(np.asarray(back), x)
    # the unpack of a gathered megabatch keeps its leading dimensions
    np.testing.assert_array_equal(
        np.asarray(codec.unpack(codec.pack(jnp.asarray(x))[None])), x[None])


@pytest.mark.parametrize("row", list(ROWS))
@pytest.mark.parametrize("kind", ["uniform", "per"])
def test_fed_rows_come_back_byte_identical_across_a_wrap(kind, row):
    """12 rows through an 8-row ring in chunks of 4: snapshot and sample
    return exactly the bytes that were fed, whatever the ring stores."""
    shape, dtype, (stored_dtype, stored_shape) = ROWS[row]
    rng = np.random.default_rng(1)
    m = _ring(kind, 8, shape, dtype)
    assert m.state.state0.dtype == stored_dtype    # pixels: packed at rest
    assert m.state.state0.shape == stored_shape    # 144 words -> 2 x 128
    chunks = [_frame_chunk(rng, s, 4, shape, dtype) for s in (0, 4, 8)]
    for c in chunks:
        m.feed_chunk(c)
    kept = [np.concatenate([getattr(c, f) for c in chunks[1:]])
            for f in ("state0", "state1")]
    snap = m.snapshot()                            # oldest first
    np.testing.assert_array_equal(snap["reward"], np.arange(4, 12))
    np.testing.assert_array_equal(snap["state0"], kept[0])
    np.testing.assert_array_equal(snap["state1"], kept[1])
    b = jax.tree_util.tree_map(np.asarray,
                               m.sample(64, jax.random.PRNGKey(0)))
    assert b.state0.dtype == dtype
    assert b.state0.shape == (64, *shape)
    age = b.reward.astype(int) - 4                 # reward names the row
    assert len(set(age.tolist())) > 4              # both kept chunks drawn
    np.testing.assert_array_equal(b.state0, kept[0][age])
    np.testing.assert_array_equal(b.state1, kept[1][age])


@pytest.mark.parametrize("kind", ["uniform", "per"])
def test_masked_write_drops_rows_and_leaves_neighbours_untouched(kind):
    from pytorch_distributed_tpu.memory.device_per import per_write_masked
    from pytorch_distributed_tpu.memory.device_replay import (
        ring_write_masked,
    )

    rng = np.random.default_rng(2)
    m = _ring(kind, 8)
    first = _frame_chunk(rng, 0, 8)
    m.feed_chunk(first)                            # full: cursor back at 0
    before = jax.device_get(m.state)
    chunk = _frame_chunk(rng, 100, 6)
    valid = np.array([True, False, True, True, False, False])
    write = per_write_masked if kind == "per" else ring_write_masked
    state, n = jax.jit(write, static_argnames="capacity")(
        m.state, chunk, valid, capacity=8)
    assert int(n) == 3 and int(state.pos) == 3
    m.state = state
    after = jax.device_get(state)
    # slots 0..2 took the valid rows in chunk order; the rest kept every
    # stored word, padding lanes included
    for f in ("state0", "state1"):
        np.testing.assert_array_equal(getattr(after, f)[3:],
                                      getattr(before, f)[3:])
        np.testing.assert_array_equal(
            m.codec.unpack(getattr(after, f)[:3]),
            getattr(chunk, f)[valid])
    snap = m.snapshot()                            # oldest first: slot 3
    np.testing.assert_array_equal(
        snap["state0"],
        np.concatenate([first.state0[3:], chunk.state0[valid]]))


@pytest.mark.parametrize("kind", ["uniform", "per"])
def test_packed_ring_on_the_mesh(kind):
    mesh = jax.sharding.Mesh(np.array(jax.devices()), ("dp",))
    rng = np.random.default_rng(3)
    m = _ring(kind, 32, mesh=mesh)
    chunks = [_frame_chunk(rng, s, 16) for s in (0, 16, 32)]
    for c in chunks:
        m.feed_chunk(c)                            # wraps once
    assert m.state.state0.shape == (32, 256)
    assert len({s.device for s in m.state.state0.addressable_shards}) == 8
    assert m.state.state0.addressable_shards[0].data.shape == (4, 256)
    snap = m.snapshot()
    np.testing.assert_array_equal(
        snap["state0"], np.concatenate([c.state0 for c in chunks[1:]]))
    b = jax.tree_util.tree_map(np.asarray,
                               m.sample(32, jax.random.PRNGKey(1)))
    np.testing.assert_array_equal(b.state1, snap["state1"][
        b.reward.astype(int) - 16])


@pytest.mark.parametrize("row", list(ROWS))
@pytest.mark.parametrize("kind", ["uniform", "per"])
def test_a_public_schema_snapshot_restores_into_the_packed_ring(kind, row):
    """What a checkpoint written before the ring packed its rows holds:
    plain columns in the public schema.  It restores, and comes back as it
    went, into a ring of another capacity too."""
    shape, dtype, _stored = ROWS[row]
    rng = np.random.default_rng(4)
    c = _frame_chunk(rng, 0, 6, shape, dtype)
    old = {f: np.asarray(getattr(c, f)) for f in c._fields
           if getattr(c, f) is not None}
    if kind == "per":
        old["leaf_priority"] = np.linspace(0.1, 2.0, 6).astype(np.float32)
        old["max_priority_base"] = np.float64(3.0)
    m = _ring(kind, 8, shape, dtype)
    assert m.restore(old) == 6
    snap = m.snapshot()
    for k, v in old.items():
        np.testing.assert_allclose(snap[k], v, rtol=1e-6)
    assert snap["state0"].dtype == dtype
    again = _ring(kind, 16, shape, dtype)
    again.restore(snap)
    np.testing.assert_array_equal(again.snapshot()["state1"], c.state1)
