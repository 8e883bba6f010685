"""HBM prioritized replay: sampling proportionality, IS weights, fused
write-back."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_tpu.memory.device_per import (
    DevicePerReplay, PerReplayState, per_sample, per_update_priorities,
)
from pytorch_distributed_tpu.utils.experience import Transition


def _mk(capacity=8, obs=(3,)):
    m = DevicePerReplay(capacity, obs, state_dtype=np.float32,
                        priority_exponent=1.0, importance_weight=0.5,
                        importance_anneal_steps=100)
    n = capacity // 2
    m.feed_chunk(Transition(
        state0=np.arange(n * 3, dtype=np.float32).reshape(n, 3),
        action=(np.arange(n) % 2).astype(np.int32),
        reward=np.arange(n, dtype=np.float32),
        gamma_n=np.full(n, 0.9, np.float32),
        state1=np.ones((n, 3), np.float32),
        terminal1=np.zeros(n, np.float32)))
    return m


def test_sampling_is_proportional_to_priority():
    m = _mk(capacity=8)
    # hand-set priorities: row 0 gets 10x the mass of rows 1-3
    m.state = m.state._replace(
        priority=jnp.asarray([10, 1, 1, 1, 0, 0, 0, 0], jnp.float32))
    b = m.sample(4096, jax.random.PRNGKey(0), beta=1.0)
    idx = np.asarray(b.index)
    assert idx.max() <= 3  # empty rows (priority 0) never drawn
    frac0 = (idx == 0).mean()
    np.testing.assert_allclose(frac0, 10 / 13, atol=0.03)


def test_is_weights_counteract_oversampling():
    m = _mk(capacity=8)
    m.state = m.state._replace(
        priority=jnp.asarray([10, 1, 1, 1, 0, 0, 0, 0], jnp.float32))
    b = m.sample(512, jax.random.PRNGKey(1), beta=1.0)
    w = np.asarray(b.weight)
    idx = np.asarray(b.index)
    # full correction at beta=1: weight ratio inverse to priority ratio,
    # normalised so the rarest row gets weight 1
    np.testing.assert_allclose(w[idx == 1], 1.0, rtol=1e-5)
    np.testing.assert_allclose(w[idx == 0], 0.1, rtol=1e-5)


def test_priority_writeback_and_max_tracking():
    m = _mk(capacity=8)
    s = per_update_priorities(m.state, jnp.asarray([0, 1]),
                              jnp.asarray([2.0, 0.5]), alpha=1.0)
    np.testing.assert_allclose(float(s.priority[0]), 2.0, atol=1e-5)
    np.testing.assert_allclose(float(s.priority[1]), 0.5, atol=1e-4)
    assert float(s.max_priority) >= 2.0
    # next feed enters at the new max
    s2 = s._replace()
    m.state = s2
    m.feed_chunk(Transition(
        state0=np.zeros((1, 3), np.float32), action=np.zeros(1, np.int32),
        reward=np.zeros(1, np.float32), gamma_n=np.ones(1, np.float32),
        state1=np.zeros((1, 3), np.float32),
        terminal1=np.zeros(1, np.float32)))
    i = (4) % 8  # cursor was at 4 after the initial half-fill
    np.testing.assert_allclose(float(m.state.priority[i]),
                               float(m.state.max_priority), rtol=1e-6)


def test_fused_step_trains_and_writes_back():
    from pytorch_distributed_tpu.models import DqnMlpModel
    from pytorch_distributed_tpu.ops.losses import (
        build_dqn_train_step, init_train_state, make_optimizer,
    )

    model = DqnMlpModel(action_space=2, hidden_dim=16)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 3)))
    tx = make_optimizer(1e-3)
    ts = init_train_state(params, tx)
    step = build_dqn_train_step(model.apply, tx)

    m = _mk(capacity=8)
    fused = m.build_fused_step(step, batch_size=4, donate=False)
    pr_before = np.asarray(m.state.priority).copy()
    ts2, rs2, metrics = fused(ts, m.state, jax.random.PRNGKey(2),
                              jnp.asarray(0.5, jnp.float32))
    assert int(ts2.step) == 1
    assert np.isfinite(float(metrics["learner/critic_loss"]))
    # sampled rows got |TD| priorities (almost surely != the initial max)
    assert not np.allclose(np.asarray(rs2.priority), pr_before)


@pytest.mark.slow
@pytest.mark.timeout(300)
def test_multi_step_dispatch_per_topology(tmp_path):
    from pytorch_distributed_tpu import runtime
    from pytorch_distributed_tpu.config import build_options

    opt = build_options(
        1, memory_type="device-per", root_dir=str(tmp_path), num_actors=1,
        steps=60, learn_start=16, batch_size=16, memory_size=1024,
        actor_sync_freq=20, param_publish_freq=10, learner_freq=20,
        evaluator_freq=30, early_stop=60, steps_per_dispatch=4,
        visualize=False)
    topo = runtime.train(opt, backend="thread")
    assert topo.clock.learner_step.value >= 60


# ---------------------------------------------------------------------------
# structure of the fused programs: the ring's rows never ride a loop;
# only the priorities do, and the row columns leave as they came in
# ---------------------------------------------------------------------------

def scans_of(jaxpr):
    """Every ``scan`` equation of a jaxpr, nested ones included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from scans_of(sub)


def carried_with_leading(jaxpr, capacity):
    """Shapes of everything a scan of the program CARRIES whose leading
    dimension is the ring's capacity."""
    out = []
    for eqn in scans_of(jaxpr):
        lo = eqn.params["num_consts"]
        for v in eqn.invars[lo:lo + eqn.params["num_carry"]]:
            if v.aval.shape[:1] == (capacity,):
                out.append(v.aval.shape)
    return out


def _toy_step(ts, batch):
    """A train step of the right signature over frame batches."""
    x = batch.state0.astype(jnp.float32).mean(axis=(1, 2, 3))
    y = batch.state1.astype(jnp.float32).mean(axis=(1, 2, 3))
    td = jnp.abs(x - y) * batch.weight
    return ts + td.sum(), {"loss": td.mean()}, td



def _toy_mega(ts, batches):
    """The megabatch twin of ``_toy_step``: M minibatches at once."""
    td = jax.vmap(lambda b: _toy_step(0.0, b)[2])(batches)
    return ts + td.sum(), {"loss": td.mean()}, td, jnp.ones(td.shape[0])


def _frame_ring(capacity=24, shape=(4, 12, 12)):
    rng = np.random.default_rng(0)
    m = DevicePerReplay(capacity, shape, state_dtype=np.uint8)
    m.feed_chunk(Transition(
        state0=rng.integers(0, 256, (capacity, *shape)).astype(np.uint8),
        action=np.zeros(capacity, np.int32),
        reward=np.arange(capacity, dtype=np.float32),
        gamma_n=np.full(capacity, 0.9, np.float32),
        state1=rng.integers(0, 256, (capacity, *shape)).astype(np.uint8),
        terminal1=np.zeros(capacity, np.float32)))
    return m


def _fused(m, K, megabatch):
    mega = _toy_mega if megabatch > 1 else None
    return m.build_fused_step(_toy_step, 4, donate=False, steps_per_call=K,
                              megabatch=megabatch, megabatch_step=mega)


def forwarded_inputs(closed, n_in_before, n_out_before, count):
    """For outputs [n_out_before, +count) of a traced jitted call: the
    index of the INPUT each one forwards unchanged, else None."""
    jaxpr = closed.jaxpr
    found = []
    for out in jaxpr.outvars[n_out_before:n_out_before + count]:
        src = out
        for eqn in jaxpr.eqns:            # through the jit's own equation
            if any(out is o for o in eqn.outvars):
                inner = eqn.params["jaxpr"].jaxpr
                k = [out is o for o in eqn.outvars].index(True)
                hits = [i for i, v in enumerate(inner.invars)
                        if v is inner.outvars[k]]
                src = eqn.invars[hits[0]] if hits else None
        hits = [i for i, v in enumerate(jaxpr.invars) if v is src]
        found.append(hits[0] if hits else None)
    return found


@pytest.mark.parametrize("K,megabatch", [(4, 1), (4, 2), (1, 1)])
def test_fused_step_carries_only_priorities_and_forwards_the_rows(
        K, megabatch):
    m = _frame_ring()
    fused = _fused(m, K, megabatch)
    keys = jax.random.split(jax.random.PRNGKey(0), K) if K > 1 \
        else jax.random.PRNGKey(0)
    args = (jnp.float32(0), m.state, keys, jnp.float32(0.4))
    closed = jax.make_jaxpr(fused)(*args)
    if K > 1:
        # (capacity,) is the priority vector; nothing else of the ring's
        # height is carried by any loop of the program
        carried = carried_with_leading(closed.jaxpr, m.capacity)
        assert carried and set(carried) == {(m.capacity,)}
    # every leaf of the ring but priority and max_priority comes out as
    # the very input it went in as, so donation aliases it through
    leaves, _ = jax.tree_util.tree_flatten(m.state)
    names = [f for f in m.state._fields if f != "codec"]
    assert len(leaves) == len(names)
    src = forwarded_inputs(closed, 1, 1, len(names))
    for i, name in enumerate(names):
        if name in ("priority", "max_priority"):
            assert src[i] is None, name
        else:
            assert src[i] == 1 + i, (name, src[i])
    # and the program still does what it did: the rows it returns are the
    # rows it was given, the priorities moved
    ts, rs, _ = fused(*args)
    np.testing.assert_array_equal(np.asarray(rs.state0),
                                  np.asarray(m.state.state0))
    assert not np.allclose(np.asarray(rs.priority),
                           np.asarray(m.state.priority))
    assert float(ts) > 0


def test_fused_multi_step_matches_sequential_single_steps():
    """K fused sub-steps == K single dispatches, with the narrowed carry:
    each sub-step samples from the previous one's priorities."""
    m = _frame_ring()
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    beta = jnp.float32(0.4)
    ts_k, rs_k, _ = _fused(m, 4, 1)(jnp.float32(0), m.state, keys, beta)
    one = _fused(m, 1, 1)
    ts, rs = jnp.float32(0), m.state
    for k in keys:
        ts, rs, _ = one(ts, rs, k, beta)
    np.testing.assert_allclose(np.asarray(rs_k.priority),
                               np.asarray(rs.priority), rtol=1e-6)
    np.testing.assert_allclose(float(rs_k.max_priority),
                               float(rs.max_priority), rtol=1e-6)
    np.testing.assert_allclose(float(ts_k), float(ts), rtol=1e-5)


@pytest.mark.parametrize("megabatch", [1, 2])
def test_uniform_fused_step_carries_no_ring_column(megabatch):
    from pytorch_distributed_tpu.memory.device_replay import (
        build_uniform_fused_step,
    )

    from pytorch_distributed_tpu.memory import DeviceReplay

    per = _frame_ring()
    m = DeviceReplay(24, (4, 12, 12), state_dtype=np.uint8)
    m.restore(per.snapshot())
    mega = _toy_mega if megabatch > 1 else None
    fused = build_uniform_fused_step(
        _toy_step, 4, steps_per_call=4, donate=False, megabatch=megabatch,
        megabatch_step=mega)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    jaxpr = jax.make_jaxpr(fused)(jnp.float32(0), m.state, keys).jaxpr
    assert list(scans_of(jaxpr))
    assert carried_with_leading(jaxpr, 24) == []


# ---------------------------------------------------------------------------
# a row-sharded ring on the 8 virtual CPU devices: the batch leaves the
# ring sharded over dp (device_replay.gather_rows), so the fused step
# behind it is data-parallel, and draws exactly what one device draws
# ---------------------------------------------------------------------------

DP = 4
# (name, K, megabatch): the three fused programs of build_fused_step
FUSED_PROGRAMS = [("one", 1, 1), ("multi", 4, 1), ("multi_mega", 4, 2)]


def _dp_mesh():
    from pytorch_distributed_tpu.parallel.mesh import make_mesh

    return make_mesh(dp_size=DP, devices=jax.devices()[:DP])


def _pixel_ring(mesh, capacity, shape, seed=0):
    """A PER ring of packed uint8 frames, full, its priorities spread;
    the same rows and priorities with or without a mesh."""
    rng = np.random.default_rng(seed)
    m = DevicePerReplay(capacity, shape, state_dtype=np.uint8, mesh=mesh)
    m.feed_chunk(Transition(
        state0=rng.integers(0, 256, (capacity, *shape)).astype(np.uint8),
        action=rng.integers(0, 6, capacity).astype(np.int32),
        reward=rng.normal(size=capacity).astype(np.float32),
        gamma_n=np.full(capacity, 0.9, np.float32),
        state1=rng.integers(0, 256, (capacity, *shape)).astype(np.uint8),
        terminal1=(rng.random(capacity) < 0.1).astype(np.float32)))
    pri = (np.abs(rng.normal(size=capacity)) + 0.01).astype(np.float32)
    m.state = m.state._replace(priority=jax.device_put(
        pri, m.state.priority.sharding))
    return m


def _dqn_steps(model, sample_obs):
    """(TrainState, step, megabatch step) of a DQN over ``model``, with
    plain SGD: the update is linear in the gradient, so two summation
    orders stay a float rounding apart (Adam's first step is lr * sign(g))."""
    import optax

    from pytorch_distributed_tpu.ops.losses import (
        build_dqn_megabatch_step, build_dqn_train_step, init_train_state,
    )

    tx = optax.sgd(0.05)
    params = model.init(jax.random.PRNGKey(0), sample_obs)
    return (init_train_state(params, tx),
            build_dqn_train_step(model.apply, tx),
            build_dqn_megabatch_step(model.apply, tx))


def _run_fused(m, ts, step, mega, B, K, megabatch, mesh):
    """One blocking call of the ring's fused program; ``(compiled, outputs)``."""
    from pytorch_distributed_tpu.parallel.mesh import replicated

    fused = m.build_fused_step(
        step, B, donate=False, steps_per_call=K, megabatch=megabatch,
        megabatch_step=mega if megabatch > 1 else None)
    keys = (jax.random.split(jax.random.PRNGKey(3), K) if K > 1
            else jax.random.PRNGKey(3))
    if mesh is not None:
        ts = jax.device_put(ts, replicated(mesh))
    args = (ts, m.state, keys, jnp.float32(0.5))
    compiled = fused.lower(*args).compile()
    # block on each call: queued multi-device programs starve each other
    # in the CPU backend's rendezvous (ShardedLearner._serialize_collectives)
    return compiled, jax.block_until_ready(compiled(*args))


def _hlo_shapes(text):
    """``{name: dims}`` of every array-valued instruction of an HLO text."""
    import re

    return {m.group(1): tuple(int(d) for d in m.group(2).split(",") if d)
            for m in re.finditer(
                r"%([\w.\-]+) = \w+\[([\d,]*)\]", text)}


# B = 24: 6 rows a chip, which the exchange's bound clamps to; B = 160: 40
# rows a chip under a bound of 32.  Neither, nor twice it, is another dim
@pytest.mark.parametrize("name,K,megabatch,B", [
    *((*p, 24) for p in FUSED_PROGRAMS), ("multi", 4, 1, 160)])
def test_fused_step_of_a_sharded_ring_is_partitioned_over_dp(
        name, K, megabatch, B):
    """From the compiled program: every convolution runs on a chip's share
    of the batch, the gradients are reduced across the chips, and the
    gathered rows reach a chip by an all-to-all of ``exchange_bound`` rows
    a block, never as a whole batch behind an all-reduce."""
    import re

    from pytorch_distributed_tpu.memory.device_replay import exchange_bound
    from pytorch_distributed_tpu.models import DqnCnnModel

    mesh = _dp_mesh()
    m = _pixel_ring(mesh, 64, (4, 84, 84))
    assert m.batch_rows(B) == f"{B // DP}x4dp"
    ts, step, mega = _dqn_steps(
        DqnCnnModel(action_space=6), jnp.zeros((1, 4, 84, 84), jnp.uint8))
    compiled, (ts2, rs2, metrics) = _run_fused(m, ts, step, mega, B, K,
                                               megabatch, mesh)
    assert np.isfinite(float(metrics["learner/critic_loss"]))
    # the train state comes back replicated, the ring as it was sharded
    for leaf in jax.tree_util.tree_leaves(ts2):
        assert leaf.sharding.is_fully_replicated
    assert rs2.state0.sharding == m.state.state0.sharding

    text = compiled.as_text()
    shapes = _hlo_shapes(text)
    whole, share = {B, B * megabatch}, (B // DP) * megabatch
    convs = re.findall(r"%([\w.\-]+) = \S+ convolution\(%([\w.\-]+), "
                       r"%([\w.\-]+)\)", text)
    assert convs
    seen_share = False
    for names in convs:
        dims = {d for n in names for d in shapes[n]}
        assert not dims & whole, (names, [shapes[n] for n in names])
        seen_share |= share in dims
    assert seen_share
    reduces = [l for l in text.splitlines() if " all-reduce(" in l]
    # the FC kernel's gradient (3136 x 512) crosses the chips
    assert any("3136" in l.split(" all-reduce(")[0] for l in reduces)
    # no all-reduce hands every chip the gathered words of the whole batch
    words = m.state.state0.shape[1]
    for line in reduces:
        for dims in re.findall(r"\w+\[([\d,]+)\]",
                               line.split(" all-reduce(")[0]):
            dims = {int(d) for d in dims.split(",")}
            assert not (words in dims and dims & whole), line
    # the exchange of the two observation columns: ``bound`` rows to each
    # chip (a whole block only where the bound clamps to it); where a draw
    # can need more rounds, the same exchange once more, in their loop
    share, bound = B // DP, exchange_bound(B // DP, DP)
    assert bound == {24: 6, 160: 32}[B]
    sent = [re.findall(rf"u32\[(?:\d+,)*(\d+),{words}\]",
                       l.split(" all-to-all(")[0])
            for l in text.splitlines() if " all-to-all(" in l]
    sent = [{int(rows) for rows in found} for found in sent if found]
    assert sent == [{bound}] * (2 if bound == share else 4), sent


@pytest.mark.parametrize("name,K,megabatch", FUSED_PROGRAMS)
def test_fused_step_of_a_sharded_ring_draws_and_trains_as_one_device(
        name, K, megabatch):
    """Same ring contents, same keys: the mesh program and the one-device
    program draw the same rows, rewrite the same priority rows, and agree
    in EVERY metric (a value the megabatch step's ``shard_map`` forgot to
    reduce would be chip 0's alone: its replication check is off) and in
    parameters to float rounding."""
    from pytorch_distributed_tpu.models import DqnMlpModel

    B, shape = 16, (4, 6, 6)
    model = DqnMlpModel(action_space=6, hidden_dim=32, norm_val=255.0)
    ts, step, mega = _dqn_steps(model, jnp.zeros((1, *shape), jnp.uint8))
    out = {}
    for where, mesh in (("one_device", None), ("mesh", _dp_mesh())):
        m = _pixel_ring(mesh, 64, shape)
        before = np.asarray(m.state.priority)
        drawn = m.sample(B, jax.random.PRNGKey(3), beta=0.5)
        _, (ts2, rs2, metrics) = _run_fused(m, ts, step, mega, B, K,
                                            megabatch, mesh)
        out[where] = dict(
            drawn=jax.device_get(drawn), before=before,
            priority=np.asarray(rs2.priority),
            max_priority=float(rs2.max_priority),
            metrics=jax.device_get(metrics),
            params=jax.device_get(ts2.params), step=int(ts2.step))
    a, b = out["one_device"], out["mesh"]
    # the draw: the same rows, bit for bit; the IS weights divide by the
    # ring's priority mass, which the mesh sums in another order
    for f, x, y in zip(a["drawn"]._fields, a["drawn"], b["drawn"]):
        if f == "weight":
            np.testing.assert_allclose(x, y, rtol=1e-6)
        else:
            np.testing.assert_array_equal(x, y, err_msg=f)
    assert a["step"] == b["step"] == K
    rewritten = a["priority"] != a["before"]
    assert rewritten.any()
    np.testing.assert_array_equal(rewritten, b["priority"] != b["before"])
    np.testing.assert_allclose(a["priority"], b["priority"], rtol=1e-5)
    np.testing.assert_allclose(a["max_priority"], b["max_priority"],
                               rtol=1e-5)
    # one device runs no exchange: the mesh reports its rounds on top
    assert b["metrics"].keys() - a["metrics"].keys() == {
        "learner/exchange_rounds"}
    assert not a["metrics"].keys() - b["metrics"].keys()
    assert b["metrics"]["learner/exchange_rounds"] == 1.0   # 4 rows a chip
    assert "learner/critic_loss" in a["metrics"]
    for k in a["metrics"]:
        np.testing.assert_allclose(a["metrics"][k], b["metrics"][k],
                                   rtol=1e-5, atol=1e-7, err_msg=k)
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_allclose(x, y, rtol=1e-4, atol=1e-6),
        a["params"], b["params"])


def _draw(name, rng, B, capacity):
    """A draw of ``B`` row numbers from a ring of ``capacity`` rows over
    ``DP`` shards, by the shape the exchange has to cope with."""
    n, share = capacity // DP, B // DP
    level = rng.integers(0, capacity, B)
    return {
        "level": level,
        "first_shard": rng.integers(0, n, B),
        "last_shard": rng.integers(capacity - n, capacity, B),
        "one_row": np.full(B, capacity // 3),
        # chip 1's whole block sits in chip 2's shard
        "remote_block": np.concatenate([
            level[:share], rng.integers(2 * n, 3 * n, share),
            level[2 * share:]]),
    }[name].astype(np.int32)


# (draw, B, rounds): B = 128 is 32 rows a chip under a bound of 24, so a
# block that sits in one shard takes two rounds; B = 24 is 6 rows a chip,
# which the bound clamps to: one round carries any draw
@pytest.mark.parametrize("draw,B,rounds", [
    ("level", 128, 1), ("first_shard", 128, 2), ("last_shard", 128, 2),
    ("one_row", 128, 2), ("remote_block", 128, 2), ("first_shard", 24, 1)])
def test_gather_rows_of_a_sharded_ring_is_the_one_device_gather(
        draw, B, rounds):
    """``gather_rows`` on the mesh is ``col[idx]`` bit for bit, every
    column, index and weight included, however the draw leans on one
    shard; ``exchange_rounds`` is the rounds it took, and nothing on one
    device."""
    from pytorch_distributed_tpu.memory.device_replay import (
        exchange_bound, exchange_rounds, gather_rows,
    )

    capacity, shape = 256, (4, 6, 6)
    assert exchange_bound(B // DP, DP) == {128: 24, 24: 6}[B]
    rng = np.random.default_rng(5)
    idx = jnp.asarray(_draw(draw, rng, B, capacity))
    weight = jnp.asarray(rng.random(B), jnp.float32)
    one, mesh = (_pixel_ring(mesh, capacity, shape)
                 for mesh in (None, _dp_mesh()))
    want = jax.jit(gather_rows)(one.state, idx, weight)
    got = jax.jit(gather_rows)(mesh.state, idx, weight)
    for f, x, y in zip(want._fields, want, got):
        assert y.sharding.spec[0] == "dp", f
        assert x.dtype == y.dtype, f
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f)
    np.testing.assert_array_equal(np.asarray(got.index), np.asarray(idx))
    assert exchange_rounds(one.state, idx) is None
    assert float(jax.jit(exchange_rounds)(mesh.state, idx)) == rounds


def test_exchange_bound_is_a_function_of_the_split_alone():
    """6.5 deviations over a level draw's mean, in whole sublanes, never
    more than a chip's share; the cell's (128, 4) sends 64 rows a block."""
    from pytorch_distributed_tpu.memory.device_replay import exchange_bound

    assert exchange_bound(128, 4) == 64
    assert exchange_bound(32, 4) == 24
    assert exchange_bound(6, 4) == 6 and exchange_bound(16, 1) == 16
    for share, ndev in [(8, 2), (64, 4), (128, 8), (512, 4), (4096, 16)]:
        b = exchange_bound(share, ndev)
        assert share / ndev < b <= share and b % 8 == 0, (share, ndev, b)


def test_fused_dispatch_reports_the_mean_rounds_of_its_substeps(monkeypatch):
    """K = 4 substeps on the mesh, two of them drawn from one shard: the
    dispatch's ``learner/exchange_rounds`` is the mean over the scan, not
    the last substep's."""
    from pytorch_distributed_tpu.memory import device_per

    B, K, capacity = 128, 4, 256
    m = _pixel_ring(_dp_mesh(), capacity, (4, 12, 12))
    lean = jnp.asarray([0, 1, 1, 0], jnp.uint32)    # substeps 1, 2: shard 0
    real_draw = device_per.per_draw

    def leaning_draw(state, key, batch_size, beta, sample_fn=None):
        idx, w = real_draw(state, key[1:], batch_size, beta, sample_fn)
        return jnp.where(key[0] > 0, idx % (capacity // DP), idx), w

    monkeypatch.setattr(device_per, "per_draw", leaning_draw)
    fused = m.build_fused_step(_toy_step, B, donate=False, steps_per_call=K)
    keys = jnp.concatenate(
        [lean[:, None], jax.random.split(jax.random.PRNGKey(0), K)], axis=1)
    _, _, metrics = jax.block_until_ready(
        fused(jnp.float32(0), m.state, keys, jnp.float32(0.4)))
    assert float(metrics["learner/exchange_rounds"]) == 1.5


def test_a_batch_that_does_not_split_over_the_mesh_is_refused():
    """On a row-sharded ring every chip takes an equal share of the batch:
    a size the data axis does not divide is refused loudly, never trained
    whole on every chip."""
    m = _pixel_ring(_dp_mesh(), 64, (4, 6, 6))
    assert m.sample(8, jax.random.PRNGKey(0)).state0.shape[0] == 8
    with pytest.raises(ValueError, match="does not split over mesh axis"):
        m.sample(6, jax.random.PRNGKey(0))


def test_fused_step_without_a_mesh_states_no_sharding():
    """No mesh: the batch's sharding helper is the identity, and the
    lowered program says nothing about shardings or collectives."""
    m = _frame_ring()
    assert m.codec.rows is None and m.batch_rows(4) == "4x1"
    for K, megabatch in [(1, 1), (4, 1), (4, 2)]:
        keys = (jax.random.split(jax.random.PRNGKey(0), K) if K > 1
                else jax.random.PRNGKey(0))
        text = _fused(m, K, megabatch).lower(
            jnp.float32(0), m.state, keys, jnp.float32(0.4)).as_text()
        for word in ("sharding", "all_to_all", "manual", "collective"):
            assert word not in text, (K, megabatch, word)
