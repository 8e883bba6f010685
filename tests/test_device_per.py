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
