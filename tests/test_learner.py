import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from pytorch_distributed_tpu.models import DdpgMlpModel, DqnMlpModel
from pytorch_distributed_tpu.ops.losses import (
    TrainState, build_ddpg_train_step, build_ddpg_train_step_coupled,
    build_dqn_train_step, init_ddpg_train_state, init_train_state,
    make_optimizer, merge_ddpg_params, split_ddpg_params,
)
from pytorch_distributed_tpu.parallel import ShardedLearner, make_mesh
from pytorch_distributed_tpu.utils.experience import Batch


def _dqn_setup(num_actions=3, obs_dim=4, lr=1e-2, **step_kw):
    model = DqnMlpModel(action_space=num_actions, hidden_dim=32)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, obs_dim)))
    tx = make_optimizer(lr)
    state = init_train_state(params, tx)
    step = build_dqn_train_step(model.apply, tx, **step_kw)
    return model, state, step


def _batch(B=16, obs_dim=4, num_actions=3, seed=0, weight=None):
    rng = np.random.default_rng(seed)
    return Batch(
        state0=rng.normal(size=(B, obs_dim)).astype(np.float32),
        action=rng.integers(0, num_actions, size=B).astype(np.int32),
        reward=rng.normal(size=B).astype(np.float32),
        gamma_n=np.full(B, 0.95, dtype=np.float32),
        state1=rng.normal(size=(B, obs_dim)).astype(np.float32),
        terminal1=(rng.random(B) < 0.3).astype(np.float32),
        weight=np.ones(B, np.float32) if weight is None else weight,
        index=np.arange(B, dtype=np.int32),
    )


def test_dqn_step_loss_matches_hand_computed():
    model, state, step = _dqn_setup()
    b = _batch()
    new_state, metrics, td_abs = jax.jit(step)(state, b)
    # hand-compute the loss with numpy against the same initial params
    q = np.asarray(model.apply(state.params, b.state0))
    q_sel = q[np.arange(16), b.action]
    qn = np.asarray(model.apply(state.params, b.state1))  # target==online at t0
    target = b.reward + b.gamma_n * qn.max(1) * (1 - b.terminal1)
    want = np.mean((q_sel - target) ** 2)  # nn.MSELoss parity
    np.testing.assert_allclose(float(metrics["learner/critic_loss"]), want,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(td_abs), np.abs(q_sel - target),
                               rtol=1e-4, atol=1e-5)
    assert int(new_state.step) == 1


def test_dqn_terminal_masks_bootstrap():
    model, state, step = _dqn_setup()
    b = _batch()
    b = b._replace(terminal1=np.ones_like(b.terminal1))
    _, metrics, td_abs = jax.jit(step)(state, b)
    q = np.asarray(model.apply(state.params, b.state0))
    q_sel = q[np.arange(16), b.action]
    np.testing.assert_allclose(np.asarray(td_abs), np.abs(q_sel - b.reward),
                               rtol=1e-4, atol=1e-5)


def test_double_dqn_uses_online_argmax():
    model, state, step = _dqn_setup(enable_double=True)
    b = _batch()
    _, metrics, td_abs = jax.jit(step)(state, b)
    q = np.asarray(model.apply(state.params, b.state0))
    q_sel = q[np.arange(16), b.action]
    qn = np.asarray(model.apply(state.params, b.state1))
    # at t0 online == target so double-dqn bootstrap = q at online argmax
    boot = qn[np.arange(16), qn.argmax(1)]
    target = b.reward + b.gamma_n * boot * (1 - b.terminal1)
    np.testing.assert_allclose(np.asarray(td_abs), np.abs(q_sel - target),
                               rtol=1e-4, atol=1e-5)


def test_per_weights_scale_loss():
    model, state, step = _dqn_setup()
    b1 = _batch()
    b2 = b1._replace(weight=np.full(16, 0.5, np.float32))
    _, m1, _ = jax.jit(step)(state, b1)
    _, m2, _ = jax.jit(step)(state, b2)
    np.testing.assert_allclose(float(m2["learner/critic_loss"]),
                               0.5 * float(m1["learner/critic_loss"]),
                               rtol=1e-5)


def test_dqn_hard_target_update_period():
    model, state, step = _dqn_setup(target_model_update=3)
    jstep = jax.jit(step)
    b = _batch()
    leaves0 = jax.tree_util.tree_leaves(state.target_params)[0].copy()
    for i in range(1, 4):
        state, _, _ = jstep(state, b)
        t_leaf = jax.tree_util.tree_leaves(state.target_params)[0]
        p_leaf = jax.tree_util.tree_leaves(state.params)[0]
        if i < 3:
            np.testing.assert_array_equal(np.asarray(t_leaf), np.asarray(leaves0))
        else:
            np.testing.assert_array_equal(np.asarray(t_leaf), np.asarray(p_leaf))


def test_dqn_fits_fixed_targets():
    # supervised sanity: repeated steps on one batch drive TD error down
    model, state, step = _dqn_setup(lr=3e-3)
    jstep = jax.jit(step)
    b = _batch()
    losses = []
    for _ in range(300):
        state, metrics, _ = jstep(state, b)
        losses.append(float(metrics["learner/critic_loss"]))
    assert losses[-1] < 0.05 * losses[0]


def _ddpg_setup(coupled=False, obs_dim=3, act_dim=1):
    model = DdpgMlpModel(action_dim=act_dim, actor_hidden=(32, 32),
                         critic_hidden=(32, 32))
    full = model.init(jax.random.PRNGKey(0), jnp.zeros((1, obs_dim)))
    actor_apply = lambda p, o: model.apply(p, o, method=model.forward_actor)
    critic_apply = lambda p, o, a: model.apply(p, o, a,
                                               method=model.forward_critic)
    if coupled:
        tx = make_optimizer(1e-3, clip_grad=40.0)
        state = init_train_state(full, tx)
        step = build_ddpg_train_step_coupled(actor_apply, critic_apply, tx)
    else:
        atx = make_optimizer(1e-4, clip_grad=40.0)
        ctx_ = make_optimizer(1e-3, clip_grad=40.0)
        state = init_ddpg_train_state(full, atx, ctx_)
        step = build_ddpg_train_step(actor_apply, critic_apply, atx, ctx_)
    return model, state, step


def _cont_batch(B=16, obs_dim=3, act_dim=1, seed=0):
    rng = np.random.default_rng(seed)
    return Batch(
        state0=rng.normal(size=(B, obs_dim)).astype(np.float32),
        action=rng.uniform(-1, 1, size=(B, act_dim)).astype(np.float32),
        reward=rng.normal(size=B).astype(np.float32),
        gamma_n=np.full(B, 0.95, np.float32),
        state1=rng.normal(size=(B, obs_dim)).astype(np.float32),
        terminal1=np.zeros(B, np.float32),
        weight=np.ones(B, np.float32),
        index=np.arange(B, dtype=np.int32),
    )


def test_ddpg_split_merge_roundtrip():
    model = DdpgMlpModel(action_dim=1)
    full = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 3)))
    split = split_ddpg_params(full)
    merged = merge_ddpg_params(split["actor"], split["critic"])
    assert jax.tree_util.tree_structure(full) == \
        jax.tree_util.tree_structure(merged)


def test_ddpg_decoupled_step_runs_and_soft_updates():
    model, state, step = _ddpg_setup()
    b = _cont_batch()
    new_state, metrics, td = jax.jit(step)(state, b)
    assert "learner/actor_loss" in metrics
    # soft update with tau=1e-3: target moved slightly toward new params
    t0 = jax.tree_util.tree_leaves(state.target_params)[0]
    t1 = jax.tree_util.tree_leaves(new_state.target_params)[0]
    p1 = jax.tree_util.tree_leaves(new_state.params)[0]
    assert not np.allclose(t0, t1)
    np.testing.assert_allclose(
        np.asarray(t1), np.asarray(0.999 * t0 + 0.001 * p1), rtol=1e-5)


def test_ddpg_coupled_policy_grads_hit_critic():
    # decoupled: critic params after the critic step depend only on the
    # critic loss; coupled: the policy loss also deposits gradients into the
    # critic (reference behaviour) -> different critic update for the same
    # batch and same init.
    _, d_state, d_step = _ddpg_setup(coupled=False)
    _, c_state, c_step = _ddpg_setup(coupled=True)
    b = _cont_batch()
    d_new, _, _ = jax.jit(d_step)(d_state, b)
    c_new, _, _ = jax.jit(c_step)(c_state, b)
    d_critic = d_new.params["critic"]["params"]["critic_out"]["kernel"]
    c_critic = c_new.params["params"]["critic_out"]["kernel"]
    assert not np.allclose(np.asarray(d_critic), np.asarray(c_critic))


def test_ddpg_critic_fits_targets():
    model, state, step = _ddpg_setup()
    jstep = jax.jit(step)
    b = _cont_batch()
    losses = []
    for _ in range(400):
        state, metrics, _ = jstep(state, b)
        losses.append(float(metrics["learner/critic_loss"]))
    assert losses[-1] < 0.1 * losses[0]


def test_sharded_learner_matches_single_device():
    mesh = make_mesh()
    assert mesh.shape["dp"] == 8
    model, state, step = _dqn_setup()
    b = _batch(B=32)
    single = ShardedLearner(step, mesh=None, donate=False)
    sharded = ShardedLearner(step, mesh=mesh, donate=False)
    s1, m1, td1 = single.step(state, b)
    s2, m2, td2 = sharded.step(sharded.place(state), b)
    np.testing.assert_allclose(float(m1["learner/critic_loss"]),
                               float(m2["learner/critic_loss"]), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(td1), np.asarray(td2),
                               rtol=1e-4, atol=1e-5)
    # params identical after the step (grad all-reduce == full-batch grad)
    for a, c in zip(jax.tree_util.tree_leaves(s1.params),
                    jax.tree_util.tree_leaves(s2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=1e-4, atol=1e-6)


def test_sharded_learner_batch_really_sharded():
    mesh = make_mesh()
    model, state, step = _dqn_setup()
    sharded = ShardedLearner(step, mesh=mesh, donate=False)
    b = sharded.shard_batch(_batch(B=32))
    devs = {s.device for s in b.state0.addressable_shards}
    assert len(devs) == 8


def test_donation_safe_with_init_train_state():
    # aliased params/target broke donation (donate same buffer twice);
    # init_train_state must keep the sharded+donated step runnable twice
    mesh = make_mesh()
    model, state, step = _dqn_setup()
    learner = ShardedLearner(step, mesh=mesh, donate=True)
    state = learner.place(state)
    b = _batch(B=32)
    state, _, _ = learner.step(state, b)
    state, _, _ = learner.step(state, b)
    assert int(state.step) == 2
    host = learner.host_params(state)
    assert isinstance(jax.tree_util.tree_leaves(host)[0], np.ndarray)


# ---------------------------------------------------------------------------
# the learner's device program, assembled in one place (factory.py
# build_learner_core / build_learner_dispatch): what run_learner and the
# Anakin driver both dispatch, and what the benchmark's mirror must lower
# ---------------------------------------------------------------------------

def _program_opts(tmp_path, row, **overrides):
    from pytorch_distributed_tpu.config import build_options

    base = dict(root_dir=str(tmp_path), refs="prog", visualize=False,
                resume="never", memory_size=128, batch_size=8)
    base.update(overrides)
    return build_options(row, **base)


_SEQ = dict(memory_type="device-sequence", seq_len=8, seq_overlap=4,
            burn_in=2, nstep=2, memory_size=256)
# id -> (CONFIGS row, overrides, K the dispatch must come out at)
DISPATCH_CASES = {
    "uniform-k1": (1, dict(memory_type="device", steps_per_dispatch=1), 1),
    "uniform-k4": (1, dict(memory_type="device", steps_per_dispatch=4), 4),
    "per-k4": (1, dict(memory_type="device-per", steps_per_dispatch=4), 4),
    # K = 3 is rounded UP to a whole number of groups of 2
    "per-megabatch2": (1, dict(memory_type="device-per",
                               steps_per_dispatch=3, megabatch=2), 4),
    "sequence-k2": (13, dict(steps_per_dispatch=2, **_SEQ), 2),
    # decoupled DDPG: two optimizers, a split param tree
    "ddpg-uniform-k2": (2, dict(memory_type="device",
                                steps_per_dispatch=2), 2),
    # one visible device: no mesh, the ring unsharded
    "per-k2-one-device": (1, dict(memory_type="device-per",
                                  steps_per_dispatch=2), 2),
}


def _feed(opt, spec, replay, n, seed=0):
    """``n`` seeded rows of the ring's own schema through its own feed."""
    from pytorch_distributed_tpu.utils.experience import Transition

    rng = np.random.default_rng(seed)
    pixels = opt.memory_params.state_dtype == "uint8"

    def obs(shape):
        return (rng.integers(0, 256, shape).astype(np.uint8) if pixels
                else rng.standard_normal(shape).astype(np.float32))

    if hasattr(replay, "T"):  # the segment ring
        from pytorch_distributed_tpu.memory.device_sequence import (
            SegmentChunk,
        )

        T = replay.T
        replay.feed_chunk(SegmentChunk(
            obs=obs((n, *replay.obs_shape)),
            action=rng.integers(0, spec.num_actions, (n, T)).astype(np.int32),
            reward=rng.standard_normal((n, T)).astype(np.float32),
            terminal=np.zeros((n, T), np.float32),
            mask=np.ones((n, T), np.float32),
            c0=np.zeros((n, replay.lstm_dim), np.float32),
            h0=np.zeros((n, replay.lstm_dim), np.float32)))
        return
    action = (rng.integers(0, spec.num_actions, n).astype(np.int32)
              if spec.discrete else
              rng.uniform(-1, 1, (n, spec.action_dim)).astype(np.float32))
    replay.feed_chunk(Transition(
        state0=obs((n, *spec.state_shape)), action=action,
        reward=rng.standard_normal(n).astype(np.float32),
        gamma_n=np.full(n, 0.99, np.float32),
        state1=obs((n, *spec.state_shape)),
        terminal1=(rng.random(n) < 0.1).astype(np.float32)))


def _assemble(opt, role="learner"):
    from pytorch_distributed_tpu import factory

    spec = factory.probe_env(opt)
    core, state = factory.build_learner_core(opt, spec)
    replay = factory.build_memory(opt, spec).learner_side.attach(
        mesh=core.mesh)
    prog = factory.build_learner_dispatch(core, replay, opt, role=role)
    return spec, prog, state, replay


def _call_args(prog, state, replay, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), prog.K)
    return (state, replay.state, keys if prog.K > 1 else keys[0],
            *((jnp.float32(replay.beta(0)),) if prog.takes_beta else ()))


class TestLearnerProgram:
    @pytest.mark.parametrize("case", list(DISPATCH_CASES))
    def test_one_dispatch_is_k_updates(self, case, tmp_path, monkeypatch):
        row, overrides, want_k = DISPATCH_CASES[case]
        if case.endswith("one-device"):
            one = jax.devices()[:1]
            monkeypatch.setattr(jax, "devices", lambda *a, **k: one)
        opt = _program_opts(tmp_path, row, **overrides)
        spec, prog, state, replay = _assemble(opt)
        assert (prog.mesh is None) == case.endswith("one-device")
        assert prog.K == want_k
        _feed(opt, spec, replay, 64)
        prioritized = hasattr(replay.state, "priority")
        assert prog.takes_beta == prioritized
        before = (np.asarray(replay.state.priority) if prioritized
                  else None)
        step0 = int(state.step)
        out = prog.fused(*_call_args(prog, state, replay))
        # the record says what came back, in order
        assert len(out) == len(prog.returns)
        got = dict(zip(prog.returns, out))
        assert int(got["state"].step) == step0 + prog.K
        loss = got["metrics"]["learner/critic_loss"]
        assert np.isfinite(float(loss))
        assert ("ring" in got) == prioritized
        if prioritized:
            assert type(got["ring"]) is type(replay.state)
            after = np.asarray(got["ring"].priority)
            assert (after != before).any()      # |TD| written back
            assert (after[before == 0] == 0).all()   # empty rows stay so
        # only the uniform ring's single step hands |TD| out
        assert ("td" in got) == (not prioritized and prog.K == 1)
        if "td" in got:
            assert got["td"].shape == (opt.agent_params.batch_size,)

    @pytest.mark.parametrize("ring", ["per", "sequence"])
    def test_the_benchmarks_mirror_lowers_the_programs_step(self, ring,
                                                            tmp_path):
        """``benchmark/harness/program.py`` assembles by hand what the
        driver measures; the factory assembles what ``run_learner`` and
        the Anakin loop run.  Same ``Options`` -> the same program text,
        or the ledger stops describing the product."""
        from benchmark.harness import program

        cfg = {"per": {"row": 12, "overrides": {
                   "memory_size": 512, "batch_size": 8,
                   "steps_per_dispatch": 4}},
               "sequence": {"row": 14, "overrides": {
                   "memory_size": 256, "batch_size": 4, "seq_len": 8,
                   "seq_overlap": 4, "burn_in": 2, "nstep": 2,
                   "steps_per_dispatch": 2}}}[ring]
        opt = program.build_opt(cfg, 3, str(tmp_path / "run"), "mirror")
        lrn = program.build_learner(opt)
        theirs = program.build_fused(lrn)
        _spec, prog, state, replay = _assemble(opt)
        assert prog.K == lrn.K and prog.takes_beta
        assert prog.returns == ("state", "ring", "metrics")

        def text(fused, st, rp):
            keys = jax.random.split(jax.random.PRNGKey(0), prog.K)
            return fused.lower(st, rp.state, keys,
                               jnp.float32(rp.beta(0))).as_text()

        assert text(prog.fused, state, replay) \
            == text(theirs, lrn.state, lrn.replay)

    @pytest.mark.parametrize("split,family", [
        ("mp", "dtqn-mlp"), ("ep", "dtqn-moe"), ("pp", "dtqn-pipe"),
        ("anakin-mp", "dtqn-mlp")])
    def test_model_split_refused_for_the_wrong_family(self, split, family,
                                                      tmp_path):
        """Each model split serves one family; any other is refused where
        the program is assembled, for every caller: the Anakin loop (dqn
        only) used to skip the check and ran the config unsplit."""
        from pytorch_distributed_tpu import factory

        match = f"{split[-2:]}_size>1 is only supported for {family}"
        if split.startswith("anakin"):
            from test_anakin import _anakin_opts, _make_driver

            with pytest.raises(AssertionError, match=match):
                _make_driver(_anakin_opts(tmp_path, mp_size=2))
            return
        opt = _program_opts(tmp_path, 1, **{f"{split}_size": 2})
        with pytest.raises(AssertionError, match=match):
            factory.build_learner_core(opt, factory.probe_env(opt))

    def test_model_file_is_loaded_before_the_state_is_built(self, tmp_path):
        """Finetune-from-file: the optimizer state and the target copy are
        made of the LOADED weights, in the split learner's assembly and in
        the Anakin driver alike."""
        from test_anakin import _anakin_opts, _make_driver

        from pytorch_distributed_tpu import factory
        from pytorch_distributed_tpu.utils import checkpoint as ckpt

        opt = _anakin_opts(tmp_path)
        spec = factory.probe_env(opt)
        model = factory.build_model(opt, spec)
        seeded = factory.init_params(opt, spec, model, seed=opt.seed)
        other = factory.init_params(opt, spec, model, seed=opt.seed + 1)
        # with and without the suffix, as --model-file is given
        ckpt.save_params(str(tmp_path / "pre.msgpack"), other)

        def same(a, b):
            return all(np.array_equal(np.asarray(x), np.asarray(y))
                       for x, y in zip(jax.tree_util.tree_leaves(a),
                                       jax.tree_util.tree_leaves(b)))

        assert not same(seeded, other)
        for model_file in (str(tmp_path / "pre"),
                           str(tmp_path / "pre.msgpack")):
            opt.model_file = model_file
            _core, state = factory.build_learner_core(opt, spec)
            assert same(state.params, other)
            assert same(state.target_params, other)
            assert int(state.step) == 0
        drv, _handles, _spec = _make_driver(opt)
        assert same(drv.state.params, other)
        assert same(drv.state.target_params, other)

    @pytest.mark.parametrize("role", ["learner", "anakin"])
    def test_megabatch_downgrade_is_loud_and_keeps_k(self, role, tmp_path,
                                                     capsys):
        """A family without a group step runs the sequential program at
        the configured K and says so under the caller's name."""
        opt = _program_opts(tmp_path, 13, steps_per_dispatch=3, megabatch=2,
                            **_SEQ)
        _spec, prog, _state, _replay = _assemble(opt, role=role)
        assert prog.K == 3
        said = capsys.readouterr().out
        assert f"[{role}] megabatch=2 is not supported for " \
               f"agent_type=r2d2" in said
        assert "steps_per_dispatch=3" in said
