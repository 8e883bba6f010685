"""R2D2 sequence family: segment assembly, sequence replay, the recurrent
unroll, the n-step-in-window targets, and the end-to-end chain topology."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pytorch_distributed_tpu.memory.sequence_replay import (
    Segment, SegmentBuilder, SequenceReplay,
)


def _carry(v: float, d: int = 4):
    return (np.full(d, v, np.float32), np.full(d, -v, np.float32))


class TestSegmentBuilder:
    def test_overlapping_emission(self):
        b = SegmentBuilder(seq_len=4, overlap=2)
        segs = []
        for t in range(10):
            segs += b.push(np.float32([t]), t % 3, float(t), False,
                           np.float32([t + 1]), _carry(float(t)))
        # windows [0..3], [2..5], [4..7], [6..9]
        assert len(segs) == 4
        s0, s1 = segs[0], segs[1]
        np.testing.assert_array_equal(s0.obs[:, 0], [0, 1, 2, 3, 4])
        np.testing.assert_array_equal(s1.obs[:, 0], [2, 3, 4, 5, 6])
        np.testing.assert_array_equal(s0.action, [0, 1, 2, 0])
        assert s0.mask.sum() == 4
        # stored state is the carry BEFORE the segment's first step
        assert s1.c0[0] == pytest.approx(2.0)
        assert s1.h0[0] == pytest.approx(-2.0)

    def test_episode_end_pads_and_masks(self):
        b = SegmentBuilder(seq_len=5, overlap=2)
        segs = []
        for t in range(3):
            segs += b.push(np.float32([t]), 0, 1.0, t == 2,
                           np.float32([t + 1]), _carry(0.0))
        assert len(segs) == 1
        s = segs[0]
        np.testing.assert_array_equal(s.mask, [1, 1, 1, 0, 0])
        np.testing.assert_array_equal(s.terminal, [0, 0, 1, 0, 0])
        # bootstrap obs sits right after the last valid step; pads repeat it
        assert s.obs[3, 0] == pytest.approx(3.0)
        assert s.obs[5, 0] == pytest.approx(3.0)
        # stream reset: the next episode starts a fresh window
        more = b.push(np.float32([9]), 0, 0.0, False, np.float32([10]),
                      _carry(9.0))
        assert more == [] and len(b._steps) == 1

    def test_no_overlap_across_episodes(self):
        b = SegmentBuilder(seq_len=4, overlap=2)
        for t in range(4):
            b.push(np.float32([t]), 0, 0.0, False, np.float32([t + 1]),
                   _carry(float(t)))
        segs = b.push(np.float32([4]), 0, 1.0, True, np.float32([5]),
                      _carry(4.0))
        # terminal flushes the overlap remainder as its own masked segment
        assert len(segs) == 1
        assert b._steps == []


class TestSequenceReplay:
    def _seg(self, v: float, T=4, d=4):
        return Segment(
            obs=np.full((T + 1, 1), v, np.float32),
            action=np.zeros(T, np.int32),
            reward=np.full(T, v, np.float32),
            terminal=np.zeros(T, np.float32),
            mask=np.ones(T, np.float32),
            c0=np.zeros(d, np.float32), h0=np.zeros(d, np.float32))

    def test_ring_and_uniform_when_alpha_zero(self):
        mem = SequenceReplay(8, 4, (1,), 4, priority_exponent=0.0)
        for i in range(10):  # wraps
            mem.feed(self._seg(float(i)))
        assert mem.size == 8
        batch = mem.sample(16, np.random.default_rng(0))
        assert batch.obs.shape == (16, 5, 1)
        assert (batch.weight == 1.0).all()

    def test_priorities_bias_sampling(self):
        mem = SequenceReplay(8, 4, (1,), 4, priority_exponent=1.0)
        for i in range(8):
            mem.feed(self._seg(float(i)))
        mem.update_priorities(np.arange(8), np.r_[np.zeros(7), 100.0])
        rng = np.random.default_rng(1)
        batch = mem.sample(256, rng)
        # row 7 holds ~all priority mass
        assert (batch.index == 7).mean() > 0.95
        # IS weights: normalized by the max (min-probability row), so the
        # oversampled hot row takes the smallest correction weight
        assert (batch.weight <= 1.0 + 1e-6).all()
        assert batch.weight[batch.index == 7].max() < 1e-3


def _linear_halves():
    # linear "recurrent" net as the step takes it, in two halves: the
    # per-observation one passes obs through, the recurrent one is
    # q = W x + carry passthrough, so targets are hand-computable;
    # carry = (c, h) each (B, 1)
    return (lambda params, obs: obs,
            lambda params, x, carry: (x @ params["w"], carry))  # (B, A)


class TestSequenceLoss:

    def test_nstep_window_targets_match_hand_computation(self):
        from pytorch_distributed_tpu.memory.sequence_replay import (
            SegmentBatch,
        )
        from pytorch_distributed_tpu.ops.sequence_losses import (
            build_drqn_train_step,
        )
        from pytorch_distributed_tpu.ops.losses import (
            init_train_state,
        )
        import optax

        T, nstep, gamma = 4, 2, 0.5
        params = {"w": jnp.eye(1, 3)}  # q(obs)[a] = obs for a=0 else 0
        tx = optax.sgd(0.0)  # zero lr: inspect td via returned priorities
        state = init_train_state(params, tx)
        step = build_drqn_train_step(
            *_linear_halves(), tx, burn_in=0, nstep=nstep, gamma=gamma,
            enable_double=False, target_model_update=10 ** 9,
            rescale_values=False, priority_eta=1.0)

        obs = np.arange(5, dtype=np.float32).reshape(1, 5, 1)  # 0..4
        batch = SegmentBatch(
            obs=obs,
            action=np.zeros((1, T), np.int32),
            reward=np.array([[1.0, 2.0, 3.0, 4.0]], np.float32),
            terminal=np.zeros((1, T), np.float32),
            mask=np.ones((1, T), np.float32),
            c0=np.zeros((1, 1), np.float32),
            h0=np.zeros((1, 1), np.float32),
            weight=np.ones(1, np.float32),
            index=np.zeros(1, np.int32))
        _state, _metrics, seq_pr = jax.jit(step)(state, batch)
        # q_sel[t] = obs[t] = t; boot[s] = max(q(obs[s])) = s
        # t=0: r0 + g r1 + g^2 * boot(2) = 1 + 1 + 0.5 = 2.5, td = 2.5
        # t=1: 2 + 1.5 + 0.25*3 = 4.25, td = 3.25
        # t=2: 3 + 2 + 0.25*4 = 6, td = 4  (boot at 4)
        # t=3 (window end, K=1): 4 + 0.5*boot(4)=6, td=3
        # eta=1 -> max |td| = 4
        assert float(seq_pr[0]) == pytest.approx(4.0, abs=1e-5)

    def test_terminal_cuts_bootstrap(self):
        from pytorch_distributed_tpu.memory.sequence_replay import (
            SegmentBatch,
        )
        from pytorch_distributed_tpu.ops.sequence_losses import (
            build_drqn_train_step,
        )
        from pytorch_distributed_tpu.ops.losses import init_train_state
        import optax

        params = {"w": jnp.eye(1, 3) * 0.0}  # q == 0 everywhere
        tx = optax.sgd(0.0)
        state = init_train_state(params, tx)
        step = build_drqn_train_step(
            *_linear_halves(), tx, burn_in=0, nstep=3, gamma=0.5,
            enable_double=False, target_model_update=10 ** 9,
            rescale_values=False, priority_eta=1.0)
        # episode ends at t=1 with reward 10; tail padded
        batch = SegmentBatch(
            obs=np.ones((1, 5, 1), np.float32),
            action=np.zeros((1, 4), np.int32),
            reward=np.array([[1.0, 10.0, 0.0, 0.0]], np.float32),
            terminal=np.array([[0.0, 1.0, 0.0, 0.0]], np.float32),
            mask=np.array([[1.0, 1.0, 0.0, 0.0]], np.float32),
            c0=np.zeros((1, 1), np.float32),
            h0=np.zeros((1, 1), np.float32),
            weight=np.ones(1, np.float32),
            index=np.zeros(1, np.int32))
        _state, _m, seq_pr = jax.jit(step)(state, batch)
        # t=0: G = 1 + 0.5*10 = 6 (no bootstrap past terminal), q=0 -> |td|=6
        # t=1: G = 10; |td| = 10 -> max
        assert float(seq_pr[0]) == pytest.approx(10.0, abs=1e-5)


class TestTruncationBootstrap:
    def test_truncated_tail_bootstraps_from_final_obs(self):
        """A time-limit truncation ends the segment WITHOUT a terminal:
        targets near the tail must bootstrap from the stored successor
        observation instead of treating the cut as a death."""
        from pytorch_distributed_tpu.memory.sequence_replay import (
            SegmentBatch,
        )
        from pytorch_distributed_tpu.ops.sequence_losses import (
            build_drqn_train_step,
        )
        from pytorch_distributed_tpu.ops.losses import init_train_state
        import optax

        params = {"w": jnp.eye(1, 3)}  # q(obs)[0] = obs
        tx = optax.sgd(0.0)
        state = init_train_state(params, tx)
        step = build_drqn_train_step(
            *_linear_halves(), tx, burn_in=0, nstep=3, gamma=0.5,
            enable_double=False, target_model_update=10 ** 9,
            rescale_values=False, priority_eta=1.0)
        # 2 valid steps (truncated, NO terminal); bootstrap obs = 7 at
        # position 2, repeated through the padding
        obs = np.array([[[1.0], [2.0], [7.0], [7.0], [7.0]]], np.float32)
        batch = SegmentBatch(
            obs=obs,
            action=np.zeros((1, 4), np.int32),
            reward=np.array([[1.0, 1.0, 0.0, 0.0]], np.float32),
            terminal=np.zeros((1, 4), np.float32),
            mask=np.array([[1.0, 1.0, 0.0, 0.0]], np.float32),
            c0=np.zeros((1, 1), np.float32),
            h0=np.zeros((1, 1), np.float32),
            weight=np.ones(1, np.float32),
            index=np.zeros(1, np.int32))
        _state, _m, seq_pr = jax.jit(step)(state, batch)
        # t=0: K=min(3, n_valid-0)=2 -> G = 1 + 0.5*1 + 0.25*boot(7)
        #      = 1.5 + 1.75 = 3.25; q_sel = 1 -> |td| = 2.25
        # t=1: K=1 -> G = 1 + 0.5*7 = 4.5; q_sel = 2 -> |td| = 2.5 (max)
        assert float(seq_pr[0]) == pytest.approx(2.5, abs=1e-5)


class TestRecurrentModel:
    def test_unroll_matches_stepwise(self):
        from pytorch_distributed_tpu.models.drqn import DrqnMlpModel, halves
        from pytorch_distributed_tpu.ops.sequence_losses import unroll

        model = DrqnMlpModel(action_space=3, hidden_dim=16, lstm_dim=8)
        obs = jnp.ones((2, 4))
        params = model.init(jax.random.PRNGKey(0), obs)
        seq = jax.random.normal(jax.random.PRNGKey(1), (5, 2, 4))
        carry = model.zero_carry(2)
        embed, core = halves(model)
        x_seq = embed(params, seq.reshape(10, 4)).reshape(5, 2, -1)
        _, q_seq = unroll(core, params, carry, x_seq)
        c = carry
        for t in range(5):
            q_t, c = model.apply(params, seq[t], c)
            np.testing.assert_allclose(np.asarray(q_seq[t]),
                                       np.asarray(q_t), rtol=1e-5)

    def test_zero_carry_default_matches_explicit(self):
        from pytorch_distributed_tpu.models.drqn import DrqnMlpModel

        model = DrqnMlpModel(action_space=3, lstm_dim=8)
        obs = jnp.ones((2, 4))
        params = model.init(jax.random.PRNGKey(0), obs)
        q_default, _ = model.apply(params, obs)
        q_explicit, _ = model.apply(params, obs, model.zero_carry(2))
        np.testing.assert_allclose(np.asarray(q_default),
                                   np.asarray(q_explicit))


def _whole_model_scan_loss(apply_fn, params, target_params, batch, *,
                           burn_in, nstep, gamma, packed_frames):
    """The update as it was before the model came in two halves, kept
    here as the oracle: the WHOLE single-step apply (torso + LSTM + head)
    scanned over time-major observations, four scans an update.
    -> (loss, per-sequence priorities)."""
    from pytorch_distributed_tpu.ops import sequence_losses as sl

    T = batch.action.shape[1]
    obs = batch.obs
    if packed_frames:
        obs = sl.unpack_frame_stacks(obs, packed_frames, T)
    obs_tm = jnp.moveaxis(obs, 0, 1)

    def scan(p, carry, o_tm):
        def body(c, o):
            q, c2 = apply_fn(p, o, c)
            return c2, q
        return jax.lax.scan(body, carry, o_tm)

    def burn_and_unroll(p, refresh):
        carry = (batch.c0, batch.h0)
        if burn_in:
            carry = refresh(scan(p, carry, obs_tm[:burn_in])[0])
        return scan(p, carry, obs_tm[burn_in:])[1]

    q_target_tm = burn_and_unroll(target_params, lambda c: c)
    q_tm = burn_and_unroll(params, jax.lax.stop_gradient)
    tm = lambda x: jnp.moveaxis(x, 0, 1)[burn_in:]
    a_tm, r_tm, d_tm, m_tm = (tm(x) for x in (
        batch.action, batch.reward, batch.terminal, batch.mask))
    q_sel = jnp.take_along_axis(
        q_tm[:T - burn_in], a_tm[..., None].astype(jnp.int32),
        axis=-1)[..., 0]
    boot = sl._bootstrap_values(q_tm, q_target_tm, True, sl.value_unrescale)
    target = sl.value_rescale(sl.nstep_window_returns(
        boot, r_tm, d_tm, m_tm, nstep=nstep, gamma=gamma))
    return sl._masked_loss_and_priority(q_sel, target, m_tm, batch.weight,
                                        0.9)


def _find_eqns(jaxpr, name, in_scan=False):
    """[(inside a scan body?, eqn)] of every ``name`` equation of a jaxpr,
    sub-jaxprs (calls, scans, custom derivatives, branches) included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            found.append((in_scan, eqn))
        inner = in_scan or eqn.primitive.name == "scan"
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _find_eqns(sub, name, inner)
    return found


class TestTwoHalves:
    """The recurrent Q-network as ``embed`` (per observation, batched over
    every frame of an update) + ``core`` (the LSTM and the head, alone in
    the time scans): models/drqn.py, ops/sequence_losses.py."""

    B, T, C = 3, 6, 4

    def _model(self, kind):
        from pytorch_distributed_tpu.models.drqn import (
            DrqnCnnModel, DrqnMlpModel,
        )

        if kind == "cnn":   # 36x36: the least the Nature convs leave a 1x1
            return DrqnCnnModel(action_space=3, lstm_dim=8,
                                compute_dtype=jnp.float32), (36, 36)
        return DrqnMlpModel(action_space=3, hidden_dim=16, lstm_dim=8,
                            norm_val=255.0), (5, 5)

    def _batch(self, hw, packed_frames, seed=0):
        from pytorch_distributed_tpu.memory.sequence_replay import (
            SegmentBatch,
        )

        B, T, C = self.B, self.T, self.C
        rng = np.random.default_rng(seed)
        shape = (B, T + C, *hw) if packed_frames else (B, T + 1, C, *hw)
        mask = np.ones((B, T), np.float32)
        mask[0, T - 1:] = 0.0       # one truncated tail
        return SegmentBatch(
            obs=rng.integers(0, 256, shape).astype(np.uint8),
            action=rng.integers(0, 3, (B, T)).astype(np.int32),
            reward=rng.normal(size=(B, T)).astype(np.float32) * mask,
            terminal=(rng.random((B, T)) < 0.1).astype(np.float32) * mask,
            mask=mask,
            c0=rng.normal(size=(B, 8)).astype(np.float32) * 0.1,
            h0=rng.normal(size=(B, 8)).astype(np.float32) * 0.1,
            weight=rng.random(B).astype(np.float32) + 0.5,
            index=np.arange(B, dtype=np.int32))

    def _state_and_step(self, kind, burn_in, packed_frames, **kw):
        import optax

        from pytorch_distributed_tpu.models.drqn import halves
        from pytorch_distributed_tpu.ops.losses import init_train_state
        from pytorch_distributed_tpu.ops.sequence_losses import (
            build_drqn_train_step,
        )

        model, hw = self._model(kind)
        params = model.init(jax.random.PRNGKey(0),
                            np.zeros((1, self.C, *hw), np.uint8))
        tx = optax.sgd(1.0)         # new params = params - gradient
        state = init_train_state(params, tx)
        # a target net that differs from the online one
        state = state._replace(target_params=jax.tree_util.tree_map(
            lambda x: 0.9 * x, params))
        step = build_drqn_train_step(
            *halves(model), tx, burn_in=burn_in, nstep=3, gamma=0.9,
            target_model_update=10 ** 9, packed_frames=packed_frames, **kw)
        return model, hw, state, step

    @pytest.mark.parametrize("packed_frames", [0, 4])
    @pytest.mark.parametrize("burn_in", [0, 2])
    @pytest.mark.parametrize("kind", ["mlp", "cnn"])
    def test_step_matches_the_whole_model_scan(self, kind, burn_in,
                                               packed_frames):
        model, hw, state, step = self._state_and_step(
            kind, burn_in, packed_frames)
        batch = self._batch(hw, packed_frames)
        new, metrics, seq_pr = jax.jit(step)(state, batch)
        (loss, want_pr), want_grads = jax.jit(jax.value_and_grad(
            lambda p: _whole_model_scan_loss(
                model.apply, p, state.target_params, batch,
                burn_in=burn_in, nstep=3, gamma=0.9,
                packed_frames=packed_frames), has_aux=True))(state.params)
        np.testing.assert_allclose(float(metrics["learner/critic_loss"]),
                                   float(loss), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(seq_pr), np.asarray(want_pr),
                                   rtol=1e-5, atol=1e-6)
        grads = jax.tree_util.tree_map(lambda a, b: a - b, state.params,
                                       new.params)
        got = jax.tree_util.tree_leaves_with_path(grads)
        want = jax.tree_util.tree_leaves_with_path(want_grads)
        assert [k for k, _ in got] == [k for k, _ in want]
        assert any(float(jnp.max(jnp.abs(w))) > 1e-4 for _, w in want)
        for (path, g), (_, w) in zip(got, want):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), rtol=1e-4, atol=2e-6,
                err_msg=jax.tree_util.keystr(path))

    @pytest.mark.parametrize("kind,shapes", [
        ("cnn", {"Conv_0": {"kernel": (8, 8, 4, 32), "bias": (32,)},
                 "Conv_1": {"kernel": (4, 4, 32, 64), "bias": (64,)},
                 "Conv_2": {"kernel": (3, 3, 64, 64), "bias": (64,)},
                 "Dense_0": {"kernel": (64, 8), "bias": (8,)},
                 "OptimizedLSTMCell_0": dict(
                     {f"i{g}": {"kernel": (8, 8)} for g in "ifgo"},
                     **{f"h{g}": {"kernel": (8, 8), "bias": (8,)}
                        for g in "ifgo"}),
                 "Dense_1": {"kernel": (8, 3), "bias": (3,)}}),
        ("mlp", {"Dense_0": {"kernel": (100, 16), "bias": (16,)},
                 "OptimizedLSTMCell_0": dict(
                     {f"i{g}": {"kernel": (16, 8)} for g in "ifgo"},
                     **{f"h{g}": {"kernel": (8, 8), "bias": (8,)}
                        for g in "ifgo"}),
                 "Dense_1": {"kernel": (8, 3), "bias": (3,)}}),
    ])
    def test_apply_is_core_of_embed_on_the_same_tree(self, kind, shapes):
        """Acting (``apply``) is the recurrent half applied to the
        per-observation half, and the parameter tree has the names and
        shapes it had when one compact call made it: a checkpoint saved
        before the split loads."""
        from pytorch_distributed_tpu.models.drqn import halves

        model, hw = self._model(kind)
        obs = np.random.default_rng(1).integers(
            0, 256, (2, self.C, *hw)).astype(np.uint8)
        params = model.init(jax.random.PRNGKey(0), obs)
        assert jax.tree_util.tree_map(jnp.shape, params) == {
            "params": shapes}
        carry = tuple(jax.random.normal(jax.random.PRNGKey(i), (2, 8))
                      for i in (2, 3))
        embed, core = halves(model)
        x = embed(params, obs)
        assert x.shape == (2, 8 if kind == "cnn" else 16)
        assert x.dtype == jnp.float32
        for got, want in zip(
                jax.tree_util.tree_leaves(core(params, x, carry)),
                jax.tree_util.tree_leaves(model.apply(params, obs, carry))):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        q0, _ = model.apply(params, obs)
        q0_halves, _ = core(params, x, model.zero_carry(2))
        np.testing.assert_array_equal(np.asarray(q0), np.asarray(q0_halves))

    def test_no_convolution_inside_a_time_scan(self):
        """Does the mechanism engage: in the drqn-cnn step every
        convolution runs OUTSIDE the scans, once per pass over all of its
        frames, and the online burn-in frames get a forward only."""
        burn_in = 2
        model, hw, state, step = self._state_and_step(
            "cnn", burn_in, 4, guard=False)
        batch = self._batch(hw, 4)
        jaxpr = jax.make_jaxpr(step)(state, batch).jaxpr
        convs = _find_eqns(jaxpr, "conv_general_dilated")
        scans = _find_eqns(jaxpr, "scan")
        # target burn-in + unroll, online burn-in + unroll + its backward
        assert len(scans) == 5
        assert [e for inside, e in convs if inside] == []
        # frames a pass: B * burn_in = 6 and B * (T + 1 - burn_in) = 15,
        # numbers no other dimension of these shapes takes
        n_burn, n_train = self.B * burn_in, self.B * (self.T + 1 - burn_in)

        def over(n):
            return sum(any(n in v.aval.shape for v in (*e.invars, *e.outvars))
                       for _, e in convs)

        # three convs, target + online, forward only: no transpose
        assert over(n_burn) == 6
        # the same six forward + the backward: Conv_1 and Conv_2 towards
        # their input and their kernel, Conv_0 (pixels in) its kernel only
        assert over(n_train) == 11
        assert len(convs) == 17
        # the oracle is what the step was: convolutions in every scan
        old = jax.make_jaxpr(lambda p: _whole_model_scan_loss(
            model.apply, p, state.target_params, batch, burn_in=burn_in,
            nstep=3, gamma=0.9, packed_frames=4)[0])(state.params).jaxpr
        assert sum(inside for inside, _ in _find_eqns(
            old, "conv_general_dilated")) == 12


@pytest.mark.slow
@pytest.mark.timeout(1200)
def test_r2d2_chain_topology_learns(tmp_path):
    from pytorch_distributed_tpu import runtime
    from pytorch_distributed_tpu.config import build_options

    opt = build_options(
        13, root_dir=str(tmp_path), num_actors=2, steps=1200, learn_start=8,
        batch_size=16, memory_size=4096, seq_len=16, seq_overlap=8,
        burn_in=4, nstep=3, actor_sync_freq=20, param_publish_freq=5,
        learner_freq=50, evaluator_freq=1, max_replay_ratio=64.0,
        lr=2e-3, target_model_update=100)
    runtime.train(opt, backend="thread")
    opt2 = build_options(13, root_dir=str(tmp_path), mode=2,
                         tester_nepisodes=5, seq_len=16,
                         model_file=opt.model_name)
    out = runtime.test(opt2)
    assert out["avg_reward"] >= 0.9
    assert out["avg_steps"] <= 10


class TestFramePacking:
    """Frame-packed segments (SegmentBuilder pack_frames): the wire/RAM
    representation drops the C-fold stack redundancy; learner-side
    reconstruction must be exact."""

    @staticmethod
    def _stacked_episode(n, C=4, H=6, W=6, seed=0):
        """Simulate a frame-stack env: per-step new frame, stack = last
        C frames (oldest first), reset stack = first frame repeated."""
        rng = np.random.default_rng(seed)
        frames = [rng.integers(0, 255, (H, W)).astype(np.uint8)
                  for _ in range(n + 1)]
        stacks = []
        for t in range(n + 1):
            window = [frames[max(0, t - C + 1 + i)] for i in range(C)]
            stacks.append(np.stack(window))
        return stacks  # obs[t] for t=0..n (obs[n] = bootstrap)

    @pytest.mark.parametrize("overlap", [0, 4])
    def test_packed_reconstruction_matches_stacks(self, overlap):
        # overlap > 0 exercises the retention path: the SECOND emitted
        # segment starts from retained raw steps, and packing must stay
        # exact there too
        import jax

        from pytorch_distributed_tpu.ops.sequence_losses import (
            unpack_frame_stacks,
        )

        T, C = 8, 4
        n_steps = T + (T - overlap)  # enough for two emissions
        stacks = self._stacked_episode(n_steps, C=C)
        packed_b = SegmentBuilder(T, overlap, state_dtype=np.uint8,
                                  pack_frames=C)
        plain_b = SegmentBuilder(T, overlap, state_dtype=np.uint8)
        carry = (np.zeros(3, np.float32), np.zeros(3, np.float32))
        packed, plain = [], []
        for t in range(n_steps):
            args = (stacks[t], t % 3, float(t), t == n_steps - 1,
                    stacks[t + 1], carry)
            packed += packed_b.push(*args)
            plain += plain_b.push(*args)
        assert len(packed) == len(plain) >= 2
        for p, u in zip(packed, plain):
            assert p.obs.shape == (T + C, 6, 6)
            rebuilt = np.asarray(unpack_frame_stacks(
                jax.numpy.asarray(p.obs[None]), C, T))[0]
            np.testing.assert_array_equal(rebuilt, u.obs)

    def test_packed_early_termination_pads_consistently(self):
        import jax

        from pytorch_distributed_tpu.ops.sequence_losses import (
            unpack_frame_stacks,
        )

        T, C, n = 8, 4, 3  # episode dies after 3 steps -> padded tail
        stacks = self._stacked_episode(n, C=C)
        b = SegmentBuilder(T, 0, state_dtype=np.uint8, pack_frames=C)
        carry = (np.zeros(2, np.float32), np.zeros(2, np.float32))
        out = []
        for t in range(n):
            out += b.push(stacks[t], 0, 1.0, t == n - 1, stacks[t + 1],
                          carry)
        seg = out[0]
        assert seg.obs.shape == (T + C, 6, 6)
        rebuilt = np.asarray(unpack_frame_stacks(
            jax.numpy.asarray(seg.obs[None]), C, T))[0]
        # valid positions 0..n-1 and the bootstrap position n are exact
        for t in range(n):
            np.testing.assert_array_equal(rebuilt[t], stacks[t])
        np.testing.assert_array_equal(rebuilt[n], stacks[n])
        # tail is masked: only shape-stability matters there
        assert float(seg.mask[:n].sum()) == n and float(seg.mask[n:].sum()) == 0

    def test_packed_drqn_step_matches_unpacked(self):
        """Same transitions, packed vs stacked wire format -> identical
        loss/priorities from build_drqn_train_step."""
        import jax

        from pytorch_distributed_tpu.memory.sequence_replay import (
            SegmentBatch,
        )
        from pytorch_distributed_tpu.models.drqn import DrqnCnnModel, halves
        from pytorch_distributed_tpu.ops.losses import (
            init_train_state, make_optimizer,
        )
        from pytorch_distributed_tpu.ops.sequence_losses import (
            build_drqn_train_step,
        )

        T, C = 6, 4
        # 36x36: the smallest square that survives the Nature conv
        # stack's VALID 8/4 -> 4/2 -> 3/1 reductions
        stacks = self._stacked_episode(T, C=C, H=36, W=36, seed=3)
        pb = SegmentBuilder(T, 0, state_dtype=np.uint8, pack_frames=C)
        ub = SegmentBuilder(T, 0, state_dtype=np.uint8)
        lstm = 8
        carry = (np.zeros(lstm, np.float32), np.zeros(lstm, np.float32))
        rng = np.random.default_rng(5)
        segs = {}
        for name, b in (("p", pb), ("u", ub)):
            rng2 = np.random.default_rng(5)
            out = []
            for t in range(T):
                out += b.push(stacks[t], int(rng2.integers(3)),
                              float(rng2.normal()), t == T - 1,
                              stacks[t + 1], carry)
            segs[name] = out[0]

        model = DrqnCnnModel(action_space=3, lstm_dim=lstm, norm_val=255.0,
                             compute_dtype=jax.numpy.float32)
        params = model.init(jax.random.PRNGKey(0),
                            np.zeros((1, C, 36, 36), np.uint8))
        tx = make_optimizer(lr=1e-3)
        losses = {}
        for name, packed_frames in (("p", C), ("u", 0)):
            s = segs[name]
            batch = SegmentBatch(
                obs=s.obs[None], action=s.action[None],
                reward=s.reward[None], terminal=s.terminal[None],
                mask=s.mask[None], c0=s.c0[None], h0=s.h0[None],
                weight=np.ones(1, np.float32),
                index=np.zeros(1, np.int32))
            step = jax.jit(build_drqn_train_step(
                *halves(model), tx, burn_in=2, nstep=3,
                target_model_update=100, packed_frames=packed_frames))
            _st, metrics, pr = step(init_train_state(params, tx), batch)
            losses[name] = (float(metrics["learner/critic_loss"]),
                            float(pr[0]))
        assert losses["p"][0] == pytest.approx(losses["u"][0], rel=1e-5)
        assert losses["p"][1] == pytest.approx(losses["u"][1], rel=1e-5)


class TestDtqnAuxiliaryLoss:
    """build_dtqn_train_step's three forms of ``window_apply``: Q alone, (Q,
    scalar) weighed by ``aux_weight`` (row 17), and (Q, dict) whose
    ``AUX_LOSS_KEY`` entry joins the loss as the model weighed it (row
    21)."""

    def _step(self, window_apply, **kw):
        from pytorch_distributed_tpu.memory.sequence_replay import (
            SegmentBatch,
        )
        from pytorch_distributed_tpu.ops.losses import (
            init_train_state, make_optimizer,
        )
        from pytorch_distributed_tpu.ops.sequence_losses import (
            build_dtqn_train_step,
        )

        B, T, A = 3, 6, 4
        params = {"w": 0.1 * jax.random.normal(jax.random.PRNGKey(0), (5, A))}
        tx = make_optimizer(lr=1e-3)
        step = build_dtqn_train_step(
            window_apply, tx, burn_in=1, nstep=2, target_model_update=100,
            guard=False, **kw)
        rng = np.random.default_rng(3)
        L = T - 1
        batch = SegmentBatch(
            obs=rng.normal(size=(B, T, 5)).astype(np.float32),
            action=rng.integers(0, A, size=(B, L)).astype(np.int32),
            reward=rng.normal(size=(B, L)).astype(np.float32),
            terminal=np.zeros((B, L), np.float32),
            mask=np.ones((B, L), np.float32),
            c0=np.zeros((B, 1), np.float32), h0=np.zeros((B, 1), np.float32),
            weight=np.ones(B, np.float32), index=np.arange(B, dtype=np.int32))
        new, metrics, _ = jax.jit(step)(init_train_state(params, tx), batch)
        grad = jax.tree_util.tree_map(lambda mu: mu / 0.1,
                                      new.opt_state[-1][0].mu)
        return metrics, grad["w"]

    @pytest.mark.parametrize("form", ["scalar", "dict"])
    def test_the_auxiliary_loss_joins_the_td_loss(self, form):
        from pytorch_distributed_tpu.ops.sequence_losses import AUX_LOSS_KEY

        q = lambda p, obs: obs @ p["w"]
        aux = lambda p: jnp.sum(jnp.square(p["w"]))
        plain, g_plain = self._step(q)
        if form == "scalar":
            got, g = self._step(lambda p, obs: (q(p, obs), aux(p)),
                                aux_weight=0.5)
            assert float(got["learner/moe_aux"]) == pytest.approx(
                float(aux({"w": 0.1 * jax.random.normal(
                    jax.random.PRNGKey(0), (5, 4))})))
        else:
            # a stray aux_weight is the scalar form's: the dict's entry
            # comes weighed
            got, g = self._step(
                lambda p, obs: (q(p, obs), {AUX_LOSS_KEY: 0.5 * aux(p),
                                            "learner/some_counter":
                                            jnp.float32(7.0),
                                            "load": jnp.zeros((4,))}),
                aux_weight=0.0)
            assert float(got["learner/some_counter"]) == 7.0
            assert "load" not in got                 # arrays are no metric
            assert float(got[AUX_LOSS_KEY]) == pytest.approx(
                float(got["learner/critic_loss"])
                - float(plain["learner/critic_loss"]), rel=1e-5)
        w = 0.1 * jax.random.normal(jax.random.PRNGKey(0), (5, 4))
        assert float(got["learner/critic_loss"]) == pytest.approx(
            float(plain["learner/critic_loss"]) + 0.5 * float(aux({"w": w})),
            rel=1e-5)
        np.testing.assert_allclose(g, g_plain + 0.5 * 2.0 * w, rtol=1e-4,
                                   atol=1e-6)

    def test_a_dict_without_the_entry_adds_nothing(self):
        q = lambda p, obs: obs @ p["w"]
        plain, g_plain = self._step(q)
        got, g = self._step(lambda p, obs: (q(p, obs), {
            "learner/some_counter": jnp.float32(1.0)}))
        assert float(got["learner/critic_loss"]) == float(
            plain["learner/critic_loss"])
        np.testing.assert_array_equal(g, g_plain)
