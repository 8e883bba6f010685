"""ISSUE-13 megabatch oracles: the fused megabatched learner step must
reproduce an unfused reference of its documented semantics exactly —
params, optimizer state, PER priorities and the key-stream schedule —
for both flat families (dqn, decoupled ddpg), with M=1 degenerating to
the production sequential step.  Plus the perf-plane drills the other
fused dispatches carry: no post-warmup retrace, transfer-audit-clean.

Group semantics under test (config.LearnerPerfParams docstring): all M
minibatch gradients at the GROUP-ENTRY params in one batched backward,
optimizer updates applied sequentially, PER write-backs in minibatch
order from group-entry sampling distributions.  Tolerances are a few
fp32 ulps (vmapped and unbatched backwards order their reductions
identically on this backend, but XLA does not contract to bitwise)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree
import optax

from pytorch_distributed_tpu.models import DdpgMlpModel, DqnMlpModel
from pytorch_distributed_tpu.ops.losses import (
    build_ddpg_megabatch_step, build_ddpg_train_step,
    build_dqn_megabatch_step, build_dqn_train_step, init_ddpg_train_state,
    init_train_state, make_optimizer, merge_ddpg_params,
)
from pytorch_distributed_tpu.utils.experience import Batch, Transition
from pytorch_distributed_tpu.utils.health import SKIPPED_KEY

OBS, ACT, B = 4, 3, 8

TOL = dict(rtol=2e-6, atol=2e-6)


def _dqn_setup(lr=1e-2, guard=True, target_update=3):
    model = DqnMlpModel(action_space=ACT, hidden_dim=32)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, OBS)))
    tx = make_optimizer(lr)
    state = init_train_state(params, tx)
    mega = build_dqn_megabatch_step(model.apply, tx, guard=guard,
                                    target_model_update=target_update)
    return model, tx, state, mega


def _batches(M, seed=0):
    """A (M, B)-leading Batch group."""
    rng = np.random.default_rng(seed)
    return Batch(
        state0=rng.normal(size=(M, B, OBS)).astype(np.float32),
        action=rng.integers(0, ACT, size=(M, B)).astype(np.int32),
        reward=rng.normal(size=(M, B)).astype(np.float32),
        gamma_n=np.full((M, B), 0.95, dtype=np.float32),
        state1=rng.normal(size=(M, B, OBS)).astype(np.float32),
        terminal1=(rng.random((M, B)) < 0.3).astype(np.float32),
        weight=np.ones((M, B), np.float32),
        index=np.tile(np.arange(B, dtype=np.int32), (M, 1)),
    )


def _mb(batches, i):
    return jax.tree_util.tree_map(lambda l: l[i], batches)


def _assert_tree_close(a, b, **kw):
    kw = kw or TOL
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_allclose(np.asarray(x),
                                                np.asarray(y), **kw),
        a, b)


class TestDqnMegabatchOracle:
    def test_matches_unfused_sequential_reference(self):
        """The fused group step == a python loop implementing the
        documented semantics with the production optimizer pieces."""
        model, tx, state, mega = _dqn_setup()
        M = 4
        batches = _batches(M)
        new_state, metrics, td_abs, ok = jax.jit(mega)(state, batches)
        assert np.asarray(ok).tolist() == [1.0] * M
        assert float(metrics[SKIPPED_KEY]) == 0.0

        def loss_fn(p, tgt, b):
            q = model.apply(p, b.state0)
            q_sel = jnp.take_along_axis(
                q, b.action.astype(jnp.int32).reshape(-1, 1), axis=1)[:, 0]
            boot = jnp.max(model.apply(tgt, b.state1), axis=-1)
            t = b.reward + b.gamma_n * boot * (1.0 - b.terminal1)
            return jnp.mean(b.weight * jnp.square(
                q_sel - jax.lax.stop_gradient(t)))

        from pytorch_distributed_tpu.utils.helpers import update_target

        p, o, s, t = (state.params, state.opt_state, state.step,
                      state.target_params)
        entry_p, entry_t = state.params, state.target_params
        ref_tds = []
        for i in range(M):
            b = _mb(batches, i)
            g = jax.grad(loss_fn)(entry_p, entry_t, b)
            upd, o = tx.update(g, o, p)
            p = optax.apply_updates(p, upd)
            s = s + 1
            t = update_target(t, p, s, 3)
        _assert_tree_close(new_state.params, p)
        _assert_tree_close(new_state.target_params, t)
        _assert_tree_close(new_state.opt_state, o)
        assert int(new_state.step) == int(s) == M

    def test_m1_group_equals_production_sequential_step(self):
        """With M=1 the group semantics ARE the sequential step's: same
        params, target, opt state, metrics, td."""
        model, tx, state, mega = _dqn_setup()
        seq = build_dqn_train_step(model.apply, tx, target_model_update=3)
        batches = _batches(1)
        s_m, m_m, td_m, ok = jax.jit(mega)(state, batches)
        s_s, m_s, td_s = jax.jit(seq)(state, _mb(batches, 0))
        _assert_tree_close(s_m.params, s_s.params)
        _assert_tree_close(s_m.opt_state, s_s.opt_state)
        np.testing.assert_allclose(np.asarray(td_m[0]), np.asarray(td_s),
                                   **TOL)
        for k in ("learner/critic_loss", "learner/q_mean",
                  "learner/grad_norm"):
            np.testing.assert_allclose(float(m_m[k]), float(m_s[k]),
                                       **TOL)

    def test_guard_skips_only_the_poisoned_minibatch(self):
        model, tx, state, mega = _dqn_setup()
        M = 3
        batches = _batches(M)
        reward = np.asarray(batches.reward).copy()
        reward[1] = np.nan  # poison the MIDDLE minibatch only
        batches = batches._replace(reward=reward)
        new_state, metrics, td_abs, ok = jax.jit(mega)(state, batches)
        assert np.asarray(ok).tolist() == [1.0, 0.0, 1.0]
        assert float(metrics[SKIPPED_KEY]) == 1.0
        # the skipped row's TD is zeroed so no write-back path can
        # scatter NaN priorities
        assert np.all(np.asarray(td_abs[1]) == 0.0)
        assert np.isfinite(
            np.asarray(ravel_pytree(new_state.params)[0])).all()
        # skipped minibatch does not advance the step counter
        assert int(new_state.step) == M - 1
        # and the applied updates equal the reference that drops mb 1
        ref_state, _m, _td, _ok = jax.jit(mega)(
            state, jax.tree_util.tree_map(
                lambda l: l[np.array([0, 2])], batches))
        _assert_tree_close(new_state.params, ref_state.params)

    def test_all_poisoned_group_passes_state_through(self):
        model, tx, state, mega = _dqn_setup()
        batches = _batches(2)
        batches = batches._replace(
            reward=np.full_like(np.asarray(batches.reward), np.nan))
        new_state, metrics, _td, ok = jax.jit(mega)(state, batches)
        assert float(metrics[SKIPPED_KEY]) == 2.0
        _assert_tree_close(new_state.params, state.params,
                           rtol=0.0, atol=0.0)
        assert int(new_state.step) == 0


class TestDdpgMegabatchOracle:
    def _setup(self):
        model = DdpgMlpModel(action_dim=1, norm_val=1.0)
        params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, OBS)))
        atx = make_optimizer(1e-2)
        ctx_ = make_optimizer(1e-2)
        state = init_ddpg_train_state(params, atx, ctx_)
        actor_apply = lambda p, o: model.apply(p, o,
                                               method=model.forward_actor)
        critic_apply = lambda p, o, a: model.apply(
            p, o, a, method=model.forward_critic)
        mega = build_ddpg_megabatch_step(actor_apply, critic_apply,
                                         atx, ctx_,
                                         target_model_update=1e-3)
        return model, atx, ctx_, state, actor_apply, critic_apply, mega

    def _cont_batches(self, M, seed=0):
        rng = np.random.default_rng(seed)
        return Batch(
            state0=rng.normal(size=(M, B, OBS)).astype(np.float32),
            action=rng.uniform(-1, 1, size=(M, B, 1)).astype(np.float32),
            reward=rng.normal(size=(M, B)).astype(np.float32),
            gamma_n=np.full((M, B), 0.95, dtype=np.float32),
            state1=rng.normal(size=(M, B, OBS)).astype(np.float32),
            terminal1=(rng.random((M, B)) < 0.3).astype(np.float32),
            weight=np.ones((M, B), np.float32),
            index=np.tile(np.arange(B, dtype=np.int32), (M, 1)),
        )

    def test_matches_unfused_sequential_reference(self):
        (model, atx, ctx_, state, actor_apply, critic_apply,
         mega) = self._setup()
        M = 3
        batches = self._cont_batches(M)
        new_state, metrics, td_abs, ok = jax.jit(mega)(state, batches)
        assert np.asarray(ok).tolist() == [1.0] * M

        from pytorch_distributed_tpu.utils.helpers import update_target

        # ddpg tolerance is looser than dqn's: the two-net backward's
        # vmapped reductions differ from the unbatched ones by ~1 ulp,
        # and Adam's m/sqrt(v) amplifies that to ~1e-5 on a handful of
        # elements (a SEMANTIC divergence — wrong critic, wrong order —
        # would shift lr-scale ~1e-3 across the tree)
        ddpg_tol = dict(rtol=1e-4, atol=5e-5)

        params, target = state.params, state.target_params
        target_full = merge_ddpg_params(target["actor"],
                                        target["critic"])

        def critic_loss(cp, ap_, b):
            full = merge_ddpg_params(ap_, cp)
            q = critic_apply(full, b.state0, b.action)
            a_next = actor_apply(target_full, b.state1)
            q_next = critic_apply(target_full, b.state1, a_next)
            tgt = b.reward + b.gamma_n * q_next * (1.0 - b.terminal1)
            return jnp.mean(b.weight * jnp.square(
                q - jax.lax.stop_gradient(tgt)))

        def actor_loss(ap_, cp, b):
            full = merge_ddpg_params(ap_, cp)
            a = actor_apply(full, b.state0)
            return -jnp.mean(critic_apply(full, b.state0, a))

        # stage 1: critic grads at entry; sequential critic chain
        cp, copt = params["critic"], state.opt_state["critic"]
        critics = []
        for i in range(M):
            g = jax.grad(critic_loss)(params["critic"], params["actor"],
                                      _mb(batches, i))
            upd, copt = ctx_.update(g, copt, cp)
            cp = optax.apply_updates(cp, upd)
            critics.append(cp)
        # stage 2: actor grads at (entry actor, FINAL critic)
        ap_, aopt = params["actor"], state.opt_state["actor"]
        tgt, s = target, state.step
        for i in range(M):
            g = jax.grad(actor_loss)(params["actor"], cp, _mb(batches, i))
            upd, aopt = atx.update(g, aopt, ap_)
            ap_ = optax.apply_updates(ap_, upd)
            s = s + 1
            tgt = update_target(tgt, {"actor": ap_, "critic": critics[i]},
                                s, 1e-3)
        _assert_tree_close(new_state.params["critic"], cp, **ddpg_tol)
        _assert_tree_close(new_state.params["actor"], ap_, **ddpg_tol)
        _assert_tree_close(new_state.target_params, tgt, **ddpg_tol)
        assert int(new_state.step) == M

    def test_m1_group_equals_production_sequential_step(self):
        (model, atx, ctx_, state, actor_apply, critic_apply,
         mega) = self._setup()
        seq = build_ddpg_train_step(actor_apply, critic_apply, atx, ctx_,
                                    target_model_update=1e-3)
        batches = self._cont_batches(1)
        s_m, m_m, td_m, _ok = jax.jit(mega)(state, batches)
        s_s, m_s, td_s = jax.jit(seq)(state, _mb(batches, 0))
        _assert_tree_close(s_m.params, s_s.params)
        _assert_tree_close(s_m.target_params, s_s.target_params)
        np.testing.assert_allclose(np.asarray(td_m[0]), np.asarray(td_s),
                                   **TOL)
        for k in ("learner/critic_loss", "learner/actor_loss",
                  "learner/grad_norm"):
            np.testing.assert_allclose(float(m_m[k]), float(m_s[k]),
                                       **TOL)


# ---------------------------------------------------------------------------
# fused-dispatch oracles over real rings (the learner's actual programs)
# ---------------------------------------------------------------------------

def _fill_ring(ring, n=128, seed=0, num_actions=ACT):
    rng = np.random.default_rng(seed)
    ring.feed_chunk(Transition(
        state0=rng.normal(size=(n, OBS)).astype(np.float32),
        action=rng.integers(0, num_actions, n).astype(np.int32),
        reward=rng.normal(size=n).astype(np.float32),
        gamma_n=np.full(n, 0.95, np.float32),
        state1=rng.normal(size=(n, OBS)).astype(np.float32),
        terminal1=(rng.random(n) < 0.2).astype(np.float32)))


class TestFusedMegabatchDispatch:
    def test_uniform_key_schedule_and_reference_parity(self):
        """One megabatched dispatch over the uniform HBM ring consumes
        keys exactly as the sequential schedule (key g*M+i draws group
        g's minibatch i) and lands on the unfused reference."""
        from pytorch_distributed_tpu.memory.device_replay import (
            DeviceReplay, build_uniform_fused_step, sample_rows,
        )
        from pytorch_distributed_tpu.utils.helpers import update_target

        model, tx, state, mega = _dqn_setup()
        seq_step = build_dqn_train_step(model.apply, tx,
                                        target_model_update=3)
        ring = DeviceReplay(128, (OBS,), state_dtype=np.float32)
        _fill_ring(ring)
        M, K = 2, 4
        fused = build_uniform_fused_step(seq_step, B, steps_per_call=K,
                                         donate=False, megabatch=M,
                                         megabatch_step=mega)
        keys = jax.random.split(jax.random.PRNGKey(7), K)
        new_state, metrics = fused(state, ring.state, keys)

        def loss_fn(p, tgt, b):
            q = model.apply(p, b.state0)
            q_sel = jnp.take_along_axis(
                q, b.action.astype(jnp.int32).reshape(-1, 1),
                axis=1)[:, 0]
            boot = jnp.max(model.apply(tgt, b.state1), axis=-1)
            t = b.reward + b.gamma_n * boot * (1.0 - b.terminal1)
            return jnp.mean(b.weight * jnp.square(
                q_sel - jax.lax.stop_gradient(t)))

        p, o, s, t = (state.params, state.opt_state, state.step,
                      state.target_params)
        for g0 in range(K // M):
            entry_p, entry_t = p, t
            for i in range(M):
                # the key-stream schedule contract: minibatch i of
                # group g samples with key g*M+i — the same draw the
                # sequential scan would make
                b = sample_rows(ring.state, keys[g0 * M + i], B)
                g = jax.grad(loss_fn)(entry_p, entry_t, b)
                upd, o = tx.update(g, o, p)
                p = optax.apply_updates(p, upd)
                s = s + 1
                t = update_target(t, p, s, 3)
        _assert_tree_close(new_state.params, p)
        assert float(metrics[SKIPPED_KEY]) == 0.0

    def test_per_dispatch_matches_unfused_reference(self):
        """The PER megabatched dispatch: group-entry sampling, grads at
        group entry, write-backs in minibatch order — priorities AND
        params land on the unfused reference."""
        from pytorch_distributed_tpu.memory.device_per import (
            DevicePerReplay, per_sample, per_update_priorities,
        )
        from pytorch_distributed_tpu.utils.helpers import update_target

        model, tx, state, mega = _dqn_setup()
        seq_step = build_dqn_train_step(model.apply, tx,
                                        target_model_update=3)
        per = DevicePerReplay(128, (OBS,), state_dtype=np.float32)
        _fill_ring(per)
        M, K = 2, 4
        fused = per.build_fused_step(seq_step, B, donate=False,
                                     steps_per_call=K, megabatch=M,
                                     megabatch_step=mega)
        keys = jax.random.split(jax.random.PRNGKey(5), K)
        beta = jnp.float32(0.5)
        new_state, rs, metrics = fused(state, per.state, keys, beta)

        def loss_fn(p, tgt, b):
            q = model.apply(p, b.state0)
            q_sel = jnp.take_along_axis(
                q, b.action.astype(jnp.int32).reshape(-1, 1),
                axis=1)[:, 0]
            boot = jnp.max(model.apply(tgt, b.state1), axis=-1)
            t = b.reward + b.gamma_n * boot * (1.0 - b.terminal1)
            td = q_sel - jax.lax.stop_gradient(t)
            return jnp.mean(b.weight * jnp.square(td)), jnp.abs(td)

        p, o, s, t = (state.params, state.opt_state, state.step,
                      state.target_params)
        rs_ref = per.state
        for g0 in range(K // M):
            entry_p, entry_t, entry_rs = p, t, rs_ref
            drawn, tds = [], []
            for i in range(M):
                b = per_sample(entry_rs, keys[g0 * M + i], B, beta)
                (_l, td), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(entry_p, entry_t, b)
                upd, o = tx.update(g, o, p)
                p = optax.apply_updates(p, upd)
                s = s + 1
                t = update_target(t, p, s, 3)
                drawn.append(b)
                tds.append(td)
            for i in range(M):
                rs_ref = per_update_priorities(rs_ref, drawn[i].index,
                                               tds[i], per.alpha)
        _assert_tree_close(new_state.params, p)
        np.testing.assert_allclose(np.asarray(rs.priority),
                                   np.asarray(rs_ref.priority), **TOL)

    def test_per_poisoned_group_leaves_priorities_untouched(self):
        """All-NaN rewards: every minibatch skipped, params pass
        through, and the write-back suppression keeps every priority
        leaf exactly as it was."""
        from pytorch_distributed_tpu.memory.device_per import (
            DevicePerReplay,
        )

        model, tx, state, mega = _dqn_setup()
        seq_step = build_dqn_train_step(model.apply, tx,
                                        target_model_update=3)
        per = DevicePerReplay(128, (OBS,), state_dtype=np.float32)
        _fill_ring(per)
        per.state = per.state._replace(
            reward=jnp.full_like(per.state.reward, jnp.nan))
        prio_before = np.asarray(per.state.priority).copy()
        M, K = 2, 2
        fused = per.build_fused_step(seq_step, B, donate=False,
                                     steps_per_call=K, megabatch=M,
                                     megabatch_step=mega)
        keys = jax.random.split(jax.random.PRNGKey(1), K)
        new_state, rs, metrics = fused(state, per.state, keys,
                                       jnp.float32(0.5))
        assert float(metrics[SKIPPED_KEY]) == K
        _assert_tree_close(new_state.params, state.params,
                           rtol=0.0, atol=0.0)
        np.testing.assert_array_equal(np.asarray(rs.priority),
                                      prio_before)


    @pytest.mark.parametrize("M", [1, 2])
    def test_uniform_ddpg_dispatch_on_a_mesh_matches_one_device(self, M):
        """A row-sharded uniform ring on the virtual CPU mesh: the fused
        dispatch (sequential at M=1; at M=2 the group step on each chip's
        share of every minibatch, device_replay.group_step_on) draws the
        one-device program's rows and lands on its parameters."""
        from pytorch_distributed_tpu.memory.device_replay import (
            DeviceReplay, build_uniform_fused_step,
        )
        from pytorch_distributed_tpu.parallel.mesh import (
            make_mesh, replicated,
        )

        model = DdpgMlpModel(action_dim=1, norm_val=1.0)
        params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, OBS)))
        # plain SGD: linear in the gradient, so another summation order
        # stays a rounding away (Adam's first step is lr * sign(g))
        atx, ctx_ = optax.sgd(1e-2), optax.sgd(1e-2)
        state = init_ddpg_train_state(params, atx, ctx_)
        actor = lambda p, o: model.apply(p, o, method=model.forward_actor)
        critic = lambda p, o, a: model.apply(p, o, a,
                                             method=model.forward_critic)
        seq = build_ddpg_train_step(actor, critic, atx, ctx_)
        mega = build_ddpg_megabatch_step(actor, critic, atx, ctx_)
        rng = np.random.default_rng(0)
        n, K = 128, 4
        chunk = Transition(
            state0=rng.normal(size=(n, OBS)).astype(np.float32),
            action=rng.uniform(-1, 1, (n, 1)).astype(np.float32),
            reward=rng.normal(size=n).astype(np.float32),
            gamma_n=np.full(n, 0.95, np.float32),
            state1=rng.normal(size=(n, OBS)).astype(np.float32),
            terminal1=(rng.random(n) < 0.2).astype(np.float32))
        keys = jax.random.split(jax.random.PRNGKey(7), K)
        out = []
        for mesh in (None, make_mesh(dp_size=4, devices=jax.devices()[:4])):
            ring = DeviceReplay(n, (OBS,), action_shape=(1,),
                                state_dtype=np.float32,
                                action_dtype=np.float32, mesh=mesh)
            ring.feed_chunk(chunk)
            fused = build_uniform_fused_step(
                seq, B, steps_per_call=K, donate=False, megabatch=M,
                megabatch_step=mega if M > 1 else None)
            ts = (state if mesh is None
                  else jax.device_put(state, replicated(mesh)))
            new, metrics = jax.block_until_ready(fused(ts, ring.state, keys))
            out.append((jax.device_get(new.params),
                        float(metrics["learner/critic_loss"]),
                        jax.device_get(ring.sample(B, keys[0]))))
        (p0, loss0, rows0), (p1, loss1, rows1) = out
        for x, y in zip(rows0, rows1):
            np.testing.assert_array_equal(x, y)
        np.testing.assert_allclose(loss0, loss1, rtol=1e-5)
        _assert_tree_close(p0, p1, rtol=1e-5, atol=1e-6)


class TestMegabatchPerfDrills:
    """The drills every fused hot-path dispatch carries (test_perf.py
    style): the megabatched program must never recompile after warmup
    and must stage zero implicit host transfers."""

    def _fused(self, M=2, K=4):
        from pytorch_distributed_tpu.memory.device_replay import (
            DeviceReplay, build_uniform_fused_step,
        )

        model, tx, state, mega = _dqn_setup()
        seq_step = build_dqn_train_step(model.apply, tx,
                                        target_model_update=3)
        ring = DeviceReplay(128, (OBS,), state_dtype=np.float32)
        _fill_ring(ring)
        fused = build_uniform_fused_step(seq_step, B, steps_per_call=K,
                                         donate=False, megabatch=M,
                                         megabatch_step=mega)
        return fused, state, ring, K

    def test_no_retrace_after_warmup(self):
        from pytorch_distributed_tpu.utils import perf

        fused, state, ring, K = self._fused()
        det = perf.RetraceDetector()
        det.register("mega_fused", fused)
        key = jax.random.PRNGKey(0)
        for _ in range(3):
            key, sub = jax.random.split(key)
            state, _m = fused(state, ring.state,
                              jax.random.split(sub, K))
        det.check()  # warmup mark
        for _ in range(3):
            key, sub = jax.random.split(key)
            state, _m = fused(state, ring.state,
                              jax.random.split(sub, K))
        assert det.check() == []
        assert det.retraces == 0

    def test_transfer_audit_clean(self):
        from pytorch_distributed_tpu.utils import perf

        fused, state, ring, K = self._fused()
        state = jax.device_put(state)
        rs = jax.device_put(ring.state)
        keys = jax.device_put(
            jax.random.split(jax.random.PRNGKey(0), K))
        aud = perf.TransferAudit()
        state, _m = aud.run(fused, state, rs, keys)
        assert aud.total == 0 and aud.sites == {}


class TestResolveAndFactory:
    def test_resolve_megabatch_rounds_dispatch_up(self):
        from pytorch_distributed_tpu.config import build_options
        from pytorch_distributed_tpu.factory import resolve_megabatch

        opt = build_options(1, megabatch=8)
        assert resolve_megabatch(opt, 1) == (8, 8)
        assert resolve_megabatch(opt, 12) == (8, 16)
        assert resolve_megabatch(opt, 16) == (8, 16)
        opt1 = build_options(1)
        assert resolve_megabatch(opt1, 5) == (1, 5)

    def test_env_override_wins(self, monkeypatch):
        from pytorch_distributed_tpu.utils.perf import resolve_mxu

        monkeypatch.setenv("TPU_APEX_MXU_MEGABATCH", "16")
        lp = resolve_mxu(None)
        assert lp.megabatch == 16
        monkeypatch.setenv("TPU_APEX_MXU_PALLAS_TORSO", "1")
        assert resolve_mxu(None).pallas_torso is True

    def test_unsupported_family_returns_none(self):
        from pytorch_distributed_tpu.config import build_options
        from pytorch_distributed_tpu.factory import (
            build_megabatch_train_step, build_model, probe_env,
        )

        opt = build_options(13)  # r2d2 sequence family
        spec = probe_env(opt)
        model = build_model(opt, spec)
        assert build_megabatch_train_step(opt, model) is None
