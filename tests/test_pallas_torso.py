"""ISSUE-13 Pallas fused dqn-cnn torso: interpret-mode parity against
the XLA reference (forward AND gradients, bf16 and fp32), the matmul
kernel's tiling/VJP contract, the factory's loud-downgrade gate, and
the MXU-filling wide torso family's lane alignment.  On CPU the kernels
run under the Pallas interpreter; a real TPU compiles the same kernels
(ops/pallas_torso.py docstring)."""

import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

from pytorch_distributed_tpu.models import DqnCnnModel, DqnCnnWideModel
from pytorch_distributed_tpu.ops.pallas_torso import (
    build_pallas_torso_apply, make_mxu_matmul,
)


@pytest.fixture(scope="module")
def cnn_setup():
    model = DqnCnnModel(action_space=6, norm_val=255.0,
                        compute_dtype=jnp.float32)
    obs = np.random.default_rng(0).integers(
        0, 255, (2, 4, 84, 84)).astype(np.uint8)
    params = model.init(jax.random.PRNGKey(0), obs)
    return model, params, obs


class TestMxuMatmul:
    def test_matches_jnp_dot_on_unaligned_shapes(self):
        # 100x70 @ 70x33: none of M/K/N on the 128 grid — the padding
        # path must be invisible in the result
        mm = make_mxu_matmul(interpret=True)
        rng = np.random.default_rng(1)
        x = rng.normal(size=(100, 70)).astype(np.float32)
        w = rng.normal(size=(70, 33)).astype(np.float32)
        np.testing.assert_allclose(np.asarray(mm(x, w)), x @ w,
                                   rtol=1e-5, atol=1e-5)

    def test_custom_vjp_matches_jnp_grads(self):
        mm = make_mxu_matmul(interpret=True)
        rng = np.random.default_rng(2)
        x = rng.normal(size=(16, 40)).astype(np.float32)
        w = rng.normal(size=(40, 24)).astype(np.float32)
        f_pal = lambda x, w: jnp.sum(mm(x, w) ** 2)
        f_ref = lambda x, w: jnp.sum((x @ w) ** 2)
        gx_p, gw_p = jax.grad(f_pal, argnums=(0, 1))(x, w)
        gx_r, gw_r = jax.grad(f_ref, argnums=(0, 1))(x, w)
        np.testing.assert_allclose(np.asarray(gx_p), np.asarray(gx_r),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(gw_p), np.asarray(gw_r),
                                   rtol=1e-4, atol=1e-4)


class TestTorsoParity:
    def test_forward_parity_fp32(self, cnn_setup):
        model, params, obs = cnn_setup
        ap = build_pallas_torso_apply(norm_val=255.0,
                                      compute_dtype=jnp.float32,
                                      interpret=True)
        np.testing.assert_allclose(np.asarray(ap(params, obs)),
                                   np.asarray(model.apply(params, obs)),
                                   rtol=1e-4, atol=1e-4)

    def test_grad_parity_fp32(self, cnn_setup):
        model, params, obs = cnn_setup
        ap = build_pallas_torso_apply(norm_val=255.0,
                                      compute_dtype=jnp.float32,
                                      interpret=True)
        # a loss shaped like the DQN TD loss (sum of squared Q): grads
        # flow through every conv + dense kernel and bias
        g_ref = jax.grad(lambda p: jnp.sum(model.apply(p, obs) ** 2))(
            params)
        g_pal = jax.grad(lambda p: jnp.sum(ap(p, obs) ** 2))(params)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-3),
            g_ref, g_pal)

    def test_forward_parity_bf16(self):
        model = DqnCnnModel(action_space=6, norm_val=255.0,
                            compute_dtype=jnp.bfloat16)
        obs = np.random.default_rng(3).integers(
            0, 255, (2, 4, 84, 84)).astype(np.uint8)
        params = model.init(jax.random.PRNGKey(0), obs)
        ap = build_pallas_torso_apply(norm_val=255.0,
                                      compute_dtype=jnp.bfloat16,
                                      interpret=True)
        q_ref = np.asarray(model.apply(params, obs))
        q_pal = np.asarray(ap(params, obs))
        # bf16 rounding between layers differs (the kernel accumulates
        # fp32 and rounds once per GEMM; XLA's conv may round more
        # often) — parity is at bf16 resolution, not fp32
        np.testing.assert_allclose(q_pal, q_ref, rtol=0.05, atol=0.05)

    def test_grad_parity_bf16(self):
        model = DqnCnnModel(action_space=6, norm_val=255.0,
                            compute_dtype=jnp.bfloat16)
        obs = np.random.default_rng(4).integers(
            0, 255, (2, 4, 84, 84)).astype(np.uint8)
        params = model.init(jax.random.PRNGKey(0), obs)
        ap = build_pallas_torso_apply(norm_val=255.0,
                                      compute_dtype=jnp.bfloat16,
                                      interpret=True)
        g_ref = jax.grad(lambda p: jnp.mean(model.apply(p, obs) ** 2))(
            params)
        g_pal = jax.grad(lambda p: jnp.mean(ap(p, obs) ** 2))(params)
        flat_r = ravel_pytree(g_ref)[0]
        flat_p = ravel_pytree(g_pal)[0]
        # cosine agreement: bf16 per-element tolerances are vacuous on
        # near-zero grads; direction agreement across the whole tree is
        # the trainability contract
        cos = float(jnp.dot(flat_r, flat_p)
                    / (jnp.linalg.norm(flat_r) * jnp.linalg.norm(flat_p)))
        assert cos > 0.999, cos

    def test_forward_parity_non_square_frames(self):
        """H != W observations: _patches must derive the output width
        from the input WIDTH (a review-caught bug had it slicing both
        spatial axes off the height)."""
        model = DqnCnnModel(action_space=5, norm_val=255.0,
                            compute_dtype=jnp.float32)
        obs = np.random.default_rng(5).integers(
            0, 255, (2, 4, 84, 108)).astype(np.uint8)
        params = model.init(jax.random.PRNGKey(2), obs)
        ap = build_pallas_torso_apply(norm_val=255.0,
                                      compute_dtype=jnp.float32,
                                      interpret=True)
        np.testing.assert_allclose(np.asarray(ap(params, obs)),
                                   np.asarray(model.apply(params, obs)),
                                   rtol=1e-4, atol=1e-4)


class TestFactoryGate:
    def _opt(self, **over):
        from pytorch_distributed_tpu.config import build_options

        return build_options(4, **over)  # pong-sim dqn-cnn row

    def test_off_by_default_keeps_model_apply(self):
        from pytorch_distributed_tpu.factory import _dqn_train_apply

        opt = self._opt()
        model = DqnCnnModel(action_space=6)
        assert _dqn_train_apply(opt, model) == model.apply

    def test_cpu_without_interpret_downgrades_loudly(self):
        from pytorch_distributed_tpu.factory import _dqn_train_apply

        opt = self._opt(pallas_torso=True)
        model = DqnCnnModel(action_space=6)
        with pytest.warns(UserWarning, match="no TPU backend"):
            apply_fn = _dqn_train_apply(opt, model)
        assert apply_fn == model.apply

    def test_interpret_knob_swaps_the_torso(self):
        from pytorch_distributed_tpu.factory import _dqn_train_apply

        opt = self._opt(pallas_torso=True, pallas_interpret=True)
        model = DqnCnnModel(action_space=6,
                            compute_dtype=jnp.float32)
        apply_fn = _dqn_train_apply(opt, model)
        assert apply_fn is not model.apply
        obs = np.zeros((1, 4, 84, 84), np.uint8)
        params = model.init(jax.random.PRNGKey(0), obs)
        q = apply_fn(params, obs)
        assert q.shape == (1, 6) and q.dtype == jnp.float32

    def test_wrong_model_type_warns_and_keeps_xla(self):
        from pytorch_distributed_tpu.factory import _dqn_train_apply
        from pytorch_distributed_tpu.models import DqnMlpModel

        opt = self._opt(pallas_torso=True)
        opt.model_type = "dqn-mlp"
        model = DqnMlpModel(action_space=3)
        with pytest.warns(UserWarning, match="dqn-cnn torso only"):
            assert _dqn_train_apply(opt, model) == model.apply


class TestWideTorso:
    def test_lane_alignment_and_shapes(self):
        model = DqnCnnWideModel(action_space=6,
                                compute_dtype=jnp.float32)
        obs = np.random.default_rng(0).integers(
            0, 255, (2, 4, 20, 20)).astype(np.uint8)
        params = model.init(jax.random.PRNGKey(0), obs)
        q = model.apply(params, obs)
        assert q.shape == (2, 6) and q.dtype == jnp.float32
        # the family's reason to exist: every conv output-channel width
        # is a multiple of the 128 MXU lanes
        def widths(tree, prefix=""):
            for k, v in tree.items():
                if k == "kernel" and v.ndim == 4:
                    yield v.shape[-1]
                elif isinstance(v, dict):
                    yield from widths(v, prefix + k + "/")
        for w in widths(params["params"]):
            assert w % 128 == 0, w

    def test_trains_through_dqn_step(self):
        from pytorch_distributed_tpu.ops.losses import (
            build_dqn_train_step, init_train_state, make_optimizer,
        )
        from pytorch_distributed_tpu.utils.experience import Batch

        model = DqnCnnWideModel(action_space=4,
                                compute_dtype=jnp.float32)
        rng = np.random.default_rng(1)
        obs = lambda n: rng.integers(0, 255, (n, 4, 20, 20)).astype(
            np.uint8)
        params = model.init(jax.random.PRNGKey(0), obs(1))
        tx = make_optimizer(1e-3)
        state = init_train_state(params, tx)
        step = jax.jit(build_dqn_train_step(model.apply, tx))
        B = 4
        batch = Batch(state0=obs(B),
                      action=rng.integers(0, 4, B).astype(np.int32),
                      reward=rng.normal(size=B).astype(np.float32),
                      gamma_n=np.full(B, 0.95, np.float32),
                      state1=obs(B),
                      terminal1=np.zeros(B, np.float32),
                      weight=np.ones(B, np.float32),
                      index=np.arange(B, dtype=np.int32))
        new_state, metrics, td = step(state, batch)
        assert int(new_state.step) == 1
        assert np.isfinite(float(metrics["learner/critic_loss"]))

    def test_factory_registration(self):
        from pytorch_distributed_tpu.config import CONFIGS, build_options
        from pytorch_distributed_tpu.factory import build_model

        row = CONFIGS[19]
        assert row[4] == "dqn-cnn-wide"
        opt = build_options(19)
        # probe-free spec: the pong-sim CNN geometry is static
        from pytorch_distributed_tpu.factory import EnvSpec

        spec = EnvSpec(state_shape=(4, 84, 84), discrete=True,
                       num_actions=6, action_dim=0, norm_val=255.0)
        model = build_model(opt, spec)
        assert isinstance(model, DqnCnnWideModel)
        assert model.width == opt.model_params.cnn_wide_width == 128
