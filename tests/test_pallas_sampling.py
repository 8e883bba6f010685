"""Pallas hierarchical PER sampler: interpret-mode equivalence against the
flat XLA scheme, distribution correctness, and the device_per plug-in hook.
On CPU the kernel runs in interpret mode; the real-TPU path compiles the
same kernel (checked on the chip by tools/kernel_check.py)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pytorch_distributed_tpu.ops.pallas_sampling import (
    flat_sample, hierarchical_sample,
)


def _priorities(n: int, zero_frac: float = 0.3, seed: int = 0):
    rng = np.random.default_rng(seed)
    return np.where(rng.random(n) < zero_frac, 0.0,
                    rng.random(n)).astype(np.float32)


class TestHierarchicalSample:
    @pytest.mark.parametrize("n", [1000, 4096, 131072])
    def test_matches_flat_scheme_exactly(self, n):
        prio = jnp.asarray(_priorities(n))
        key = jax.random.PRNGKey(7)
        idx_h, p_h = hierarchical_sample(prio, key, 64, interpret=True)
        idx_f, p_f = flat_sample(prio, key, 64)
        np.testing.assert_array_equal(np.asarray(idx_h), np.asarray(idx_f))
        np.testing.assert_allclose(np.asarray(p_h), np.asarray(p_f),
                                   rtol=1e-6)

    def test_never_draws_empty_rows(self):
        # half-filled ring: tail rows hold priority 0
        prio = np.zeros(8192, np.float32)
        prio[:3000] = _priorities(3000, zero_frac=0.0)
        idx, _ = hierarchical_sample(jnp.asarray(prio),
                                     jax.random.PRNGKey(3), 256,
                                     interpret=True)
        assert (np.asarray(idx) < 3000).all()

    def test_distribution_proportional_to_priority(self):
        # coarse chi-square-ish check on a small support
        prio = np.zeros(2048, np.float32)
        hot = [5, 100, 1024, 2000]
        weights = [1.0, 2.0, 4.0, 8.0]
        for i, w in zip(hot, weights):
            prio[i] = w
        counts = np.zeros(2048)
        for s in range(40):
            idx, _ = hierarchical_sample(
                jnp.asarray(prio), jax.random.PRNGKey(s), 128,
                interpret=True)
            np.add.at(counts, np.asarray(idx), 1)
        frac = counts[hot] / counts.sum()
        expect = np.asarray(weights) / np.sum(weights)
        np.testing.assert_allclose(frac, expect, atol=0.03)

    def test_single_block_edge(self):
        # N smaller than one superblock exercises the padding path
        prio = jnp.asarray(_priorities(100, zero_frac=0.0))
        idx, _ = hierarchical_sample(prio, jax.random.PRNGKey(1), 32,
                                     interpret=True)
        assert (np.asarray(idx) < 100).all()


class TestDevicePerHook:
    def test_per_sample_accepts_custom_draw(self):
        from pytorch_distributed_tpu.memory.device_per import (
            DevicePerReplay, per_sample,
        )
        from pytorch_distributed_tpu.utils.experience import Transition

        replay = DevicePerReplay(capacity=512, state_shape=(4,),
                                 state_dtype=np.float32)
        n = 64
        rng = np.random.default_rng(0)
        replay.feed_chunk(Transition(
            state0=rng.normal(size=(n, 4)).astype(np.float32),
            action=np.arange(n, dtype=np.int32),
            reward=np.ones(n, np.float32),
            gamma_n=np.full(n, 0.99, np.float32),
            state1=rng.normal(size=(n, 4)).astype(np.float32),
            terminal1=np.zeros(n, np.float32)))

        def draw(p, key, batch_size):
            return hierarchical_sample(p, key, batch_size, interpret=True)

        batch = jax.jit(
            lambda st, k: per_sample(st, k, 32, jnp.float32(0.4),
                                     sample_fn=draw)
        )(replay.state, jax.random.PRNGKey(0))
        idx = np.asarray(batch.index)
        assert (idx < n).all()  # only fed rows are drawable
        assert np.isfinite(np.asarray(batch.weight)).all()
        assert (np.asarray(batch.weight) <= 1.0 + 1e-6).all()
