"""Nature-DQN convolutional Q-network as a Flax module.

Functional re-design of reference core/models/dqn_cnn_model.py:16-56 —
same architecture (conv 32x8x8/4, 64x4x4/2, 64x3x3/1, FC 512, linear head to
``action_space``) and the same /norm_val input normalisation
(reference :54-56), with two deliberate TPU-first changes:

- layout: inputs arrive as (B, C, H, W) frame stacks (the replay layout) and
  are transposed once to NHWC, the layout XLA tiles best onto the MXU;
- init: orthogonal initialisation is *applied* — the reference defines it
  but never calls it (reference dqn_cnn_model.py:33 commented out;
  SURVEY.md "known quirks").  Set ``ModelParams.orthogonal_init=False`` for
  reference-faithful default init.

The forward runs in ``compute_dtype`` (bfloat16 by default) with fp32
params, returning fp32 Q-values.
"""

from __future__ import annotations

from typing import Tuple

import flax.linen as nn
import jax.numpy as jnp
from flax.linen.initializers import orthogonal, zeros_init


class DqnCnnModel(nn.Module):
    action_space: int
    norm_val: float = 255.0
    orthogonal_init: bool = True
    compute_dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        # x: (B, C, H, W) uint8/float -> NHWC compute in bf16
        x = x.astype(self.compute_dtype) / jnp.asarray(
            self.norm_val, dtype=self.compute_dtype)
        x = jnp.transpose(x, (0, 2, 3, 1))
        kw = {}
        if self.orthogonal_init:
            # sqrt(2) gain for ReLU trunk, 1.0 for the linear head — the
            # gains the reference's dead init intended (dqn_cnn_model.py:39-52).
            kw = dict(kernel_init=orthogonal(jnp.sqrt(2.0)),
                      bias_init=zeros_init())
        x = nn.Conv(32, (8, 8), strides=(4, 4), padding="VALID",
                    dtype=self.compute_dtype, **kw)(x)
        x = nn.relu(x)
        x = nn.Conv(64, (4, 4), strides=(2, 2), padding="VALID",
                    dtype=self.compute_dtype, **kw)(x)
        x = nn.relu(x)
        x = nn.Conv(64, (3, 3), strides=(1, 1), padding="VALID",
                    dtype=self.compute_dtype, **kw)(x)
        x = nn.relu(x)
        x = x.reshape((x.shape[0], -1))
        x = nn.Dense(512, dtype=self.compute_dtype, **kw)(x)
        x = nn.relu(x)
        head_kw = dict(kernel_init=orthogonal(1.0), bias_init=zeros_init()) \
            if self.orthogonal_init else {}
        q = nn.Dense(self.action_space, dtype=self.compute_dtype, **head_kw)(x)
        return q.astype(jnp.float32)

    @staticmethod
    def example_input(batch: int = 1,
                      state_shape: Tuple[int, ...] = (4, 84, 84)) -> jnp.ndarray:
        return jnp.zeros((batch, *state_shape), dtype=jnp.uint8)
