"""MXU-filling IMPALA-deep convolutional Q-network (ISSUE 13).

The Nature CNN (models/dqn_cnn.py) structurally underfills a 128-lane
MXU: its 4/32/64-wide conv channels leave most lanes idle regardless of
batch size or dtype (``mfu`` 13.7 % in ``apex_pong.learner_only``:
ledger, PR 27).  This family is the third front of the MFU campaign: an
IMPALA-style residual stack (Espeholt et al. 2018) whose channel widths
are MULTIPLES OF 128 — sections (width, 2*width, 2*width) with
``width`` defaulting to 128 (ModelParams.cnn_wide_width) — so every
conv GEMM's contraction and output lanes land on the MXU grid exactly.
~50x the Nature torso's FLOPs per forward, meant to be spent at high
utilization instead of idling lanes (the Podracer recipe); its rate and
utilization on the chip are not measured: no benchmark cell runs CONFIGS
row 19 (ROADMAP queue 3).

Same external contract as DqnCnnModel — (B, C, H, W) uint8 frame
stacks, /norm_val normalisation, compute-dtype forward with fp32
params, fp32 Q-values, ``example_input`` — so the factory, replay
geometry, eval plane and checkpoints plug in unchanged (CONFIGS row
19).  Sample-efficiency parity vs the Nature torso is an eval-plane
drive (TESTING.md), not an assumption: the family trains through the
SAME loss/target machinery, only the torso widens.
"""

from __future__ import annotations

from typing import Tuple

import flax.linen as nn
import jax.numpy as jnp
from flax.linen.initializers import orthogonal, zeros_init


class _ResBlock(nn.Module):
    channels: int
    compute_dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        kw = dict(kernel_init=orthogonal(jnp.sqrt(2.0)),
                  bias_init=zeros_init())
        y = nn.relu(x)
        y = nn.Conv(self.channels, (3, 3), padding="SAME",
                    dtype=self.compute_dtype, **kw)(y)
        y = nn.relu(y)
        y = nn.Conv(self.channels, (3, 3), padding="SAME",
                    dtype=self.compute_dtype, **kw)(y)
        return x + y


class DqnCnnWideModel(nn.Module):
    action_space: int
    norm_val: float = 255.0
    # base width; sections run (width, 2*width, 2*width).  Keep it a
    # multiple of 128 — that alignment IS this family's reason to exist.
    width: int = 128
    compute_dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        # x: (B, C, H, W) uint8/float -> NHWC compute in bf16 (the
        # DqnCnnModel input contract)
        x = x.astype(self.compute_dtype) / jnp.asarray(
            self.norm_val, dtype=self.compute_dtype)
        x = jnp.transpose(x, (0, 2, 3, 1))
        kw = dict(kernel_init=orthogonal(jnp.sqrt(2.0)),
                  bias_init=zeros_init())
        for channels in (self.width, 2 * self.width, 2 * self.width):
            x = nn.Conv(channels, (3, 3), padding="SAME",
                        dtype=self.compute_dtype, **kw)(x)
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
            x = _ResBlock(channels, self.compute_dtype)(x)
            x = _ResBlock(channels, self.compute_dtype)(x)
        x = nn.relu(x)
        x = x.reshape((x.shape[0], -1))
        x = nn.Dense(512, dtype=self.compute_dtype, **kw)(x)
        x = nn.relu(x)
        q = nn.Dense(self.action_space, dtype=self.compute_dtype,
                     kernel_init=orthogonal(1.0),
                     bias_init=zeros_init())(x)
        return q.astype(jnp.float32)

    @staticmethod
    def example_input(batch: int = 1,
                      state_shape: Tuple[int, ...] = (4, 84, 84)
                      ) -> jnp.ndarray:
        return jnp.zeros((batch, *state_shape), dtype=jnp.uint8)
