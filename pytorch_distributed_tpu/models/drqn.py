"""Recurrent Q-networks (R2D2 family).

No reference equivalent — the reference's only sequence notion is the
n-step window and 4-frame stack (SURVEY.md §5 "long-context: store
contiguous episode segments, not only single transitions"); this is the
model side of that extension: an LSTM core over the torso so the Q-function
conditions on history far beyond the frame stack (Kapturowski et al. 2019,
"Recurrent Experience Replay in Distributed RL").

Interface contract shared by both variants.  A recurrent Q-network is TWO
halves, and each model exposes both:

- ``embed(obs (N, *S)) -> x (N, F)`` — the per-observation half: cast,
  normalise, torso (convs or Dense) and its relu.  It carries no state
  from one step to the next, so nothing makes it wait for the recurrence:
  the train step (``ops/sequence_losses.build_drqn_train_step``) runs it
  ONCE, batched over every frame of an update, outside any time loop.
- ``core(x (B, F), carry) -> (q, carry')`` — the recurrent half: the LSTM
  cell and the Q head; ``carry`` is the flax LSTM ``(c, h)`` pair.  This
  alone is what ``ops/sequence_losses.unroll`` scans over time.
- ``apply(params, obs, carry)`` -> ``(q, carry')`` — one recurrent step on
  a batch of observations, and IS ``core`` applied to ``embed``: what
  acting (``agents/recurrent_actor.py``, the inference server) calls.
- ``apply(params, obs)`` (carry omitted) starts from the zero state, so
  the factory's ``init_params``/``example_obs`` probe works unchanged.
- ``zero_carry(batch)`` builds the start-of-episode state; the same zeros
  are what segment builders record at episode starts.
- ``halves(model)`` hands the two as pure ``(params, ...)`` functions.

The submodules are made in ``setup`` under the names the parameter tree
has always had (``Conv_0..2``, ``Dense_0``, ``OptimizedLSTMCell_0``,
``Dense_1``: what one ``@nn.compact`` call numbered them), so both halves
reach them and a tree saved before the split loads as it is.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import flax.linen as nn
import jax.numpy as jnp

Carry = Tuple[jnp.ndarray, jnp.ndarray]  # flax LSTM (c, h)


class _RecurrentQ(nn.Module):
    """What the variants share: the recurrent half (LSTM cell + Q head,
    made by ``setup`` from the variant's ``lstm_dim`` / ``action_space``)
    and ``__call__`` = ``core`` applied to the variant's ``embed``."""

    def setup(self):
        self.OptimizedLSTMCell_0 = nn.OptimizedLSTMCell(self.lstm_dim)
        self.Dense_1 = nn.Dense(self.action_space)

    def zero_carry(self, batch: int) -> Carry:
        z = jnp.zeros((batch, self.lstm_dim), dtype=jnp.float32)
        return (z, z)

    def core(self, x: jnp.ndarray, carry: Carry
             ) -> Tuple[jnp.ndarray, Carry]:
        carry, x = self.OptimizedLSTMCell_0(carry, x)
        return self.Dense_1(x), carry

    def __call__(self, obs: jnp.ndarray, carry: Optional[Carry] = None
                 ) -> Tuple[jnp.ndarray, Carry]:
        x = self.embed(obs)
        if carry is None:
            carry = self.zero_carry(x.shape[0])
        return self.core(x, carry)


class DrqnMlpModel(_RecurrentQ):
    """MLP torso -> LSTM -> Q head, the low-dim recurrent counterpart of
    DqnMlpModel (reference core/models/dqn_mlp_model.py's 3x256 ReLU MLP,
    with the middle layer replaced by the recurrent core)."""

    action_space: int
    hidden_dim: int = 256
    lstm_dim: int = 256
    norm_val: float = 1.0

    def setup(self):
        self.Dense_0 = nn.Dense(self.hidden_dim)
        super().setup()

    def embed(self, obs: jnp.ndarray) -> jnp.ndarray:
        x = obs.astype(jnp.float32) / self.norm_val
        x = x.reshape(x.shape[0], -1)
        return nn.relu(self.Dense_0(x))


class DrqnCnnModel(_RecurrentQ):
    """Nature-CNN torso -> LSTM -> Q head: the R2D2 pixel architecture
    (Nature-DQN convs as in reference core/models/dqn_cnn_model.py:16-30,
    with the first FC layer's output feeding the LSTM)."""

    action_space: int
    lstm_dim: int = 512
    norm_val: float = 255.0
    compute_dtype: jnp.dtype = jnp.bfloat16

    def setup(self):
        conv = lambda f, k, s: nn.Conv(
            f, (k, k), strides=(s, s), padding="VALID",
            dtype=self.compute_dtype)
        self.Conv_0 = conv(32, 8, 4)
        self.Conv_1 = conv(64, 4, 2)
        self.Conv_2 = conv(64, 3, 1)
        self.Dense_0 = nn.Dense(self.lstm_dim, dtype=self.compute_dtype)
        super().setup()

    def embed(self, obs: jnp.ndarray) -> jnp.ndarray:
        # NCHW uint8 frames -> NHWC for XLA's TPU conv layouts
        x = obs.astype(self.compute_dtype) / jnp.asarray(
            self.norm_val, self.compute_dtype)
        x = jnp.transpose(x, (0, 2, 3, 1))
        x = nn.relu(self.Conv_0(x))
        x = nn.relu(self.Conv_1(x))
        x = nn.relu(self.Conv_2(x))
        x = x.reshape(x.shape[0], -1)
        x = nn.relu(self.Dense_0(x))
        return x.astype(jnp.float32)  # LSTM state/gates stay fp32


def halves(model: nn.Module) -> Tuple[Callable, Callable]:
    """A recurrent model's two halves as pure functions:
    ``embed_fn(params, obs (N, *S)) -> x (N, F)`` and
    ``core_fn(params, x (B, F), carry) -> (q, carry')``."""
    return (lambda params, obs: model.apply(params, obs,
                                            method=model.embed),
            lambda params, x, carry: model.apply(params, x, carry,
                                                 method=model.core))
