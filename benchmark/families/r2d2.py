"""Family ``r2d2``: recurrent Q-learning on stored-state segments drawn
from the HBM segment ring (``memory/device_sequence.py``, frame-packed),
Nature conv torso -> LSTM -> linear head (``models/drqn.py``)."""

from __future__ import annotations

from ..harness import check, program
from ..harness.shapes import (dense_flops, lstm_step_flops,
                              nature_cnn_forward_flops)


def forward_flops(state_shape, num_actions: int, lstm: int) -> int:
    """One step of one segment: torso into an FC of the LSTM's width, one
    LSTM step, the head."""
    return (nature_cnn_forward_flops(state_shape, fc=lstm)
            + lstm_step_flops(lstm, lstm) + dense_flops(lstm, num_actions))


def update_flops(shapes: dict, state_shape, num_actions: int) -> int:
    """Per segment: the target net unrolls all T+1 steps; the online net
    unrolls the burn-in prefix without gradient and the T+1-burn_in train
    steps with one (forward + backward = 3 forwards).  Double-DQN action
    selection reuses the online unroll, so it adds nothing."""
    steps = shapes["seq_len"] + 1
    passes = steps + shapes["burn_in"] + 3 * (steps - shapes["burn_in"])
    return shapes["batch_size"] * passes * forward_flops(
        state_shape, num_actions, shapes["lstm_dim"])


def seed_chunk(key, n: int, lrn):
    """Frame-packed segments; one in ten ends early (masked tail with a
    terminal at its last valid step), as episodes do."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.memory.device_sequence import SegmentChunk

    replay, num_actions = lrn.replay, lrn.spec.num_actions
    T, burn_in = replay.T, lrn.opt.agent_params.burn_in
    k = jax.random.split(key, 7)
    full = jax.random.uniform(k[0], (n,)) < 0.9
    length = jnp.where(full, T, jax.random.randint(
        k[1], (n,), min(burn_in + 2, T), T + 1))
    t = jnp.arange(T)[None, :]
    mask = (t < length[:, None]).astype(jnp.float32)
    last = (t == (length[:, None] - 1)) & ~full[:, None]
    return SegmentChunk(
        obs=jax.random.bits(k[2], (n, *replay.obs_shape), jnp.uint8),
        action=jax.random.randint(k[3], (n, T), 0, num_actions, jnp.int32),
        reward=0.1 * jax.random.normal(k[4], (n, T), jnp.float32) * mask,
        terminal=last.astype(jnp.float32),
        mask=mask,
        c0=0.1 * jax.random.normal(k[5], (n, replay.lstm_dim), jnp.float32),
        h0=0.1 * jax.random.normal(k[6], (n, replay.lstm_dim), jnp.float32),
    )


def update_priorities(lrn):
    from pytorch_distributed_tpu.memory.device_sequence import (
        seq_update_priorities,
    )

    return seq_update_priorities


build_step = program.build_fused
agrees = check.fused_update_agrees
