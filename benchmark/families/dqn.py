"""Family ``dqn``: one-step/n-step Q-learning on single transitions drawn
from the prioritized HBM ring (``memory/device_per.py``), Nature-CNN
Q-network (``models/``: conv torso, FC 512, linear head)."""

from __future__ import annotations

from ..harness import check, program
from ..harness.shapes import NATURE_FC, dense_flops, nature_cnn_forward_flops


def forward_flops(state_shape, num_actions: int) -> int:
    return (nature_cnn_forward_flops(state_shape)
            + dense_flops(NATURE_FC, num_actions))


def update_flops(shapes: dict, state_shape, num_actions: int) -> int:
    """Per row: online forward on s0 (+ on s1 for double DQN), target
    forward on s1, online backward (2x the forward)."""
    passes = 1 + 1 + 2 + (1 if shapes.get("double", False) else 0)
    return shapes["batch_size"] * passes * forward_flops(state_shape,
                                                         num_actions)


def seed_chunk(key, n: int, lrn):
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.utils.experience import Transition

    spec, ap = lrn.spec, lrn.opt.agent_params
    k = jax.random.split(key, 5)
    shape = (n, *spec.state_shape)
    return Transition(
        state0=jax.random.bits(k[0], shape, jnp.uint8),
        action=jax.random.randint(k[1], (n,), 0, spec.num_actions,
                                  jnp.int32),
        reward=jax.random.normal(k[2], (n,), jnp.float32),
        gamma_n=jnp.full((n,), ap.gamma ** ap.nstep, jnp.float32),
        state1=jax.random.bits(k[3], shape, jnp.uint8),
        terminal1=(jax.random.uniform(k[4], (n,)) < 0.05
                   ).astype(jnp.float32),
    )


def update_priorities(lrn):
    from pytorch_distributed_tpu.memory.device_per import (
        per_update_priorities,
    )

    return per_update_priorities


build_step = program.build_fused
agrees = check.fused_update_agrees
