"""Nature-DQN torso in plain float32 ``jax.numpy`` (Mnih et al. 2015,
Methods, "Model architecture"): conv 32x8x8/4, 64x4x4/2, 64x3x3/1, each
followed by ReLU, then a fully connected layer and ReLU.  Inputs are
(B, C, H, W) uint8 frame stacks divided by ``norm_val``.

Departure from the paper, shared with the program: none in the torso.  The
parameter tree is read by the names Flax gives the program's modules
(``Conv_0`` .. ``Conv_2``, ``Dense_0``); kernels are HWIO.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

STRIDES = (4, 2, 1)


def torso(p, obs, norm_val: float):
    x = obs.astype(jnp.float32) / jnp.float32(norm_val)
    x = jnp.transpose(x, (0, 2, 3, 1))                      # NHWC
    for i, stride in enumerate(STRIDES):
        layer = p[f"Conv_{i}"]
        x = jax.lax.conv_general_dilated(
            x, layer["kernel"].astype(jnp.float32), (stride, stride), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        x = jax.nn.relu(x + layer["bias"])
    x = x.reshape(x.shape[0], -1)
    return jax.nn.relu(x @ p["Dense_0"]["kernel"] + p["Dense_0"]["bias"])
