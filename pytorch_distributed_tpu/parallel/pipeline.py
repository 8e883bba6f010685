"""Pipeline parallelism: the DTQN block stack staged over the mesh ``pp``
axis with a GPipe microbatch schedule.

No reference equivalent (SURVEY.md §2 "parallelism strategies" lists
pipeline parallelism as NOT present in the single-GPU reference) — this
is the capability that makes the mesh's ``pp`` axis real for the
stacked-block DTQN (models/dtqn_pipeline.py).

Design — the SPMD pipeline pattern, expressed as one ``shard_map``:

- the model's stacked block params (leading ``depth`` axis) shard over
  ``pp``; each of the S stages holds ``depth / S`` contiguous blocks and
  runs them as a local ``lax.scan`` (same ``block_forward`` math as the
  single-device path);
- the dp-sharded batch splits into M microbatches; a ``lax.scan`` over
  ``M + S - 1`` ticks drives the classic GPipe schedule: stage 0 injects
  microbatch t, every stage applies its blocks, activations hop to the
  next stage via one ``jax.lax.ppermute`` over ICI, and the last stage
  banks its finished microbatch.  Warm-up/drain bubbles execute garbage
  that the injection/banking masks ignore — the standard (S-1)/M
  overhead;
- the banked output lives on the last stage only, so one masked ``psum``
  over pp replicates it (cheap: done once, after the loop);
- the whole thing is differentiable (scan + ppermute + psum all have
  transposes), so ``jax.grad`` through the pipelined apply yields the
  backward pipeline automatically — with stage grads landing exactly on
  the ``pp`` shard that owns the stage's params.

Embedding and the Q head run OUTSIDE the shard_map (replicated compute;
they are a few percent of the FLOPs — cheaper than two more stages).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pytorch_distributed_tpu.models.dtqn_pipeline import block_forward


def pipeline_blocks(stacked: Any, x: jnp.ndarray, *, mesh: Mesh,
                    heads: int, num_microbatches: int) -> jnp.ndarray:
    """Run the stacked blocks over ``x`` (B, T, D) with the layer axis
    sharded over ``pp`` and the batch over ``dp``."""
    S = mesh.shape["pp"]
    M = num_microbatches
    perm = [(i, (i + 1) % S) for i in range(S)]

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(jax.tree_util.tree_map(lambda _: P("pp"), stacked),
                       P("dp")),
             out_specs=P("dp"), check_vma=False)
    def run(local_stack, x_loc):
        idx = jax.lax.axis_index("pp")
        Bl, T, D = x_loc.shape
        assert Bl % M == 0, (
            f"per-dp-shard batch {Bl} must divide into {M} microbatches")
        mb = Bl // M
        micro = x_loc.reshape(M, mb, T, D)

        def stage(h):
            def body(hh, layer):
                return block_forward(layer, hh, heads=heads), None

            out, _ = jax.lax.scan(body, h, local_stack)
            return out

        def tick(carry, t):
            act, banked = carry
            inj = jax.lax.dynamic_index_in_dim(
                micro, jnp.clip(t, 0, M - 1), 0, keepdims=False)
            y = stage(jnp.where(idx == 0, inj, act))
            ot = t - (S - 1)
            write = jnp.logical_and(ot >= 0, ot < M)
            upd = jax.lax.dynamic_update_index_in_dim(
                banked, y, jnp.clip(ot, 0, M - 1), 0)
            banked = jnp.where(write, upd, banked)
            act = jax.lax.ppermute(y, "pp", perm)
            return (act, banked), None

        zeros = jnp.zeros((mb, T, D), x_loc.dtype)
        banked0 = jnp.zeros((M, mb, T, D), x_loc.dtype)
        (_, banked), _ = jax.lax.scan(tick, (zeros, banked0),
                                      jnp.arange(M + S - 1))
        # only the last stage banked real outputs; replicate over pp
        banked = jax.lax.psum(
            jnp.where(idx == S - 1, banked, jnp.zeros_like(banked)), "pp")
        return banked.reshape(Bl, T, D)

    return run(stacked, x)


def pipelined_window_apply(model, mesh: Mesh,
                           num_microbatches: int) -> Callable:
    """The learner-side ``window_apply`` for a DtqnPipelineModel on a
    mesh with pp > 1: embed (replicated) -> pipelined block stack ->
    head (replicated).  Same (params, obs_seq) -> (B, T, A) contract as
    ``model.window_q``."""
    S = mesh.shape["pp"]
    assert model.depth % S == 0, (
        f"depth {model.depth} must divide over pp={S} stages")

    def apply(params, obs_seq):
        x = model.apply(params, obs_seq, method=model.embed)
        y = pipeline_blocks(params["params"]["blocks"], x, mesh=mesh,
                            heads=model.heads,
                            num_microbatches=num_microbatches)
        return model.apply(params, y, method=model.head)

    return apply


def pipeline_state_shardings(state: Any, mesh: Mesh) -> Any:
    """A NamedSharding pytree for a DtqnPipelineModel TrainState: every
    leaf under a ``blocks`` subtree shards its leading (layer) axis over
    ``pp``; everything else replicates.  Params, target params and Adam
    moments share paths, so one rule shards all three."""

    from pytorch_distributed_tpu.parallel.tensor_parallel import (
        _path_strings,
    )

    def spec(path, leaf):
        if "blocks" in _path_strings(path) and getattr(leaf, "ndim", 0) >= 1:
            return P("pp", *([None] * (leaf.ndim - 1)))
        return P()

    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, spec(path, leaf)), state)
