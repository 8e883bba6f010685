"""Sharded learner: the train step compiled over the device mesh.

This is the TPU-native answer to the reference's "multi-learner hook" —
where ``num_learners > 1`` in the reference would race unsynchronized Adam
steps on one shared CUDA model (reference main.py:83-94, SURVEY.md "known
quirks"), here scaling the learner means *one* jit-compiled update whose
batch is sharded across the mesh's dp axis; XLA partitions the forward/
backward per chip and inserts the gradient all-reduce over ICI.  Params,
optimizer state and the target net are replicated; donated so the whole
TrainState updates in place in HBM.

That sentence holds for BOTH steps.  The unfused step gets its batch
sharding here (``in_shardings`` below, the host batch placed by
``shard_batch``).  The fused step (memory/device_per.py
``build_fused_step``, device_replay.py ``build_uniform_fused_step``: what
every TPU default runs) is a plain ``jax.jit`` whose batch is born inside
the program; it gets its sharding where it leaves the row-sharded HBM
ring, in ``device_replay.gather_rows`` (each chip receives its ``B / dp``
rows of the global draw), and the compiler partitions the train step
behind it the same way.  With no sharding stated there every chip would
train the whole batch (PERF.md, PR 30).  Where the partitioner puts the
gradient all-reduce is its choice: under bf16 compute it sums the chips'
partial gradients as bf16 leaves, before their cast to the float32 of
the parameters (seen in the compiled dp4 program; PERF.md section 6,
PR 30, has what that costs in agreement with a float32 reference).

Usage:
    learner = ShardedLearner(step_fn, mesh)          # step_fn from ops.losses
    state = learner.place(state)                     # replicate onto mesh
    state, metrics, td = learner.step(state, batch)  # batch: host np arrays

Health contract: the ``step_fn``s the factory hands over are wrapped by
the in-jit finite guard (utils/health.finite_guard, on by default) — a
non-finite step returns the INPUT state selected through unchanged,
``metrics["learner/skipped"]`` = 1 and a zeroed ``td``.  The guard is a
per-leaf in-graph select, so it composes transparently with everything
here: donation (the select resolves before outputs), dp-sharded batches,
tensor/expert/pipeline state shardings, and the ICI all-reduce.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax

from pytorch_distributed_tpu.parallel.mesh import batch_sharding, replicated
from pytorch_distributed_tpu.utils.experience import Batch


class ShardedLearner:
    def __init__(self, step_fn: Callable, mesh: Optional[jax.sharding.Mesh],
                 donate: bool = True, state_shardings=None):
        """``state_shardings``: optional NamedSharding pytree matching the
        TrainState — e.g. parallel/tensor_parallel.dtqn_state_shardings for
        a Megatron-split FFN over mp.  Default replicates the state."""
        self.mesh = mesh
        self._serialize_collectives = (
            mesh is not None
            and mesh.devices.flat[0].platform == "cpu"
            and mesh.size > 1)
        if mesh is None:
            self._step = jax.jit(step_fn,
                                 donate_argnums=(0,) if donate else ())
            self._batch_sharding = None
        else:
            self._batch_sharding = batch_sharding(mesh)
            self._state_sharding = (replicated(mesh)
                                    if state_shardings is None
                                    else state_shardings)
            # dp-sharded batch + (replicated | tensor-sharded) state; XLA
            # lowers the gradient reduction to an ICI all-reduce (plus the
            # mp psums when FFN kernels are split) automatically.
            self._step = jax.jit(
                step_fn,
                in_shardings=(self._state_sharding, self._batch_sharding),
                out_shardings=(self._state_sharding, replicated(mesh),
                               self._batch_sharding),
                donate_argnums=(0,) if donate else (),
            )

    def place(self, state: Any) -> Any:
        """Move a host-initialised TrainState onto the mesh (replicated)."""
        if self.mesh is None:
            return jax.device_put(state)
        return jax.device_put(state, self._state_sharding)

    def shard_batch(self, batch: Batch) -> Batch:
        if self._batch_sharding is None:
            return jax.device_put(batch)
        dp = self.mesh.shape["dp"]
        bsz = batch.reward.shape[0]
        if bsz % dp != 0:
            raise ValueError(
                f"batch_size {bsz} must be divisible by the mesh dp axis "
                f"({dp}) for data-parallel sharding")
        return jax.device_put(batch, self._batch_sharding)

    def step(self, state, batch: Batch):
        out = self._step(state, self.shard_batch(batch))
        if self._serialize_collectives:
            # XLA's CPU collective thunks rendezvous on a shared thread
            # pool; several queued multi-device programs can starve each
            # other into the 40 s rendezvous abort.  Blocking per step only
            # on the CPU simulation keeps the 8-virtual-device test path
            # deterministic; TPU keeps full async dispatch.
            jax.block_until_ready(out[0])
        return out

    def host_params(self, state) -> Any:
        """Fetch the current online params to host memory for publication to
        actors (the explicit versioned-publication replacing the reference's
        implicit shared-CUDA visibility, SURVEY.md §7 "hard parts").

        Actor-side inference must run on published host copies — NOT on the
        mesh-sharded TrainState — both because actors live in other
        processes and because issuing dependent multi-device programs
        against in-flight collective state can deadlock the CPU backend's
        rendezvous (and serialises the TPU pipeline).
        """
        return jax.device_get(state.params)


class ReplicaExchange:
    """Cross-host twin of the in-host dp ``psum`` above (ISSUE 15): the
    glue between the jitted grad/apply split
    (ops/losses.build_dqn_grad_and_apply) and the DCN replica channel
    (parallel/dcn.py ReplicaRegistry / ReplicaClient).

    Two-tier reduction story: WITHIN a host, gradients all-reduce over
    ICI inside the jitted step — ``ShardedLearner`` stays the fast path
    and nothing here touches it.  ACROSS hosts, the replica driver
    (agents/learner.py) ravels the (already ICI-reduced) gradient
    pytree to one fp32 vector, submits it as a generation-stamped round
    through the gateway, and unravels the survivors' mean back.  The
    ravel template is captured from the first local gradient, so the
    exchange needs no a-priori knowledge of the param tree."""

    def __init__(self, channel):
        self.channel = channel
        self.rounds = 0
        self.degraded_rounds = 0
        self.last_members: list = []

    def exchange(self, round_idx: int, grads, ok: bool = True,
                 pidx=None, ptd=None) -> tuple:
        """One allreduce round: returns ``(reply, reduced_grads)`` —
        ``reduced_grads`` is None when the round applied nothing (all
        contributions non-finite: the skipped-step case).  Fenced/stale/
        timeout statuses are returned in ``reply`` for the driver to
        classify (rejoin vs exit); this layer only moves bytes."""
        import numpy as np
        from jax.flatten_util import ravel_pytree

        host_grads = jax.device_get(grads)
        flat, unravel = ravel_pytree(host_grads)
        reply = self.channel.submit_round(
            round_idx, np.asarray(flat, dtype=np.float32), ok=ok,
            pidx=pidx, ptd=ptd)
        from pytorch_distributed_tpu.parallel.dcn import RSTAT_OK

        if reply["status"] != RSTAT_OK:
            return reply, None
        self.rounds += 1
        members = list(reply.get("members", []))
        if self.last_members and len(members) < len(self.last_members):
            self.degraded_rounds += 1
        self.last_members = members
        if reply.get("applied", 0) <= 0 or reply.get("grad") is None:
            return reply, None
        return reply, unravel(np.asarray(reply["grad"],
                                         dtype=np.float32))
