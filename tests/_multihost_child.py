"""Child process for tests/test_multihost.py: joins a 2-process
jax.distributed CPU cluster via parallel/mesh.init_multihost, builds the
global mesh, and runs one cross-process reduction.

Run: python _multihost_child.py <coordinator> <num_processes> <process_id>
Prints MULTIHOST_OK <total> on success.  Must configure platform before
first jax use (the calling shell may export another platform list)."""

import os
import re
import sys


def main() -> None:
    coordinator, num_processes, process_id = (
        sys.argv[1], int(sys.argv[2]), int(sys.argv[3]))

    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   os.environ.get("XLA_FLAGS", ""))
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=2")
    import jax

    jax.config.update("jax_platforms", "cpu")

    from pytorch_distributed_tpu.parallel.mesh import init_multihost, make_mesh

    init_multihost(coordinator_address=coordinator,
                   num_processes=num_processes, process_id=process_id)

    assert jax.process_index() == process_id
    assert len(jax.local_devices()) == 2
    assert jax.device_count() == 2 * num_processes, jax.device_count()

    # the same mesh code a pod uses, now spanning both processes' devices
    mesh = make_mesh(dp_size=2 * num_processes)

    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import multihost_utils
    from jax.sharding import NamedSharding, PartitionSpec as P

    # each process contributes rows valued (process_id + 1); the jitted
    # sum over the dp-sharded global array forces a cross-process
    # all-reduce through the distributed runtime
    local = np.full((2, 3), float(process_id + 1), np.float32)
    arr = multihost_utils.host_local_array_to_global_array(
        local, mesh, P("dp"))
    total = jax.jit(jnp.sum,
                    out_shardings=NamedSharding(mesh, P()))(arr)
    expected = 3.0 * 2 * sum(range(1, num_processes + 1))
    np.testing.assert_allclose(float(total), expected)

    # the learner-spans-hosts leg: the production DQN train step jitted
    # over the global mesh — params replicated on every host, the batch
    # dp-sharded across hosts, XLA closing the gradients with a
    # cross-process all-reduce
    from pytorch_distributed_tpu.models import DqnMlpModel
    from pytorch_distributed_tpu.ops.losses import (
        build_dqn_train_step, init_train_state, make_optimizer,
    )
    from pytorch_distributed_tpu.utils.experience import Batch

    model = DqnMlpModel(action_space=3, hidden_dim=32)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 6)))
    state = init_train_state(params, make_optimizer(lr=1e-3))
    step = build_dqn_train_step(model.apply, make_optimizer(lr=1e-3),
                                enable_double=True, target_model_update=10)

    def replicate(x):
        return multihost_utils.host_local_array_to_global_array(
            np.asarray(x), mesh, P())

    def shard_rows(x):
        return multihost_utils.host_local_array_to_global_array(
            np.asarray(x), mesh, P("dp"))

    gstate = jax.tree_util.tree_map(replicate, state)
    rng = np.random.default_rng(7)  # same on every process; rows split
    B_local = 4
    lo = process_id * B_local
    full = rng.normal(size=(num_processes * B_local, 6)).astype(np.float32)
    acts = rng.integers(0, 3, size=num_processes * B_local).astype(np.int32)
    rew = rng.normal(size=num_processes * B_local).astype(np.float32)
    batch = Batch(
        state0=shard_rows(full[lo:lo + B_local]),
        action=shard_rows(acts[lo:lo + B_local]),
        reward=shard_rows(rew[lo:lo + B_local]),
        gamma_n=shard_rows(np.full(B_local, 0.95, np.float32)),
        state1=shard_rows(full[lo:lo + B_local] + 0.1),
        terminal1=shard_rows(np.zeros(B_local, np.float32)),
        weight=shard_rows(np.ones(B_local, np.float32)),
        index=shard_rows(np.arange(lo, lo + B_local, dtype=np.int32)),
    )
    fn = jax.jit(step)
    for _ in range(2):
        gstate, metrics, _td = fn(gstate, batch)
    jax.block_until_ready(gstate.params)
    assert int(jax.device_get(gstate.step)) == 2
    loss = float(jax.device_get(metrics["learner/critic_loss"]))
    assert np.isfinite(loss)
    # every process must see the identical post-all-reduce loss
    losses = multihost_utils.process_allgather(np.float32(loss))
    np.testing.assert_allclose(losses, losses[0])

    multihost_utils.sync_global_devices("test_done")
    print(f"MULTIHOST_OK {float(total)} loss={loss:.6f}", flush=True)


if __name__ == "__main__":
    main()
