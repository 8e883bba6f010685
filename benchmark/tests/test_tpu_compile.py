"""The fused programs of the real configurations, compiled at real size for
a chip that is described and not attached (on-chip-measurement guide,
section 2): what the TPU compiler refuses here costs no chip time.  These
guard the ring capacities the configurations ship with: a program whose
argument + temp bytes outgrow one chip's HBM is refused here, as on the chip.
About 10 s each, so they live here and not in tier 1.

One file, topology described inside a fixture: only one process may load
libtpu, and only the worker that is given this file does.
"""

import json
import os

import numpy as np
import pytest

from benchmark.harness import manifest

HBM_BYTES = int(15.75 * 2 ** 30)        # what the compiler reports for a v5e
RESERVED = 258 * 2 ** 20


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever stops the description
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one."""
    import jax

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def compile_fused(topo, config: str, tmp_path):
    """The K=32 fused step of ``configs/<config>.json`` with its ring at
    the configured capacity, as shapes placed on the described chips."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)

    from benchmark.harness import program
    from pytorch_distributed_tpu import factory

    with open(os.path.join(manifest.BENCH_DIR, "configs",
                           f"{config}.json")) as f:
        cfg = json.load(f)
    chips, K = cfg["chips"], 32
    if chips == 1:
        rows = rep = SingleDeviceSharding(topo.devices[0])
    else:
        mesh = Mesh(np.array(topo.devices[:chips]).reshape(chips, 1, 1, 1, 1),
                    ("dp", "sp", "mp", "ep", "pp"))
        rows, rep = NamedSharding(mesh, P("dp")), NamedSharding(mesh, P())
    real = program.build_opt(cfg, 0, str(tmp_path / "real"), "t")
    small = program.build_opt(
        dict(cfg, overrides=dict(cfg["overrides"], memory_size=4096)), 0,
        str(tmp_path / "small"), "t")
    spec = factory.probe_env(real)
    capacity = factory.build_memory(real, spec).learner_side.capacity
    # the objects at a small capacity on the CPU, the shapes at the real one
    lrn = program.build_learner(small)
    if chips == 1 and real.memory_type == "device-per":
        # what DevicePerReplay picks on an unsharded TPU ring
        from pytorch_distributed_tpu.ops.pallas_sampling import (
            hierarchical_sample,
        )
        lrn.replay._draw_fn = hierarchical_sample
    fused = lrn.replay.build_fused_step(
        lrn.step_fn, real.agent_params.batch_size, donate=True,
        steps_per_call=K)

    def ring_leaf(x):
        if x.ndim == 0:
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep)
        return jax.ShapeDtypeStruct((capacity, *x.shape[1:]), x.dtype,
                                    sharding=rows)

    ring = jax.tree_util.tree_map(ring_leaf, lrn.replay.state)
    state = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep),
        lrn.state)
    keys = jax.ShapeDtypeStruct((K, 2), jnp.uint32, sharding=rep)
    beta = jax.ShapeDtypeStruct((), jnp.float32, sharding=rep)
    return fused.lower(state, ring, keys, beta).compile(), capacity


@pytest.mark.parametrize("config", ["apex_pong", "r2d2_pong",
                                    "apex_pong_dp4"])
def test_fused_step_fits_one_chip_at_the_configured_capacity(
        topo, no_cache, tmp_path, config):
    compiled, capacity = compile_fused(topo, config, tmp_path)
    m = compiled.memory_analysis()
    need = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert need + RESERVED < HBM_BYTES, (config, capacity, need)
    # a deployment-sized cell: more than a quarter of the chip (the driver's
    # floor), per chip
    assert need > 0.25 * 16e9, (config, capacity, need)
    text = compiled.as_text()
    if config == "apex_pong":
        assert "tpu_custom_call" in text        # the Pallas sampler is in
    if config == "apex_pong_dp4":
        assert "all-reduce" in text             # gradients cross the chips
