"""Pytree parameter-update helpers.

Functional equivalents of reference utils/helpers.py:19-25
(``update_target_model``): the reference mutates a torch module in place;
here both flavours are pure pytree→pytree functions that jit/fuse on TPU.
"""

from __future__ import annotations

import json
import os
from typing import Any, Mapping, Optional

import jax
import jax.numpy as jnp

PyTree = Any


def hard_update(target: PyTree, online: PyTree) -> PyTree:
    """Full copy — reference utils/helpers.py:24-25 (the every-N-steps
    branch).  Pure: returns the new target pytree."""
    return jax.tree_util.tree_map(lambda o: o, online)


def soft_update(target: PyTree, online: PyTree, tau: float) -> PyTree:
    """Polyak averaging ``t <- (1-tau) t + tau o`` — reference
    utils/helpers.py:20-23 (the tau<1 branch, used by DDPG)."""
    return jax.tree_util.tree_map(
        lambda t, o: ((1.0 - tau) * t + tau * o).astype(t.dtype),
        target, online
    )


def periodic_update(target: PyTree, online: PyTree, step: jnp.ndarray,
                    period: int) -> PyTree:
    """Hard update every ``period`` learner steps, as a jit-safe select —
    reference dqn_learner.py:91 calls update_target_model each step and the
    helper internally gates on ``step % period == 0``."""
    do = (step % period) == 0
    return jax.tree_util.tree_map(
        lambda t, o: jnp.where(do, o.astype(t.dtype), t), target, online
    )


def update_target(target: PyTree, online: PyTree, step: jnp.ndarray,
                  target_model_update: float) -> PyTree:
    """Dispatch on the reference's overloaded ``target_model_update``
    scalar: <1 means soft tau-update every step, >=1 means hard update every
    N steps (reference utils/helpers.py:19-25)."""
    if target_model_update < 1:
        return soft_update(target, online, float(target_model_update))
    return periodic_update(target, online, step, int(target_model_update))


_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir(environ: Optional[Mapping[str, str]] = None) -> str:
    """Where the persistent XLA compile cache lives — the ONE rule every
    chip-owning entry point shares (main.py, fleet.py, chip_smoke.py's
    legs, benchmark/run.py): ``JAX_COMPILATION_CACHE_DIR`` verbatim when the
    environment sets it, else ``<checkout>/.jax_cache`` (git-ignored).
    Never a temp name, pid or timestamp: the directory is part of the
    cache key's reach, so a path that moves between runs never hits."""
    env = os.environ if environ is None else environ
    return env.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> Optional[str]:
    """Install the process's compile-path record (utils/profiling; every
    entry point calls this before its first program), then turn on JAX's
    persistent XLA compile cache at ``compile_cache_dir()`` — TPU only.
    Here an entry point first touches the backend: under an explicit
    ``JAX_PLATFORMS=tpu,cpu`` a chip that is absent or busy raises HERE.

    On the CPU backend the cache stays OFF: XLA's CPU AOT loader can
    nondeterministically SIGABRT re-loading cached collective-dense
    multi-device programs (A/B-reproduced 2026-07-31: 3/8 aborts with the
    cache vs 0/22 without on the pp pipeline step).  Spawned workers are
    CPU processes and run cache-free too (runtime.cpu_child_env)."""
    from pytorch_distributed_tpu.utils.profiling import install_compile_record

    install_compile_record()
    if jax.devices()[0].platform != "tpu":
        # an ambient env var set before jax import has already landed
        # in the live config
        jax.config.update("jax_compilation_cache_dir", None)
        return None
    cache_dir = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    # The names a profiler trace shows (utils/profiling.DEVICE_PHASES,
    # flax module paths) are the EXECUTABLE's HLO metadata.  JAX's default
    # key strips debug info, so a tree with new scopes loads the
    # executable an older tree cached and its traces keep the old names
    # (seen on the chip, PR 24: the dp4 step of the scoped tree, run after
    # its parent against one cache directory, showed no scope at all).
    # With the metadata in the key a program's entry also depends on the
    # checkout's path and on line numbers along its call stack, as every
    # program that holds a Pallas kernel already did (Mosaic serialises
    # locations into the kernel body): an edit there recompiles once.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return cache_dir


def record_startup(log_dir: str, role: str, **fields: Any) -> dict:
    """Append this process's start-up record — role, pid, the JAX
    platform it actually initialised, plus ``fields`` — to
    ``<log_dir>/startup.jsonl`` and return it.  Every process of a run
    writes one (the learner's carries its device policy), so "the
    learner owns the chip and every child is a CPU process" is checkable
    from the run's own artifacts (chip_smoke.py does)."""
    dev = jax.devices()[0]
    rec = dict(role=role, pid=os.getpid(), platform=dev.platform,
               device_kind=dev.device_kind, device_count=len(jax.devices()),
               **fields)
    os.makedirs(log_dir, exist_ok=True)
    with open(os.path.join(log_dir, "startup.jsonl"), "a") as f:
        f.write(json.dumps(rec) + "\n")
    return rec


def host_cpu_device():
    """The host CPU jax device.  Present alongside the accelerator only
    when the platform list names it: the chip invocation is
    ``JAX_PLATFORMS=tpu,cpu`` (``tpu`` alone never initialises the CPU
    backend and this raises)."""
    return jax.local_devices(backend="cpu")[0]


def pin_to_cpu(tree: PyTree) -> PyTree:
    """Commit a pytree to the host CPU device.  Rollout-side inference
    (actors, evaluator, tester) pins its params/keys here so batch-1
    forwards compile and run on the host instead of competing with the
    learner for the accelerator — the learner alone owns the mesh
    (SURVEY.md §7 design stance).  jit follows committed inputs, so no
    backend= plumbing is needed in the act functions."""
    return jax.device_put(tree, host_cpu_device())


def unravel_on_cpu(unravel, flat) -> PyTree:
    """unravel (ravel_pytree's inverse) onto the host CPU: the jnp concat/
    reshape ops inside it would otherwise land on the default device."""
    with jax.default_device(host_cpu_device()):
        return pin_to_cpu(unravel(flat))


def global_norm(tree: PyTree) -> jnp.ndarray:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in leaves))


def tree_size(tree: PyTree) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(tree))
