"""The learner-side objects of the program under test, built from
``config.build_options`` and the ``factory`` exactly as
``agents/learner.py run_learner`` builds them: model, train step, HBM ring
and fused program.  Nothing here is a benchmark-only model or geometry; the
benchmark only assembles, because ``run_learner`` keeps these as locals
(PERF.md, open questions).

Also the one place that writes seeded rows, made ON THE DEVICE by the
configuration's family file, through the program's own feed path.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import shutil
from typing import Any, Callable, Dict, Optional


def build_opt(cfg: Dict[str, Any], seed: int, run_dir: str, refs: str,
              **extra: Any):
    """Options for one run: the CONFIGS row of the configuration, its
    overrides, then the traffic's.  Logs and checkpoints go under
    ``run_dir`` (inside the checkout, emptied first so ``resume`` finds
    nothing)."""
    from pytorch_distributed_tpu.config import build_options

    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir, exist_ok=True)
    overrides = dict(cfg.get("overrides", {}))
    overrides.update(extra)
    return build_options(
        int(cfg["row"]), seed=int(seed), root_dir=run_dir, refs=refs,
        resume="never", visualize=False, **overrides)


@dataclasses.dataclass
class Learner:
    """What ``run_learner`` holds once its set-up is done."""
    opt: Any
    spec: Any
    mesh: Any
    model: Any
    step_fn: Callable
    state: Any                # TrainState, placed (replicated on a mesh)
    memory: Any               # the ingest front end (learner side)
    replay: Any               # the attached HBM ring
    K: int                    # updates per dispatch


def build_learner(opt, *, with_ring: bool = True,
                  replay: Any = None) -> Learner:
    """Mirror of run_learner's set-up (agents/learner.py: mesh, model,
    params, train state, placement, ``memory.attach``), without resume,
    publication, telemetry and fault planes."""
    import jax

    from pytorch_distributed_tpu import factory
    from pytorch_distributed_tpu.parallel.learner import ShardedLearner
    from pytorch_distributed_tpu.parallel.mesh import make_mesh

    pp = opt.parallel_params
    spec = factory.probe_env(opt)
    mesh = None
    if len(jax.devices()) > 1:
        mesh = make_mesh(pp.dp_size, pp.mp_size, pp.sp_size, pp.ep_size,
                         pp.pp_size)
    model = factory.build_model(opt, spec)
    # weights from the seed in one jitted call, not leaf by leaf; the seed
    # is an argument, so every seed finds the one program in the cache
    params = jax.jit(functools.partial(factory.init_params, opt, spec,
                                       model))(opt.seed)
    state, step_fn = factory.build_train_state_and_step(
        opt, spec, model, params, mesh=mesh)
    state = ShardedLearner(step_fn, mesh, donate=pp.donate).place(state)
    memory = None
    if with_ring:
        memory = factory.build_memory(opt, spec).learner_side
        replay = memory.attach(mesh=mesh)
    return Learner(opt=opt, spec=spec, mesh=mesh, model=model,
                   step_fn=step_fn, state=state, memory=memory,
                   replay=replay,
                   K=factory.resolve_steps_per_dispatch(opt))


def build_fused(lrn: Learner, steps_per_call: Optional[int] = None):
    """The fused sample -> train -> write-back program, as run_learner
    builds it for the fused-priority rings (megabatch resolved the same
    way)."""
    from pytorch_distributed_tpu import factory

    opt = lrn.opt
    K = lrn.K if steps_per_call is None else steps_per_call
    kw = {}
    if steps_per_call is None:
        M, K_mb = factory.resolve_megabatch(opt, K)
        if M > 1:
            mega = factory.build_megabatch_train_step(opt, lrn.model)
            if mega is not None:
                K = lrn.K = K_mb
                kw = dict(megabatch=M, megabatch_step=mega)
    return lrn.replay.build_fused_step(
        lrn.step_fn, opt.agent_params.batch_size,
        donate=opt.parallel_params.donate, steps_per_call=K, **kw)


# ---------------------------------------------------------------------------
# seeded rows, made on the device
# ---------------------------------------------------------------------------

def _row_sharding(mesh):
    if mesh is None:
        return None
    from pytorch_distributed_tpu.parallel.mesh import batch_sharding

    return batch_sharding(mesh)


def fill_ring(lrn: Learner, seed: int, chunk_rows: int, family) -> int:
    """Fill the ring to capacity from ``seed``: chunks are generated on the
    device (``family.seed_chunk``) and written through the program's own
    ``feed_chunk`` (so cursor, fill and max-priority bookkeeping are the
    program's), then priorities are spread by one seeded |TD| write-back
    through the program's own update function
    (``family.update_priorities``), so the sampler does not see a flat
    vector.  ``family`` is the configuration's ``families/<family>.py``.
    Returns the rows written."""
    import jax
    import jax.numpy as jnp

    replay = lrn.replay
    cap = replay.capacity
    chunk_rows = min(chunk_rows, cap)
    gen = functools.partial(family.seed_chunk, n=chunk_rows, lrn=lrn)
    update = family.update_priorities(lrn)
    rows = _row_sharding(lrn.mesh)
    gen = jax.jit(gen, out_shardings=rows)
    key = jax.random.PRNGKey(seed)
    written = 0
    while written < cap:
        key, sub = jax.random.split(key)
        replay.feed_chunk(gen(sub))
        written += chunk_rows
    if update is None:
        return written

    def spread(state, key):
        td = jnp.abs(jax.random.normal(key, (cap,), jnp.float32))
        new = update(state, jnp.arange(cap, dtype=jnp.int32), td,
                     replay.alpha)
        return new.priority, new.max_priority

    # only the two leaves the write-back changes come out of the program:
    # handing the whole ring through it would hold two rings for a moment
    priority, max_priority = jax.jit(spread)(replay.state, key)
    replay.state = replay.state._replace(priority=priority,
                                         max_priority=max_priority)
    return written


def program_memory(fused, *args) -> Dict[str, int]:
    """Bytes the TPU compiler says one call of ``fused`` needs on a chip
    (``compiled.memory_analysis()``): arguments, scratch (``temp``) and the
    total with outputs that do not alias an argument.  The backend's
    allocator statistics do not show a program's scratch (seen on the chip,
    PR 22), and scratch is what bounds the ring today."""
    m = fused.lower(*args).compile().memory_analysis()
    if m is None:
        return {}
    return {"arguments_bytes": int(m.argument_size_in_bytes),
            "scratch_bytes": int(m.temp_size_in_bytes),
            "total_bytes": int(
                m.argument_size_in_bytes + m.temp_size_in_bytes
                + m.output_size_in_bytes - m.alias_size_in_bytes)}
