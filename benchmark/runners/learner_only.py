"""Runner ``learner_only``: no actor, no evaluator.  The ring is filled to
capacity on the device from the seed, then the learner's fused program is
dispatched back to back as ``agents/learner.py run_learner`` dispatches it:
``memory.drain()`` (nothing arrives), a buffer of pre-split keys refilled
every 64 dispatches together with beta as a device scalar, donated train
and ring state, no pacing.  Left out on purpose, because nobody consumes
them here: parameter publication and the stats cadence (a closed-loop
runner, when the benchmark gets one, carries those).

Why not ``run_learner`` itself under a ``Topology`` with no actors: it
attaches its ring inside itself (no way to pre-fill without subclassing the
ingest), bumps its one counter at enqueue (so a rate read from it leads the
device by the dispatches in flight, seconds for R2D2), and keeps state and
ring as locals (so the check after the window cannot see them).  PERF.md
lists this for the ``tracing`` issue.

Traffic parameters (``traffic/learner_only.json``): ``warm_dispatches``,
``max_in_flight``, ``trace_seconds``, ``trace_min_dispatches``,
``trace_max_seconds``, ``step_modules``.  The rows one seeded feed writes
(``fill_chunk``) are the configuration's: their unit is the ring's.  What
depends on the model family (seeded rows, the step program, the check, the
FLOPs count) is in ``families/<family>.py``.
"""

from __future__ import annotations

import os
import time

from ..harness import manifest, program
from ..harness.cell import RunArgs, RunResult, hbm_peak_bytes, memory_now


def run(cell: manifest.Cell, args: RunArgs) -> RunResult:
    import jax
    import numpy as np

    from pytorch_distributed_tpu.utils.health import SKIPPED_KEY

    from ..harness.window import (CompletionWatcher, completion_jitter,
                                  completion_rate)

    tp = cell.traffic
    family = manifest.load_module("families", cell.config["family"])
    opt = program.build_opt(cell.config, args.seed, args.run_dir,
                            refs=cell.name, num_actors=0,
                            evaluator_nepisodes=0)
    lrn = program.build_learner(opt)
    args.phases.lap("build")
    memory = {"built": memory_now()}
    program.fill_ring(lrn, args.seed, int(cell.config["fill_chunk"]), family)
    jax.block_until_ready(lrn.replay.state)
    args.phases.lap("fill")
    memory["filled"] = memory_now()

    fused = family.build_step(lrn)
    K, replay, ingest = lrn.K, lrn.replay, lrn.memory
    annotate = jax.profiler.TraceAnnotation
    device_key = jax.random.PRNGKey(args.seed)
    key_buf: list = []
    beta_dev = None
    lstep = 0

    watcher = CompletionWatcher(
        jax.block_until_ready,
        lambda metrics: float(metrics.get(SKIPPED_KEY, 0.0)),
        slots=int(tp["max_in_flight"]))

    def dispatch() -> None:
        nonlocal device_key, key_buf, beta_dev, lstep
        with annotate("bench/wait_slot"):
            watcher.take_slot()
        with annotate("bench/drain"):
            ingest.drain()
        if not key_buf:
            with annotate("bench/keys"):
                keys = jax.random.split(device_key, 64 * K + 1)
                device_key, rest = keys[0], keys[1:]
                key_buf = (list(rest.reshape(64, K, *rest.shape[1:]))
                           if K > 1 else list(rest))
                beta_dev = jax.device_put(np.float32(replay.beta(lstep)))
        with annotate("bench/dispatch"):
            lrn.state, replay.state, metrics = fused(
                lrn.state, replay.state, key_buf.pop(), beta_dev)
        lstep += K
        watcher.submit(metrics)

    # ---- warm-up: every shape the window uses, counted as set-up ----
    warm = int(tp["warm_dispatches"])
    for _ in range(warm):
        dispatch()
    while watcher.completed() < warm:
        if watcher.error is not None:
            raise watcher.error
        time.sleep(0.001)
    args.phases.lap("warm")
    setup_compile_s = args.compiles.snapshot()[2]

    # ---- the measured window ----
    compiled_before = args.compiles.snapshot()
    t_open = time.perf_counter()
    setup_s = t_open - args.phases.t_start
    t_close = t_open + args.seconds
    # a traced run profiles a steady stretch inside the window; the span
    # ``bench/window`` marks it on the trace's own clock, opened and closed
    # right after an enqueue, while the device has work
    trace_dir = None
    trace_at = t_open + min(1.0, 0.1 * args.seconds) if args.trace else None
    span = None             # the open bench/window span
    span_from = (0.0, 0)    # when it opened, dispatches completed by then
    while time.perf_counter() < t_close:
        if span is not None:
            age = time.perf_counter() - span_from[0]
            seen = watcher.completed() - span_from[1]
            if age >= tp["trace_max_seconds"] or (
                    age >= tp["trace_seconds"]
                    and seen >= tp["trace_min_dispatches"]):
                span.__exit__(None, None, None)
                span = None
                jax.profiler.stop_trace()
                t_close = max(t_close, time.perf_counter() + 1.0)
        start_now = trace_at is not None and time.perf_counter() >= trace_at
        if start_now:
            trace_at = None
            trace_dir = os.path.join(args.run_dir, "trace")
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        dispatch()
        if start_now:
            # the profiler's start drained the pipeline: refill it first
            for _ in range(int(tp["max_in_flight"])):
                dispatch()
            span = annotate("bench/window")
            span.__enter__()
            span_from = (time.perf_counter(), watcher.completed())
    if span is not None:
        span.__exit__(None, None, None)
        jax.profiler.stop_trace()
    watcher.close()
    t_end = time.perf_counter()
    compiles_in_window, loads_in_window, _ = args.compiles.since(
        compiled_before)
    memory["window"] = memory_now()

    rate, n = completion_rate(watcher.done_at, t_open, t_end, K)
    inside = [s for t, s in zip(watcher.done_at, watcher.skipped)
              if t_open <= t <= t_end]
    # after the window: the compiler's account of the step program (a load
    # from the cache), then the check
    step_memory = program.program_memory(
        fused, lrn.state, replay.state, key_buf[-1] if key_buf
        else jax.random.split(device_key, K), beta_dev)
    end_to_end = {"hbm_peak_gb": hbm_peak_bytes(
        memory["window"], step_memory.get("scratch_bytes", 0)) / 1e9}
    if rate is not None:
        end_to_end["updates_per_s"] = rate
    result = RunResult(
        attempted=K * n,
        failed=int(round(sum(inside))),
        end_to_end=end_to_end,
        setup_s=setup_s,
        compiles_in_window=compiles_in_window,
        check={},
        memory_peak_bytes=max(memory["window"]["peak"]),
        updates_per_dispatch=K,
        trace_dir=trace_dir,
        notes={"setup_compile_s": setup_compile_s,
               "cache_loads_in_window": loads_in_window,
               "dispatches_in_window": n,
               "completion_jitter": completion_jitter(
                   watcher.done_at, t_open, t_end, K),
               "memory_bytes": memory,
               "step_memory": step_memory,
               "ring_capacity": replay.capacity,
               "sampler": getattr(replay, "sampler", "n/a"),
               "batch_size": opt.agent_params.batch_size,
               "state_shape": list(lrn.spec.state_shape),
               "num_actions": lrn.spec.num_actions},
    )
    reference = manifest.load_module("reference", cell.config["reference"])
    result.check = family.agrees(lrn, cell.config, reference, args.seed)
    return result
