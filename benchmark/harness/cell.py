"""Run one cell and assemble its result line.

The runner (``runners/<runner>.py``) drives the program and hands back a
``RunResult``; everything that is the same for every cell happens here: the
device check, the compile cache, the compile counter, peak memory, the trace
reduction, the per-layer readers and the shape of the last line.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
from typing import Any, Dict, List, Optional

from . import manifest, peaks, trace as trace_mod, window

RUN_DIR = ".bench_run"       # inside the checkout, git-ignored


class NoAccelerator(RuntimeError):
    """JAX found no accelerator, or not the chips the cell asks for."""


@dataclasses.dataclass
class RunArgs:
    seed: int
    seconds: float
    trace: bool
    run_dir: str
    phases: window.PhaseClock
    compiles: window.CompileCounter


@dataclasses.dataclass
class RunResult:
    """What a runner hands back.  ``end_to_end`` holds the runner's own
    readings by metric name; ``setup_s`` ends where its window opened."""
    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    setup_s: float
    compiles_in_window: int
    check: Dict[str, Any]                   # the family's agrees(); has "ok"
    memory_peak_bytes: int                  # allocator's, when the window closed
    fatal: Optional[str] = None             # why the program stopped early
    updates_per_dispatch: int = 1
    trace_dir: Optional[str] = None
    notes: Dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Ctx:
    """What a per-layer reader may read."""
    cell: manifest.Cell
    result: RunResult
    phases: Dict[str, float]
    device_count: int
    peaks: Optional[peaks.Peaks]
    trace: Optional[trace_mod.Summary]

    def flops_per_update(self) -> int:
        """FLOPs of one learner update, counted by the configuration's
        family file.  Looked up when a reader asks, so a cell that lists no
        such metric needs no count."""
        family = manifest.load_module("families", self.cell.config["family"])
        notes = self.result.notes
        return family.update_flops(self.cell.config["shapes"],
                                   notes["state_shape"], notes["num_actions"])


def _devices(cell: manifest.Cell, require_accelerator: bool):
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if require_accelerator and platform == "cpu":
        raise NoAccelerator(
            "JAX found no accelerator (platform cpu); the benchmark has no "
            "CPU fallback")
    if len(devices) != cell.chips:
        raise NoAccelerator(
            f"cell {cell.name} is defined on {cell.chips} chip(s); JAX sees "
            f"{len(devices)} {platform} device(s)")
    return devices


def memory_now() -> Dict[str, List[int]]:
    """The backend's allocator statistics, per chip, in bytes: in use now
    and at their peak since the process started.  (None of a program's
    scratch is in them: seen on the chip, PR 22.)"""
    import jax

    stats = [d.memory_stats() or {} for d in jax.devices()]
    return {"in_use": [int(s.get("bytes_in_use", 0)) for s in stats],
            "peak": [int(s.get("peak_bytes_in_use", 0)) for s in stats]}


def hbm_peak_bytes(at_close: Dict[str, List[int]], scratch_bytes: int) -> int:
    """The most HBM the job needs on its fullest chip: the allocator's
    peak (set-up included: under a mesh ``DeviceReplay._alloc`` builds whole
    arrays on device 0), or what is allocated when the window closes plus
    the scratch the learner's step program needs on top while it runs,
    whichever is larger.  The second is what decides whether a ring of this
    capacity compiles at all; the allocator cannot see it."""
    return max(max(peak, in_use + scratch_bytes)
               for in_use, peak in zip(at_close["in_use"], at_close["peak"]))


def _reduce_trace(cell: manifest.Cell,
                  result: RunResult) -> Optional[trace_mod.Summary]:
    xplane = trace_mod.find_xplane(result.trace_dir) \
        if result.trace_dir else None
    if xplane is None:
        return None
    return trace_mod.reduce(trace_mod.load(xplane),
                            step_modules=cell.traffic.get("step_modules", ()))


def _per_layer(cell: manifest.Cell, ctx: Ctx,
               reported: Dict[str, Any]) -> Dict[str, Any]:
    """Each per-layer metric of the cell whose end-to-end metric is reported
    there, read by its own file; a reader that finds nothing to read returns
    None and the metric is left out."""
    metrics = {}
    for m in cell.per_layer:
        if m["moves"] not in reported:
            continue
        value = manifest.load_module("layer_metrics", m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return metrics


def run(workload: str, seed: int, seconds: float, traced: bool,
        t_start: float, require_accelerator: bool = True) -> Dict[str, Any]:
    """Returns the result line as a dict.  Raises ``NoAccelerator`` /
    ``ManifestError`` / ``UnknownDevice`` before anything is measured."""
    cell = manifest.load_cell(workload)
    import jax

    devices = _devices(cell, require_accelerator)
    phases = window.PhaseClock(t_start)
    phases.lap("backend")
    kind = devices[0].device_kind
    on_chip = devices[0].platform != "cpu"
    chip = peaks.peaks_of(kind) if on_chip else None

    from pytorch_distributed_tpu.utils.helpers import enable_compile_cache

    # the program's one rule: $JAX_COMPILATION_CACHE_DIR, else
    # <checkout>/.jax_cache.  Keep every program, however quick to compile,
    # so that a cell's second run in a checkout compiles nothing.
    if enable_compile_cache():
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compiles = window.CompileCounter().install()

    runner = manifest.load_module("runners", cell.traffic["runner"])
    args = RunArgs(seed=seed, seconds=seconds, trace=traced,
                   run_dir=os.path.join(manifest.ROOT, RUN_DIR, workload),
                   phases=phases, compiles=compiles)
    result: RunResult = runner.run(cell, args)

    values = dict(result.end_to_end, setup_s=result.setup_s)
    # the cell's end-to-end metrics the run has a reading of
    reported = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                for m in cell.end_to_end if m["name"] in values}
    correct = bool(result.check.get("ok")) and result.fatal is None \
        and result.compiles_in_window == 0
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": result.memory_peak_bytes}
    line: Dict[str, Any] = {
        "correct": correct, "attempted": int(result.attempted),
        "failed": int(result.failed)}
    detail = {"workload": workload, "seed": seed, "traced": traced,
              "check": result.check, "fatal": result.fatal,
              "compiles_in_window": result.compiles_in_window,
              "setup_phases_s": dict(phases.seconds), "notes": result.notes}

    if not traced:
        metrics = reported
    else:
        summary = _reduce_trace(cell, result) if on_chip else None
        if summary is not None:
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
            line["breakdown"] = {
                "device_ops": [[n, s] for n, s in summary.device_ops],
                "idle_gaps": [[n, s] for n, s in summary.idle_gaps]}
            detail["end_to_end_while_traced"] = reported
            detail["step_module"] = summary.step_module
        ctx = Ctx(cell=cell, result=result, phases=dict(phases.seconds),
                  device_count=len(devices), peaks=chip, trace=summary)
        metrics = _per_layer(cell, ctx, reported)

    if not on_chip:
        # a CPU rehearsal (benchmark/tests only) proves the plumbing; no
        # number of it may stand under a device metric's name
        detail["rehearsal_metric_names"] = sorted(metrics)
        metrics = {}
    line["metrics"] = metrics
    line["device"] = device
    # what the line has no key for (the check's agreement numbers, set-up
    # phases, memory per stage): to stderr, for whoever reads the run's log
    print("[benchmark] detail: " + json.dumps(detail), file=sys.stderr)
    return line


def main(argv: List[str], t_start: float,
         require_accelerator: bool = True) -> int:
    """Parse the driver's arguments, run the cell, print the line LAST on
    the real stdout.  Everything the program and its children print goes to
    stderr meanwhile."""
    import argparse

    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = p.parse_args(argv)

    sys.stdout.flush()
    real_stdout = os.dup(1)
    os.dup2(2, 1)
    try:
        line = run(a.workload, a.seed, a.seconds, bool(a.trace), t_start,
                   require_accelerator)
    except (NoAccelerator, manifest.ManifestError, peaks.UnknownDevice) as e:
        print(f"[benchmark] {type(e).__name__}: {e}", file=sys.stderr)
        return 3
    finally:
        sys.stdout.flush()
        os.dup2(real_stdout, 1)
        os.close(real_stdout)
    print(json.dumps(line), flush=True)
    return 0
