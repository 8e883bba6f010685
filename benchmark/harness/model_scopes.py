"""Device time of one learner update, split by the MODEL's parts.

A model that names its layers (``pytorch_distributed_tpu/utils/profiling.py``
``MODEL_SCOPES``: ``model.embed``, ``model.ssm``, ``model.attn``,
``model.moe``, ``model.head``; entered inside ``train.target`` /
``train.online``, inside each ``jax.checkpoint`` and scan body) cuts the
time of those two phases another way: by layer kind, the target pass, the
online pass, its backward and what is computed again for it together.  The
parts do not add to the eight ``phase_*_ms``; with the ops of the two phases
that stand under no model scope (the loss) they add up to ``phase_target_ms``
+ ``phase_online_ms``.

Same rules as ``phases.per_update_ms``, whose decoder (``phases.load``) and
self times (``trace.self_times``) this reuses: only ops inside whole events
of the cell's step module, self time per op, per update, averaged over the
chips that ran the step; an op's part is the INNERMOST model scope on its
``tf_op`` path.  One kind of op has no path at all: the grouped matmul the
TPU compiler makes of ``jax.lax.ragged_dot`` is a custom call named
``ragged-dot-*`` that keeps no ``op_name`` (seen on the chip, PR 26: 100 of
525 ms).  It is filed under ``model.moe`` by that name, and only in a
program that names the scope: the expert layers are the one caller.  A trace
whose step program names no model scope (another model family, or a program
from before the scopes) reads nothing: {}.
"""

from __future__ import annotations

import collections
import functools
import re
from typing import Dict, List, Optional, Sequence, Tuple

from . import phases, trace as T

# metric name (layer_metrics/phase_<x>_ms.py) -> the model's scope
PARTS = {"embed": "model.embed", "ssm": "model.ssm", "attn": "model.attn",
         "moe": "model.moe", "head": "model.head"}
_PART = re.compile(
    r"(?<![\w.])(" + "|".join(re.escape(p) for p in PARTS.values())
    + r")(?![\w.])")


GROUPED_MATMUL = "ragged-dot"      # the compiler's name, "%" stripped


def part_of(tf_op: Optional[str], name: str = "") -> Optional[str]:
    found = _PART.findall(tf_op) if tf_op else ()
    if found:
        return next(k for k, v in PARTS.items() if v == found[-1])
    return "moe" if T.op_name(name).startswith(GROUPED_MATMUL) else None


def ops_in_steps(devices: Sequence[phases.DevicePlane],
                 window: Optional[T.Interval], step_modules: Sequence[str]
                 ) -> List[Tuple[phases.DevicePlane, Dict[int, float],
                                 Dict[int, int], int]]:
    """Per chip that ran the step: (its plane, self ns per op metadata id,
    events per id, whole step events), over the ops inside whole events of
    the cell's step module in the window (the rule of
    ``phases.per_update_ms``)."""
    out = []
    for d in devices:
        lo, hi = window or (min(s for _, s, _ in d.ops),
                            max(e for _, _, e in d.ops))
        named = [(d.meta[m].name, s, e) for m, s, e in d.modules
                 if m in d.meta]
        ran = {T.module_name(raw) for raw, _, _ in named}
        step = next((m for m in step_modules if m in ran), None)
        steps = sorted((s, e) for raw, s, e in named
                       if T.module_name(raw) == step and s >= lo and e <= hi)
        if steps:   # the profiler's stop leaves a nanosecond event behind
            half = 0.5 * T.median([e - s for s, e in steps])
            steps = [st for st in steps if st[1] - st[0] >= half]
        if not steps:
            continue
        inside, at = [], 0
        for op in sorted(d.ops, key=lambda ev: ev[1]):
            while at < len(steps) and steps[at][1] < op[2]:
                at += 1
            if at < len(steps) and steps[at][0] <= op[1] and op[0] in d.meta:
                inside.append(op)
        out.append((d, T.self_times(inside),
                    collections.Counter(op[0] for op in inside), len(steps)))
    return out


def per_update_ms(devices: Sequence[phases.DevicePlane],
                  window: Optional[T.Interval], step_modules: Sequence[str],
                  updates_per_dispatch: int) -> Dict[str, float]:
    """``{part: ms per update}``, averaged over the chips that ran the
    step."""
    totals: Dict[str, float] = {}
    chips = ops_in_steps(devices, window, step_modules)
    for d, self_ns, _events, steps in chips:
        for meta_id, ns in self_ns.items():
            part = part_of(d.meta[meta_id].tf_op, d.meta[meta_id].name)
            if part is not None:
                totals[part] = totals.get(part, 0.0) + ns / (
                    1e6 * updates_per_dispatch * steps)
    if not any(part_of(m.tf_op) for d in devices for m in d.meta.values()):
        return {}       # no model scope anywhere: nothing of it is named
    return {part: ms / len(chips) for part, ms in totals.items()}


def kernel_per_update(devices: Sequence[phases.DevicePlane],
                      window: Optional[T.Interval],
                      step_modules: Sequence[str], updates_per_dispatch: int,
                      kernel: str) -> Optional[Tuple[float, float]]:
    """(ms, calls) per update of the ops named ``<kernel>`` or
    ``<kernel>.<n>`` (a Pallas kernel's name in the compiled program),
    averaged over the chips that ran the step; None where there is none."""
    named = re.compile(re.escape(kernel) + r"(\.\d+)?$")
    ms = calls = 0.0
    chips = ops_in_steps(devices, window, step_modules)
    for d, self_ns, events, steps in chips:
        for meta_id, ns in self_ns.items():
            if named.match(T.op_name(d.meta[meta_id].name)):
                ms += ns / (1e6 * updates_per_dispatch * steps)
                calls += events[meta_id] / (updates_per_dispatch * steps)
    return (ms / len(chips), calls / len(chips)) if calls else None


@functools.lru_cache(maxsize=2)
def _of_file(path: str, step_modules: Tuple[str, ...],
             updates_per_dispatch: int) -> Dict[str, float]:
    devices, window = _planes(path)
    return per_update_ms(devices, window, step_modules, updates_per_dispatch)


@functools.lru_cache(maxsize=2)
def _planes(path: str):
    return phases.load(path)


def _trace_of(ctx) -> Optional[str]:
    trace_dir = getattr(ctx.result, "trace_dir", None)
    if ctx.trace is None or not trace_dir:
        return None
    return T.find_xplane(trace_dir)


def read(ctx, part: str) -> Optional[float]:
    """ms per update of one model part in this run's trace, or None where
    there is nothing to read."""
    path = _trace_of(ctx)
    if path is None:
        return None
    return _of_file(path, tuple(ctx.cell.traffic.get("step_modules", ())),
                    int(ctx.result.updates_per_dispatch)).get(part)


def read_kernel(ctx, kernel: str) -> Optional[Tuple[float, float]]:
    """(ms, calls) per update of one Pallas kernel in this run's trace."""
    path = _trace_of(ctx)
    if path is None:
        return None
    devices, window = _planes(path)
    return kernel_per_update(
        devices, window, tuple(ctx.cell.traffic.get("step_modules", ())),
        int(ctx.result.updates_per_dispatch), kernel)
