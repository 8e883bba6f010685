"""Reduce a JAX profiler trace (``*.xplane.pb``) to the numbers the
per-layer metrics read.  Needs nothing but JAX (``jax.profiler.ProfileData``).

What a TPU trace holds (looked at by hand, PR 22): one plane per chip,
``/device:TPU:<n>``, with a line ``XLA Ops`` (one event per HLO op executed;
an op that encloses others, such as a ``while``, is an event that spans its
children on the same line) and a line ``XLA Modules`` (one event per
executed program).  Host threads are lines of the ``/host:CPU`` plane; the
benchmark's own ``jax.profiler.TraceAnnotation`` spans are events there
whose names start with ``bench/``.  Host and device events share one time
axis, but not exactly one clock: in the recorded test trace the device reads
about 1.4 ms behind the host, so a host span labels a gap of milliseconds
reliably and one of microseconds not at all.

Definitions (on-chip-measurement guide, section 4):
  busy        union of the intervals in which an op runs on the device
  idle share  1 - busy / window
  self time   an op's duration minus the part its child ops cover
  exposed     self time of a collective op: nothing else runs on that
              chip's one in-order core while it waits
"""

from __future__ import annotations

import dataclasses
import glob
import math
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench/"
WINDOW_SPAN = "bench/window"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|collective-permute|reduce-scatter|all-to-all)")

Event = Tuple[str, float, float]          # name, start_ns, end_ns
Interval = Tuple[float, float]


@dataclasses.dataclass
class DeviceTrace:
    plane: str
    ops: List[Event]
    modules: List[Event]


@dataclasses.dataclass
class Trace:
    devices: List[DeviceTrace]
    host_spans: List[Event]               # the benchmark's own annotations


def op_name(raw: str) -> str:
    """``%fusion.12 = f32[..] fusion(...)`` -> ``fusion.12``."""
    return raw.split(" = ", 1)[0].strip().lstrip("%")


def module_name(raw: str) -> str:
    """``jit_multi(1234567)`` -> ``jit_multi``."""
    return raw.split("(", 1)[0].strip()


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def _events(line) -> List[Event]:
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    devices, spans = [], []
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(op_name(n), s, e) for n, s, e in _events(line)]
                elif line.name == MODULES_LINE:
                    modules = [(module_name(n), s, e)
                               for n, s, e in _events(line)]
            devices.append(DeviceTrace(plane.name, ops, modules))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [ev for ev in _events(line)
                          if ev[0].startswith(SPAN_PREFIX)]
    devices.sort(key=lambda d: d.plane)
    return Trace(devices, sorted(spans, key=lambda ev: ev[1]))


def describe(path: str, head: int = 6) -> str:
    """Planes, lines, event counts and the first names: for looking at a
    trace by hand before trusting the reduction."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            names = [e.name[:60] for e in evs[:head]]
            out.append(f"  line {line.name!r}: {len(evs)} events {names}")
    return "\n".join(out)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return [(lo, hi) for lo, hi in merged]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def gaps(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The complement of a merged, clipped interval list inside [lo, hi]."""
    out, at = [], lo
    for a, b in merged:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def self_times(events: Sequence[Event]) -> Dict[str, float]:
    """Self time per op name, in ns: each event's duration minus what its
    children on the same line cover.  An event is a child of the nearest
    earlier event that still encloses its start."""
    out: Dict[str, float] = {}
    stack: List[List] = []                # [name, end, child_ns, start]

    def close(item) -> None:
        name, end, child, start = item
        out[name] = out.get(name, 0.0) + (end - start) - child

    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            close(stack.pop())
        if stack:
            stack[-1][2] += min(end, stack[-1][1]) - start
        stack.append([name, end, 0.0, start])
    while stack:
        close(stack.pop())
    return out


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest rank, an observed value."""
    vals = sorted(values)
    return vals[min(len(vals) - 1, max(0, math.ceil(q * len(vals)) - 1))]


def median(values: Sequence[float]) -> float:
    vals = sorted(values)
    mid = len(vals) // 2
    return vals[mid] if len(vals) % 2 else 0.5 * (vals[mid - 1] + vals[mid])


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                         # averaged over the chips
    chips: int
    op_self_s: Dict[str, float]           # every op: self seconds, averaged
    device_ops: List[Tuple[str, float]]   # the heaviest of them, sorted
    idle_gaps: List[Tuple[str, float]]    # first chip: host label, seconds
    step_module: Optional[str]            # the step program's module, if run
    step_ms: List[float]                  # its event durations, first chip
    step_starts_s: List[float]            # and their start times
    step_gaps_ms: List[float]             # idle between its consecutive events
    collective_exposed_s: float           # averaged over the chips

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def _window(trace: Trace) -> Interval:
    marked = [ev for ev in trace.host_spans if ev[0] == WINDOW_SPAN]
    if marked:
        return marked[-1][1], marked[-1][2]
    starts = [ev[1] for d in trace.devices for ev in d.ops]
    ends = [ev[2] for d in trace.devices for ev in d.ops]
    return min(starts), max(ends)


def label_gap(gap: Interval, spans: Sequence[Event]) -> str:
    """What the host was doing in a device-idle gap, as far as the
    benchmark's own spans can say: the span that covers most of it."""
    best, best_ns = "other", 0.0
    cover: Dict[str, float] = {}
    for name, s, e in spans:
        if name == WINDOW_SPAN:
            continue
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > 0:
            cover[name] = cover.get(name, 0.0) + ov
    for name, ns in cover.items():
        if ns > best_ns:
            best, best_ns = name[len(SPAN_PREFIX):], ns
    return best


def reduce(trace: Trace, step_modules: Sequence[str] = (),
           top: int = 10) -> Summary:
    """``step_modules`` names the learner's step program (its module name
    in the trace).  Where none of them ran -- the program renamed it, or the
    traffic file is wrong -- there is no step to read: ``step_module`` is
    None and the step lists are empty, so the readers built on them report
    nothing.  No other module stands in: the heaviest one may be the feed."""
    with_ops = [d for d in trace.devices if d.ops]
    if not with_ops:
        raise ValueError("the trace holds no device op: nothing ran on the "
                         "chip inside the traced window")
    lo, hi = _window(trace)
    n = len(with_ops)
    busy = 0.0
    ops_ns: Dict[str, float] = {}
    exposed = 0.0
    for d in with_ops:
        inside = [(nm, max(s, lo), min(e, hi)) for nm, s, e in d.ops
                  if min(e, hi) > max(s, lo)]
        busy += total(union((s, e) for _, s, e in inside))
        for name, ns in self_times(inside).items():
            ops_ns[name] = ops_ns.get(name, 0.0) + ns
            if COLLECTIVE.match(name):
                exposed += ns
    op_self_s = {name: ns / n / 1e9 for name, ns in ops_ns.items()}
    first = with_ops[0]
    merged = clip(union((s, e) for _, s, e in first.ops), lo, hi)
    idle = sorted(gaps(merged, lo, hi), key=lambda g: g[0] - g[1])[:top]

    ran = {name for name, _, _ in first.modules}
    step_module = next((m for m in step_modules if m in ran), None)
    # only whole events of the step module: one cut by a window edge would
    # read as a short step
    steps = sorted((s, e) for name, s, e in first.modules
                   if name == step_module and s >= lo and e <= hi)
    return Summary(
        window_s=(hi - lo) / 1e9,
        busy_s=busy / n / 1e9,
        chips=n,
        op_self_s=op_self_s,
        device_ops=sorted(op_self_s.items(), key=lambda kv: -kv[1])[:top],
        idle_gaps=[(label_gap(g, trace.host_spans), (g[1] - g[0]) / 1e9)
                   for g in idle],
        step_module=step_module,
        step_ms=[(e - s) / 1e6 for s, e in steps],
        step_starts_s=[s / 1e9 for s, _ in steps],
        step_gaps_ms=[(b[0] - a[1]) / 1e6 for a, b in zip(steps, steps[1:])],
        collective_exposed_s=exposed / n / 1e9,
    )
