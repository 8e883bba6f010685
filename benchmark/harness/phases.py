"""Device time of one learner update, split by the PROGRAM's phases.

The program enters ``jax.named_scope`` names where it writes its work
(``pytorch_distributed_tpu/utils/profiling.py``: ``replay.draw``,
``replay.gather``, ``train.target``, ``train.online``, ``train.optimizer``,
``replay.writeback``).  A scope lands in each HLO op's ``op_name`` path, and
the profiler stores that path per op in the device plane's EVENT METADATA as
the stat ``tf_op`` (beside ``hlo_category`` and ``program_id``), e.g.

    jit(multi)/while/body/closed_call/train.online/transpose(jvp(Conv_0))/conv

``jax.profiler.ProfileData`` hands out events but not their metadata's
stats, so this file decodes the five messages of ``xplane.proto`` it needs
(XSpace, XPlane, XLine, XEvent, XEventMetadata/XStat) from the protobuf wire
format by hand: nothing but the standard library, about 0.1 s per MB of
trace in the pure-Python varint loop, after the window and outside
``setup_s``.  (``tensorflow.tsl.profiler.protobuf.xplane_pb2`` parses the
same bytes and takes 15 s to import; benchmark/tests/test_phases.py checks
this decoder against sums worked out by hand.)

An op's phase is the INNERMOST vocabulary name on its path, inside
``jvp(...)`` / ``transpose(...)`` too, so the backward pass counts to the
phase of its forward.  Ops under no phase are split by the compiler's own
``hlo_category``: ``data formatting`` (layout changes and copies the
compiler added on its own account: a recompile cannot rename a category as
it renames ``copy.33``) is ``relayout``, everything else ``unnamed``.

Only ops inside WHOLE events of the cell's step module count (the rule of
``trace.reduce``), self time per op (``trace.self_times``), per update =
divided by updates per dispatch x whole step events, averaged over chips.
The eight numbers add up to the self time of every op in those events.
A phase the step program does not contain reads None, and so does every
phase, ``relayout`` and ``unnamed`` too, of a trace in which no op stands
under a scope of the vocabulary (no ``tf_op``, or a program without scopes
as the parent of the PR that added them): never 0.
"""

from __future__ import annotations

import dataclasses
import functools
import re
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from . import trace as T

# metric name (layer_metrics/phase_<x>_ms.py) -> the program's scope
PHASES = {
    "draw": "replay.draw",
    "gather": "replay.gather",
    "target": "train.target",
    "online": "train.online",
    "optimizer": "train.optimizer",
    "writeback": "replay.writeback",
}
RELAYOUT, UNNAMED = "relayout", "unnamed"
RELAYOUT_CATEGORY = "data formatting"
# a name is one whole path component, bare or inside jvp(...)/transpose(...)
_PHASE = re.compile(
    r"(?<![\w.])(" + "|".join(re.escape(p) for p in PHASES.values())
    + r")(?![\w.])")


def phase_of(tf_op: Optional[str], category: Optional[str]) -> str:
    """The key of ``PHASES`` whose scope is innermost on the path, else
    ``relayout`` / ``unnamed`` by the compiler's category."""
    found = _PHASE.findall(tf_op) if tf_op else ()
    if found:
        return next(k for k, v in PHASES.items() if v == found[-1])
    return RELAYOUT if category == RELAYOUT_CATEGORY else UNNAMED


# ---------------------------------------------------------------------------
# protobuf wire format, as much of it as xplane.proto uses
# ---------------------------------------------------------------------------

def _varint(buf, at: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def fields(buf) -> Iterator[Tuple[int, int, object]]:
    """``(field number, wire type, value)`` of one message: an int for
    varints and fixed-width fields (raw bits), a memoryview for
    length-delimited ones."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, at = _varint(buf, at)
        elif wire == 2:
            size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        elif wire == 1:
            value, at = int.from_bytes(buf[at:at + 8], "little"), at + 8
        elif wire == 5:
            value, at = int.from_bytes(buf[at:at + 4], "little"), at + 4
        else:
            raise ValueError(f"wire type {wire} is not in xplane.proto")
        yield number, wire, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _last(buf) -> Dict[int, object]:
    """A message whose fields occur once: ``{field number: value}``."""
    return {number: value for number, _wire, value in fields(buf)}


def _stat(buf) -> Tuple[int, object]:
    """XStat -> (metadata_id, value) for the kinds the three stats read
    here come in: a string, an integer, or ``("ref", id)`` of the stat
    metadata that holds the string."""
    stat = _last(buf)
    if 5 in stat:
        return stat.get(1, 0), _text(stat[5])
    if 7 in stat:
        return stat.get(1, 0), ("ref", stat[7])
    return stat.get(1, 0), stat.get(3, stat.get(4))


@dataclasses.dataclass
class OpMeta:
    name: str = ""
    tf_op: Optional[str] = None
    category: Optional[str] = None
    program_id: Optional[int] = None


@dataclasses.dataclass
class DevicePlane:
    name: str
    ops: List[Tuple[int, float, float]]        # metadata id, start ns, end ns
    modules: List[Tuple[int, float, float]]
    meta: Dict[int, OpMeta]


def _line_name(buf) -> str:
    return next((_text(v) for number, _wire, v in fields(buf)
                 if number == 2), "")


def _events(buf) -> List[Tuple[int, float, float]]:
    """``(metadata id, start ns, end ns)`` of every XEvent of an XLine.  A
    traced R2D2 window holds over a million op events, so this one loop
    reads its varints in place and skips an event's stats unread."""
    t0_ns, events = 0, []
    for number, _wire, v in fields(buf):
        if number == 3:
            t0_ns = v
        elif number == 4:
            events.append(v)
    out = []
    for ev in events:
        meta_id = offset_ps = duration_ps = 0
        at, end = 0, len(ev)
        while at < end:
            key = ev[at]
            at += 1
            if key & 7 == 0 and key < 0x80:           # a varint field < 16
                value = shift = 0
                while True:
                    byte = ev[at]
                    at += 1
                    value |= (byte & 0x7F) << shift
                    if byte < 0x80:
                        break
                    shift += 7
                if key == 0x08:
                    meta_id = value
                elif key == 0x10:
                    offset_ps = value
                elif key == 0x18:
                    duration_ps = value
            elif key == 0x22:                         # stats: skipped whole
                size, at = _varint(ev, at)
                at += size
            else:                                     # nothing else is there
                raise ValueError(f"unexpected XEvent field key {key:#x}")
        start = t0_ns + offset_ps / 1000.0
        out.append((meta_id, start, start + duration_ps / 1000.0))
    return out


def _name(buf) -> str:
    """The ``name`` of an XPlane (field 2, ahead of its lines)."""
    return next((_text(v) for number, _wire, v in fields(buf)
                 if number == 2), "")


def _device_plane(name: str, buf) -> DevicePlane:
    lines, metas, stat_names = [], [], {}
    for number, _wire, v in fields(buf):
        if number == 3:
            lines.append(v)
        elif number == 4:                     # map<int64, XEventMetadata>
            metas.append(v)
        elif number == 5:                     # map<int64, XStatMetadata>
            entry = _last(v)
            stat_names[entry.get(1, 0)] = _text(
                _last(entry.get(2, b"")).get(2, b""))
    meta: Dict[int, OpMeta] = {}
    for raw in metas:
        entry = _last(raw)
        m = OpMeta()
        for number, _wire, v in fields(entry.get(2, b"")):
            if number == 2:
                m.name = _text(v)
            elif number == 5:
                stat_id, value = _stat(v)
                if isinstance(value, tuple):               # ref_value
                    value = stat_names.get(value[1])
                stat = stat_names.get(stat_id)
                if stat == "tf_op":
                    m.tf_op = value
                elif stat == "hlo_category":
                    m.category = value
                elif stat == "program_id":
                    m.program_id = value
        meta[entry.get(1, 0)] = m
    by_name = {_line_name(raw): raw for raw in lines}
    return DevicePlane(
        name, meta=meta,
        ops=_events(by_name.get(T.OPS_LINE, b"")),
        modules=_events(by_name.get(T.MODULES_LINE, b"")))


def load(path: str) -> Tuple[List[DevicePlane], Optional[T.Interval]]:
    """The device planes of an ``.xplane.pb`` that hold ops, with their op
    metadata, and the benchmark's ``bench/window`` host span if the trace
    has one."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    devices, window = [], None
    for number, _wire, v in fields(space):
        if number != 1:
            continue
        name = _name(v)
        if T.DEVICE_PLANE.match(name):
            plane = _device_plane(name, v)
            if plane.ops:
                devices.append(plane)
        elif name.startswith("/host:"):
            window = _window_span(v) or window
    devices.sort(key=lambda d: d.name)
    return devices, window


def _window_span(buf) -> Optional[T.Interval]:
    """The last ``bench/window`` event of a host plane (any line)."""
    wanted, lines = set(), []
    for number, _wire, v in fields(buf):
        if number == 3:
            lines.append(v)
        elif number == 4:
            entry = _last(v)
            if _text(_last(entry.get(2, b"")).get(2, b"")) == T.WINDOW_SPAN:
                wanted.add(entry.get(1, 0))
    spans = [(s, e) for raw in lines for meta_id, s, e in _events(raw)
             if meta_id in wanted] if wanted else []
    return max(spans) if spans else None


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------

def per_update_ms(devices: Sequence[DevicePlane],
                  window: Optional[T.Interval],
                  step_modules: Sequence[str],
                  updates_per_dispatch: int) -> Dict[str, float]:
    """``{phase: ms per update}`` over the whole step events inside the
    window, averaged over the chips that ran the step.  Holds only the
    phases that have an op in the step program; empty where no step ran or
    where no op of it stands under a scope of the vocabulary."""
    totals: Dict[str, float] = {}
    chips = 0
    for d in devices:
        lo, hi = window or (min(s for _, s, _ in d.ops),
                            max(e for _, _, e in d.ops))
        named = [(d.meta[m].name, s, e) for m, s, e in d.modules
                 if m in d.meta]
        ran = {T.module_name(raw) for raw, _, _ in named}
        step = next((m for m in step_modules if m in ran), None)
        steps = sorted((s, e, raw) for raw, s, e in named
                       if T.module_name(raw) == step and s >= lo and e <= hi)
        # the profiler's stop leaves the step in flight behind as an event
        # of a nanosecond (seen on the chip, PR 24): not a whole step
        if steps:
            half = 0.5 * T.median([e - s for s, e, _ in steps])
            steps = [st for st in steps if st[1] - st[0] >= half]
        if not steps:
            continue
        chips += 1
        # ``jit_multi(<program id>)``: an op of another program cannot run
        # inside a step event on the chip's one in-order core, but the
        # stat is there, so it is held to
        programs = {int(p) for _, _, raw in steps
                    for p in re.findall(r"\((\d+)\)$", raw)}
        inside, at = [], 0
        for op in sorted(d.ops, key=lambda ev: ev[1]):
            while at < len(steps) and steps[at][1] < op[2]:
                at += 1                      # that step ended before the op
            m = d.meta.get(op[0])
            if (at < len(steps) and steps[at][0] <= op[1] and m is not None
                    and (m.program_id is None or not programs
                         or m.program_id in programs)):
                inside.append(op)
        for meta_id, ns in T.self_times(inside).items():
            phase = phase_of(d.meta[meta_id].tf_op, d.meta[meta_id].category)
            totals[phase] = totals.get(phase, 0.0) + ns / (
                1e6 * updates_per_dispatch * len(steps))
    if not any(phase in PHASES for phase in totals):
        return {}       # a program without scopes: nothing of it is named
    return {phase: ms / chips for phase, ms in totals.items()}


@functools.lru_cache(maxsize=2)
def _of_file(path: str, step_modules: Tuple[str, ...],
             updates_per_dispatch: int) -> Dict[str, float]:
    devices, window = load(path)
    return per_update_ms(devices, window, step_modules,
                         updates_per_dispatch)


def read(ctx, phase: str) -> Optional[float]:
    """What ``layer_metrics/phase_<phase>_ms.py`` returns: ms per update of
    one phase in this run's trace, or None where there is nothing to read
    (an untraced or CPU run, no step event, a program without scopes, a
    phase the step program does not contain).  The trace is decoded once
    per file for the eight readers."""
    if ctx.trace is None or not ctx.result.trace_dir:
        return None
    path = T.find_xplane(ctx.result.trace_dir)
    if path is None:
        return None
    return _of_file(path, tuple(ctx.cell.traffic.get("step_modules", ())),
                    int(ctx.result.updates_per_dispatch)).get(phase)
