"""Tracing / profiling subsystem.

The reference has none — only stdout banners and TensorBoard scalars
(SURVEY.md §5 "tracing: none").  Four first-class tools here:

- the **device phase vocabulary** (``PHASE_*``): the ``jax.named_scope``
  names every learner step program and ring feed enters where the work
  is written, so a device trace reads in the program's words and not in
  the compiler's (``copy.33``, ``fusion.563``).
- ``StepTimer``: cheap per-role wall-time accounting.  Workers wrap their
  hot-loop phases (act / env.step / feed / learn / drain / publish) and the
  accumulated per-phase seconds flow into the metrics stream on the normal
  logger cadence, so "where does the step time go" is a dashboard read, not
  a guess.  Every ``phase()`` is also a ``jax.profiler.TraceAnnotation``
  named ``<prefix>/<phase>``: a host span on the clock the device events
  of a profiler trace are on.
- ``trace``: a context manager around ``jax.profiler.trace`` that captures
  a real XLA trace (TensorBoard-viewable) for a bounded window, gated so it
  can be left in production code and switched on with an env var
  (``TPU_APEX_PROFILE=dir``).
- the **compile-path record** (``CompileRecord``): JAX's own spans of
  tracing, lowering and compiling or loading each program, kept per
  program name on the ``StepTimer`` clock.  One a process, always on
  (``install_compile_record``, called by ``helpers.enable_compile_cache``);
  ``utils/perf.RetraceDetector`` reads it, and so do the benchmark's
  ``setup_*`` / ``step_*`` set-up metrics.

Cross-role request tracing (per-hop trace ids + latency histograms) lives
in utils/tracing.py; the post-mortem event rings in
utils/flight_recorder.py.  README "Observability" documents all three
together with the env knobs.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
import re
import sys
import threading
import time
import warnings
from typing import Dict, Iterable, Iterator, List, Optional, Tuple

# ---------------------------------------------------------------------------
# device phases: the ONE vocabulary of jax.named_scope names (README
# "Observability", PERF.md section 3).  A scope is metadata only: it lands
# in each HLO op's ``op_name`` path (the profiler's ``tf_op``, xprof's
# framework-op view) and changes neither the compiled program nor its
# compile-cache key.  Dotted, never slashed: inside ``jvp(...)`` /
# ``transpose(...)`` a name stays one path component only without a "/".
# benchmark/harness/phases.py reads them back out of a trace.
# ---------------------------------------------------------------------------
PHASE_DRAW = "replay.draw"            # index draw + IS weights
PHASE_GATHER = "replay.gather"        # row/segment gathers, frame unpacking
PHASE_TARGET = "train.target"         # every pass through target_params
PHASE_ONLINE = "train.online"         # online forward + loss (and, as
#                                       transpose(jvp(train.online)), backward)
PHASE_OPTIMIZER = "train.optimizer"   # pmean, clip, Adam, target sync,
#                                       finite-guard select, metrics
PHASE_WRITEBACK = "replay.writeback"  # |TD| priority scatter + its guard
PHASE_FEED = "replay.feed"            # ring writes (set-up, closed loop)
DEVICE_PHASES = (PHASE_DRAW, PHASE_GATHER, PHASE_TARGET, PHASE_ONLINE,
                 PHASE_OPTIMIZER, PHASE_WRITEBACK, PHASE_FEED)
# the recurrent family nests these inside train.target / train.online:
# the scans of the network's recurrent half (ops/sequence_losses.py); its
# per-observation half runs before them under SCOPE_EMBED
SCOPE_BURN_IN = "burn_in"
SCOPE_UNROLL = "unroll"
# the hybrid trunk (models/hybrid.py) names its layers inside train.target /
# train.online, INSIDE each jax.checkpoint and scan body: the model's parts
# cut the same device time as the two phases another way
SCOPE_EMBED = "model.embed"
SCOPE_SSM = "model.ssm"
SCOPE_GDN = "model.gdn"               # gated delta rule; holds the one below
SCOPE_GDN_CHUNK = "gdn.chunk"         # the recurrence proper: decay, the
#                                       triangular inverse, the chunk
#                                       products, the scan over chunk states
SCOPE_KDA = "model.kda"               # channel-gated delta rule; holds:
SCOPE_KDA_CHUNK = "kda.chunk"         # its recurrence proper, as gdn.chunk
SCOPE_ATTN = "model.attn"
SCOPE_MLA = "model.mla"               # latent attention
SCOPE_MLP = "model.mlp"               # a dense feed-forward block
SCOPE_SCONV = "model.sconv"           # gated short convolution; holds:
SCOPE_SCONV_MIX = "sconv.mix"         # its gate-conv-gate middle
SCOPE_MOE = "model.moe"               # holds the three below
SCOPE_MOE_ROUTE = "moe.route"
SCOPE_MOE_EXPERTS = "moe.experts"
SCOPE_MOE_SHARED = "moe.shared"
SCOPE_HEAD = "model.head"
MODEL_SCOPES = (SCOPE_EMBED, SCOPE_SSM, SCOPE_GDN, SCOPE_KDA, SCOPE_ATTN,
                SCOPE_MLA, SCOPE_MLP, SCOPE_SCONV, SCOPE_MOE, SCOPE_HEAD)


@functools.lru_cache(maxsize=None)
def _trace_annotation():
    """``jax.profiler.TraceAnnotation``, imported at the first timed phase
    of the process and not at module load; None where JAX cannot be
    imported: such a role keeps its timer and has no profiler to write
    into."""
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:
        return None
    return TraceAnnotation


class StepTimer:
    """Accumulates wall seconds per named phase; drain() returns and resets
    per-phase mean/max/call-count as flat metrics.  The max and count ride
    along because a mean averages stalls away: one 2 s drain in a window
    of 100 × 2 ms drains reads as 22 ms mean — the ``*_max_ms`` row is
    what makes the stall visible."""

    def __init__(self, prefix: str):
        self.prefix = prefix
        self._acc: Dict[str, float] = {}
        self._max: Dict[str, float] = {}
        self._n: Dict[str, int] = {}
        self._last_wall: Dict[str, float] = {}
        # seconds of the phase that closed last: lets a caller book ONE
        # clock reading under a second name (a Tracer span) instead of
        # timing the same stretch twice
        self.last_s = 0.0

    @contextlib.contextmanager
    def phase(self, name: str) -> Iterator[None]:
        """Time the enclosed block under ``name`` and, while a profiler
        trace is being captured, show it there as the host span
        ``<prefix>/<name>`` (a TraceMe: one atomic load when no trace is
        active)."""
        annotate = _trace_annotation()
        span = (annotate(f"{self.prefix}/{name}") if annotate is not None
                else contextlib.nullcontext())
        t0 = time.perf_counter()
        try:
            with span:
                yield
        finally:
            self.add(name, time.perf_counter() - t0)

    def add(self, name: str, seconds: float) -> None:
        """Accumulate an externally-timed duration — for callers that
        need one measurement to land under several phase names (the
        pipelined actor loop books dispatch+sync both under their own
        phases and under the serial loop's ``act`` so dashboards stay
        comparable across schedules)."""
        self.last_s = seconds
        self._acc[name] = self._acc.get(name, 0.0) + seconds
        if seconds > self._max.get(name, 0.0):
            self._max[name] = seconds
        self._n[name] = self._n.get(name, 0) + 1
        # wall epoch of the phase's LAST occurrence this window: drained
        # rows otherwise carry only the flush wall, which lets a
        # timeline mis-order a stalled phase against blackbox events by
        # a whole cadence (ISSUE 8 satellite; tools/timeline.py reads
        # the *_last_wall row as the phase's true clock position)
        self._last_wall[name] = time.time()

    def drain(self) -> Dict[str, float]:
        out = {}
        for name, secs in self._acc.items():
            n = self._n[name]
            out[f"{self.prefix}/time_{name}_ms"] = secs / max(n, 1) * 1e3
            out[f"{self.prefix}/time_{name}_max_ms"] = \
                self._max.get(name, 0.0) * 1e3
            out[f"{self.prefix}/time_{name}_calls"] = float(n)
            # the window's TOTAL: means hide call-count asymmetry, so
            # per-phase means never sum to wall time — totals do, which
            # is what a stacked phase-share plot needs
            # (tools/plot_run.py --phase-breakdown)
            out[f"{self.prefix}/time_{name}_total_ms"] = secs * 1e3
            # schema-additive (plot_run's _total_ms regex ignores it):
            # the epoch above, exported as a plain scalar row
            out[f"{self.prefix}/time_{name}_last_wall"] = \
                self._last_wall.get(name, 0.0)
        self._acc.clear()
        self._max.clear()
        self._n.clear()
        self._last_wall.clear()
        return out


def sanitize_label(label: str) -> str:
    """A trace label safe to join into the trace path.  Labels arrive
    from callers AND from the network (the DCN ``T_PROFILE`` verb
    forwards a client-supplied label), so anything outside
    ``[A-Za-z0-9._-]`` — path separators above all — is squashed to
    ``-`` and leading dots are stripped; an emptied label falls back to
    ``trace``."""
    clean = re.sub(r"[^A-Za-z0-9._-]+", "-", str(label)).lstrip(".-")
    return clean or "trace"


# one profiler per process: jax.profiler.trace raises on a nested
# start, which used to turn an inner library trace (a T_PROFILE window
# inside a TPU_APEX_PROFILE'd run) into a crash of the OUTER capture
_trace_lock = threading.Lock()
_trace_active = False


@contextlib.contextmanager
def trace(label: str, log_dir: Optional[str] = None
          ) -> Iterator[Optional[str]]:
    """Capture an XLA profiler trace for the enclosed block when enabled.

    Enabled by passing ``log_dir`` or by setting ``TPU_APEX_PROFILE`` to a
    directory; otherwise a no-op.  Yields the trace directory (None when
    disabled or when a trace is already active — a nested capture is a
    warning + no-op, never a profiler error: the outer window keeps
    recording and the inner caller learns from the None).  View with
    TensorBoard's profile plugin.
    """
    global _trace_active
    target = log_dir or os.environ.get("TPU_APEX_PROFILE")
    if not target:
        yield None
        return
    with _trace_lock:
        nested = _trace_active
        if not nested:
            _trace_active = True
    if nested:
        # warn + no-op OUTSIDE the lock: yielding with it held would
        # stall the outer trace's exit behind this caller's whole body
        # (and deadlock a doubly-nested same-thread capture)
        warnings.warn(
            f"profiling.trace({label!r}): a trace is already active "
            f"in this process; nested capture skipped (the outer "
            f"window keeps recording)", stacklevel=3)
        yield None
        return
    try:
        import jax

        os.makedirs(target, exist_ok=True)
        path = os.path.join(target, sanitize_label(label))
        with jax.profiler.trace(path):
            yield path
    finally:
        with _trace_lock:
            _trace_active = False


# ---------------------------------------------------------------------------
# compile path: JAX's own spans of each program's way to an executable
# ---------------------------------------------------------------------------
# jax/_src/dispatch.py ``log_elapsed_time`` opens each of these with a
# ``record_scalar(event, start)`` and closes it with a
# ``record_event_time_span(event, start, end)``, both on the thread doing
# the work; a span therefore arrives at its END, inner before outer.
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"    # fun_name: f
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"  # jit(f)
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"     # jit(f)
# recorded inside a backend-compile span that the persistent cache served
# (jax/_src/compiler.py compile_or_get_cached): that span is a LOAD
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_KIND = {TRACE_EVENT: "trace", LOWER_EVENT: "lower", BACKEND_EVENT: "compile"}
# the spans a trace can lie inside: a trace's own ``jnp`` calls, and a
# lowering's rules, which trace functions of their own (a ``pallas_call``
# body is traced while its caller lowers)
_PARENTS = (TRACE_EVENT, LOWER_EVENT)


# a lower or backend span of ``jax.jit(functools.partial(f, ...))``: its
# trace span names ``f``, its module does not
_UNNAMED = "<unknown>"


def program_name(fun_name: str) -> str:
    """The program a compile-path span belongs to: ``jit(multi)`` (a lower
    or backend span) and ``multi`` (a trace span) are both ``multi``; the
    profiler shows the same module as ``jit_multi``."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


@dataclasses.dataclass
class CompileTotals:
    """The process's compile path so far."""
    # union of trace spans outside any lowering: a nested one counts once,
    # one made while lowering counts in ``lower_s`` alone
    trace_s: float = 0.0
    lower_s: float = 0.0
    compile_s: float = 0.0
    load_s: float = 0.0       # backend spans the persistent cache served
    traces: int = 0           # trace spans, nested ones each time


@dataclasses.dataclass
class ProgramRecord:
    """One program name's compile path.  Spans are ``(start, end)`` on
    ``time.perf_counter()``, the clock of ``StepTimer`` and of the
    benchmark's set-up phases."""
    name: str
    traces: int = 0           # trace spans, nested ones each time
    trace_s: float = 0.0      # its outermost trace spans
    self_s: float = 0.0       # its trace spans less the spans inside them
    lowers: int = 0
    lower_s: float = 0.0
    compiles: int = 0
    compile_s: float = 0.0
    loads: int = 0
    load_s: float = 0.0
    first_trace: Optional[Tuple[float, float]] = None   # outermost only
    first_lower: Optional[Tuple[float, float]] = None
    first_ready: Optional[Tuple[float, float]] = None   # compile or load
    # the process's totals when ``first_ready`` closed: a program's set-up
    # is what the process spent on its compile path up to that moment
    at_ready: Optional[CompileTotals] = None
    # executables made ready, by the thread that made them: the thread that
    # dispatched the program (``RetraceDetector`` keys on it)
    ready_on: Dict[int, int] = dataclasses.field(default_factory=dict)

    @property
    def ready(self) -> int:
        """Executables made ready for this name: compiled or loaded."""
        return self.compiles + self.loads

    @property
    def setup_s(self) -> float:
        return self.trace_s + self.lower_s + self.compile_s + self.load_s


class _Open:
    """A span whose start JAX announced and whose end has not come."""
    __slots__ = ("event", "start", "children_s")

    def __init__(self, event: str, start: float):
        self.event, self.start, self.children_s = event, start, 0.0


class _ThreadState:
    __slots__ = ("open", "hit", "last")

    def __init__(self):
        self.open: List[_Open] = []   # innermost last
        self.hit = False              # a cache hit awaits its backend span
        # the program whose top-level trace or lower closed last: an
        # unnamed lower or backend span that follows is its (trace, lower,
        # compile run one after another on one thread; lowering rules
        # trace jnp functions of their own, inside the lower span)
        self.last = _UNNAMED


class CompileRecord:
    """Every trace, lower and compile-or-load span JAX reports in this
    process, folded per program name as it arrives.

    A trace span's parent is the innermost trace or lower span open around
    it on the same thread (JAX traces every ``jnp`` call inside a jitted
    function as a program of its own, and a lowering rule may trace one
    too); its self time is its duration less its children's.  A trace
    with no parent is outermost: the process's ``trace_s`` is theirs, so a
    trace made while lowering counts in ``lower_s`` and not twice.  State
    is one ``ProgramRecord`` a program name and, for each thread that ever
    compiled, the spans open on it right now: it
    grows with the programs and the nesting depth, never with the events
    (about 13,500 trace spans in a hybrid cell's set-up).  A dispatch
    that hits the jit cache reports nothing, so the hot path never
    reaches this code."""

    def __init__(self):
        self._lock = threading.Lock()
        self._threads: Dict[int, _ThreadState] = {}
        self._programs: Dict[str, ProgramRecord] = {}
        self._totals = CompileTotals()
        # JAX stamps spans with time.time(); converted once to perf_counter
        self._to_perf = time.perf_counter() - time.time()

    def listen(self) -> "CompileRecord":
        import jax.monitoring as monitoring

        monitoring.register_scalar_listener(self.on_start)
        monitoring.register_event_time_span_listener(self.on_span)
        monitoring.register_event_listener(self.on_event)
        return self

    # -- listeners: called by JAX on the thread that does the work ---------

    def on_start(self, event: str, value: float, **_kw) -> None:
        if event in _KIND:
            with self._lock:
                self._thread().open.append(_Open(event, value))

    def on_event(self, event: str, **_kw) -> None:
        if event == CACHE_HIT_EVENT:
            with self._lock:
                self._thread().hit = True

    def on_span(self, event: str, start: float, end: float, **kw) -> None:
        kind = _KIND.get(event)
        if kind is None:
            return
        name = program_name(str(kw.get("fun_name", "")))
        seconds = max(0.0, end - start)
        span = (start + self._to_perf, end + self._to_perf)
        with self._lock:
            th = self._thread()
            opened = th.open
            children_s = 0.0
            for i in range(len(opened) - 1, -1, -1):
                if opened[i].event == event and opened[i].start == start:
                    children_s = opened[i].children_s
                    del opened[i:]
                    break
            parent = next((o for o in reversed(opened)
                           if o.event in _PARENTS and o.start <= start),
                          None)
            if parent is not None:
                parent.children_s += seconds
            if kind == "compile" and th.hit:
                kind, th.hit = "load", False
            if kind != "trace" and name == _UNNAMED:
                name = th.last
            if kind in ("trace", "lower") and not opened:
                th.last = name
            self._fold(name, kind, seconds, span, children_s,
                       outermost=parent is None,
                       thread=threading.get_ident())

    def _thread(self) -> _ThreadState:
        tid = threading.get_ident()
        th = self._threads.get(tid)
        if th is None:
            th = self._threads[tid] = _ThreadState()
        return th

    def _fold(self, name: str, kind: str, seconds: float,
              span: Tuple[float, float], children_s: float,
              outermost: bool, thread: int) -> None:
        p = self._programs.get(name)
        if p is None:
            p = self._programs[name] = ProgramRecord(name)
        t = self._totals
        if kind == "trace":
            p.traces += 1
            t.traces += 1
            p.self_s += max(0.0, seconds - children_s)
            if outermost:
                p.trace_s += seconds
                t.trace_s += seconds
                if p.first_trace is None:
                    p.first_trace = span
        elif kind == "lower":
            p.lowers += 1
            p.lower_s += seconds
            t.lower_s += seconds
            if p.first_lower is None:
                p.first_lower = span
        else:
            if kind == "load":
                p.loads += 1
                p.load_s += seconds
                t.load_s += seconds
            else:
                p.compiles += 1
                p.compile_s += seconds
                t.compile_s += seconds
            p.ready_on[thread] = p.ready_on.get(thread, 0) + 1
            if p.first_ready is None:
                p.first_ready = span
                p.at_ready = dataclasses.replace(t)

    # -- readers -------------------------------------------------------------

    def totals(self) -> CompileTotals:
        with self._lock:
            return dataclasses.replace(self._totals)

    def program(self, name: str) -> Optional[ProgramRecord]:
        with self._lock:
            p = self._programs.get(name)
            return _copy(p) if p is not None else None

    def programs(self) -> List[ProgramRecord]:
        with self._lock:
            return [_copy(p) for p in self._programs.values()]

    def ready_on(self, name: str, thread: int) -> int:
        """Executables made ready for ``name`` on thread ``thread``."""
        with self._lock:
            p = self._programs.get(name)
            return p.ready_on.get(thread, 0) if p is not None else 0

    def first_ready_of(self, names: Iterable[str]
                       ) -> Optional[ProgramRecord]:
        """Of the programs ``names``, the one whose executable was ready
        first; None where none was."""
        ready = [p for p in map(self.program, names)
                 if p is not None and p.first_ready is not None]
        return min(ready, key=lambda p: p.first_ready[1], default=None)

    def setup_scalars(self, prefix: str) -> Dict[str, float]:
        t = self.totals()
        return {f"{prefix}/setup_trace_s": t.trace_s,
                f"{prefix}/setup_lower_s": t.lower_s,
                f"{prefix}/setup_compile_s": t.compile_s,
                f"{prefix}/setup_load_s": t.load_s,
                f"{prefix}/setup_traces": float(t.traces)}

    def setup_line(self, top: int = 5) -> str:
        """One line: the totals, then the ``top`` programs by seconds on
        their compile path (name, traces, trace / self / lower /
        compile-or-load seconds; self = its trace spans less the spans
        inside them: a step's own equations, or a kernel body traced while
        its caller lowered)."""
        t = self.totals()
        worst = sorted(self.programs(), key=lambda p: p.setup_s,
                       reverse=True)[:top]
        parts = [f"{p.name} x{p.traces} {p.trace_s:.2f}/{p.self_s:.2f}/"
                 f"{p.lower_s:.2f}/{p.compile_s + p.load_s:.2f}s"
                 for p in worst]
        return (f"[setup] trace {t.trace_s:.2f} s, lower {t.lower_s:.2f} s, "
                f"compile {t.compile_s:.2f} s, load {t.load_s:.2f} s, "
                f"{t.traces} traces; top (trace/self/lower/ready): "
                + ", ".join(parts))


def _copy(p: ProgramRecord) -> ProgramRecord:
    return dataclasses.replace(p, ready_on=dict(p.ready_on))


_record: Optional[CompileRecord] = None
_record_lock = threading.Lock()


def install_compile_record() -> Optional[CompileRecord]:
    """This process's compile-path record, listening from the first call
    on; later calls return the same record.  None where JAX cannot be
    imported (such a process compiles nothing)."""
    global _record
    with _record_lock:
        if _record is None:
            try:
                _record = CompileRecord().listen()
            except ImportError:
                return None
        return _record


def compile_record() -> Optional[CompileRecord]:
    """The installed record, or None where nothing installed one."""
    return _record


def report_setup(writer, step: int, prefix: str = "learner") -> None:
    """Write the record once into a role's scalar stream
    (``<prefix>/setup_trace_s`` … ``setup_traces``) and print its
    ``[setup]`` line to stderr: the operator's view of set-up, called once
    the role's first step program has run."""
    record = compile_record()
    if record is None:
        return
    writer.scalars(record.setup_scalars(prefix), step=step)
    print(record.setup_line(), file=sys.stderr, flush=True)
