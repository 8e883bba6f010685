"""Tracing / profiling subsystem.

The reference has none — only stdout banners and TensorBoard scalars
(SURVEY.md §5 "tracing: none").  Three first-class tools here:

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

Cross-role request tracing (per-hop trace ids + latency histograms) lives
in utils/tracing.py; the post-mortem event rings in
utils/flight_recorder.py.  README "Observability" documents all three
together with the env knobs.
"""

from __future__ import annotations

import contextlib
import functools
import os
import re
import threading
import time
import warnings
from typing import Dict, Iterator, Optional

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
SCOPE_MOE = "model.moe"               # holds the three below
SCOPE_MOE_ROUTE = "moe.route"
SCOPE_MOE_EXPERTS = "moe.experts"
SCOPE_MOE_SHARED = "moe.shared"
SCOPE_HEAD = "model.head"
MODEL_SCOPES = (SCOPE_EMBED, SCOPE_SSM, SCOPE_GDN, SCOPE_KDA, SCOPE_ATTN,
                SCOPE_MLA, SCOPE_MLP, SCOPE_MOE, SCOPE_HEAD)


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
