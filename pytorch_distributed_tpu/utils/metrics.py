"""Metrics writer.

The reference logs scalars through a dedicated logger process into
TensorBoard (tensorboardX SummaryWriter, reference
core/single_processes/dqn_logger.py:15) with the global learner step as the
x-axis for everything.  Here the writer is a small append-only JSONL sink
(always on — machine-readable for tools/CI) plus TensorBoard event files via
``torch.utils.tensorboard`` when available; scalar names match the reference
so existing dashboards carry over (``evaluator/avg_reward``,
``actor/total_nframes``, ``learner/critic_loss``, ... — reference
dqn_logger.py:23-55).

Three row kinds share ``scalars.jsonl`` (discriminated by ``kind``,
scalars carry none for backward compatibility):

- scalar     — ``{tag, value, step, wall}``
- histogram  — ``{tag, kind: "histogram", count, mean, p50, p95, max,
  step, wall}``: a distribution summarized at the WRITER (utils/tracing.py
  span reservoirs land here); percentiles, not just means, because stalls
  live in the tail.
- span       — ``{tag, kind: "span", span, role, trace_id, value, step,
  wall}``: one sampled distributed-trace event (JSONL only — per-event
  TensorBoard points would drown the dashboards).

Every row is stamped with ``role`` and ``run_id`` when the writer knows
them, so merging the JSONL streams of a multi-role/multi-host run never
relies on directory layout.
"""

from __future__ import annotations

import json
import math
import os
import time
from typing import Dict, List, Optional, Sequence


def summarize_histogram(values: Sequence[float]) -> Dict[str, float]:
    """count/mean/p50/p95/max of a sample set.  Nearest-rank percentiles
    (no interpolation): deterministic, and an observed-value answer —
    "the p95 enqueue was THIS put" — which is what latency forensics
    wants."""
    vals = sorted(float(v) for v in values)
    n = len(vals)
    if n == 0:
        raise ValueError("summarize_histogram of an empty sample")

    def pct(q: float) -> float:
        return vals[min(n - 1, max(0, math.ceil(q * n) - 1))]

    return {"count": n, "mean": sum(vals) / n,
            "p50": pct(0.50), "p95": pct(0.95), "max": vals[-1]}


class MetricsWriter:
    def __init__(self, log_dir: str, enable_tensorboard: bool = True,
                 role: Optional[str] = None, run_id: Optional[str] = None):
        self.log_dir = log_dir
        self.role = role
        self.run_id = run_id
        os.makedirs(log_dir, exist_ok=True)
        self._jsonl = open(os.path.join(log_dir, "scalars.jsonl"), "a",
                           buffering=1)
        self._tb = None
        if enable_tensorboard:
            try:
                from torch.utils.tensorboard import SummaryWriter

                self._tb = SummaryWriter(log_dir=log_dir)
            except Exception:  # noqa: BLE001 - TB is best-effort
                self._tb = None

    def _write(self, rec: dict) -> None:
        # setdefault: a row carrying its own attribution (e.g. a span
        # recorded by the gateway but flushed by the learner's writer)
        # keeps it
        if self.role is not None:
            rec.setdefault("role", self.role)
        if self.run_id is not None:
            rec.setdefault("run_id", self.run_id)
        self._jsonl.write(json.dumps(rec) + "\n")

    def scalar(self, tag: str, value: float, step: int,
               wall: Optional[float] = None) -> None:
        rec = {"tag": tag, "value": float(value), "step": int(step),
               "wall": wall if wall is not None else time.time()}
        self._write(rec)
        if self._tb is not None:
            # explicit walltime: TB's wall-clock view must show the same
            # capture-true timestamps the JSONL rows carry
            self._tb.add_scalar(tag, float(value), int(step),
                                walltime=rec["wall"])

    def scalars(self, kv: dict, step: int,
                wall: Optional[float] = None) -> None:
        if wall is None:
            wall = time.time()
        for tag, value in kv.items():
            self.scalar(tag, value, step, wall)

    def histogram(self, tag: str, values: Sequence[float], step: int,
                  wall: Optional[float] = None,
                  count: Optional[int] = None) -> None:
        """One summarized-distribution row (p50/p95/max, not just the
        mean); mirrored to TensorBoard as ``<tag>/p50|p95|max`` scalars
        so tail latency is a dashboard read.  ``count`` overrides the
        reported event count when ``values`` is a bounded reservoir of a
        larger population (utils/tracing.py Tracer reservoirs)."""
        if not values:
            return
        s = summarize_histogram(values)
        rec = {"tag": tag, "kind": "histogram", "step": int(step),
               "wall": wall if wall is not None else time.time()}
        rec.update(s)
        if count is not None:
            rec["count"] = int(count)
        self._write(rec)
        if self._tb is not None:
            for k in ("p50", "p95", "max"):
                self._tb.add_scalar(f"{tag}/{k}", float(s[k]), int(step),
                                    walltime=rec["wall"])

    def bucket_histogram(self, tag: str, counts, *, log10_lo: float,
                         log10_hi: float, step: int,
                         wall: Optional[float] = None,
                         extra: Optional[dict] = None) -> None:
        """One pre-bucketed distribution row (``kind: "buckets"``) —
        for distributions summarized at the SOURCE (the ISSUE-8
        priority X-ray buckets its leaves in-jit on device so only the
        counts cross to the host; raw values never exist host-side).
        ``counts`` spans the fixed log10 grid [log10_lo, log10_hi);
        ``extra`` scalars (ess, mass, ...) ride the same row.  JSONL
        only — TB gets the companion scalar rows the caller writes."""
        rec = {"tag": tag, "kind": "buckets", "step": int(step),
               "wall": wall if wall is not None else time.time(),
               "counts": [int(c) for c in counts],
               "log10_lo": float(log10_lo), "log10_hi": float(log10_hi)}
        if extra:
            rec.update({k: (float(v) if isinstance(v, (int, float))
                            else v) for k, v in extra.items()})
        self._write(rec)

    def span(self, span: str, role: str, trace_id: str, dur_ms: float,
             step: int = 0, wall: Optional[float] = None) -> None:
        """One sampled distributed-trace event (utils/tracing.py).  JSONL
        only — per-event TB points would drown the dashboards."""
        self._write({"tag": f"trace/{role}/{span}", "kind": "span",
                     "span": span, "role": role, "trace_id": trace_id,
                     "value": float(dur_ms), "step": int(step),
                     "wall": wall if wall is not None else time.time()})

    def flush(self) -> None:
        self._jsonl.flush()
        if self._tb is not None:
            self._tb.flush()

    def close(self) -> None:
        self.flush()
        self._jsonl.close()
        if self._tb is not None:
            self._tb.close()


class ScalarsTail:
    """Incremental reader of a run dir's ``scalars.jsonl`` for refresh
    loops (tools/fleet_top.py ``--metrics``): remembers the byte offset
    of the last fully-terminated line, so each ``poll()`` costs O(new
    rows) instead of O(run) — a long run's metrics file grows without
    bound and a full ``read_scalars`` per refresh turns the monitor
    itself into the I/O hog.

    Torn-tail handling follows read_scalars' philosophy with one
    refinement the offset makes possible: a trailing line WITHOUT a
    newline is not consumed at all (the writer may still be mid-append
    — next poll re-reads it complete), while a newline-terminated line
    that still fails to decode (a SIGKILL-torn line mid-file) is
    skipped for good.  A file that shrank (rotation, a fresh run
    reusing the dir) resets the cursor to the start.

    ``max_bytes`` bounds one poll's read (the T_METRICS push path,
    utils/telemetry.MetricsPusher): a pusher that fell far behind — or
    attached to an old, huge stream — catches up over several cadences
    instead of encoding the whole backlog into one wire frame.  A
    bounded read that lands mid-line simply resumes from the last
    complete newline next poll; a single line LONGER than the bound
    (impossible for well-formed scalar rows) is dropped rather than
    livelocking the cursor."""

    def __init__(self, log_dir: str, max_bytes: Optional[int] = None):
        self.path = os.path.join(log_dir, "scalars.jsonl")
        self._offset = 0
        self._max_bytes = max_bytes

    def poll(self) -> List[dict]:
        """All rows appended since the previous poll (up to the
        ``max_bytes`` read bound when one is set)."""
        try:
            with open(self.path, "rb") as f:
                f.seek(0, os.SEEK_END)
                size = f.tell()
                if size < self._offset:
                    self._offset = 0  # truncated/rotated: start over
                f.seek(self._offset)
                data = (f.read() if self._max_bytes is None
                        else f.read(self._max_bytes))
        except OSError:
            return []
        end = data.rfind(b"\n")
        if end < 0:
            if (self._max_bytes is not None
                    and len(data) >= self._max_bytes):
                # one line wider than the whole read bound: skip it or
                # every future poll re-reads the same undecodable chunk
                self._offset += len(data)
            return []  # only an unterminated tail so far — wait
        self._offset += end + 1
        out = []
        for line in data[:end + 1].splitlines():
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line.decode()))
            except (ValueError, UnicodeDecodeError):
                continue  # torn mid-file line (kill); the rest is good
        return out


def is_scalar_row(rec: dict) -> bool:
    """True for plain scalar rows of the JSONL schema (module
    docstring): a ``tag`` + numeric ``value`` and no distribution
    ``kind``.  The telemetry aggregator (utils/telemetry.py) and the
    T_METRICS push path admit only these — histogram/span/bucket rows
    are already summarized at their writer."""
    return (isinstance(rec, dict) and "tag" in rec
            and isinstance(rec.get("value"), (int, float))
            and rec.get("kind") in (None, "scalar"))


def read_scalars(log_dir: str) -> List[dict]:
    """Load all JSONL records from a run dir (tests/tools use this).
    A SIGKILL mid-write leaves a torn trailing line — skip undecodable
    lines instead of raising, matching the torn-artifact philosophy of
    the checkpoint tier (utils/checkpoint.py: a torn epoch is skipped,
    never fatal)."""
    path = os.path.join(log_dir, "scalars.jsonl")
    if not os.path.exists(path):
        return []
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except ValueError:
                continue  # torn line (kill mid-write); the rest is good
    return out
