"""Training health sentinel: numeric guards, anomaly detection, quarantine.

The fleet survives *process* faults — DCN reconnects, SIGKILL with
crash-consistent epochs, post-mortem blackboxes — but until this module
nothing protected the *training computation*: one NaN gradient reached
Adam and every parameter was garbage forever, one poisoned experience
chunk sat in replay getting re-sampled, and an alive-but-stuck worker
stalled the run with exit code 0 never arriving.  This module is the
detection half of the detection → containment → recovery ladder:

- **in-jit numeric guards** (``finite_guard``): wraps any learner train
  step ``(TrainState, batch) -> (TrainState, metrics, td)`` so a step
  whose loss/grad-norm/TD comes out non-finite is *skipped* — params,
  opt-state and the step counter pass through unchanged via an in-graph
  select, and the returned metrics carry ``learner/skipped`` so the PER
  write-back paths (memory/device_per.py, memory/device_sequence.py, the
  host path in agents/learner.py) suppress the priority scatter for that
  step.  The guard is pure XLA — no host syncs, no extra dispatches —
  and costs a handful of selects: on the chip 0.016 ms of the 0.353 ms
  Ape-X update, but 34 ms of the hybrid trunk's 438 (it selects over the
  whole 8.4 GB train state; PERF.md sections 5 and 7).
- **host-side anomaly detection** (``AnomalyDetector``): rolling EWMA
  z-score on the loss, grad-norm spike ratio, |TD| explosion,
  priority-mass collapse and the skipped-step counter, evaluated on the
  learner's stats cadence.  Past ``anomaly_threshold`` consecutive
  anomalous windows the learner triggers an automatic rollback to the
  last good checkpoint epoch (agents/learner.py; bounded by
  ``max_rollbacks`` before the run fails fast).
- **ingest quarantine** (``ChunkValidator`` + ``QuarantineStore``):
  transitions are validated at the single-owner ingest boundaries —
  the learner-side queue drains (memory/feeder.py QueueOwner,
  memory/device_replay.py DeviceReplayIngest) and the DCN gateway
  (parallel/dcn.py) — and offenders are written to
  ``{log_dir}/quarantine/<source>-<n>.npz`` with their trace id instead
  of entering replay.  Per-source counters feed the T_STATUS health
  plane so ``fleet_top`` shows which actor is poisoning.

Knobs live in ``config.HealthParams``; every field is env-overridable as
``TPU_APEX_HEALTH_<FIELD>`` (the same spawn-inheritance trick the fault
planes use), so drills and fleet launchers can flip them without
plumbing.  ``TPU_APEX_QUARANTINE=0`` kills the ingest-validation plane
entirely (chunks flow unchecked, the pre-sentinel behaviour).

The hang-watchdog half of the sentinel lives in utils/supervision.py
(``ProgressBoard``) and the supervisors (runtime.py, fleet.py).
"""

from __future__ import annotations

import dataclasses
import math
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from pytorch_distributed_tpu.utils.profiling import PHASE_OPTIMIZER

# metrics key every consumer of the guard keys on: 1.0 for a skipped
# (non-finite) substep, 0.0 otherwise; summed — not last-sampled — over
# fused multi-step dispatches (reduce_scan_metrics)
SKIPPED_KEY = "learner/skipped"
# rounds the row exchange out of a dp-sharded ring ran for a substep's
# draw (memory/device_replay.py exchange_rounds; absent on one device);
# a dispatch reports the MEAN over its K substeps: 1.0 = every update fit
# one bounded exchange
EXCHANGE_ROUNDS_KEY = "learner/exchange_rounds"

_ENV_PREFIX = "TPU_APEX_HEALTH_"


def _env_flag(name: str, default: bool) -> bool:
    raw = os.environ.get(name)
    if raw is None:
        return default
    return raw.strip().lower() not in ("0", "false", "off", "no", "")


def resolve(hp) -> Any:
    """Apply ``TPU_APEX_HEALTH_<FIELD>`` env overrides to a HealthParams
    (config.py) — same override-by-env contract as the fault planes, so
    a drill can flip sentinel knobs on spawn children without threading
    them through every constructor.  Returns a NEW instance; the input
    is never mutated (Options rides spawn pickles)."""
    changes = {}
    for f in dataclasses.fields(hp):
        raw = os.environ.get(_ENV_PREFIX + f.name.upper())
        if raw is None:
            continue
        if f.type in ("bool", bool) or isinstance(getattr(hp, f.name), bool):
            changes[f.name] = raw.strip().lower() not in (
                "0", "false", "off", "no", "")
        elif isinstance(getattr(hp, f.name), int):
            changes[f.name] = int(float(raw))
        else:
            changes[f.name] = float(raw)
    return dataclasses.replace(hp, **changes) if changes else hp


def quarantine_active() -> bool:
    """Is the ingest-validation plane on in this process?  Default on —
    the per-transition cost is a few scalar finiteness checks (image
    states are uint8 and skip the array scan entirely)."""
    return _env_flag("TPU_APEX_QUARANTINE", True)


# ---------------------------------------------------------------------------
# in-jit numeric guards
# ---------------------------------------------------------------------------

def finite_guard(step_fn):
    """Wrap a ``(TrainState, batch) -> (TrainState, metrics, td)`` train
    step with an in-graph finite check: when any metric scalar (loss,
    grad norm, ...) or the TD/priority output is non-finite, the ENTIRE
    candidate state is discarded and the input state passes through
    unchanged (``jnp.where`` select per leaf — donation-safe, no host
    round trip), so one bad batch never reaches Adam, the target net, or
    the step counter.  ``metrics[SKIPPED_KEY]`` reports the skip; the
    raw (possibly non-finite) loss stays in the metrics so the host-side
    anomaly detector sees what actually happened.  TD output is zeroed
    on a skip so a write-back path that ignores the flag still cannot
    scatter NaN priorities."""
    import jax
    import jax.numpy as jnp

    def guarded(state, batch):
        new_state, metrics, td = step_fn(state, batch)
        with jax.named_scope(PHASE_OPTIMIZER):
            ok = jnp.all(jnp.isfinite(td))
            for v in metrics.values():
                ok = ok & jnp.all(jnp.isfinite(v))
            sel = lambda n, o: jax.tree_util.tree_map(
                lambda a, b: jnp.where(ok, a, b), n, o)
            out_state = sel(new_state, state)
            out_td = jnp.where(ok, td, jnp.zeros_like(td))
            metrics = dict(metrics)
            metrics[SKIPPED_KEY] = 1.0 - ok.astype(jnp.float32)
        return out_state, metrics, out_td

    return guarded


def reduce_scan_metrics(metrics):
    """Collapse a scanned fused dispatch's stacked substep metrics to one
    row: the last substep's value per key — the sampling contract the
    learner's stats cadence already has — EXCEPT counter-like keys:
    ``learner/skipped`` sums over the scan, so a dispatch reports how
    many of its K substeps were skipped, not just whether the last one
    was, and ``learner/exchange_rounds`` is their mean (one skewed draw
    in K must show)."""
    import jax
    import jax.numpy as jnp

    over_scan = {SKIPPED_KEY: jnp.sum, EXCHANGE_ROUNDS_KEY: jnp.mean}
    with jax.named_scope(PHASE_OPTIMIZER):
        if not isinstance(metrics, dict):
            return jax.tree_util.tree_map(lambda x: x[-1], metrics)
        return {k: (over_scan[k](v, axis=0) if k in over_scan else v[-1])
                for k, v in metrics.items()}


def suppress_writeback(ok_flag, updated_replay, prior_replay):
    """Select between a priority-updated replay state and the untouched
    one on the guard's skip flag — the fused PER planes call this so a
    skipped step's (zeroed) TD never overwrites real priorities."""
    import jax
    import jax.numpy as jnp

    ok = ok_flag < 0.5  # SKIPPED_KEY semantics: 1.0 == skipped
    return jax.tree_util.tree_map(
        lambda n, o: jnp.where(ok, n, o), updated_replay, prior_replay)


# ---------------------------------------------------------------------------
# priority X-ray (ISSUE 8): distribution telemetry over the PER leaves
# ---------------------------------------------------------------------------

# fixed log10 bucket grid shared with the in-jit device twin
# (memory/device_per.priority_xray_device) so fleet_top renders either
PRIORITY_XRAY_LOG10_LO = -6.0
PRIORITY_XRAY_LOG10_HI = 3.0


def provenance_stats(prov, current_version: int,
                     learner_step: int) -> Optional[Dict[str, Any]]:
    """The data-plane staleness math, shared by the learner's stats
    cadence (agents/learner.py) and tests/test_provenance.py, which
    holds it to hand-computed rows.  ``prov`` is an (n, 4) provenance matrix;
    sentinel rows (actor_id < 0) are masked out.  Returns None when no
    row carries provenance, else arrays ``staleness`` (versions),
    ``age`` (learner steps) and ``shares`` (per-actor sample
    fraction)."""
    prov = np.asarray(prov)
    known = prov[prov[:, 0] >= 0]
    if not len(known):
        return None
    _ids, cnt = np.unique(known[:, 0], return_counts=True)
    return {
        "staleness": np.maximum(current_version - known[:, 2], 0),
        "age": np.maximum(learner_step - known[:, 3], 0),
        "shares": cnt / float(len(known)),
    }


def priority_xray(leaves, bins: int = 16) -> Optional[Dict[str, Any]]:
    """Summarize a PER leaf vector (p^alpha units) into the data-plane
    X-ray: a log10-bucketed histogram over the fixed [1e-6, 1e3) decade
    grid, the effective sample size ``(sum p)^2 / sum p^2`` (how many
    rows the sampler EFFECTIVELY draws from — n means uniform, ~1 means
    one row dominates), and its fraction of the row count.  This is the
    distribution the AnomalyDetector consumes instead of a bare mass
    ratio: mass can look healthy while ESS has collapsed onto a handful
    of rows.  Returns None for an empty/all-zero leaf set."""
    p = np.asarray(leaves, dtype=np.float64)
    p = p[p > 0]
    if p.size == 0:
        return None
    s1, s2 = float(p.sum()), float((p * p).sum())
    ess = (s1 * s1 / s2) if s2 > 0 else 0.0
    logp = np.log10(np.maximum(p, 10.0 ** PRIORITY_XRAY_LOG10_LO))
    t = (logp - PRIORITY_XRAY_LOG10_LO) / (
        PRIORITY_XRAY_LOG10_HI - PRIORITY_XRAY_LOG10_LO)
    b = np.clip((t * bins).astype(np.int64), 0, bins - 1)
    counts = np.bincount(b, minlength=bins)[:bins]
    return {
        "rows": int(p.size),
        "mass": s1,
        "ess": ess,
        "ess_frac": ess / p.size,
        "counts": counts,
        "log10_lo": PRIORITY_XRAY_LOG10_LO,
        "log10_hi": PRIORITY_XRAY_LOG10_HI,
        "p_max": float(p.max()),
    }


# ---------------------------------------------------------------------------
# host-side rolling anomaly detection
# ---------------------------------------------------------------------------

class _Ewma:
    """Exponentially weighted mean/std with a warmup count."""

    def __init__(self, decay: float = 0.97):
        self.decay = decay
        self.n = 0
        self.mean = 0.0
        self.var = 0.0

    def update(self, x: float) -> None:
        self.n += 1
        if self.n == 1:
            self.mean = x
            return
        d = x - self.mean
        self.mean += (1.0 - self.decay) * d
        self.var = self.decay * (self.var + (1.0 - self.decay) * d * d)

    @property
    def std(self) -> float:
        return math.sqrt(max(self.var, 0.0))


class AnomalyDetector:
    """Rolling divergence detector fed on the learner's stats cadence.

    ``observe(...)`` returns the list of anomaly labels this window
    tripped (empty = healthy) and maintains the consecutive-anomalous-
    window streak; ``should_rollback()`` is true once the streak reaches
    ``threshold``.  Signals:

    - ``nonfinite``        — loss or grad norm is NaN/inf (a guard skip
      that still surfaced, or a guardless run diverging);
    - ``skipped``          — the in-jit guard skipped >= 1 step in the
      window;
    - ``loss_spike``       — loss z-score against its own EWMA above
      ``zmax`` (warmup: the first ``warmup`` windows never trip);
    - ``grad_spike``       — grad norm above ``grad_spike`` x its EWMA;
    - ``td_explosion``     — mean |TD| above ``grad_spike`` x its EWMA;
    - ``priority_collapse``— the PER distribution stopped doing useful
      work: total mass fell to ~0 while the buffer holds rows, or —
      with the ISSUE-8 priority X-ray wired in — the normalized
      effective sample size (``priority_ess`` = ESS / rows) fell under
      ``ess_floor``: mass can look healthy while sampling has
      concentrated onto a handful of rows.
    """

    WARMUP = 8

    def __init__(self, zmax: float = 8.0, grad_spike: float = 100.0,
                 threshold: int = 3, ess_floor: float = 0.02):
        self.zmax = zmax
        self.grad_spike = grad_spike
        self.ess_floor = ess_floor
        self.threshold = max(1, int(threshold))
        self.loss = _Ewma()
        self.grad = _Ewma()
        self.td = _Ewma()
        self.streak = 0
        self.windows = 0
        self.anomalies_total = 0

    def observe(self, loss: Optional[float] = None,
                grad_norm: Optional[float] = None,
                td_mean: Optional[float] = None,
                priority_mass: Optional[float] = None,
                replay_rows: int = 0,
                skipped: float = 0.0,
                priority_ess: Optional[float] = None) -> List[str]:
        self.windows += 1
        out: List[str] = []
        if skipped and skipped > 0:
            out.append("skipped")
        for val, ewma, spike_label in ((loss, self.loss, "loss_spike"),
                                       (grad_norm, self.grad, "grad_spike"),
                                       (td_mean, self.td, "td_explosion")):
            if val is None:
                continue
            if not math.isfinite(val):
                if "nonfinite" not in out:
                    out.append("nonfinite")
                continue  # never fold infinities into the EWMA
            warm = ewma.n >= self.WARMUP
            if warm and spike_label == "loss_spike":
                z = abs(val - ewma.mean) / max(ewma.std, 1e-12)
                if z > self.zmax:
                    out.append(spike_label)
            elif warm and abs(val) > self.grad_spike * max(
                    abs(ewma.mean), 1e-12):
                out.append(spike_label)
            if spike_label not in out:
                # anomalous readings stay OUT of the baseline: a spike
                # that shifted its own EWMA would mask the next one
                ewma.update(val)
        if replay_rows > 0 and (
                (priority_mass is not None and priority_mass <= 1e-12)
                or (priority_ess is not None
                    and priority_ess < self.ess_floor)):
            out.append("priority_collapse")
        self.streak = self.streak + 1 if out else 0
        self.anomalies_total += len(out)
        return out

    def should_rollback(self) -> bool:
        return self.streak >= self.threshold

    def reset(self) -> None:
        """Post-rollback: restart the streak AND the baselines — the
        restored epoch's loss scale may legitimately differ from the
        diverged tail's."""
        self.loss = _Ewma()
        self.grad = _Ewma()
        self.td = _Ewma()
        self.streak = 0


# ---------------------------------------------------------------------------
# ingest validation + quarantine
# ---------------------------------------------------------------------------

def poison_items(items):
    """Deterministically poison a ``[(Transition, priority), ...]`` chunk
    — the ``poison_chunk`` fault verb's payload (utils/faults.py):
    rewards go NaN, priorities go NaN (the garbage a diverged actor
    would compute), and float observations go NaN too (uint8 frames
    cannot hold NaN, so image chunks poison through the scalars).
    Preserves a TracedChunk wrapper so the quarantine file keeps the
    trace id."""
    out = []
    for t, _p in items:
        repl = {"reward": np.asarray(t.reward).dtype.type(np.nan)}
        s0 = np.asarray(t.state0)
        if s0.dtype.kind == "f":
            repl["state0"] = np.full_like(s0, np.nan)
        out.append((t._replace(**repl), float("nan")))
    from pytorch_distributed_tpu.utils import tracing

    if isinstance(items, tracing.TracedChunk):
        return tracing.TracedChunk(out, trace_id=items.trace_id,
                                   born=items.born)
    return out

def _finite_scalar(x) -> bool:
    try:
        return bool(np.isfinite(x))
    except TypeError:
        return False


class ChunkValidator:
    """Per-ingest-boundary transition validator.

    Checks, per ``(Transition, priority)`` item: non-finite
    obs/reward/gamma/terminal (float state arrays scanned; integer
    states — the uint8 Atari rows — cannot hold NaN and skip the array
    scan), non-finite or negative priority, non-finite float actions,
    discrete actions outside ``[0, num_actions)``, and shape/dtype
    drift against the expected schema.  The schema comes from the
    owning memory when it declares one (``state_shape``/``state_dtype``)
    and is otherwise latched from the first item seen — drift mid-run
    is what poisons a fixed-schema ring."""

    def __init__(self, state_shape: Optional[Tuple[int, ...]] = None,
                 state_dtype=None, num_actions: Optional[int] = None):
        self.state_shape = tuple(state_shape) if state_shape else None
        self.state_dtype = np.dtype(state_dtype) if state_dtype else None
        self.num_actions = num_actions
        self.checked = 0
        self.rejected = 0
        # Segment rows carry (T+1, *state_shape) (or frame-packed)
        # observations — latched from the first row, never compared to
        # the memory's PER-STEP state_shape
        self._seg_obs_shape: Optional[Tuple[int, ...]] = None

    @classmethod
    def for_memory(cls, memory) -> "ChunkValidator":
        return cls(state_shape=getattr(memory, "state_shape", None),
                   state_dtype=getattr(memory, "state_dtype", None))

    def _check(self, t, priority) -> Optional[str]:
        if priority is not None and (
                not _finite_scalar(priority) or float(priority) < 0.0):
            return f"invalid priority {priority!r}"
        if not hasattr(t, "state0"):
            # R2D2 Segment row (memory/sequence_replay.py): vector
            # fields per step, no six-column schema.  Until this branch
            # the validator scalar-checked t.reward — a (T,) array —
            # and crashed the learner's first drain on every sequence
            # topology with quarantine active (found driving config 13
            # under the ISSUE-9 verification pass).
            return self._check_segment(t)
        for name in ("reward", "gamma_n", "terminal1"):
            if not _finite_scalar(getattr(t, name)):
                return f"non-finite {name}"
        for name in ("state0", "state1"):
            arr = np.asarray(getattr(t, name))
            if self.state_shape is None:
                self.state_shape = arr.shape
            elif arr.shape != self.state_shape:
                return (f"{name} shape {arr.shape} != "
                        f"expected {self.state_shape}")
            if self.state_dtype is None:
                self.state_dtype = arr.dtype
            elif arr.dtype != self.state_dtype:
                return (f"{name} dtype {arr.dtype} != "
                        f"expected {self.state_dtype}")
            if arr.dtype.kind == "f" and not np.isfinite(arr).all():
                return f"non-finite {name}"
        a = np.asarray(t.action)
        if a.dtype.kind == "f" and not np.isfinite(a).all():
            return "non-finite action"
        if (self.num_actions is not None and a.dtype.kind in "iu"
                and a.size and not ((a >= 0) & (a < self.num_actions)).all()):
            return f"action out of range [0, {self.num_actions})"
        return None

    def _check_segment(self, t) -> Optional[str]:
        """Segment-row validation: finiteness over the per-step vector
        fields, obs shape/dtype drift latched from the first row (a
        segment's obs is the whole window — (T+1, *state_shape), or the
        frame-packed (T+C, H, W) — so the memory's per-step
        ``state_shape`` must not be compared against it)."""
        for name in ("reward", "terminal", "mask"):
            arr = np.asarray(getattr(t, name, 0.0))
            if arr.dtype.kind == "f" and not np.isfinite(arr).all():
                return f"non-finite {name}"
        obs = np.asarray(t.obs)
        if self._seg_obs_shape is None:
            self._seg_obs_shape = obs.shape
        elif obs.shape != self._seg_obs_shape:
            return (f"obs shape {obs.shape} != "
                    f"expected {self._seg_obs_shape}")
        if self.state_dtype is None:
            self.state_dtype = obs.dtype
        elif obs.dtype != self.state_dtype:
            return (f"obs dtype {obs.dtype} != "
                    f"expected {self.state_dtype}")
        if obs.dtype.kind == "f" and not np.isfinite(obs).all():
            return "non-finite obs"
        for name in ("c0", "h0"):
            arr = np.asarray(getattr(t, name, 0.0))
            if arr.dtype.kind == "f" and not np.isfinite(arr).all():
                return f"non-finite {name}"
        a = np.asarray(t.action)
        if a.dtype.kind == "f" and not np.isfinite(a).all():
            return "non-finite action"
        if (self.num_actions is not None and a.dtype.kind in "iu"
                and a.size and not ((a >= 0) & (a < self.num_actions)).all()):
            return f"action out of range [0, {self.num_actions})"
        return None

    def filter(self, items) -> Tuple[list, List[Tuple[Any, Optional[float],
                                                      str]]]:
        """Split ``[(Transition, priority), ...]`` into (clean items,
        rejected ``(transition, priority, reason)`` triples).  The clean
        list preserves the input's TracedChunk identity when nothing was
        rejected (the common case costs no copy of the wrapper)."""
        self.checked += len(items)
        bad: List[Tuple[Any, Optional[float], str]] = []
        good: list = []
        for t, p in items:
            reason = self._check(t, p)
            if reason is None:
                good.append((t, p))
            else:
                bad.append((t, p, reason))
        self.rejected += len(bad)
        if not bad:
            return items, bad
        from pytorch_distributed_tpu.utils import tracing

        if isinstance(items, tracing.TracedChunk):
            good = tracing.TracedChunk(good, trace_id=items.trace_id,
                                       born=items.born)
        return good, bad


class QuarantineStore:
    """One ingest source's quarantine sink: rejected transitions land in
    ``{log_dir}/quarantine/<source>-<n>.npz`` (columns best-effort
    stacked, plus ``reason``/``trace_id`` columns) instead of replay.
    The directory rides the same per-process configuration as the
    flight recorder (``flight_recorder.configure`` / the
    ``TPU_APEX_BLACKBOX_DIR`` spawn-inheritance env), so no new
    plumbing reaches the workers.  Bounded: past ``max_files`` writes
    the store only counts — a poisoning actor must not fill the disk
    before the supervisor reacts."""

    # single-owner declaration (apexlint): quarantine diversion happens
    # at the declared ingest boundaries only — QueueOwner.drain, the
    # device ingest drains, and the DCN gateway's per-slot validator;
    # a caller elsewhere would hide data-loss from those counters
    __apex_mutators__ = ("put",)
    __apex_owner__ = ("memory.", "parallel.dcn", "utils.health")

    def __init__(self, source: str, max_files: int = 64):
        self.source = source
        self.max_files = max_files
        self.count = 0       # transitions quarantined (lifetime)
        self.files = 0       # files actually written
        self.last_path: Optional[str] = None
        self._lock = threading.Lock()

    def _dir(self) -> Optional[str]:
        from pytorch_distributed_tpu.utils import flight_recorder

        base = flight_recorder._dump_dir()
        return os.path.join(base, "quarantine") if base else None

    def put(self, rejected, trace_id: int = 0) -> Optional[str]:
        """Record ``[(transition, priority, reason), ...]``; returns the
        written path (None when no log dir is configured or the file
        budget is spent — counting continues either way)."""
        if not rejected:
            return None
        with self._lock:
            self.count += len(rejected)
            n = self.files
            if n >= self.max_files:
                return None
            self.files += 1
        target = self._dir()
        if not target:
            return None
        from pytorch_distributed_tpu.utils.experience import (
            REPLAY_FIELDS, stack_prov,
        )
        from pytorch_distributed_tpu.utils import flight_recorder
        from pytorch_distributed_tpu.utils.tracing import format_trace_id

        cols: Dict[str, np.ndarray] = {}
        # transition rows dump the six replay columns; Segment rows
        # (sequence topologies) dump their own schema — the validator
        # now rejects segments too, and put() must not assume the
        # six-column shape (it crashed on the first quarantined
        # segment before this branch)
        first = rejected[0][0]
        fields = (REPLAY_FIELDS if hasattr(first, "state0")
                  else tuple(f for f in getattr(first, "_fields", ())
                             if f != "prov"))
        for f in fields:
            vals = [np.asarray(getattr(t, f, np.zeros(0)))
                    for t, _p, _r in rejected]
            try:
                cols[f] = np.stack(vals)
            except ValueError:  # shape-drifted offenders can't stack
                cols[f] = np.array([str(v.shape) + ":" + str(v.dtype)
                                    for v in vals])
        cols["priority"] = np.array(
            [np.nan if p is None else float(p) for _t, p, _r in rejected],
            dtype=np.float64)
        cols["reason"] = np.array([r for _t, _p, r in rejected])
        cols["trace_id"] = np.array([format_trace_id(trace_id)])
        # correlation keys (ISSUE 8 satellite): per-row provenance,
        # capture wall clock and run id — tools/timeline.py joins
        # quarantine files to the incident timeline by these, never by
        # directory layout
        cols["prov"] = stack_prov([(t, p) for t, p, _r in rejected])
        cols["wall"] = np.array([time.time()], dtype=np.float64)
        rid = flight_recorder.run_id()
        if rid:
            cols["run_id"] = np.array([rid])
        safe = "".join(c if (c.isalnum() or c in "-_.") else "_"
                       for c in self.source) or "source"
        path = os.path.join(target, f"{safe}-{n:05d}.npz")
        try:
            os.makedirs(target, exist_ok=True)
            tmp = path + ".tmp.npz"
            np.savez(tmp, **cols)
            os.replace(tmp, path)  # readers never see a torn file
        except OSError:
            return None  # quarantine is best-effort; counting is not
        self.last_path = path
        if n == 0:  # first offender per source is loud; the rest are
            # counters on the health plane (a poisoning actor would
            # otherwise flood the log at chunk rate)
            print(f"[health] quarantined {len(rejected)} transition(s) "
                  f"from {self.source} ({rejected[0][2]}) -> {path}",
                  flush=True)
        return path


# per-process registry, mirroring flight_recorder's: one store per
# source, aggregated counters for the T_STATUS health plane
_q_lock = threading.Lock()
_q_stores: Dict[str, QuarantineStore] = {}


# factory → owning-class mapping for apexlint's receiver resolution:
# ``get_quarantine(...).put(...)`` is a QuarantineStore mutation
__apex_factories__ = {"get_quarantine": "QuarantineStore"}


def get_quarantine(source: str, max_files: int = 64) -> QuarantineStore:
    with _q_lock:
        st = _q_stores.get(source)
        if st is None:
            st = _q_stores[source] = QuarantineStore(source,
                                                     max_files=max_files)
        return st


def quarantine_counts() -> Dict[str, int]:
    """{source: transitions quarantined} across this process — the
    health plane's read (fleet.py _health_snapshot -> T_STATUS ->
    fleet_top)."""
    with _q_lock:
        return {s: st.count for s, st in _q_stores.items() if st.count}


def reset() -> None:
    """Test isolation: drop all quarantine stores."""
    with _q_lock:
        _q_stores.clear()
