"""Performance observability plane: live MFU, throughput attribution,
device-memory watermarks, retrace + transfer auditing, on-demand
profiling windows.

This is the LIVE plane: what a running fleet reports about itself ("is
the learner's MFU moving?", "is the fleet actor-bound right now?")
without being stopped.  It is not the yardstick: what a change does to
the system's speed is measured on the chip by ``benchmark/run.py`` and
recorded in PERF.md and ``PERF_LEDGER.jsonl``; ``benchmark/harness/
peaks.py`` is that benchmark's own peak table, ``PEAK_FLOPS`` below is
this plane's.  Podracer (Hessel et al. 2021)
treats continuous device-utilization accounting as part of the training
loop itself, and Ape-X tunes its actor/learner balance off live
throughput ratios; this module gives the fleet the same continuously
exported signals:

- **FLOPs capture** (``flops_of_compiled``): the one XLA
  ``cost_analysis()`` extraction.  A ``PerfMonitor`` captures the fused
  learner program's per-update FLOPs at compile time, so MFU is one
  multiplication per stats window forever after.
- **Live rates** (``PerfMonitor``): each role counts its work units
  (learner updates, actor env frames) with one integer add on the hot
  path; the drain on the role's normal metrics cadence turns them into
  ``learner/updates_per_s`` / ``learner/mfu`` /
  ``actor/env_frames_per_s`` scalar rows plus whatever gauges the role
  sets (replay ratio, ingest-queue utilization).
- **Memory watermarks**: device ``live``/``peak`` bytes from
  ``device.memory_stats()`` where the backend reports them (TPU), host
  RSS current/peak everywhere — an OOM that is still ten minutes away
  is a dashboard read, not a post-mortem.
- **Retrace detector** (``RetraceDetector``): registered hot-path jit
  programs are expected to compile during warmup and NEVER again; any
  executable JAX makes ready for one after the warmup mark (the
  compile-path record of utils/profiling.py) is counted, named, and
  exported — a recompile on the hot path is a silent throughput cliff
  (the no-retrace smoke in tests/test_actor_pipeline.py pins one program
  at one point in time; this watches all of them, live).
- **Transfer audit** (``TransferAudit``): opt-in
  ``jax.transfer_guard``-based attribution of IMPLICIT host<->device
  transfers on paths that must be transfer-free (the fused learner
  dispatch: state, ring and keys are all device-resident).  A flagged
  call is attributed to its python call site and retried with
  transfers allowed, so the audit observes without killing the run.
- **On-demand profile windows** (``run_profile_window``): a bounded
  ``utils/profiling.trace`` capture for the DCN gateway's sessionless
  ``T_PROFILE`` verb (parallel/dcn.py), so ``fleet_top --profile``
  pulls a real XLA trace off a RUNNING fleet without restarts.

Per-process registry (``get_monitor``) mirrors utils/tracing.py: one
monitor per role name, and ``status_snapshot()`` feeds the last drained
values into the gateway's T_STATUS health plane so ``fleet_top`` shows
them live.  Knobs live in config.PerfParams, env-overridable as
``TPU_APEX_PERF_<FIELD>`` (bare ``TPU_APEX_PERF=1`` = ``enabled``).
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
import traceback
from typing import Any, Callable, Dict, List, Optional, Tuple

from pytorch_distributed_tpu.utils import profiling

# ---------------------------------------------------------------------------
# peak FLOP/s + cost-analysis FLOPs extraction
# ---------------------------------------------------------------------------

# Peak dense bf16 FLOP/s per chip by device_kind, for the MFU estimate.
# Public figures (Google Cloud TPU documentation).  The directly attached
# v5e reports device_kind "TPU v5 lite" (chip run, ISSUE 21).  A TPU kind
# missing from this table is an error where a peak is used, never a
# default; non-TPU devices have no peak (None).
PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5e": 197e12,
    "TPU v5 lite": 197e12,
    "TPU v5p": 459e12,
    "TPU v6e": 918e12,
    "TPU v6 lite": 918e12,
}

# Peak scaling per compute dtype relative to the bf16 table above: the
# MXU runs fp32 matmuls at half the bf16 rate (two passes), so an fp32
# run scored against the bf16 peak under-reports MFU by 2x (ISSUE-13
# satellite: config.compute_dtype admits fp32, and a denominator that
# ignores it makes an fp32 run look like an MFU collapse instead of the
# same chip at its fp32 peak).
DTYPE_PEAK_SCALE = {
    "bfloat16": 1.0,
    "float32": 0.5,
}


def peak_flops_of(device, compute_dtype: Optional[str] = None
                  ) -> Optional[float]:
    """Peak dense FLOP/s for a jax device.  None for a non-TPU device
    (the CPU has no table entry and no MFU); a TPU whose ``device_kind``
    is not in ``PEAK_FLOPS`` raises — the chip path must know its
    device, and an MFU against a guessed peak is worse than none.
    ``compute_dtype`` scales the bf16 table entry to the dtype's MXU
    peak (fp32 = half); unknown dtypes keep the bf16 figure."""
    kind = getattr(device, "device_kind", "") or ""
    for name, peak in PEAK_FLOPS.items():
        if kind.lower().startswith(name.lower()):
            if compute_dtype is not None:
                peak *= DTYPE_PEAK_SCALE.get(str(compute_dtype), 1.0)
            return peak
    if getattr(device, "platform", "") == "tpu" \
            or kind.lower().startswith("tpu"):
        raise ValueError(
            f"no peak FLOP/s known for TPU device_kind {kind!r}; add it "
            f"to utils/perf.PEAK_FLOPS with its source")
    return None


def flops_of_compiled(compiled) -> Optional[float]:
    """Per-call FLOPs off a ``cost_analysis()``-bearing jax stage — an
    AOT-compiled executable, or a ``Lowered`` program where the
    backend supports pre-compile analysis (same figures, no XLA
    compile).  XLA counts a scan/while body ONCE, so for a fused
    multi-update program the figure is per-UPDATE, not per-dispatch.
    Best-effort: backends without cost analysis return None."""
    try:
        cost = compiled.cost_analysis()
        c = cost[0] if isinstance(cost, (list, tuple)) else cost
        f = (c or {}).get("flops")
        if f and f > 0:
            return float(f)
    except Exception:  # noqa: BLE001 - cost analysis is best-effort
        pass
    return None


# ---------------------------------------------------------------------------
# knob resolution (config.PerfParams + TPU_APEX_PERF_* env overrides)
# ---------------------------------------------------------------------------

_ENV_PREFIX = "TPU_APEX_PERF_"


def resolve(pp=None):
    """Apply ``TPU_APEX_PERF_<FIELD>`` env overrides to a PerfParams
    (config.py), plus the bare ``TPU_APEX_PERF`` shorthand for
    ``enabled`` — same override-by-env contract as health.resolve, so a
    drive can flip the plane on without threading knobs through every
    constructor.  Returns a NEW instance; the input is never mutated
    (Options rides spawn pickles)."""
    from pytorch_distributed_tpu.config import PerfParams

    if pp is None:
        pp = PerfParams()
    changes: Dict[str, Any] = {}
    raw_on = os.environ.get("TPU_APEX_PERF")
    if raw_on is not None:
        changes["enabled"] = raw_on.strip().lower() not in (
            "0", "false", "off", "no", "")
    for f in dataclasses.fields(pp):
        raw = os.environ.get(_ENV_PREFIX + f.name.upper())
        if raw is None:
            continue
        cur = getattr(pp, f.name)
        if isinstance(cur, bool):
            changes[f.name] = raw.strip().lower() not in (
                "0", "false", "off", "no", "")
        elif isinstance(cur, int) and not isinstance(cur, bool):
            changes[f.name] = int(float(raw))
        else:
            changes[f.name] = float(raw)
    return dataclasses.replace(pp, **changes) if changes else pp


_MXU_PREFIX = "TPU_APEX_MXU_"


def resolve_mxu(lp=None):
    """Apply ``TPU_APEX_MXU_<FIELD>`` env overrides to a
    LearnerPerfParams (config.py) — the ISSUE-13 MFU-campaign knob
    family (megabatch factor, Pallas torso), same override-by-env
    contract as ``resolve``.  Returns a NEW instance; the input is
    never mutated (Options rides spawn pickles)."""
    from pytorch_distributed_tpu.config import LearnerPerfParams

    if lp is None:
        lp = LearnerPerfParams()
    changes: Dict[str, Any] = {}
    for f in dataclasses.fields(lp):
        raw = os.environ.get(_MXU_PREFIX + f.name.upper())
        if raw is None:
            continue
        cur = getattr(lp, f.name)
        if isinstance(cur, bool):
            changes[f.name] = raw.strip().lower() not in (
                "0", "false", "off", "no", "")
        else:
            changes[f.name] = int(float(raw))
    return dataclasses.replace(lp, **changes) if changes else lp


def export_env(pp) -> None:
    """Export a RESOLVED PerfParams into the environment so spawn
    children (and their children — tools forked from workers) resolve
    the same plane even when it was enabled programmatically rather
    than by env.  setdefault: an operator's explicit env always
    wins."""
    if pp.enabled:
        os.environ.setdefault("TPU_APEX_PERF", "1")
    for f in dataclasses.fields(pp):
        val = getattr(pp, f.name)
        if val != f.default:
            os.environ.setdefault(_ENV_PREFIX + f.name.upper(),
                                  ("1" if val is True else
                                   "0" if val is False else str(val)))


# ---------------------------------------------------------------------------
# retrace detector
# ---------------------------------------------------------------------------

class RetraceDetector:
    """Counts, per registered hot-path program, the executables JAX made
    ready for it after warmup, and names the program.

    It reads the process's compile-path record (utils/profiling
    ``CompileRecord``: every backend span, compile or load, filed under
    its program name), which the first registration installs for a
    process that never called ``helpers.enable_compile_cache``.  A program is
    registered by its jitted function or by its name, on the thread that
    dispatches it; None (a server-side jit, an engine with no program of
    its own) is skipped, so callers can register unconditionally.  JAX
    reports a program by name alone, so programs are told apart by name
    AND thread: two actors of a thread fleet each watch their own ``act``,
    and programs that one thread dispatches need names of their own
    (the inference server's ``act`` / ``act_rows`` / ``roll_act``).  The FIRST
    ``check()`` is the warmup mark: everything made ready up to it is
    expected; any executable made ready after it is a retrace — a
    shape/dtype leak paying compile latency on the hot path."""

    def __init__(self):
        # label -> (program name, the thread that dispatches it)
        self._names: Dict[str, Tuple[str, int]] = {}
        self._warm: Optional[Dict[Tuple[str, int], int]] = None
        self.retraces = 0                  # post-warmup recompiles, total
        self.fired: Dict[str, int] = {}    # per-label retrace counts

    def register(self, label: str, program: Any) -> None:
        name = (program if program is None or isinstance(program, str)
                else getattr(program, "__name__", None))
        if name:
            profiling.install_compile_record()
            self._names[label] = (name, threading.get_ident())

    def _ready(self) -> Dict[Tuple[str, int], int]:
        record = profiling.compile_record()
        return {key: record.ready_on(*key) if record is not None else 0
                for key in set(self._names.values())}

    def mark_warm(self) -> None:
        """Snapshot the executables made so far as the expected set."""
        self._warm = self._ready()

    def check(self) -> List[str]:
        """Labels of programs that recompiled since the last check.  The
        first call marks warmup instead of firing (startup compiles are
        legitimate); each recompile is counted once (the high-water
        advances)."""
        if self._warm is None:
            self.mark_warm()
            return []
        grown = {}
        for key, ready in self._ready().items():
            prev = self._warm.get(key)
            self._warm[key] = ready
            if prev is not None and ready > prev:  # else: a late register
                grown[key] = ready - prev
        self.retraces += sum(grown.values())
        fired = [label for label, key in self._names.items()
                 if key in grown]
        for label in fired:
            self.fired[label] = self.fired.get(label, 0) \
                + grown[self._names[label]]
        return fired


# ---------------------------------------------------------------------------
# transfer audit
# ---------------------------------------------------------------------------

class TransferAudit:
    """Attribute IMPLICIT host<->device transfers on a supposedly
    transfer-free path to their call sites.

    ``run(fn, *args)`` executes ``fn`` under ``jax.transfer_guard
    ("disallow")`` — which trips on implicit transfers only; explicit
    ``device_put``/``device_get`` are intended by definition and pass.
    On a trip the XLA error's traceback is walked to the innermost
    frame OUTSIDE jax itself (the call site that smuggled a host array
    onto the device path), the site is counted, and the call is retried
    with transfers allowed so the run continues.  The guard raises
    while STAGING the offending argument — before the program executes
    — so the retry is the only execution of a flagged jit dispatch."""

    def __init__(self):
        self.total = 0
        self.sites: Dict[str, int] = {}
        self.last_error: Optional[str] = None

    @staticmethod
    def _is_transfer_error(e: BaseException) -> bool:
        msg = str(e).lower()
        return "transfer" in msg and "disallow" in msg

    @staticmethod
    def _frame_site(frames) -> Optional[str]:
        site = None
        for fr in frames:
            path = fr.filename.replace(os.sep, "/")
            if "/jax/" in path or "/jaxlib/" in path \
                    or path.endswith("utils/perf.py"):
                continue
            site = f"{fr.filename}:{fr.lineno} ({fr.name})"
        return site

    @classmethod
    def _attribute(cls, e: BaseException) -> str:
        """Innermost python frame outside jax/jaxlib that owns the
        stray host array: from the error's traceback when the transfer
        staged deep inside the audited callable, else from the caller
        stack (the audited callable IS the jit dispatch — the guard
        trips while staging its arguments, so the interesting frame is
        the dispatch site above us)."""
        site = cls._frame_site(traceback.extract_tb(e.__traceback__))
        if site is None:
            site = cls._frame_site(traceback.extract_stack())
        return site or "<unattributed>"

    def run(self, fn, *args, **kwargs):
        import jax

        try:
            with jax.transfer_guard("disallow"):
                return fn(*args, **kwargs)
        except Exception as e:  # noqa: BLE001 - only transfer trips handled
            if not self._is_transfer_error(e):
                raise
            site = self._attribute(e)
            first = site not in self.sites
            self.total += 1
            self.sites[site] = self.sites.get(site, 0) + 1
            self.last_error = str(e).splitlines()[0][:300]
            if first:  # one warning per site, not per tick
                print(f"[perf] transfer audit: implicit transfer on an "
                      f"audited hot path at {site}: {self.last_error}",
                      flush=True)
            with jax.transfer_guard("allow"):
                return fn(*args, **kwargs)


# ---------------------------------------------------------------------------
# host/device memory watermarks
# ---------------------------------------------------------------------------

def host_rss_bytes() -> Optional[int]:
    """Current resident set size of this process (Linux /proc)."""
    try:
        with open("/proc/self/statm") as f:
            pages = int(f.read().split()[1])
        return pages * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError, IndexError):
        return None


def host_peak_rss_bytes() -> Optional[int]:
    """Lifetime peak RSS (getrusage; ru_maxrss is KiB on Linux)."""
    try:
        import resource

        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    except Exception:  # noqa: BLE001 - exotic hosts
        return None


def device_memory_watermarks() -> Dict[str, float]:
    """``live``/``peak`` bytes from the first device's
    ``memory_stats()`` — present on TPU backends, None on CPU (where
    the host RSS rows carry the watermark instead)."""
    out: Dict[str, float] = {}
    try:
        import jax

        stats = jax.devices()[0].memory_stats()
    except Exception:  # noqa: BLE001 - no backend yet / no stats
        return out
    if not stats:
        return out
    live = stats.get("bytes_in_use")
    peak = stats.get("peak_bytes_in_use")
    if live is not None:
        out["device_live_bytes"] = float(live)
    if peak is not None:
        out["device_peak_bytes"] = float(peak)
    return out


# ---------------------------------------------------------------------------
# the monitor
# ---------------------------------------------------------------------------

class PerfMonitor:
    """Per-role performance accounting.

    Hot-path surface is two integer adds (``note_updates`` /
    ``note_frames``) that early-out when the plane is disabled; all
    derivation — window rates, MFU, watermarks, retrace checks — runs
    in ``drain()`` on the role's normal metrics cadence and returns a
    flat ``{tag: value}`` dict for the role's MetricsWriter.  The last
    drained dict is kept for the registry's ``status_snapshot`` so the
    T_STATUS health plane serves fresh values without re-deriving."""

    def __init__(self, name: str, params=None, prefix: Optional[str] = None):
        self.name = name
        # "actor-3" -> tag prefix "actor": tags stay fleet-comparable,
        # rows are process-attributed by the writer's role stamp
        self.prefix = prefix if prefix is not None else name.split("-")[0]
        self.params = resolve(params)
        self.enabled = self.params.enabled
        self.flops_per_update: Optional[float] = None
        self.flops_per_frame: Optional[float] = None
        # the role's matmul compute dtype, scaling the auto-resolved MFU
        # denominator (fp32 runs score against the fp32 peak, not the
        # bf16 one); set by the learner from config.compute_dtype BEFORE
        # the first drain.  An explicit peak_flops knob is never scaled
        # — the operator named the denominator.
        self.compute_dtype: Optional[str] = None
        self._peak: Optional[float] = None
        self._peak_resolved = False
        self.retraces = RetraceDetector()
        self.audit = (TransferAudit()
                      if self.enabled and self.params.transfer_audit
                      else None)
        self._updates = 0
        self._frames = 0
        self._gauges: Dict[str, float] = {}
        self._anchor: Optional[tuple] = None  # (mono, updates, frames)
        self._flops_reported = False
        self.last: Dict[str, float] = {}

    # -- compile-time capture ------------------------------------------------

    def capture_flops(self, lower_thunk: Callable[[], Any]
                      ) -> Optional[float]:
        """AOT-compile the hot program once (``lower_thunk`` returns a
        ``Lowered``) and keep its per-update FLOPs.  Best-effort: a
        backend that cannot lower/compile/cost-analyse leaves MFU off
        rather than failing the role."""
        if not self.enabled:
            return None
        try:
            self.flops_per_update = flops_of_compiled(
                lower_thunk().compile())
        except Exception as e:  # noqa: BLE001
            print(f"[perf] {self.name}: flops capture failed ({e!r}); "
                  f"mfu reporting disabled", flush=True)
            self.flops_per_update = None
        return self.flops_per_update

    def capture_frame_flops(self, lower_thunk: Callable[[], Any],
                            frames_per_call: int) -> Optional[float]:
        """Frame-denominated twin of ``capture_flops`` for the actor
        plane: keep the fused rollout's per-env-frame FLOPs, so the
        device actor's MFU rides the SAME frames counter the
        env-frames/s rate uses (ISSUE 7: the rollout program's
        utilization is a live-plane read, not an offline artifact).

        Cost analysis is read off the LOWERED program when the backend
        supports it (lowering is tracing-only — no XLA compile), so
        the rollout is not compiled twice at actor startup (once for
        flops, once for the first real dispatch); backends without
        lowered-stage analysis fall back to the AOT compile."""
        if not self.enabled:
            return None
        try:
            lowered = lower_thunk()
            total = flops_of_compiled(lowered)
            if total is None:
                total = flops_of_compiled(lowered.compile())
            self.flops_per_frame = (total / frames_per_call
                                    if total else None)
        except Exception as e:  # noqa: BLE001
            print(f"[perf] {self.name}: frame-flops capture failed "
                  f"({e!r}); rollout mfu reporting disabled", flush=True)
            self.flops_per_frame = None
        return self.flops_per_frame

    def register_jit(self, label: str, program: Any) -> None:
        """Watch ``program`` (a jitted function or its name) for
        recompiles after warmup (``RetraceDetector``)."""
        if self.enabled and self.params.retrace_detector:
            self.retraces.register(label, program)

    # -- hot path ------------------------------------------------------------

    def note_updates(self, n: int) -> None:
        if self.enabled:
            self._updates += n

    def note_frames(self, n: int) -> None:
        if self.enabled:
            self._frames += n

    def set_gauge(self, tag: str, value: float) -> None:
        if self.enabled:
            self._gauges[tag] = float(value)

    # -- cadence -------------------------------------------------------------

    def set_compute_dtype(self, dtype: Optional[str]) -> None:
        """Pin the dtype the MFU denominator scales by (idempotent
        until the first drain resolves the peak)."""
        if self.enabled and not self._peak_resolved:
            self.compute_dtype = str(dtype) if dtype is not None else None

    def _peak_flops(self) -> Optional[float]:
        if not self._peak_resolved:
            self._peak_resolved = True
            if self.params.peak_flops > 0:
                self._peak = float(self.params.peak_flops)
            else:
                import jax

                self._peak = peak_flops_of(jax.devices()[0],
                                           self.compute_dtype)
        return self._peak

    def drain(self, step: int = 0, now: Optional[float] = None
              ) -> Dict[str, float]:
        """Window rates + derived metrics since the previous drain, as
        ``{tag: value}``.  The first call anchors the window (and the
        retrace warmup) and returns only non-rate rows."""
        if not self.enabled:
            return {}
        if now is None:
            now = time.monotonic()
        out: Dict[str, float] = {}
        anchor = self._anchor
        self._anchor = (now, self._updates, self._frames)
        if anchor is not None and now > anchor[0]:
            dt = now - anchor[0]
            d_up = self._updates - anchor[1]
            d_fr = self._frames - anchor[2]
            # achieved FLOP/s SUMS the update- and frame-denominated
            # programs: a monitor carrying both (the co-located Anakin
            # loop, whose learner dispatches and rollout dispatches
            # share one chip) reports the chip's total utilization, not
            # whichever branch ran last
            achieved = 0.0
            if self._updates or d_up:
                ups = d_up / dt
                out[f"{self.prefix}/updates_per_s"] = ups
                if self.flops_per_update:
                    achieved += ups * self.flops_per_update
            if self._frames or d_fr:
                fps = d_fr / dt
                out[f"{self.prefix}/env_frames_per_s"] = fps
                if self.flops_per_frame:
                    achieved += fps * self.flops_per_frame
            if achieved:
                out[f"{self.prefix}/achieved_flops_per_s"] = achieved
                peak = self._peak_flops()
                if peak:
                    out[f"{self.prefix}/mfu"] = achieved / peak
        if self.flops_per_update and not self._flops_reported:
            self._flops_reported = True
            out[f"{self.prefix}/flops_per_update"] = self.flops_per_update
        out.update(self._gauges)
        if self.params.memory_watermarks:
            rss = host_rss_bytes()
            if rss is not None:
                out[f"perf/{self.prefix}/rss_bytes"] = float(rss)
            peak_rss = host_peak_rss_bytes()
            if peak_rss is not None:
                out[f"perf/{self.prefix}/rss_peak_bytes"] = float(peak_rss)
            for k, v in device_memory_watermarks().items():
                out[f"perf/{self.prefix}/{k}"] = v
        if self.params.retrace_detector and self.retraces._names \
                and (self._updates or self._frames):
            # gated on work having happened: the warmup mark must land
            # AFTER the first dispatches compiled (an anchor-only drain
            # before the loop would otherwise read them as retraces)
            fired = self.retraces.check()
            if fired:
                print(f"[perf] {self.name}: post-warmup recompile of "
                      f"{', '.join(fired)} — a shape/dtype leak is "
                      f"paying compile latency on the hot path",
                      flush=True)
            out[f"perf/{self.prefix}/retraces"] = float(
                self.retraces.retraces)
        if self.audit is not None:
            out[f"perf/{self.prefix}/transfers_flagged"] = float(
                self.audit.total)
        self.last = dict(out)
        return out

    def snapshot(self) -> Dict[str, float]:
        """Last drained values plus cumulative counters — the read the
        STATUS health plane serves.  No derivation, no reset: safe from
        any thread at any rate."""
        snap = dict(self.last)
        snap["updates_total"] = float(self._updates)
        snap["frames_total"] = float(self._frames)
        if self.flops_per_update:
            snap[f"{self.prefix}/flops_per_update"] = self.flops_per_update
        if self.flops_per_frame:
            snap[f"{self.prefix}/flops_per_frame"] = self.flops_per_frame
        return snap


# ---------------------------------------------------------------------------
# per-process registry (mirrors utils/tracing.py get_tracer)
# ---------------------------------------------------------------------------

_registry_lock = threading.Lock()
_monitors: Dict[str, PerfMonitor] = {}


def get_monitor(name: str, params=None,
                prefix: Optional[str] = None) -> PerfMonitor:
    with _registry_lock:
        m = _monitors.get(name)
        if m is None:
            m = _monitors[name] = PerfMonitor(name, params=params,
                                              prefix=prefix)
        return m


def status_snapshot() -> Dict[str, Dict[str, float]]:
    """{role: snapshot} for every enabled monitor in this process that
    has seen work — the ``perf`` block of the gateway's T_STATUS."""
    with _registry_lock:
        monitors = list(_monitors.values())
    out = {}
    for m in monitors:
        if m.enabled and (m.last or m._updates or m._frames):
            out[m.name] = m.snapshot()
    return out


def reset() -> None:
    """Drop all registered monitors (test isolation)."""
    with _registry_lock:
        _monitors.clear()


# ---------------------------------------------------------------------------
# on-demand profile windows (the T_PROFILE provider)
# ---------------------------------------------------------------------------

_profile_lock = threading.Lock()
_prewarmed = False


def prewarm_profiler() -> threading.Thread:
    """Warm the XLA profiler's one-time session init on a background
    thread (a throwaway ~50 ms trace into a temp dir).

    Measured on this image: the FIRST ``jax.profiler.start_trace`` of a
    process pays ~20 s of lazy TSL/import work when idle — and over a
    MINUTE when a hot dispatch loop is starving the GIL on a small
    host; every later trace starts in milliseconds even under full
    load.  The fleet topology calls this at startup (perf plane
    enabled only), so the operator's first ``fleet_top --profile``
    answers at window speed instead of minutes into a saturated run.
    Holds the one-window lock while warming: a concurrent T_PROFILE
    gets the explicit busy error, not a nested capture."""
    def _warm() -> None:
        global _prewarmed
        import shutil
        import tempfile

        tmp = tempfile.mkdtemp(prefix="perf_profiler_warm_")
        try:
            run_profile_window(tmp, label="_warmup", seconds=0.05)
            _prewarmed = True
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    t = threading.Thread(target=_warm, name="perf-profiler-warm",
                         daemon=True)
    t.start()
    return t


def run_profile_window(trace_dir: str, label: str = "tprofile",
                       seconds: float = 3.0,
                       max_seconds: float = 30.0) -> Dict[str, Any]:
    """Capture one bounded XLA profiler window of THIS process's device
    activity into ``trace_dir`` and report where it landed.

    Blocks for the (clamped) window — the caller is a gateway serve
    thread with its own connection, so blocking is free concurrency-
    wise.  One window at a time: a second request while one is active
    gets an error reply instead of a nested capture (utils/profiling.
    trace would no-op a nested window anyway; the explicit error tells
    the operator WHY there is no trace)."""
    from pytorch_distributed_tpu.utils import profiling

    try:
        seconds = float(seconds)
    except (TypeError, ValueError):
        return {"error": f"bad seconds value {seconds!r}"}
    seconds = max(0.05, min(seconds, max_seconds))
    if not _profile_lock.acquire(blocking=False):
        return {"error": "a profile window is already active"}
    try:
        with profiling.trace(str(label), log_dir=trace_dir) as path:
            if path is None:
                return {"error": "profiler unavailable (a trace is "
                                 "already active in this process)"}
            time.sleep(seconds)
        return {"trace_dir": path, "seconds": seconds}
    except Exception as e:  # noqa: BLE001 - report, never kill the serve
        return {"error": f"profile capture failed: {e!r}"}
    finally:
        _profile_lock.release()
