"""End-to-end flow control and graceful degradation (ISSUE 11 tentpole).

Until this module the experience plane had exactly one answer to
overload: block.  A slow learner filled the spawn queue, the queue
blocked the gateway's ``put_chunk``, the blocked serve thread stalled
the remote actor's synchronous RPC, and the whole fleet froze behind
one saturated host — the failure model in parallel/dcn.py stated it
outright ("legitimate backpressure stalls the actor").  Ape-X (Horgan
et al. 2018) assumes actors OUTRUN the learner by design, and
In-Network Experience Sampling (PAPERS.md) makes the same point at the
transport layer: under pressure the experience plane must *degrade*
(freshest-data-wins drops, every one counted), never deadlock.  This
module is that policy layer, consumed by every transport:

- **OverloadGovernor** — the gateway's explicit overload state machine
  (``healthy -> throttled -> shedding``) driven by a live pressure
  signal (ingest-queue utilization on real topologies), with dwell
  gating on escalation and a separate recover threshold + hysteresis
  window on de-escalation so the band between them never flaps.
  Sustained shedding climbs a **brownout ladder**: tier 1 sheds
  telemetry pushes, tier 2 additionally sheds trace sampling, tier 3
  additionally sheds oldest experience — the learn path is never
  *silently* corrupted; every rung is counted and every transition is
  a flight-recorder ``overload`` event (LOUD on tools/timeline.py)
  plus a ``flow/overload_state`` scalar the alert rules watch.
- **GatewayFlow** — the DcnGateway's per-slot admission plane: credit
  grants riding every T_CLOCK ack (healthy = no credit field =
  unlimited; throttled = token-bucket-metered grants; shedding = 0),
  per-slot token buckets + the tier-3 shed of non-credit-aware peers
  (one runaway actor drains its OWN bucket, not its neighbours'), and
  the conservation ledger: ``minted = ingested + dropped + quarantined
  (+ still-buffered)``, checkable live from the STATUS ``flow`` block.
- **DropOldestRing** — the bounded client/feeder buffer: overflow
  drops the OLDEST chunk (newest experience wins, Ape-X
  priority-on-arrival), every drop counted and provenance-stamped
  (per-actor row counts off the ISSUE-8 prov columns).
- **Process-local brownout hooks** (``set_brownout``/``telemetry_shed``
  /``trace_shed``) — the client side of the ladder: DcnClient latches
  the tier carried on gateway replies, RemoteStats then sheds stat
  pushes (tier >= 1) and QueueFeeder stops minting traced chunks
  (tier >= 2), each counted via ``note_shed``/``shed_counts``.

Knobs live in ``config.FlowParams``, env-overridable as
``TPU_APEX_FLOW_<FIELD>`` (bare ``TPU_APEX_FLOW=0`` = ``enabled``) —
the same spawn-inheritance contract the health/perf/metrics planes
use.  The plane defaults ON but INERT: in the healthy state no credit
field rides the wire, nothing is ever shed, and the hot-path cost is a
few dict/float ops (its share of a tick is not measured).

Drilled by ``tools/chaos_soak.py --flood`` / ``--slow-learner-ingest``
/ ``--slow-slot`` (deadlock, unbounded memory, uncounted drops and
unexpected alerts are each violations) and tests/test_flow.py.
"""

from __future__ import annotations

import collections
import dataclasses
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional

_ENV_PREFIX = "TPU_APEX_FLOW_"

# overload state -> scalar code for the ``flow/overload_state`` series
# (what the DEFAULT_RULES ``overload_shed`` threshold rule watches)
STATE_CODE = {"healthy": 0.0, "throttled": 1.0, "shedding": 2.0}


def resolve_flow(fp=None):
    """FlowParams + ``TPU_APEX_FLOW_<FIELD>`` env overrides, plus the
    bare ``TPU_APEX_FLOW`` shorthand for ``enabled`` — same
    override-by-env contract as perf/health/metrics resolve.  Returns
    a NEW instance; the input is never mutated (Options rides spawn
    pickles)."""
    from pytorch_distributed_tpu.config import FlowParams

    if fp is None:
        fp = FlowParams()
    changes: Dict[str, Any] = {}
    raw_on = os.environ.get("TPU_APEX_FLOW")
    if raw_on is not None:
        changes["enabled"] = raw_on.strip().lower() not in (
            "0", "false", "off", "no", "")
    for f in dataclasses.fields(fp):
        raw = os.environ.get(_ENV_PREFIX + f.name.upper())
        if raw is None:
            continue
        cur = getattr(fp, f.name)
        if isinstance(cur, bool):
            changes[f.name] = raw.strip().lower() not in (
                "0", "false", "off", "no", "")
        elif isinstance(cur, int) and not isinstance(cur, bool):
            changes[f.name] = int(float(raw))
        elif isinstance(cur, float):
            changes[f.name] = float(raw)
        else:
            changes[f.name] = raw.strip()
    return dataclasses.replace(fp, **changes) if changes else fp


def export_env(fp) -> None:
    """Export a RESOLVED FlowParams into the environment so spawn
    children (actor processes building their own QueueFeeders) resolve
    the same plane as the topology that configured it programmatically.
    setdefault: an operator's explicit env always wins."""
    if not fp.enabled:
        os.environ.setdefault("TPU_APEX_FLOW", "0")
    for f in dataclasses.fields(fp):
        val = getattr(fp, f.name)
        if val != f.default:
            os.environ.setdefault(_ENV_PREFIX + f.name.upper(),
                                  ("1" if val is True else
                                   "0" if val is False else str(val)))


# ---------------------------------------------------------------------------
# process-local brownout state (the client side of the ladder)
# ---------------------------------------------------------------------------

_brownout_lock = threading.Lock()
_brownout_tier = 0
_shed_counts: Dict[str, int] = {}


def set_brownout(tier: int) -> None:
    """Latch the brownout tier the gateway last announced (DcnClient
    reads it off T_CLOCK replies).  Process-wide on purpose: the
    feeder/stats/tracing hooks live in the same actor process as the
    client that learns the tier."""
    global _brownout_tier
    with _brownout_lock:
        _brownout_tier = int(tier)


def brownout_tier() -> int:
    with _brownout_lock:
        return _brownout_tier


def telemetry_shed() -> bool:
    """Tier >= 1: stat/metrics pushes are shed (counted, never silent)."""
    return brownout_tier() >= 1


def trace_shed() -> bool:
    """Tier >= 2: new chunks ship untraced (span minting suppressed)."""
    return brownout_tier() >= 2


def note_shed(kind: str, n: int = 1) -> None:
    """Count one shed at a declared shed point (``shed_counts`` is the
    observability half of 'drops are counted, never silent')."""
    with _brownout_lock:
        _shed_counts[kind] = _shed_counts.get(kind, 0) + int(n)


def shed_counts() -> Dict[str, int]:
    with _brownout_lock:
        return dict(_shed_counts)


def reset_shed_state() -> None:
    """Test hook: clear the process-local tier + counters."""
    global _brownout_tier
    with _brownout_lock:
        _brownout_tier = 0
        _shed_counts.clear()


# ---------------------------------------------------------------------------
# token bucket (per-slot admission metering)
# ---------------------------------------------------------------------------

class TokenBucket:
    """Classic refill-on-read token bucket, thread-safe.  ``take``
    consumes on success; ``level`` is the credit-grant read (a grant
    may overshoot by at most the grant cap between takes — flow
    control, not accounting)."""

    def __init__(self, rate: float, burst: float,
                 clock: Callable[[], float] = time.monotonic):
        self.rate = float(rate)
        self.burst = float(burst)
        self._clock = clock
        self._lock = threading.Lock()
        self._tokens = self.burst
        self._last = clock()

    def _refill(self, now: float) -> None:
        self._tokens = min(self.burst,
                           self._tokens + (now - self._last) * self.rate)
        self._last = now

    def take(self, n: float = 1.0) -> bool:
        with self._lock:
            self._refill(self._clock())
            if self._tokens >= n:
                self._tokens -= n
                return True
            return False

    def level(self) -> float:
        with self._lock:
            self._refill(self._clock())
            return self._tokens


# ---------------------------------------------------------------------------
# bounded drop-oldest buffer (the client/feeder shed point)
# ---------------------------------------------------------------------------

def _prov_actor(t, owner: int) -> int:
    """Actor id off a transition's ISSUE-8 prov column (-1 sentinel and
    prov-less rows fall back to ``owner``) — the one extraction every
    counted shed point stamps drops with."""
    prov = getattr(t, "prov", None)
    if prov is not None and len(prov) and int(prov[0]) >= 0:
        return int(prov[0])
    return int(owner)


class DropOldestRing:
    """Bounded chunk buffer: ``put`` appends the newest chunk and, at
    capacity, evicts the OLDEST (newest experience wins — Ape-X
    priority-on-arrival; In-Network Experience Sampling's
    freshest-data-wins drop policy).  Every drop is counted
    (chunks + rows) and provenance-stamped: per-actor dropped-row
    tallies off the ISSUE-8 prov columns (falling back to ``owner`` for
    rows minted without provenance), so the data X-ray can name WHOSE
    experience the overload cost."""

    def __init__(self, max_chunks: int, owner: int = -1):
        self.max_chunks = max(1, int(max_chunks))
        self.owner = int(owner)
        self._lock = threading.Lock()
        self._buf: collections.deque = collections.deque()
        self.dropped_chunks = 0
        self.dropped_rows = 0
        self.buffered_high = 0  # high-water mark, chunks (bounded-memory proof)
        self.dropped_by_actor: Dict[int, int] = {}

    def _stamp(self, chunk: list) -> None:
        for row in chunk:
            t = row[0] if isinstance(row, tuple) else row
            actor = _prov_actor(t, self.owner)
            self.dropped_by_actor[actor] = (
                self.dropped_by_actor.get(actor, 0) + 1)

    def put(self, chunk: list) -> int:
        """Buffer one chunk; returns rows DROPPED to make room (0 when
        the ring had space)."""
        dropped = 0
        with self._lock:
            self._buf.append(chunk)
            self.buffered_high = max(self.buffered_high, len(self._buf))
            while len(self._buf) > self.max_chunks:
                old = self._buf.popleft()
                self.dropped_chunks += 1
                self.dropped_rows += len(old)
                dropped += len(old)
                self._stamp(old)
        return dropped

    def pop(self) -> Optional[list]:
        """Oldest buffered chunk, or None."""
        with self._lock:
            return self._buf.popleft() if self._buf else None

    def unpop(self, chunk: list) -> None:
        """Return a popped chunk to the FRONT (drain loops that hit a
        still-full sink put the in-flight chunk back without reordering
        — and without it counting as a fresh arrival)."""
        with self._lock:
            self._buf.appendleft(chunk)

    def __len__(self) -> int:
        with self._lock:
            return len(self._buf)

    @property
    def buffered_rows(self) -> int:
        with self._lock:
            return sum(len(c) for c in self._buf)


# ---------------------------------------------------------------------------
# the overload state machine + brownout ladder
# ---------------------------------------------------------------------------

class OverloadGovernor:
    """``healthy -> throttled -> shedding`` off a 0..1 pressure signal.

    Escalation: pressure sustained >= the next state's threshold for
    ``dwell_s`` climbs ONE state per dwell (a pressure step to 1.0
    still walks healthy -> throttled -> shedding, so the timeline shows
    the ramp).  De-escalation: pressure sustained < ``recover_at`` for
    ``recover_s`` steps down one state — the hysteresis band between
    ``recover_at`` and ``throttle_at`` holds the current state.

    Inside shedding, the brownout tier climbs one rung per
    ``brownout_dwell_s`` (1 = shed telemetry, 2 = + trace sampling,
    3 = + oldest experience) and resets as the state de-escalates.

    Every state/tier transition is recorded to the flight recorder
    (``kind: "overload"`` — a LOUD tools/timeline.py kind, clock-
    aligned with the alerts it should trigger) and written as a
    ``flow/overload_state`` scalar when a writer is wired, which is
    what the DEFAULT_RULES ``overload_shed`` threshold rule watches."""

    STATES = ("healthy", "throttled", "shedding")

    def __init__(self, params=None, recorder=None, writer=None,
                 clock: Callable[[], float] = time.monotonic,
                 wall: Callable[[], float] = time.time):
        self.params = resolve_flow(params)
        self._recorder = recorder
        self.writer = writer
        self._clock = clock
        self._wall = wall
        self._lock = threading.Lock()
        self.state = "healthy"
        self.tier = 0
        self.transitions = 0
        self._above_since: Optional[float] = None
        self._below_since: Optional[float] = None
        self._tier_since: Optional[float] = None
        self.last_pressure = 0.0

    def _record(self, now: float, pressure: float, why: str) -> None:
        self.transitions += 1
        if self._recorder is not None:
            self._recorder.record("overload", state=self.state,
                                  tier=self.tier,
                                  pressure=round(pressure, 4), why=why)
        if self.writer is not None:
            try:
                self.writer.scalar("flow/overload_state",
                                   STATE_CODE[self.state] + 0.0,
                                   step=self.transitions,
                                   wall=self._wall())
                self.writer.scalar("flow/brownout_tier", float(self.tier),
                                   step=self.transitions,
                                   wall=self._wall())
                self.writer.flush()
            except Exception:  # noqa: BLE001 - telemetry must not kill flow
                pass

    def update(self, pressure: float,
               now: Optional[float] = None) -> Optional[str]:
        """One evaluation; returns the new state on a transition (state
        OR tier change), else None."""
        p = self.params
        if now is None:
            now = self._clock()
        with self._lock:
            self.last_pressure = float(pressure)
            level = self.STATES.index(self.state)
            next_thresh = (p.throttle_at if level == 0 else p.shed_at)
            changed = False
            if level < 2 and pressure >= next_thresh:
                self._below_since = None
                if self._above_since is None:
                    self._above_since = now
                if now - self._above_since >= p.dwell_s:
                    level += 1
                    self.state = self.STATES[level]
                    self._above_since = now  # next rung needs its own dwell
                    if self.state == "shedding":
                        self.tier = 1
                        self._tier_since = now
                    changed = True
                    self._record(now, pressure, "escalate")
            elif pressure < p.recover_at:
                self._above_since = None
                if self._below_since is None:
                    self._below_since = now
                if level > 0 and now - self._below_since >= p.recover_s:
                    level -= 1
                    self.state = self.STATES[level]
                    self._below_since = now  # next step down re-dwells
                    self.tier = 0 if self.state != "shedding" else self.tier
                    self._tier_since = None
                    changed = True
                    self._record(now, pressure, "recover")
            else:
                # the hysteresis band: hold state, reset both dwells
                self._above_since = None
                self._below_since = None
            if (self.state == "shedding" and self.tier < 3
                    and self._tier_since is not None
                    and now - self._tier_since >= p.brownout_dwell_s):
                self.tier += 1
                self._tier_since = now
                changed = True
                self._record(now, pressure, "brownout")
            return self.state if changed else None


# ---------------------------------------------------------------------------
# the gateway's composed flow plane
# ---------------------------------------------------------------------------

class GatewayFlow:
    """Per-slot admission control + credit grants + the conservation
    ledger, owned by one DcnGateway.

    ``admit(slot, rows)`` runs on every EXP frame: it time-gates a
    governor update off the wired ``pressure`` provider, meters the
    slot's token bucket, and returns False — SHED this chunk, counted —
    only at brownout tier 3 when the slot's bucket is dry (the
    declared gateway shed point for peers that ignore credits; credit-
    aware clients never reach it, they buffer client-side at grant 0).

    ``grant(slot)`` sizes the credit field riding the slot's next ack:
    None while healthy (no field on the wire — byte-compatible with
    old peers and zero-cost for compliant ones), a bucket-metered
    integer while throttled, 0 while shedding.

    Conservation: clients report cumulative ``minted``/``dropped``/
    ``buffered`` row counters on their tick cadence (idempotent under
    retransmit — cumulative, not deltas); the gateway adds its own
    ``ingested_rows``/``shed_rows`` and the quarantine counts it
    already keeps, and ``conservation()`` checks the ledger live
    (one-sided — see its docstring; the chaos drills assert exact
    equality at quiescence)."""

    def __init__(self, params=None, pressure=None, recorder=None,
                 writer=None, clock: Callable[[], float] = time.monotonic,
                 update_every: float = 0.25):
        self.params = resolve_flow(params)
        self.pressure = pressure
        self._clock = clock
        self._update_every = float(update_every)
        self.governor = OverloadGovernor(self.params, recorder=recorder,
                                         writer=writer, clock=clock)
        self._recorder = recorder
        self._lock = threading.Lock()
        self._buckets: Dict[int, TokenBucket] = {}
        self._next_update = 0.0
        self.ingested_rows = 0
        self.shed_chunks = 0
        self.shed_rows: Dict[int, int] = {}
        self.client_reports: Dict[int, Dict[str, int]] = {}
        self._shed_logged = 0
        # byte legs of the conservation ledger (ISSUE 18): every acked
        # EXP frame's payload bytes land in exactly ONE of these —
        # rejected (schema-invalid, acked), shed (admit False, acked),
        # or ingested (everything else; quarantine refines rows, not
        # bytes).  The client's matching cumulative ``acked_bytes``
        # rides its tick report.
        self.ingested_bytes = 0
        self.rejected_bytes = 0
        self.shed_bytes = 0
        # rung attribution: brownout tier -> shed bytes (the --flood
        # drill reports bytes shed per rung)
        self.shed_bytes_by_tier: Dict[int, int] = {}

    # -- plumbing ------------------------------------------------------------

    def _bucket(self, slot: int) -> TokenBucket:
        with self._lock:
            b = self._buckets.get(slot)
            if b is None:
                b = self._buckets[slot] = TokenBucket(
                    self.params.bucket_rate, self.params.bucket_burst,
                    clock=self._clock)
            return b

    def refresh(self, now: Optional[float] = None) -> None:
        """Time-gated governor update off the pressure provider (runs on
        the serve threads — cheap by construction, every
        ``update_every`` seconds at most)."""
        if now is None:
            now = self._clock()
        with self._lock:
            if now < self._next_update:
                return
            self._next_update = now + self._update_every
        p = 0.0
        if self.pressure is not None:
            try:
                p = float(self.pressure())
            except Exception:  # noqa: BLE001 - a failing probe reads healthy
                p = 0.0
        self.governor.update(p, now=now)

    # -- the two hot-path reads ----------------------------------------------

    def admit(self, slot: Optional[int], rows: int,
              nbytes: int = 0) -> bool:
        """Gateway-side admission for one decoded EXP chunk.  Always
        meters the slot's bucket (so fairness accounting is live before
        overload); only SHEDS — returns False — at brownout tier 3 with
        the bucket dry.  Shed chunks are counted per slot and recorded
        (throttled to the first few) as ``flow-shed`` events."""
        self.refresh()
        s = -1 if slot is None else int(slot)
        has_tokens = self._bucket(s).take(1.0)
        if self.governor.tier >= 3 and not has_tokens:
            with self._lock:
                self.shed_chunks += 1
                self.shed_rows[s] = self.shed_rows.get(s, 0) + int(rows)
                self.shed_bytes += int(nbytes)
                tier = self.governor.tier
                self.shed_bytes_by_tier[tier] = \
                    self.shed_bytes_by_tier.get(tier, 0) + int(nbytes)
                self._shed_logged += 1
                log_it = self._shed_logged <= 3
            if self._recorder is not None:
                self._recorder.record("flow-shed", slot=s, rows=int(rows),
                                      tier=self.governor.tier)
            if log_it:
                print(f"[flow] tier-3 brownout: shed {rows}-row chunk "
                      f"from slot {s} (bucket dry)", flush=True)
            return False
        return True

    def note_ingested(self, rows: int) -> None:
        """Count rows that actually entered the learn path (admitted
        AND clean of quarantine) — the ``ingested`` leg of the
        conservation ledger.  Counted separately from ``admit`` so a
        quarantined row lands in exactly one bucket."""
        with self._lock:
            self.ingested_rows += int(rows)

    def note_ingested_bytes(self, nbytes: int) -> None:
        """Count an admitted EXP frame's payload bytes (frame-granular:
        counted even when quarantine empties the chunk — its rows land
        in the quarantined bucket, its bytes stay here)."""
        with self._lock:
            self.ingested_bytes += int(nbytes)

    def note_rejected_bytes(self, nbytes: int) -> None:
        """Count a schema-rejected (but acked) EXP frame's payload
        bytes — the ``framed-reject`` leg of the byte ledger."""
        with self._lock:
            self.rejected_bytes += int(nbytes)

    def grant(self, slot: Optional[int]) -> Optional[int]:
        """Credit grant for the slot's next ack; None = no credit field
        (healthy — unlimited)."""
        self.refresh()
        state = self.governor.state
        if state == "healthy":
            return None
        if state == "shedding":
            return 0
        s = -1 if slot is None else int(slot)
        return max(0, min(self.params.credits_throttled,
                          int(self._bucket(s).level())))

    # -- reports + reads -----------------------------------------------------

    def on_client_report(self, slot: Optional[int], report: dict) -> None:
        """Absorb a client's cumulative flow counters off its T_TICK
        (idempotent: retransmitted ticks carry the same cumulative
        values, so the dedup window cannot double-count drops)."""
        if slot is None or not isinstance(report, dict):
            return
        clean: Dict[str, int] = {}
        for k in ("minted", "acked", "acked_bytes", "dropped",
                  "buffered"):
            try:
                clean[k] = int(report.get(k, 0))
            except (TypeError, ValueError):
                clean[k] = 0
        with self._lock:
            self.client_reports[int(slot)] = clean

    def conservation(self, quarantined: int = 0) -> dict:
        """The ledger: every minted row must be ingested, counted
        dropped, quarantined, or still buffered client-side.  Only
        meaningful over slots that REPORT (credit-aware clients); a
        fleet of legacy peers reports nothing and the check degrades
        to 'unknown', never to a false alarm.

        The LIVE check flags only ``minted > accounted`` — a row the
        clients minted that no counted bucket can explain (the
        uncounted-drop smell).  ``accounted`` legitimately overshoots
        ``minted`` in flight: client counters are tick-cadence stale
        while the gateway's ``ingested`` is real-time, and a legacy
        (non-reporting) peer's rows land in ``ingested`` with no
        ``minted`` to match — neither is a leak.  Quiescent drills
        (tools/chaos_soak.py) assert exact equality from final
        counters instead."""
        with self._lock:
            reports = {s: dict(r) for s, r in self.client_reports.items()}
            gw_shed = sum(self.shed_rows.values())
            ingested = self.ingested_rows
            ingested_b = self.ingested_bytes
            rejected_b = self.rejected_bytes
            shed_b = self.shed_bytes
        minted = sum(r["minted"] for r in reports.values())
        dropped = sum(r["dropped"] for r in reports.values())
        buffered = sum(r["buffered"] for r in reports.values())
        acked_b = sum(r.get("acked_bytes", 0) for r in reports.values())
        out = {
            "minted": minted,
            "ingested": ingested,
            "dropped_client": dropped,
            "shed_gateway": gw_shed,
            "quarantined": int(quarantined),
            "buffered_client": buffered,
            # the byte ledger (ISSUE 18): every acked EXP payload byte
            # is ingested, framed-rejected, or gateway-shed; unlike
            # rows there is no client-side byte bucket — ring-dropped
            # chunks are never encoded, so their bytes never exist
            "acked_bytes": acked_b,
            "ingested_bytes": ingested_b,
            "rejected_bytes": rejected_b,
            "shed_bytes": shed_b,
            "reporting_slots": sorted(reports),
        }
        if reports:
            accounted = (ingested + dropped + gw_shed
                         + int(quarantined) + buffered)
            out["accounted"] = accounted
            out["balanced"] = bool(minted <= accounted)
            # one-sided for the same reason as rows: client counters
            # are tick-cadence stale while the gateway legs are
            # real-time, and legacy peers ingest bytes with no report
            accounted_b = ingested_b + rejected_b + shed_b
            out["accounted_bytes"] = accounted_b
            out["bytes_balanced"] = bool(acked_b <= accounted_b)
        return out

    def status_block(self, quarantined: int = 0) -> dict:
        """The STATUS ``flow`` block: overload state + tier, per-slot
        credit grants and shed counts, client-reported drop counters,
        per-actor drop share (next to ``replay/actor_share`` in the
        data X-ray), and the conservation ledger."""
        with self._lock:
            slots = sorted(set(self._buckets) | set(self.shed_rows)
                           | set(self.client_reports))
            shed = {str(s): n for s, n in sorted(self.shed_rows.items())}
            reports = {str(s): dict(r)
                       for s, r in sorted(self.client_reports.items())}
        # built from the locked snapshots, so the share a slot shows is
        # consistent with the counts printed next to it in the same block
        drops = {s: (shed.get(s, 0) + reports.get(s, {}).get("dropped", 0))
                 for s in (str(x) for x in slots)}
        total_drops = sum(drops.values())
        blk = {
            "state": self.governor.state,
            "tier": self.governor.tier,
            "pressure": round(self.governor.last_pressure, 4),
            "transitions": self.governor.transitions,
            "credits": {str(s): self.grant(s) for s in slots
                        if self.governor.state != "healthy"},
            "shed_rows": shed,
            "shed_chunks": self.shed_chunks,
            "client": reports,
            "drop_share": ({s: round(n / total_drops, 4)
                            for s, n in drops.items() if n}
                           if total_drops else {}),
            "conservation": self.conservation(quarantined=quarantined),
        }
        return blk


# ---------------------------------------------------------------------------
# local-transport shed policy (spawn-queue feeder / device-replay pending)
# ---------------------------------------------------------------------------

def shed_overflow(pending: List, max_rows: int,
                  counters: Dict[str, int],
                  owner: int = -1) -> List:
    """Drop-OLDEST overflow for a pending-row list (the device-replay
    ingest's ``local_policy="shed"`` bound): returns the trimmed list,
    counts the shed into ``counters`` (``shed_rows`` + per-actor
    ``shed_by_actor:<id>`` keys stamped from prov)."""
    over = len(pending) - int(max_rows)
    if over <= 0:
        return pending
    dropped, kept = pending[:over], pending[over:]
    counters["shed_rows"] = counters.get("shed_rows", 0) + over
    for t in dropped:
        k = f"shed_by_actor:{_prov_actor(t, owner)}"
        counters[k] = counters.get(k, 0) + 1
    return kept
