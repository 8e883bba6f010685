"""Byte-exact bandwidth accounting for every wire, ring, and
checkpoint plane (ISSUE 18 tentpole).

Ape-X's defining cost is moving experience: at production actor counts
the DCN wire and replay HBM are the ceilings after compute (ROADMAP
item 4), and INES (PAPERS.md) makes the case that *where bytes flow*
decides distributed-RL scale.  Before this module the repo counted
chunks, rows, and rejects everywhere but **bytes nowhere** — the
compression campaign cannot be built, measured, or gated until
bytes/transition and bytes/round are first-class, live-queryable
series with an exact conservation story.  This module is that plane:

- **LinkAccountant** — a process-wide, lock-guarded table of
  cumulative ``(bytes, frames)`` per ``link x verb x slot x
  direction``, stamped at every transport boundary: ``_send_frame`` /
  ``_recv_frame`` in parallel/dcn.py (chunk ingest, clock acks,
  metrics pushes, replica rounds, journal T_SYNC), the spawn-queue
  mint/drain boundaries (memory/feeder.py, memory/device_replay.py),
  replay occupancy by column dtype, and per-artifact checkpoint-epoch
  sizes (utils/checkpoint.py).  The hot path is counter-only: one
  dict lookup + two integer adds under a lock that is never held
  across I/O (its share of a frame's cost is not measured).
- **Socket registry** — ``socket.socket`` declares ``__slots__`` so
  transport identity cannot ride the object; a WeakKeyDictionary side
  table maps live sockets to ``(link, slot)`` without pinning them.
- **Headline series** (``emit_scalars`` on the learner stats cadence,
  ``status_block`` on the gateway STATUS path): ``wire/<link>/
  bytes_per_s``, ``wire/bytes_per_transition`` (wire bytes / ingested
  rows — the number frame packing claims 4x on), ``wire/
  replica_bytes_per_round``, ``replay/hbm_bytes``, ``ckpt/
  epoch_bytes`` — flowing MetricsWriter -> FleetMetrics -> T_STATUS
  ``wire`` block -> fleet_top -> OpenMetrics -> timeline counters.
- **Byte conservation ledger** — rides the ISSUE-11 flow ledger
  verbatim: the client counts each experience payload ONCE at encode
  (``acked_bytes``, cumulative, retransmit-idempotent — a retransmit
  resends the same frame, it does not re-encode), the report rides
  every T_TICK, and the gateway legs (``ingested_bytes`` +
  ``rejected_bytes`` + ``shed_bytes``) live in flow.GatewayFlow so
  ``conservation()`` can assert ``acked_bytes <= accounted_bytes``
  live and EXACT equality at drill quiescence.  Frames that die
  mid-wire (corrupt -> decode ConnectionError -> connection dropped)
  are counted by NEITHER side: the client already counted the clean
  encode, the gateway counts only the clean retransmit it finally
  acks.  The gateway byte legs are journaled across failover exactly
  like the row legs (``_ha_ledger`` / ``_seed_records`` in
  parallel/dcn.py).

Knobs live in ``config.BandwidthParams``, env-overridable as
``TPU_APEX_WIRE_<FIELD>`` (bare ``TPU_APEX_WIRE=0`` = ``enabled``) —
the same spawn-inheritance contract the flow/perf/metrics planes use.
ON by default; disabled, every hook is a single module-flag check.

Drilled by ``tools/chaos_soak.py --flood`` (byte ledger exact under
brownout, bytes shed per rung) and ``--gateway-failover`` (journaled
byte carry) and covered by tests/test_bandwidth.py, which also holds the
deterministic counts (``wire/bytes_per_transition``,
``wire/replica_bytes_per_round``).
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
import weakref
from typing import Any, Dict, Iterable, Optional, Tuple

_ENV_PREFIX = "TPU_APEX_WIRE_"

# Known link names (for reference; the accountant accepts any string):
#   client   — DcnClient RPC socket (per remote actor process)
#   gateway  — DcnGateway accepted conns (slot = actor index, after HELLO)
#   replica  — ReplicaClient lease/round sockets (slot = replica index)
#   sync     — HA standby journal-pull socket (T_SYNC)
#   probe    — sessionless RPCs (fleet_top STATUS polls, health probes)
#   spawn    — spawn-queue mint/drain (verb "mint" / "drain")
#   ckpt     — checkpoint epoch writes (verb = artifact name)


def resolve_bandwidth(bp=None):
    """BandwidthParams + ``TPU_APEX_WIRE_<FIELD>`` env overrides, plus
    the bare ``TPU_APEX_WIRE`` shorthand for ``enabled`` — same
    override-by-env contract as flow/perf/health/metrics resolve.
    Returns a NEW instance; the input is never mutated (Options rides
    spawn pickles)."""
    from pytorch_distributed_tpu.config import BandwidthParams

    if bp is None:
        bp = BandwidthParams()
    changes: Dict[str, Any] = {}
    raw_on = os.environ.get("TPU_APEX_WIRE")
    if raw_on is not None:
        changes["enabled"] = raw_on.strip().lower() not in (
            "0", "false", "off", "no", "")
    for f in dataclasses.fields(bp):
        raw = os.environ.get(_ENV_PREFIX + f.name.upper())
        if raw is None:
            continue
        cur = getattr(bp, f.name)
        if isinstance(cur, bool):
            changes[f.name] = raw.strip().lower() not in (
                "0", "false", "off", "no", "")
        elif isinstance(cur, int) and not isinstance(cur, bool):
            changes[f.name] = int(float(raw))
        elif isinstance(cur, float):
            changes[f.name] = float(raw)
        else:
            changes[f.name] = raw.strip()
    return dataclasses.replace(bp, **changes) if changes else bp


def export_env(bp) -> None:
    """Export a RESOLVED BandwidthParams into the environment so spawn
    children (actor processes stamping their own mint boundaries)
    resolve the same plane as the topology that configured it
    programmatically.  setdefault: an operator's explicit env wins."""
    if not bp.enabled:
        os.environ.setdefault("TPU_APEX_WIRE", "0")
    for f in dataclasses.fields(bp):
        val = getattr(bp, f.name)
        if val != f.default:
            os.environ.setdefault(_ENV_PREFIX + f.name.upper(),
                                  ("1" if val is True else
                                   "0" if val is False else str(val)))


# ---------------------------------------------------------------------------
# verb names — dcn registers its frame-type map at import time so this
# module never imports parallel/dcn (no circular import); unknown
# frame types account under "t<code>" rather than getting lost
# ---------------------------------------------------------------------------

_VERB_NAMES: Dict[int, str] = {}


def register_verbs(mapping: Dict[int, str]) -> None:
    _VERB_NAMES.update({int(k): str(v) for k, v in mapping.items()})


def verb_name(ftype: int) -> str:
    return _VERB_NAMES.get(ftype) or f"t{ftype}"


# ---------------------------------------------------------------------------
# byte sizing helpers — deterministic on both sides of a queue so the
# spawn plane conserves by construction
# ---------------------------------------------------------------------------

def payload_nbytes(obj, _depth: int = 0) -> int:
    """Array-payload bytes of a structured value: sum of ``.nbytes``
    over every array reachable through NamedTuples (Transition,
    ReplayState, PerReplayState), dicts, lists, and tuples — the
    dominant (and compressible) term of any pickled/savez'd frame,
    NOT the envelope: pickling a chunk twice just to weigh it would
    violate the counter-only hot path, and the same rule applied at
    mint and drain conserves exactly."""
    if obj is None or _depth > 4:
        return 0
    nb = getattr(obj, "nbytes", None)
    if nb is not None:
        return int(nb)
    if isinstance(obj, (bytes, bytearray)):
        return len(obj)
    if hasattr(obj, "_fields"):              # NamedTuple
        vals: Iterable[Any] = tuple(obj)
    elif isinstance(obj, dict):
        vals = obj.values()
    elif isinstance(obj, (list, tuple)):
        vals = obj
    else:
        return 0
    total = 0
    for v in vals:
        total += payload_nbytes(v, _depth + 1)
    return total


def chunk_nbytes(items) -> int:
    """Spawn-queue chunk bytes: a chunk is a ``[(Transition,
    priority), ...]`` list (possibly a TracedChunk)."""
    return payload_nbytes(items)


def replay_nbytes(state) -> int:
    """HBM/host occupancy of a replay state (ReplayState /
    PerReplayState NamedTuples, dicts of arrays, sidecar lists)."""
    return payload_nbytes(state)


# ---------------------------------------------------------------------------
# the accountant
# ---------------------------------------------------------------------------

class LinkAccountant:
    """Process-wide cumulative ``(bytes, frames)`` per ``link x verb x
    slot x direction``.  Counter-only hot path: ``note`` is one dict
    get + two int adds under a lock never held across I/O."""

    def __init__(self, params=None) -> None:
        self.params = params if params is not None else resolve_bandwidth()
        self._lock = threading.Lock()
        # (link, verb, slot, dir) -> [bytes, frames]
        self._counts: Dict[Tuple[str, str, Optional[int], str],
                           list] = {}
        # live socket -> (link, slot); socket.socket has __slots__, so
        # identity rides a weak side table, never the object
        self._socks: "weakref.WeakKeyDictionary[Any, Tuple[str, Optional[int]]]" \
            = weakref.WeakKeyDictionary()
        self._gauges: Dict[str, float] = {}
        self.transitions = 0       # rows ingested by the gateway
        self.rounds = 0            # replica rounds completed
        # rate state for emit_scalars: link -> (mono, cum_bytes)
        self._rate: Dict[str, Tuple[float, int]] = {}

    # -- socket identity ----------------------------------------------------

    def register_socket(self, sock, link: str,
                        slot: Optional[int] = None) -> None:
        """Tag a live socket with its link name (and slot once known —
        the gateway re-registers an accepted conn when HELLO reveals
        the actor index).  Weak: no socket is ever pinned."""
        try:
            with self._lock:
                self._socks[sock] = (link, slot)
        except TypeError:  # unweakrefable test double — account as anon
            pass

    def link_of(self, sock) -> Tuple[str, Optional[int]]:
        try:
            return self._socks.get(sock) or ("anon", None)
        except TypeError:
            return ("anon", None)

    # -- the hot path -------------------------------------------------------

    def note(self, link: str, verb: str, nbytes: int, direction: str,
             slot: Optional[int] = None, frames: int = 1) -> None:
        key = (link, verb, slot, direction)
        with self._lock:
            c = self._counts.get(key)
            if c is None:
                c = self._counts[key] = [0, 0]
            c[0] += int(nbytes)
            c[1] += int(frames)

    def note_frame(self, sock, ftype: int, nbytes: int,
                   direction: str) -> None:
        link, slot = self.link_of(sock)
        self.note(link, verb_name(ftype), nbytes, direction, slot=slot)

    def note_transitions(self, rows: int) -> None:
        with self._lock:
            self.transitions += int(rows)

    def note_round(self) -> None:
        with self._lock:
            self.rounds += 1

    def set_gauge(self, tag: str, value: float) -> None:
        with self._lock:
            self._gauges[str(tag)] = float(value)

    # -- queries ------------------------------------------------------------

    def totals(self, link: Optional[str] = None,
               verb: Optional[str] = None,
               direction: Optional[str] = None) -> Tuple[int, int]:
        """Cumulative ``(bytes, frames)`` over every key matching the
        given filters (None = any)."""
        b = f = 0
        with self._lock:
            for (lk, vb, _slot, dr), (cb, cf) in self._counts.items():
                if link is not None and lk != link:
                    continue
                if verb is not None and vb != verb:
                    continue
                if direction is not None and dr != direction:
                    continue
                b += cb
                f += cf
        return b, f

    def snapshot(self) -> Dict[str, Any]:
        """Full counter table, JSON-shaped: ``{link: {verb: {dir:
        [bytes, frames]}}}`` (slots folded — per-slot detail stays
        queryable via totals/status for the drills that need it)."""
        out: Dict[str, Any] = {}
        with self._lock:
            items = list(self._counts.items())
        for (lk, vb, _slot, dr), (cb, cf) in items:
            d = out.setdefault(lk, {}).setdefault(vb, {})
            cur = d.get(dr)
            if cur is None:
                d[dr] = [cb, cf]
            else:
                cur[0] += cb
                cur[1] += cf
        return out

    def bytes_per_transition(self) -> float:
        """Wire bytes per ingested row: experience-verb bytes RECEIVED
        on the gateway link / rows the gateway ingested.  rx-side only
        so a loopback topology (client and gateway in one process, as
        every test runs) never double-counts."""
        with self._lock:
            rows = self.transitions
        if rows <= 0:
            return 0.0
        nb, _ = self.totals(link="gateway", verb="exp", direction="rx")
        return nb / rows

    def replica_bytes_per_round(self) -> float:
        """Replica-plane bytes (lease + round + prio verbs, both
        directions, gateway side) per completed round."""
        with self._lock:
            rounds = self.rounds
        if rounds <= 0:
            return 0.0
        nb = 0
        for verb in ("rlease", "rgrad", "rprio"):
            b, _ = self.totals(link="gateway", verb=verb)
            nb += b
        return nb / rounds

    # -- export -------------------------------------------------------------

    def emit_scalars(self, now: Optional[float] = None) -> Dict[str, float]:
        """The headline series, shaped for ``MetricsWriter.scalars``.
        Rates come from deltas against the previous emit (first call
        primes the baseline and emits totals-only)."""
        now = time.monotonic() if now is None else now
        out: Dict[str, float] = {}
        per_link: Dict[str, int] = {}
        with self._lock:
            for (lk, _vb, _slot, _dr), (cb, _cf) in self._counts.items():
                per_link[lk] = per_link.get(lk, 0) + cb
            gauges = dict(self._gauges)
        for lk, cum in per_link.items():
            prev = self._rate.get(lk)
            self._rate[lk] = (now, cum)
            if prev is not None:
                dt = now - prev[0]
                if dt >= max(1e-3, float(self.params.rate_floor_s)):
                    out[f"wire/{lk}/bytes_per_s"] = (cum - prev[1]) / dt
        bpt = self.bytes_per_transition()
        if bpt > 0:
            out["wire/bytes_per_transition"] = bpt
        bpr = self.replica_bytes_per_round()
        if bpr > 0:
            out["wire/replica_bytes_per_round"] = bpr
        out.update(gauges)          # replay/hbm_bytes, ckpt/epoch_bytes
        return out

    def status_block(self) -> Dict[str, Any]:
        """The T_STATUS ``wire`` block (fleet_top's panel source):
        per-link cumulative totals + the headline ratios + gauges.
        The byte-conservation verdict rides the ``flow`` block's
        ``conservation`` (flow.GatewayFlow owns the gateway byte
        legs); fleet_top joins the two."""
        per_link: Dict[str, Dict[str, int]] = {}
        with self._lock:
            items = list(self._counts.items())
            transitions = self.transitions
            rounds = self.rounds
            gauges = dict(self._gauges)
        for (lk, _vb, _slot, dr), (cb, cf) in items:
            d = per_link.setdefault(lk, {"bytes": 0, "frames": 0,
                                         "tx_bytes": 0, "rx_bytes": 0})
            d["bytes"] += cb
            d["frames"] += cf
            d["tx_bytes" if dr == "tx" else "rx_bytes"] += cb
        return {
            "links": per_link,
            "transitions": transitions,
            "rounds": rounds,
            "bytes_per_transition": round(self.bytes_per_transition(), 2),
            "replica_bytes_per_round": round(
                self.replica_bytes_per_round(), 2),
            "gauges": gauges,
        }


# ---------------------------------------------------------------------------
# the process-wide plane (spawn-safe: each process resolves its own)
# ---------------------------------------------------------------------------

_acct_lock = threading.Lock()
_ACCT: Optional[LinkAccountant] = None
_RESOLVED = False
_ENABLED = True     # module-level fast flag: the only cost when off


def get_accountant() -> Optional[LinkAccountant]:
    """The process accountant, or None when the plane is disabled
    (``TPU_APEX_WIRE=0``).  Lazily resolved once per process."""
    global _ACCT, _RESOLVED, _ENABLED
    if _RESOLVED:
        return _ACCT
    with _acct_lock:
        if not _RESOLVED:
            params = resolve_bandwidth()
            _ENABLED = bool(params.enabled)
            _ACCT = LinkAccountant(params) if params.enabled else None
            _RESOLVED = True
    return _ACCT


def enabled() -> bool:
    if not _RESOLVED:
        get_accountant()
    return _ENABLED


def reset_for_tests() -> None:
    """Drop the process accountant so the next hook re-resolves from
    the (possibly monkeypatched) environment.  Tests only."""
    global _ACCT, _RESOLVED, _ENABLED
    with _acct_lock:
        _ACCT = None
        _RESOLVED = False
        _ENABLED = True


# -- module-level hooks: what the transports actually call (each is a
#    flag check + delegate, so instrumented code never branches on
#    plane state itself) -----------------------------------------------------

def register_socket(sock, link: str, slot: Optional[int] = None) -> None:
    acct = get_accountant()
    if acct is not None:
        acct.register_socket(sock, link, slot)


def note_frame(sock, ftype: int, nbytes: int, direction: str) -> None:
    acct = get_accountant()
    if acct is not None:
        acct.note_frame(sock, ftype, nbytes, direction)


def note(link: str, verb: str, nbytes: int, direction: str,
         slot: Optional[int] = None, frames: int = 1) -> None:
    acct = get_accountant()
    if acct is not None:
        acct.note(link, verb, nbytes, direction, slot=slot, frames=frames)


def note_spawn(verb: str, items, frames: int = 1) -> None:
    """Spawn-queue boundary accounting (QueueFeeder mint / QueueOwner +
    DeviceReplayIngest drain): array-payload bytes of the chunk, gated
    on ``BandwidthParams.spawn`` (sizing is linear in rows — flush
    cadence, never per-frame)."""
    acct = get_accountant()
    if acct is not None and acct.params.spawn and frames > 0:
        acct.note("spawn", verb, chunk_nbytes(items),
                  "tx" if verb == "mint" else "rx", frames=frames)


_REPLAY_COLUMNS = ("state0", "action", "reward", "gamma_n", "state1",
                   "terminal1", "prov")


def note_device_replay(*states) -> None:
    """Gauge the attached HBM ring(s): ``replay/hbm_bytes`` total plus
    per-column ``replay/hbm_bytes/<field>`` occupancy by dtype.  One
    shot at attach — ring geometry is fixed for the run."""
    acct = get_accountant()
    if acct is None:
        return
    total = 0
    fields: Dict[str, int] = {}
    for st in states:
        if st is None:
            continue
        if hasattr(st, "_fields"):
            for name, v in zip(st._fields, tuple(st)):
                nb = payload_nbytes(v)
                fields[name] = fields.get(name, 0) + nb
                total += nb
        else:
            total += payload_nbytes(st)
    acct.set_gauge("replay/hbm_bytes", float(total))
    for name, nb in fields.items():
        acct.set_gauge(f"replay/hbm_bytes/{name}", float(nb))


def note_host_replay(mem) -> None:
    """Gauge a host-side replay's column arrays (+ the ISSUE-8 prov
    sidecar): ``replay/host_bytes`` total plus per-column detail.  One
    shot at construction — host columns are preallocated."""
    acct = get_accountant()
    if acct is None:
        return
    total = 0
    for name in _REPLAY_COLUMNS:
        nb = payload_nbytes(getattr(mem, name, None))
        if nb:
            acct.set_gauge(f"replay/host_bytes/{name}", float(nb))
            total += nb
    acct.set_gauge("replay/host_bytes", float(total))


def note_transitions(rows: int) -> None:
    acct = get_accountant()
    if acct is not None:
        acct.note_transitions(rows)


def note_round() -> None:
    acct = get_accountant()
    if acct is not None:
        acct.note_round()


def set_gauge(tag: str, value: float) -> None:
    acct = get_accountant()
    if acct is not None:
        acct.set_gauge(tag, value)


def emit_scalars() -> Dict[str, float]:
    acct = get_accountant()
    return acct.emit_scalars() if acct is not None else {}


def status_block() -> Optional[Dict[str, Any]]:
    acct = get_accountant()
    return acct.status_block() if acct is not None else None
