"""DCN transport: cross-host experience ingestion + parameter publication.

No reference equivalent — the reference's entire communication backend is
single-machine ``torch.multiprocessing`` shared memory (reference main.py:13,
core/memories/shared_memory.py:30-37; SURVEY.md §2 "distributed communication
backend").  On a TPU pod the learner host owns the mesh and remote actor
hosts cannot share pages with it, so the three shared-state mechanisms the
reference relies on become one explicit wire protocol over DCN
(host-to-host Ethernet/ICI-external network):

- **experience in** — actors stream fixed-schema transition chunks to the
  learner host's ``DcnGateway``, which forwards them into the same
  single-owner spawn queue the local feeders use (memory/feeder.py,
  memory/device_replay.py): the learner drains local and remote experience
  through one path.
- **weights out** — the gateway answers versioned parameter requests from
  the learner's ``ParamStore`` snapshot; remote actors poll on their
  ``actor_sync_freq`` cadence exactly like local ones (reference
  dqn_actor.py:176-178), with staleness bounded by cadence + one RTT.
- **clocks/stats** — the global learner step rides back on every reply
  (actors need it only for termination, reference dqn_actor.py:62), and
  actor-step/stat increments are batched client-side so the hot loop never
  blocks on the network.

Wire format: 1-byte frame type + 8-byte big-endian payload length, then the
payload — JSON for control frames, ``np.savez`` for experience chunks, raw
fp32 for parameter snapshots.  No pickle on the wire: frames are
schema-checked, so a gateway never executes peer-controlled code.

Failure model (the session layer; drills in tests/test_chaos.py and
tools/chaos_soak.py, policy knobs via ``DCN_*`` env vars):

- **Transient disconnects are transparent.**  A send/recv error inside
  ``DcnClient._request`` redials with exponential backoff, re-HELLOs with
  a bumped **incarnation number**, and retransmits the one unacknowledged
  frame — experience delivery is at-least-once (a chunk whose ack was
  lost may be fed twice; replay sampling tolerates duplicates, lost
  chunks it cannot).  Retransmitted T_TICKs, whose double-count would
  skew the fleet step count and stats, are deduplicated gateway-side by
  sequence number (the one residual window: an ack lost across a
  gateway RESTART, which forgets the dedup map).  A reconnect that exhausts its budget
  (``DCN_RECONNECT_TIMEOUT``) is terminal: the client raises
  ``DcnDisconnected`` and latches ``disconnected`` so the worker exits
  **nonzero** and the supervision layer (utils/supervision.RestartBudget)
  engages — never a silent "run complete".
- **Slot fencing.**  The gateway keys each actor slot by incarnation; a
  HELLO carrying a higher incarnation for an already-held slot evicts the
  stale predecessor connection (the half-open leftover of a partition)
  instead of bouncing off "slot already connected".  Equal/lower
  incarnations are refused — that is a genuine duplicate actor, the
  config error that silently skews the fleet-wide Ape-X epsilon schedule.
- **Liveness vs backpressure.**  The client pings (T_PING) after
  ``DCN_HEARTBEAT_INTERVAL`` of idleness and bounds every reply wait with
  ``DCN_REPLY_DEADLINE``; the gateway drops connections idle longer than
  ``DCN_IDLE_DEADLINE`` (> the ping interval), freeing their slots.  The
  deadlines are deliberately long relative to ingest stalls: a brief
  stall (learner compile) rides under them, while a frozen or
  partitioned peer trips the deadline and enters the reconnect path
  instead of hanging forever on the old ``settimeout(None)`` socket.
- **Overload degrades, never deadlocks (ISSUE 11, utils/flow.py).**
  Sustained pressure (full spawn queue, slow learner ingest) no longer
  stalls the fleet through blocking puts: the gateway's overload
  governor (healthy → throttled → shedding, surfaced on T_STATUS and
  alerted via DEFAULT_RULES) sizes per-slot send credits onto every
  T_CLOCK ack; a creditless client parks chunks in a bounded
  drop-oldest ring (newest experience wins, every drop counted +
  provenance-stamped) while its T_PING heartbeats keep flowing — so a
  throttled actor never reads as dead, is never reaped by the idle
  deadline, and never blocks its own rollout loop.  Per-slot token
  buckets meter the throttled grants (one runaway actor drains its own
  bucket, not its neighbours'), and sustained shedding climbs a
  brownout ladder — telemetry pushes first, then trace sampling, then
  (tier 3, for credit-ignoring peers) oldest experience at the
  gateway's one declared shed point.  Conservation is checkable live:
  minted = ingested + dropped + quarantined (+ still-buffered), from
  the counters on the STATUS ``flow`` block.  Drilled by
  ``chaos_soak --flood`` / ``--slow-learner-ingest`` / ``--slow-slot``.
- **"Learner said stop" and "connection lost" are distinct states**:
  ``DcnClient.stop`` is set only by a T_CLOCK reply carrying
  ``stop: true``; ``DcnClient.disconnected`` only by a terminal session
  loss.  fleet.py maps them to exit codes 0 / EXIT_DISCONNECTED.
- **Learner replicas are leased, not sessioned (ISSUE 15,
  ReplicaRegistry below).**  N data-parallel learner replicas hold
  renewable leases with MONOTONIC generation numbers; a missed lease
  expires the replica and fences its stragglers (stale-generation
  gradient/priority write-backs are counted rejects, never applied —
  the slot-fencing contract lifted to the learner plane).  The gradient
  exchange is a generation-stamped allreduce round that reconfigures on
  membership change: a dead replica's round completes over the
  surviving set within one lease window (a HUNG-but-renewing replica is
  expelled by the round-stall rule — leases prove liveness, rounds
  prove progress), and an N=1 completion is bit-identical to the solo
  learner.  Rejoin = re-lease at a new generation + sync from the
  join-barrier checkpoint epoch.  Drilled by ``chaos_soak
  --kill-replica / --hang-replica / --rejoin`` and the
  tests/test_replicas.py parity oracle.

Client-side adapters (``RemoteMemory``, ``RemoteParamStore``,
``RemoteClock``, ``RemoteStats``) present the exact surfaces the actor
harness binds to (agents/actor.py), so ``run_dqn_actor``/``run_ddpg_actor``
run unmodified on a remote host.

Observability (utils/tracing.py, utils/flight_recorder.py): EXP frames
carry the chunk's trace id + birth wall-clock as savez columns, so the
gateway records the actor→gateway wire hop against the same trace the
learner-side drain continues; session transitions (claims, fences,
releases, reconnects, terminal losses) land in per-role flight-recorder
rings dumped to ``blackbox/`` on abnormal exits.  The ``T_STATUS`` verb
answers a live health snapshot — slot/incarnation/heartbeat-age states
plus topology-provided replay/queue/budget/rate fields — to sessionless
probes (``fetch_status``; rendered by tools/fleet_top.py).
"""

from __future__ import annotations

import hashlib
import io
import json
import os
import socket
import struct
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Set, Tuple

import numpy as np

from pytorch_distributed_tpu.agents.param_store import ParamStore
from pytorch_distributed_tpu.memory.feeder import QueueFeeder
from pytorch_distributed_tpu.utils import bandwidth, experience, \
    flight_recorder, flow, tracing
from pytorch_distributed_tpu.utils.experience import Transition
from pytorch_distributed_tpu.utils.faults import FaultInjector

# ---------------------------------------------------------------------------
# framing
# ---------------------------------------------------------------------------

_HDR = struct.Struct("!BQ")

T_HELLO = 1    # JSON {role, process_ind, incarnation} -> T_CLOCK
T_EXP = 2      # savez transition chunk              -> T_CLOCK
T_GETP = 3     # !Q min_version                      -> T_PARAMS
T_PARAMS = 4   # !Q version + raw fp32 (empty = no newer snapshot)
T_CLOCK = 5    # JSON {learner_step, stop}
T_TICK = 6     # JSON {actor_steps, stats?, seq?}    -> T_CLOCK
T_BYE = 7      # empty                               -> (close)
T_PING = 8     # empty heartbeat                     -> T_CLOCK
T_STATUS = 9   # empty -> T_STATUS JSON health snapshot (no HELLO needed)
T_PROFILE = 10  # JSON {seconds, label?, role?} -> T_PROFILE JSON reply
#                (sessionless like T_STATUS: triggers a bounded XLA
#                profiler window on the learner host and reports the
#                trace directory back — tools/fleet_top.py --profile)
T_METRICS = 11  # JSON {rows, offset?, host?} -> T_METRICS JSON reply
#                (sessionless like T_STATUS, outside the fault plane:
#                fleet hosts push batched scalar-window deltas into the
#                learner-host aggregator on the stats cadence; the
#                reply's ``wall`` lets the pusher estimate its clock
#                offset NTP-style — utils/telemetry.MetricsPusher)
# ---- the elastic multi-learner replica plane (ISSUE 15).  Sessionless-
# adjacent: no actor-slot HELLO — membership is the LEASE table below,
# riding the same incarnation-fencing idea as slot claims.  Outside the
# gateway's wire fault plane like T_STATUS (replica drills inject at the
# replica driver through REPLICA_FAULTS — utils/faults.py — where a
# kill/hang is the real failure mode; routing these frames through the
# wire injector would also shift every existing drill's frame schedule).
T_RLEASE = 12   # JSON {action, replica, incarnation|generation, ...}
#                -> JSON reply: lease acquire/renew/release/activate/
#                epoch/status against the gateway's ReplicaRegistry
T_RGRAD = 13    # savez round submission (generation-stamped gradient +
#                PER write-back) -> savez reply (reduced gradient,
#                merged write-backs, surviving membership); BLOCKS the
#                serve thread until the round completes or fences
T_RPRIO = 14    # savez out-of-round |TD| priority write-back -> JSON
#                reply; stale-generation writes are counted rejects
#                (last-generation-wins fencing: a zombie replica can
#                never resurrect stale priorities)
T_SYNC = 15     # JSON {since} -> JSON {term, seq, base_seq, records,
#                wall}: the gateway HA control-plane stream (ISSUE 16).
#                Sessionless like T_STATUS and outside the wire fault
#                plane: the warm standby pulls journal records past its
#                applied offset on its sync cadence; records are
#                ABSOLUTE state snapshots (idempotent to re-apply), so
#                a standby that restarts mid-sync can resync from any
#                offset without double-counting ledger entries.  Only
#                an HA primary answers with records; everyone else
#                replies with an ``error`` key — the verb is never sent
#                unless the HA plane is on, keeping the pre-HA wire
#                byte-identical.
# --- sharded-replay verbs (ISSUE 20): sessionless-adjacent like the
# replica verbs, OUTSIDE the wire fault plane (the shard fault plane is
# lease expiry + generation fencing in memory/shard_plane.py — a
# kill/hang of the shard HOST is the real failure mode).  None of these
# frames is ever sent unless ShardParams.shards > 1, keeping the
# pre-shard wire byte-identical.  All codecs live in shard_plane.py;
# the gateway dispatches to duck-typed ``handle_*`` methods on its
# ``shards=`` object (a LocalShard on shard hosts, a ShardRegistry on
# the coordinator) so this module never imports the plane.
T_SSAMPLE = 16  # savez {meta=[shard, generation], values?} -> savez
#                mass report (+ sampled rows when values were sent):
#                the two-level sample's shard-local leg; empty values
#                doubles as the level-1 mass poll
T_SMASS = 17    # JSON shard membership verbs against the coordinator's
#                ShardRegistry (acquire/renew/release/activate/status)
#                or a mass poll against a shard host
T_SPRIO = 18    # savez {meta=[shard, generation], pidx, ptd} -> JSON
#                reply; stale-generation write-backs are counted
#                rejects (the T_RPRIO contract on the shard plane)

_MAX_FRAME = 1 << 31  # 2 GiB — far above any chunk; rejects garbage lengths

# verb names for the bandwidth X-ray (utils/bandwidth.py): registered
# here so the accountant never imports this module (no import cycle)
bandwidth.register_verbs({
    T_HELLO: "hello", T_EXP: "exp", T_GETP: "getp", T_PARAMS: "params",
    T_CLOCK: "clock", T_TICK: "tick", T_BYE: "bye", T_PING: "ping",
    T_STATUS: "status", T_PROFILE: "profile", T_METRICS: "metrics",
    T_RLEASE: "rlease", T_RGRAD: "rgrad", T_RPRIO: "rprio",
    T_SYNC: "sync", T_SSAMPLE: "ssample", T_SMASS: "smass",
    T_SPRIO: "sprio",
})


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except (TypeError, ValueError):
        return default


def _send_frame(sock: socket.socket, ftype: int, payload: bytes) -> None:
    # stamp BEFORE sendall so the tx note happens-before the peer's
    # reply can complete an RPC — a reader polling the accountant after
    # a synchronous round-trip must never observe the request counted
    # but the reply missing (byte-exact means exact at every quiescent
    # point, not eventually)
    bandwidth.note_frame(sock, ftype, _HDR.size + len(payload), "tx")
    sock.sendall(_HDR.pack(ftype, len(payload)) + payload)


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        part = sock.recv(n - len(buf))
        if not part:
            raise ConnectionError("peer closed")
        buf.extend(part)
    return bytes(buf)


def _recv_frame(sock: socket.socket) -> Tuple[int, bytes]:
    ftype, length = _HDR.unpack(_recv_exact(sock, _HDR.size))
    if length > _MAX_FRAME:
        raise ConnectionError(f"oversized frame: {length}")
    payload = _recv_exact(sock, length) if length else b""
    bandwidth.note_frame(sock, ftype, _HDR.size + length, "rx")
    return ftype, payload


# ---------------------------------------------------------------------------
# experience chunk encoding: columnar, no pickle
# ---------------------------------------------------------------------------

# the six replay columns come from the ONE schema declaration
# (utils.experience.REPLAY_FIELDS) — a re-typed copy here would drift
# silently when a column lands (apexlint schema-contract)
_FIELDS = experience.REPLAY_FIELDS

# Everything encode_chunk may put on the wire / decode_chunk may read:
# the declared wire schema apexlint checks the codec against.  Extending
# the wire format means extending this tuple FIRST (and keeping decode
# tolerant of peers that don't ship the new column yet).
WIRE_COLUMNS = experience.REPLAY_FIELDS + (
    "priority", "priority_ok", "prov", "trace_id", "trace_born")


def encode_chunk(items: List[Tuple[Transition, Optional[float]]]) -> bytes:
    """Stack a chunk of (transition, priority) into one savez payload.
    ``priority`` None (uniform / new-sample-max semantics) travels as an
    explicit ``priority_ok`` validity column — NOT as a NaN sentinel:
    a genuine NaN priority from a diverged actor used to silently decode
    as None ("give it the new-sample max"), the exact corruption the
    ingest quarantine exists to catch; with the validity column a NaN
    survives the wire as the NaN it is and is quarantined at the
    gateway.  (Decode still accepts sentinel-era frames from old peers.)
    A ``tracing.TracedChunk`` carries its trace id + birth wall-clock as
    two extra columns (still no pickle on the wire), so the trace minted
    at the actor survives the hop to the gateway."""
    cols = {f: np.stack([np.asarray(getattr(t, f)) for t, _ in items])
            for f in _FIELDS}
    cols["priority"] = np.array(
        [np.nan if p is None else float(p) for _, p in items],
        dtype=np.float32)
    cols["priority_ok"] = np.array([p is not None for _, p in items],
                                   dtype=np.bool_)
    prov = experience.stack_prov(items)
    if (prov >= 0).any():
        # provenance rides as one (n, 4) int64 column (ISSUE 8); rows
        # minted without provenance are the explicit -1 sentinel.  Only
        # shipped when at least one row carries it, so legacy peers and
        # synthetic chunks keep their exact wire bytes.
        cols["prov"] = prov
    if isinstance(items, tracing.TracedChunk):
        cols["trace_id"] = np.array([items.trace_id], dtype=np.uint64)
        cols["trace_born"] = np.array([items.born], dtype=np.float64)
    out = io.BytesIO()
    np.savez(out, **cols)
    return out.getvalue()


def decode_chunk(payload: bytes
                 ) -> List[Tuple[Transition, Optional[float]]]:
    """Decode + schema-validate one EXP payload.

    Raises ``ValueError`` on a WELL-FRAMED but malformed chunk — missing
    columns, truncated/mismatched column lengths, non-numeric dtypes —
    which the gateway answers with a counted reject + ack (the PEER is
    malformed; retransmitting the same bytes can never help).  Bytes
    ``np.load`` itself cannot parse raise ``ConnectionError`` instead —
    wire-level corruption stays on the drop-connection path, where the
    client's retransmit IS the cure (its copy is clean)."""
    try:
        with np.load(io.BytesIO(payload)) as z:
            cols = {k: z[k] for k in z.files}
    except Exception as e:
        raise ConnectionError(f"unparseable EXP payload: {e!r}")
    missing = [f for f in _FIELDS + ("priority",) if f not in cols]
    if missing:
        raise ValueError(f"malformed chunk: missing columns {missing}")
    pr = cols["priority"]
    if pr.ndim != 1 or pr.dtype.kind != "f":
        raise ValueError(
            f"malformed chunk: priority must be a 1-D float column "
            f"(got ndim={pr.ndim}, dtype={pr.dtype})")
    n = len(pr)
    for f in _FIELDS:
        c = cols[f]
        if c.ndim < 1 or len(c) != n:
            raise ValueError(
                f"malformed chunk: column {f} is "
                f"{'scalar' if c.ndim < 1 else f'length {len(c)}'}, "
                f"want length {n}")
        if c.dtype.kind not in "fiub":
            raise ValueError(
                f"malformed chunk: column {f} dtype {c.dtype} "
                f"is not numeric")
    ok = cols.get("priority_ok")
    if ok is not None and (ok.ndim != 1 or len(ok) != n):
        raise ValueError("malformed chunk: priority_ok length mismatch")
    pv = cols.get("prov")
    if pv is not None and (pv.ndim != 2 or len(pv) != n
                           or pv.shape[1] != len(experience.PROV_FIELDS)
                           or pv.dtype.kind not in "iu"):
        raise ValueError("malformed chunk: prov column must be "
                         f"(n, {len(experience.PROV_FIELDS)}) integer "
                         f"(got shape {pv.shape}, dtype {pv.dtype})")
    items: List[Tuple[Transition, Optional[float]]] = []
    for i in range(n):
        t = Transition(*(cols[f][i] for f in _FIELDS))
        if pv is not None and pv[i][0] >= 0:
            t = t._replace(prov=np.asarray(pv[i],
                                           experience.PROV_DTYPE))
        p = pr[i]
        if ok is not None:
            valid = bool(ok[i])
        else:  # sentinel-era peer: NaN meant None on the old wire
            valid = not np.isnan(p)
        items.append((t, float(p) if valid else None))
    if "trace_id" in cols:  # re-wrap: the trace continues past the wire
        return tracing.TracedChunk(items,
                                   trace_id=int(cols["trace_id"][0]),
                                   born=float(cols["trace_born"][0]))
    return items


# ---------------------------------------------------------------------------
# elastic multi-learner replica plane (ISSUE 15): lease-fenced membership
# + fault-tolerant, generation-stamped gradient exchange
# ---------------------------------------------------------------------------

def resolve_replica(rp=None):
    """ReplicaParams + ``TPU_APEX_REPLICA_<FIELD>`` env overrides — the
    same override-by-env contract as the health/perf/flow planes
    (flow.resolve_flow is the template).  Returns a NEW instance; the
    input is never mutated (Options rides spawn pickles)."""
    import dataclasses

    from pytorch_distributed_tpu.config import ReplicaParams

    if rp is None:
        rp = ReplicaParams()
    changes: Dict[str, Any] = {}
    for f in dataclasses.fields(rp):
        raw = os.environ.get("TPU_APEX_REPLICA_" + f.name.upper())
        if raw is None:
            continue
        cur = getattr(rp, f.name)
        if isinstance(cur, bool):
            changes[f.name] = raw.strip().lower() not in (
                "0", "false", "off", "no", "")
        elif isinstance(cur, int) and not isinstance(cur, bool):
            changes[f.name] = int(float(raw))
        elif isinstance(cur, float):
            changes[f.name] = float(raw)
        else:
            changes[f.name] = raw.strip()
    return dataclasses.replace(rp, **changes) if changes else rp


def export_replica_env(rp) -> None:
    """Export a RESOLVED ReplicaParams into the environment so spawn
    children resolve the same plane the topology configured
    programmatically.  setdefault: an operator's explicit env wins."""
    import dataclasses

    for f in dataclasses.fields(rp):
        val = getattr(rp, f.name)
        if val != f.default:
            os.environ.setdefault("TPU_APEX_REPLICA_" + f.name.upper(),
                                  str(val))


# ---------------------------------------------------------------------------
# gateway high availability (ISSUE 16): durable control plane + warm-standby
# failover with fenced promotion
# ---------------------------------------------------------------------------

def resolve_gateway(gp=None):
    """GatewayParams + ``TPU_APEX_GATEWAY_<FIELD>`` env overrides — the
    same override-by-env contract as the health/perf/flow/replica
    planes.  Returns a NEW instance; the input is never mutated."""
    import dataclasses

    from pytorch_distributed_tpu.config import GatewayParams

    if gp is None:
        gp = GatewayParams()
    changes: Dict[str, Any] = {}
    for f in dataclasses.fields(gp):
        raw = os.environ.get("TPU_APEX_GATEWAY_" + f.name.upper())
        if raw is None:
            continue
        cur = getattr(gp, f.name)
        if isinstance(cur, bool):
            changes[f.name] = raw.strip().lower() not in (
                "0", "false", "off", "no", "")
        elif isinstance(cur, int) and not isinstance(cur, bool):
            changes[f.name] = int(float(raw))
        elif isinstance(cur, float):
            changes[f.name] = float(raw)
        else:
            changes[f.name] = raw.strip()
    return dataclasses.replace(gp, **changes) if changes else gp


def export_gateway_env(gp) -> None:
    """Export a RESOLVED GatewayParams into the environment so spawn
    children (remote actor mains, the standby runner) resolve the same
    HA plane the topology configured.  setdefault: an operator's
    explicit env wins."""
    import dataclasses

    for f in dataclasses.fields(gp):
        val = getattr(gp, f.name)
        if val != f.default:
            os.environ.setdefault("TPU_APEX_GATEWAY_" + f.name.upper(),
                                  str(val))


def parse_endpoints(spec) -> List[Tuple[str, int]]:
    """``host:port,host:port`` (or a ready-made address/list) -> ordered
    endpoint list for DcnClient failover dialing.  IPv6 is out of scope
    for the fleet CLI (matching fleet.py's coordinator parsing)."""
    if not spec:
        return []
    if isinstance(spec, (list, tuple)):
        if (len(spec) == 2 and isinstance(spec[0], str)
                and isinstance(spec[1], int)):
            return [(spec[0], int(spec[1]))]  # a single ("host", port)
        out: List[Tuple[str, int]] = []
        for item in spec:
            if isinstance(item, str):
                out.extend(parse_endpoints(item))
            else:
                h, p = item
                out.append((h, int(p)))
        return out
    out = []
    for part in str(spec).split(","):
        part = part.strip()
        if not part:
            continue
        host, _, port = part.rpartition(":")
        out.append((host or "127.0.0.1", int(port)))
    return out


def _rec_digest(seq: int, kind: str, data: Dict[str, Any]) -> str:
    """Per-record WAL digest: seq|kind|canonical-json, first 12 hex of
    sha256 — enough to catch torn/bit-rotted lines, cheap to verify on
    every recovery scan."""
    blob = f"{seq}|{kind}|{json.dumps(data, sort_keys=True)}"
    return hashlib.sha256(blob.encode()).hexdigest()[:12]


class GatewayJournal:
    """Append-only fsynced WAL for the gateway's mutable control state
    (ISSUE 16) under ``{log_dir}/gateway/`` — the same shared-storage,
    atomic-rename + digest discipline as the PR-2 checkpoint epochs.

    Layout::

        {log_dir}/gateway/TERM.json          # {"term", "wall", "sha"}
        {log_dir}/gateway/wal-<term>.jsonl   # one JSON record per line
        {log_dir}/gateway/standby/wal-0.jsonl  # standby's applied copy

    ``TERM.json`` is the fencing substrate: it is only ever replaced
    atomically (tmp + ``os.replace``) with a strictly larger term, and
    every HA gateway re-reads it (mtime-gated) before applying writes —
    a resurrected primary whose term is below the on-disk term fences
    itself.  Each WAL line is ``{"seq", "kind", "data", "sha"}`` with a
    per-record digest; recovery scans the newest term's file, SKIPS a
    torn/undigestable trailing record (the ``read_scalars`` discipline)
    and falls back to a counted clean slate on an empty/corrupt journal
    — torn state is never fatal, only warm-start warmth is lost.
    Records carry ABSOLUTE values (cumulative ledgers, incarnation and
    seq high-waters), so applying any suffix — or the whole file twice —
    is idempotent by construction."""

    def __init__(self, root: str, standby: bool = False):
        self.dir = os.path.join(root, "gateway")
        if standby:
            # the standby journals its APPLIED copy of the stream in a
            # subdir so it never touches the primary's term WAL; on the
            # shared log_dir both survive either host
            self.dir = os.path.join(self.dir, "standby")
        os.makedirs(self.dir, exist_ok=True)
        self._standby = standby
        self._lock = threading.Lock()
        self._fh = None
        self.term = 0          # term this journal is appending under
        self.seq = 0           # last appended/applied record seq
        self.base_seq = 0      # first seq held in the in-memory tail
        self.appends = 0
        self.recover_warnings = 0
        # in-memory tail served over T_SYNC; bounded — a standby that
        # falls further behind than this gets base_seq back and re-pulls
        # from there (records are idempotent, so the overlap is safe)
        self._tail: List[Dict[str, Any]] = []
        self._tail_max = 65536

    # -- term file (the fencing substrate) --------------------------------

    def _term_path(self) -> str:
        # the term file always lives at the SHARED top-level gateway dir
        # (even for the standby journal, which writes it on promotion)
        d = os.path.dirname(self.dir) if self._standby else self.dir
        return os.path.join(d, "TERM.json")

    def read_term(self) -> int:
        """Digest-checked read of the on-disk term; torn/corrupt/missing
        reads as 0 with a counted warning (never fatal — a gateway that
        cannot prove a HIGHER term exists keeps leading)."""
        try:
            with open(self._term_path()) as fh:
                doc = json.load(fh)
            term = int(doc["term"])
            want = _rec_digest(term, "term", {"wall": doc["wall"]})
            if doc.get("sha") != want:
                self.recover_warnings += 1
                return 0
            return term
        except FileNotFoundError:
            return 0
        except Exception:
            self.recover_warnings += 1
            return 0

    def write_term(self, term: int) -> None:
        """Atomically publish a new (strictly larger) term — tmp +
        ``os.replace``, digest-stamped, fsynced before the rename so a
        torn publish can never read as valid."""
        path = self._term_path()
        wall = time.time()
        doc = {"term": int(term), "wall": wall,
               "sha": _rec_digest(int(term), "term", {"wall": wall})}
        tmp = path + ".tmp"
        with open(tmp, "w") as fh:
            json.dump(doc, fh)
            fh.flush()
            os.fsync(fh.fileno())
        os.replace(tmp, path)

    # -- the WAL itself ---------------------------------------------------

    def _wal_path(self, term: int) -> str:
        return os.path.join(self.dir, f"wal-{term:08d}.jsonl")

    def start_term(self, term: int) -> None:
        """Open (append mode) the WAL for ``term``; subsequent appends
        land there.  seq continues from whatever recover() found so the
        (term, seq) pair is globally monotonic."""
        with self._lock:
            if self._fh is not None:
                self._fh.close()
            self.term = int(term)
            self._fh = open(self._wal_path(self.term), "a")

    def append(self, kind: str, data: Dict[str, Any]) -> int:
        """fsynced append of one control record; returns its seq.
        Raises OSError if the backing store is gone — the gateway treats
        a failed append as self-fencing (can't journal => can't lead)."""
        with self._lock:
            if self._fh is None:
                raise OSError("journal not open")
            self.seq += 1
            rec = {"seq": self.seq, "kind": kind, "data": data,
                   "sha": _rec_digest(self.seq, kind, data)}
            self._fh.write(json.dumps(rec, sort_keys=True) + "\n")
            self._fh.flush()
            os.fsync(self._fh.fileno())
            self.appends += 1
            self._tail.append(rec)
            if len(self._tail) > self._tail_max:
                drop = len(self._tail) - self._tail_max
                del self._tail[:drop]
            self.base_seq = self._tail[0]["seq"] if self._tail else self.seq
            return self.seq

    def apply(self, rec: Dict[str, Any]) -> bool:
        """Standby side: persist one pulled record verbatim (same seq
        numbering as the primary) and advance the applied offset.
        Already-applied seqs are ignored — the resync overlap after a
        standby restart is a no-op, not a double-count."""
        seq = int(rec.get("seq", 0))
        with self._lock:
            if seq <= self.seq:
                return False
            if self._fh is None:
                self._fh = open(self._wal_path(0), "a")
            self.seq = seq
            self._fh.write(json.dumps(rec, sort_keys=True) + "\n")
            self._fh.flush()
            os.fsync(self._fh.fileno())
            self.appends += 1
            self._tail.append(rec)
            if len(self._tail) > self._tail_max:
                del self._tail[:len(self._tail) - self._tail_max]
            self.base_seq = self._tail[0]["seq"] if self._tail else self.seq
            return True

    def records_since(self, since: int) -> Tuple[int, List[Dict[str, Any]]]:
        """(base_seq, records with seq > since) from the in-memory tail —
        the T_SYNC reply body.  A ``since`` below base_seq gets the whole
        tail (idempotent records make the overlap harmless)."""
        with self._lock:
            recs = [r for r in self._tail if r["seq"] > since]
            return self.base_seq, recs

    def recover(self) -> Tuple[int, List[Dict[str, Any]]]:
        """Scan this journal dir newest-term-first and return
        ``(term, records)`` of the first file that yields any valid
        records — digest-verifying every line, skipping a torn or
        undigestable TRAILING record, and counting (never raising) a
        clean-slate fallback on empty/corrupt journals."""
        try:
            names = sorted((n for n in os.listdir(self.dir)
                            if n.startswith("wal-")
                            and n.endswith(".jsonl")), reverse=True)
        except OSError:
            self.recover_warnings += 1
            return 0, []
        top_term = max((int(n[len("wal-"):-len(".jsonl")]) for n in names),
                       default=0)
        for name in names:
            recs: List[Dict[str, Any]] = []
            torn = 0
            try:
                with open(os.path.join(self.dir, name)) as fh:
                    lines = fh.read().split("\n")
            except OSError:
                self.recover_warnings += 1
                continue
            for line in lines:
                if not line.strip():
                    continue
                try:
                    rec = json.loads(line)
                    if rec.get("sha") != _rec_digest(
                            int(rec["seq"]), rec["kind"], rec["data"]):
                        raise ValueError("digest mismatch")
                except Exception:
                    torn += 1
                    continue
                recs.append(rec)
            if torn:
                self.recover_warnings += torn
            if recs:
                with self._lock:
                    self.seq = max(int(r["seq"]) for r in recs)
                    self._tail = recs[-self._tail_max:]
                    self.base_seq = self._tail[0]["seq"]
                # the TERM floor is the newest file seen even when that
                # file itself was empty — a bump can never collide
                return top_term, recs
            if name == names[0]:
                # newest journal empty/corrupt: counted clean slate
                self.recover_warnings += 1
        return top_term, []

    def close(self) -> None:
        with self._lock:
            if self._fh is not None:
                self._fh.close()
                self._fh = None


# the in-process registry handle: FleetTopology sets it at construction
# so the lead learner (which runs in the gateway's own process) joins
# the replica plane through a LocalReplicaChannel instead of dialling
# its own gateway over loopback
_LOCAL_REGISTRY: List[Any] = [None]


def set_local_registry(registry) -> None:
    _LOCAL_REGISTRY[0] = registry


def local_registry():
    return _LOCAL_REGISTRY[0]


# T_RGRAD / T_RPRIO round status codes (int64 ``status`` column)
RSTAT_OK = 0        # round completed; reduced gradient + merge attached
RSTAT_FENCED = 1    # submitter's lease is gone / generation superseded
RSTAT_STALE = 2     # stale round or stale generation: counted reject
RSTAT_TIMEOUT = 3   # round could not complete (wedged registry guard)
RSTAT_NOREG = 4     # no ReplicaRegistry wired on this gateway

# every savez column the replica round codec may ship, either direction
# (the declared wire schema, same contract as WIRE_COLUMNS for EXP
# frames; the codec helpers below are the only writers/readers)
REPLICA_WIRE_COLUMNS = (
    "meta", "ok", "grad", "pidx", "ptd",            # submission
    "status", "generation", "round", "members",     # reply control
    "applied", "epoch_due", "wsrc", "wcount", "widx", "wtd")


def _pack_round(replica: int, generation: int, round_idx: int, ok: bool,
                grad: np.ndarray, pidx: Optional[np.ndarray] = None,
                ptd: Optional[np.ndarray] = None) -> bytes:
    cols = {
        "meta": np.asarray([replica, generation, round_idx], np.int64),
        "ok": np.asarray([1 if ok else 0], np.int64),
        "grad": np.ascontiguousarray(grad, dtype=np.float32),
    }
    if pidx is not None and len(pidx):
        cols["pidx"] = np.ascontiguousarray(pidx, dtype=np.int32)
        cols["ptd"] = np.ascontiguousarray(ptd, dtype=np.float32)
    out = io.BytesIO()
    np.savez(out, **cols)
    return out.getvalue()


def _unpack_round(payload: bytes) -> dict:
    try:
        with np.load(io.BytesIO(payload)) as z:
            cols = {k: z[k] for k in z.files}
    except Exception as e:
        raise ConnectionError(f"unparseable RGRAD payload: {e!r}")
    meta = cols.get("meta")
    if meta is None or meta.shape != (3,) or meta.dtype.kind not in "iu":
        raise ValueError("malformed RGRAD frame: bad meta column")
    return cols


def _pack_round_reply(status: int, generation: int = 0, round_idx: int = 0,
                      grad: Optional[np.ndarray] = None,
                      members: Tuple[int, ...] = (), applied: int = 0,
                      epoch_due: bool = False,
                      writebacks: Optional[List[Tuple[int, np.ndarray,
                                                      np.ndarray]]] = None
                      ) -> bytes:
    cols = {
        "status": np.asarray([status], np.int64),
        "generation": np.asarray([generation], np.int64),
        "round": np.asarray([round_idx], np.int64),
        "members": np.asarray(list(members), np.int64),
        "applied": np.asarray([applied], np.int64),
        "epoch_due": np.asarray([1 if epoch_due else 0], np.int64),
    }
    if grad is not None:
        cols["grad"] = np.ascontiguousarray(grad, dtype=np.float32)
    if writebacks:
        # merged |TD| write-backs, one group per contributing replica in
        # the deterministic merge order: every replica applies ALL
        # groups sequentially, so the N local PER rings stay one
        # logical priority plane
        cols["wsrc"] = np.asarray([s for s, _i, _t in writebacks],
                                  np.int64)
        cols["wcount"] = np.asarray([len(i) for _s, i, _t in writebacks],
                                    np.int64)
        cols["widx"] = np.concatenate(
            [np.asarray(i, np.int32) for _s, i, _t in writebacks])
        cols["wtd"] = np.concatenate(
            [np.asarray(t, np.float32) for _s, _i, t in writebacks])
    out = io.BytesIO()
    np.savez(out, **cols)
    return out.getvalue()


def _unpack_round_reply(payload: bytes) -> dict:
    try:
        with np.load(io.BytesIO(payload)) as z:
            cols = {k: z[k] for k in z.files}
    except Exception as e:
        raise ConnectionError(f"unparseable RGRAD reply: {e!r}")
    out: Dict[str, Any] = {
        "status": int(cols["status"][0]),
        "generation": int(cols.get("generation", [0])[0]),
        "round": int(cols.get("round", [0])[0]),
        "members": [int(m) for m in cols.get("members", [])],
        "applied": int(cols.get("applied", [0])[0]),
        "epoch_due": bool(cols.get("epoch_due", [0])[0]),
        "grad": cols.get("grad"),
    }
    wb: List[Tuple[int, np.ndarray, np.ndarray]] = []
    if "wsrc" in cols and len(cols["wsrc"]):
        off = 0
        for s, n in zip(cols["wsrc"], cols["wcount"]):
            wb.append((int(s), cols["widx"][off:off + int(n)],
                       cols["wtd"][off:off + int(n)]))
            off += int(n)
    out["writebacks"] = wb
    return out


def _pack_prio(replica: int, generation: int, pidx: np.ndarray,
               ptd: np.ndarray) -> bytes:
    out = io.BytesIO()
    np.savez(out,
             meta=np.asarray([replica, generation], np.int64),
             pidx=np.ascontiguousarray(pidx, dtype=np.int32),
             ptd=np.ascontiguousarray(ptd, dtype=np.float32))
    return out.getvalue()


def _pack_noshard_reply() -> bytes:
    """The ONE shard-plane frame this module authors: an SSTAT_NOSHARD
    T_SSAMPLE reply (memory/shard_plane.py owns every other codec and
    the status vocabulary; 3 == shard_plane.SSTAT_NOSHARD — its test
    pins the pair so they cannot drift) for gateways with no ``shards=``
    handler wired."""
    out = io.BytesIO()
    np.savez(out, status=np.asarray([3], np.int64),
             generation=np.asarray([0], np.int64))
    return out.getvalue()


class ReplicaRegistry:
    """Gateway-side membership + round coordinator for the elastic
    multi-learner plane (ISSUE 15).

    **Lease-fenced membership.**  Each replica holds a renewable lease
    stamped with a monotonic GENERATION number (one counter across the
    registry — every acquire, including a rejoin, consumes a fresh
    generation, so generations totally order membership history).  A
    lease neither renewed nor exercised (a round submission is proof of
    life) within ``lease_s`` expires: the member is removed, counted,
    and FENCED — any later gradient or priority write-back stamped with
    its dead generation is a counted reject (``stale_grad_rejected`` /
    ``stale_prio_rejected``), never applied.  A second acquire for the
    same replica id with a HIGHER incarnation evicts the stale holder
    (the double-lease case: a replacement process fencing its own
    half-open predecessor — PR 1's slot fencing lifted to the learner
    plane); equal/lower incarnations are refused.

    **Fault-tolerant rounds.**  ``submit`` blocks until round ``r`` has
    contributions from every live member whose ``joined_round <= r``.
    Membership can shrink while waiting: expiry (dead renewer) or the
    ROUND-STALL rule — once the first contribution lands, members still
    silent after one lease window are expelled (this is how a HUNG
    replica whose background renewer is still faithfully renewing gets
    fenced: leases prove liveness, rounds prove progress).  The round
    then completes over the surviving set: the reduced gradient is the
    mean over the surviving contributions summed in ascending replica
    order (a fixed fp32 reduction order, so an N=1 completion is
    bit-identical to the solo learner's own gradient), and the merged
    per-replica |TD| write-backs ride the reply in the same order so
    every survivor applies the identical priority mutation sequence.

    **Elastic rejoin.**  A mid-training acquire schedules a JOIN
    BARRIER: the round before the joiner's entry round replies
    ``epoch_due`` to every member (rank 0 commits a checkpoint epoch of
    the post-round state — utils/checkpoint.save_epoch), survivors then
    hold at the entry round until the joiner loads that exact epoch and
    ``activate``s (or its ``join_timeout_s`` lapses and the join is
    cancelled).  State convergence is by construction: the joiner
    resumes the very bytes the survivors checkpointed.

    Pure stdlib+numpy — no jax — so tools/chaos_soak.py drills the
    whole plane in milliseconds."""

    def __init__(self, params=None, writer=None):
        self.params = resolve_replica(params)
        self._cond = threading.Condition()
        self._gen = 0
        # replica -> {generation, incarnation, expires, joined_round,
        #             round, renews, born, marks: [(mono, round)]}
        self._members: Dict[int, Dict[str, Any]] = {}
        # fenced generations: replica -> last dead generation (the
        # last-generation-wins check reads the LIVE table; this map is
        # observability for drills)
        self._fenced_gen: Dict[int, int] = {}
        self._rounds: Dict[int, Dict[str, Any]] = {}
        self._round_done = -1
        # replica -> {generation, join_round, deadline}
        self._joining: Dict[int, Dict[str, Any]] = {}
        self._epoch_due: Dict[int, bool] = {}   # round -> commit due
        self._epoch_step: Dict[int, int] = {}   # round -> committed step
        self._oob_writebacks: List[Tuple[int, np.ndarray,
                                         np.ndarray]] = []
        self._churn: List[float] = []  # walls of expiry/fence events
        self._writer = writer
        self._last_emit = 0.0
        self._recorder = flight_recorder.get_recorder("replica-registry")
        # counters (the drill ledger: chaos_soak asserts these EXACTLY)
        self.leases_granted = 0
        self.leases_expired = 0
        self.leases_released = 0
        self.lease_fenced = 0           # double-lease evictions
        self.stale_grad_rejected = 0
        self.stale_prio_rejected = 0
        self.prio_merged_rows = 0
        self.rounds_completed = 0
        self.degraded_completions = 0   # completed over a shrunk set
        self.joins_completed = 0
        self.joins_timed_out = 0

    # -- internals (all under self._cond) -----------------------------------

    def _lease_window(self) -> float:
        return max(0.05, float(self.params.lease_s))

    def _emit_locked(self, force: bool = False) -> None:
        """``replica/*`` scalar rows for mission control (ISSUE 10):
        membership size, current generation, and generation churn
        (lease-consuming events — expiries + fences — in the last 60 s)
        — the series the ``replica_membership`` / ``replica_churn``
        DEFAULT_RULES watch.  Rate-limited; event paths force."""
        if self._writer is None:
            return
        now = time.monotonic()
        if not force and now - self._last_emit < 1.0:
            return
        self._last_emit = now
        wall = time.time()
        cutoff = wall - 60.0
        self._churn = [w for w in self._churn if w >= cutoff]
        try:
            self._writer.scalar("replica/members",
                                float(len(self._members)),
                                step=self._round_done + 1, wall=wall)
            self._writer.scalar("replica/generation", float(self._gen),
                                step=self._round_done + 1, wall=wall)
            self._writer.scalar("replica/generation_churn",
                                float(len(self._churn)),
                                step=self._round_done + 1, wall=wall)
            self._writer.flush()
        except Exception:  # noqa: BLE001 - telemetry is best-effort
            pass

    def _note_churn_locked(self) -> None:
        self._churn.append(time.time())

    def _expire_locked(self, now: float, round_waiting: Optional[int] = None
                       ) -> None:
        """Expire dead leases; with ``round_waiting`` set, also apply
        the round-stall rule to members blocking that round."""
        stalled: List[int] = []
        rnd = self._rounds.get(round_waiting) if round_waiting is not None \
            else None
        for rid, m in list(self._members.items()):
            dead = now > m["expires"]
            reason = "lease-expired"
            if not dead and rnd is not None and not rnd["done"] \
                    and m["joined_round"] <= round_waiting \
                    and rid not in rnd["contribs"] \
                    and rid not in self._joining \
                    and now - rnd["first_at"] > self._lease_window():
                # renewing but not progressing: a hung replica must not
                # wedge the survivors — expelled within one lease window
                dead, reason = True, "round-stall"
            if not dead:
                continue
            del self._members[rid]
            self._fenced_gen[rid] = m["generation"]
            self._joining.pop(rid, None)
            self.leases_expired += 1
            self._note_churn_locked()
            stalled.append(rid)
            self._recorder.record("lease-expired", replica=rid,
                                  generation=m["generation"],
                                  reason=reason)
            print(f"[replica] lease expired: replica {rid} "
                  f"(generation {m['generation']}, {reason})", flush=True)
        if stalled:
            self._emit_locked(force=True)
            self._cond.notify_all()
        # cancel joins whose deadline lapsed (the joiner never loaded
        # its barrier epoch): survivors must proceed
        for rid, j in list(self._joining.items()):
            if now > j["deadline"]:
                del self._joining[rid]
                m = self._members.pop(rid, None)
                if m is not None:
                    self._fenced_gen[rid] = m["generation"]
                self.joins_timed_out += 1
                self._note_churn_locked()
                self._recorder.record("join-timeout", replica=rid)
                self._emit_locked(force=True)
                self._cond.notify_all()

    def _live(self, rid: int, generation: int) -> bool:
        m = self._members.get(rid)
        return m is not None and m["generation"] == generation

    def _required_locked(self, round_idx: int) -> Set[int]:
        return {rid for rid, m in self._members.items()
                if m["joined_round"] <= round_idx}

    # -- lease verbs ---------------------------------------------------------

    def acquire(self, replica: int, incarnation: int) -> dict:
        with self._cond:
            now = time.monotonic()
            self._expire_locked(now)
            held = self._members.get(replica)
            if held is not None:
                if incarnation <= held["incarnation"]:
                    return {"status": "refused",
                            "error": f"replica {replica} already leased "
                                     f"(incarnation {incarnation} <= "
                                     f"{held['incarnation']})"}
                # double-lease: same slot, newer incarnation — fence the
                # stale holder, the newer incarnation wins
                self._fenced_gen[replica] = held["generation"]
                self.lease_fenced += 1
                self._note_churn_locked()
                self._recorder.record("lease-fenced", replica=replica,
                                      old=held["generation"])
            self._gen += 1
            g = self._gen
            open_max = max(self._rounds.keys(), default=self._round_done)
            fresh = self._round_done < 0 and not self._rounds
            if fresh or not (self._members.keys() - {replica}):
                joined = max(0, open_max + 1)
                barrier = None
            else:
                # mid-training join: enter at J, with the round J-1
                # completion carrying the epoch_due flag (rank 0
                # commits the post-(J-1) state the joiner will load)
                joined = open_max + 2
                barrier = joined - 1
                self._epoch_due[barrier] = True
                self._joining[replica] = {
                    "generation": g, "join_round": joined,
                    "deadline": now + max(self.params.join_timeout_s,
                                          self._lease_window())}
            self._members[replica] = {
                "generation": g, "incarnation": int(incarnation),
                "expires": now + self._lease_window(),
                "joined_round": joined, "round": joined - 1,
                "renews": 0, "born": now,
                "marks": [(now, joined - 1)]}
            self.leases_granted += 1
            self._recorder.record("lease-granted", replica=replica,
                                  generation=g, joined_round=joined)
            self._emit_locked(force=True)
            self._cond.notify_all()
            return {"status": "ok", "generation": g,
                    "lease_s": self._lease_window(), "round": joined,
                    "members": sorted(self._members),
                    "epoch_barrier": barrier}

    def renew(self, replica: int, generation: int,
              round_idx: Optional[int] = None) -> dict:
        with self._cond:
            now = time.monotonic()
            self._expire_locked(now)
            if not self._live(replica, generation):
                return {"status": "expired"}
            m = self._members[replica]
            m["expires"] = now + self._lease_window()
            m["renews"] += 1
            if round_idx is not None:
                m["round"] = max(m["round"], int(round_idx))
                m["marks"].append((now, m["round"]))
                del m["marks"][:-8]
            self._emit_locked()
            reply = {"status": "ok", "generation": generation,
                     "members": sorted(self._members)}
            j = self._joining.get(replica)
            if j is not None:
                reply["join"] = {
                    "round": j["join_round"],
                    "epoch_round": j["join_round"] - 1,
                    "epoch_step": self._epoch_step.get(
                        j["join_round"] - 1)}
            return reply

    def release(self, replica: int, generation: int) -> dict:
        with self._cond:
            if self._live(replica, generation):
                m = self._members.pop(replica)
                self._fenced_gen[replica] = m["generation"]
                self._joining.pop(replica, None)
                self.leases_released += 1
                self._recorder.record("lease-released", replica=replica,
                                      generation=generation)
                self._emit_locked(force=True)
                self._cond.notify_all()
            return {"status": "ok"}

    def activate(self, replica: int, generation: int,
                 epoch_step: Optional[int] = None) -> dict:
        """A rejoiner confirms it loaded the barrier epoch: it becomes a
        full member of its join round and the held survivors proceed."""
        with self._cond:
            if not self._live(replica, generation):
                return {"status": "expired"}
            j = self._joining.pop(replica, None)
            if j is not None:
                self.joins_completed += 1
                self._recorder.record("join-activated", replica=replica,
                                      generation=generation,
                                      epoch_step=epoch_step)
            m = self._members[replica]
            now = time.monotonic()
            m["expires"] = now + self._lease_window()
            # restart the entry round's stall clock: the survivors'
            # submissions set first_at while the joiner was still
            # loading the epoch — without this reset, a first-round jit
            # compile longer than one lease window would expel the
            # freshly-activated joiner under the round-stall rule
            rnd = self._rounds.get(m["joined_round"])
            if rnd is not None and not rnd["done"]:
                rnd["first_at"] = now
            self._emit_locked(force=True)
            self._cond.notify_all()
            return {"status": "ok", "round": m["joined_round"],
                    "members": sorted(self._members)}

    def note_epoch(self, replica: int, generation: int, round_idx: int,
                   step: int) -> dict:
        """Rank 0 reports the barrier epoch committed at ``step`` —
        the signal a pending joiner polls for (via ``renew``)."""
        with self._cond:
            if not self._live(replica, generation):
                return {"status": "expired"}
            self._epoch_step[round_idx] = int(step)
            self._epoch_due.pop(round_idx, None)
            self._recorder.record("epoch-committed", round=round_idx,
                                  step=step, by=replica)
            self._cond.notify_all()
            return {"status": "ok"}

    # -- the generation-stamped allreduce round ------------------------------

    def submit(self, replica: int, generation: int, round_idx: int,
               grad: np.ndarray, ok: bool = True,
               pidx: Optional[np.ndarray] = None,
               ptd: Optional[np.ndarray] = None) -> dict:
        """One blocking round contribution; returns the completed
        round's result (or a fenced/stale/timeout status).  The caller's
        serve thread (or the local channel's caller) parks on the
        registry condition; submitting and waiting both count as proof
        of life, so a member blocked on a slow peer is never expired —
        the PEER is, by the round-stall rule."""
        deadline_s = self.params.round_timeout_s or \
            (3.0 * self._lease_window() + 1.0)
        with self._cond:
            now = time.monotonic()
            self._expire_locked(now)
            done = self._rounds.get(round_idx)
            if done is not None and done["done"] \
                    and replica in done["contribs"] \
                    and done["contribs"][replica][0] == generation:
                # idempotent retransmit: this replica already completed
                # this round and its reply ack was lost to a wire blip
                # — hand the retained result back instead of fencing a
                # perfectly live member for retrying
                return done["result"]
            if round_idx <= self._round_done \
                    or (not self._live(replica, generation)):
                stale = not self._live(replica, generation)
                self.stale_grad_rejected += 1
                self._recorder.record("stale-grad-rejected",
                                      replica=replica,
                                      generation=generation,
                                      round=round_idx)
                return {"status": (RSTAT_FENCED if stale
                                   else RSTAT_STALE)}
            rnd = self._rounds.get(round_idx)
            if rnd is None:
                rnd = self._rounds[round_idx] = {
                    "contribs": {}, "first_at": now, "done": False,
                    "result": None,
                    "starting_members": len(self._required_locked(
                        round_idx))}
            rnd["contribs"][replica] = (
                generation, bool(ok),
                np.ascontiguousarray(grad, dtype=np.float32),
                (None if pidx is None or not len(pidx)
                 else (np.ascontiguousarray(pidx, np.int32),
                       np.ascontiguousarray(ptd, np.float32))))
            m = self._members[replica]
            m["round"] = max(m["round"], round_idx)
            m["marks"].append((now, round_idx))
            del m["marks"][:-8]
            self._cond.notify_all()
            deadline = now + deadline_s
            while True:
                now = time.monotonic()
                # waiting in a round is progress: refresh my own lease
                me = self._members.get(replica)
                if me is None or me["generation"] != generation:
                    # fenced while waiting (double-lease eviction)
                    return {"status": RSTAT_FENCED}
                me["expires"] = now + self._lease_window()
                self._expire_locked(now, round_waiting=round_idx)
                if rnd["done"]:
                    return rnd["result"]
                self._try_complete_locked(round_idx)
                if rnd["done"]:
                    return rnd["result"]
                # a PENDING joiner legitimately stretches its entry
                # round past the normal wait (it is loading the barrier
                # epoch, bounded by its own join deadline) — survivors
                # must hold for it, not time out under it
                eff = deadline
                for j in self._joining.values():
                    if j["join_round"] <= round_idx:
                        eff = max(eff, j["deadline"] + 1.0)
                if now > eff:
                    return {"status": RSTAT_TIMEOUT}
                self._cond.wait(0.05)

    def _try_complete_locked(self, round_idx: int) -> None:
        rnd = self._rounds.get(round_idx)
        if rnd is None or rnd["done"]:
            return
        required = self._required_locked(round_idx)
        if not required:
            return
        # only contributions from members STILL live at completion time
        # count (a contributor that died mid-round is dropped from the
        # reduce — its generation is fenced, its gradient with it)
        have = {rid for rid in rnd["contribs"]
                if self._live(rid, rnd["contribs"][rid][0])}
        if not required <= have:
            return
        ids = sorted(required)
        valid = [rid for rid in ids if rnd["contribs"][rid][1]]
        reduced = None
        if valid:
            # fixed fp32 reduction order (ascending replica id): at
            # N=1 the "mean" is grad / 1.0 — bit-identical to the solo
            # learner's own gradient, the degraded-parity contract
            acc = rnd["contribs"][valid[0]][2].astype(np.float32,
                                                      copy=True)
            for rid in valid[1:]:
                acc += rnd["contribs"][rid][2]
            reduced = acc / np.float32(len(valid))
        writebacks = [(rid,) + rnd["contribs"][rid][3]
                      for rid in valid
                      if rnd["contribs"][rid][3] is not None]
        if self._oob_writebacks:
            # fenced-validated out-of-round merges land AFTER the
            # in-round groups, in arrival order — identically on every
            # member, so the logical priority plane never forks
            writebacks.extend(self._oob_writebacks)
            self._oob_writebacks = []
        rnd["result"] = {
            "status": RSTAT_OK,
            "grad": reduced,
            "applied": len(valid),
            "members": list(ids),
            "round": round_idx,
            "epoch_due": bool(self._epoch_due.get(round_idx)),
            "writebacks": writebacks,
        }
        rnd["done"] = True
        self._round_done = max(self._round_done, round_idx)
        self.rounds_completed += 1
        bandwidth.note_round()
        if len(ids) < rnd["starting_members"]:
            self.degraded_completions += 1
            self._recorder.record("round-degraded", round=round_idx,
                                  survivors=ids,
                                  started=rnd["starting_members"])
        # retire old round state (completed results are only read by
        # waiters already parked on them; keep a couple for stragglers)
        for r in [r for r in self._rounds if r < round_idx - 2]:
            del self._rounds[r]
        self._emit_locked()
        self._cond.notify_all()

    def merge_prio(self, replica: int, generation: int, pidx: np.ndarray,
                   ptd: np.ndarray) -> dict:
        """Out-of-round |TD| write-back merge with last-generation-wins
        fencing: live-generation writes queue for the next round's
        merged reply; a zombie's stale-generation write is a counted
        reject and never touches the priority plane."""
        with self._cond:
            self._expire_locked(time.monotonic())
            if not self._live(replica, generation):
                self.stale_prio_rejected += 1
                self._recorder.record("stale-prio-rejected",
                                      replica=replica,
                                      generation=generation,
                                      rows=int(len(pidx)))
                return {"status": "stale"}
            self._oob_writebacks.append(
                (replica, np.ascontiguousarray(pidx, np.int32),
                 np.ascontiguousarray(ptd, np.float32)))
            self.prio_merged_rows += int(len(pidx))
            return {"status": "ok"}

    # -- observability -------------------------------------------------------

    def status_block(self) -> dict:
        """The gateway STATUS ``replicas`` block: membership with lease
        ages + per-replica round rates, the generation counter, and the
        fencing/round ledger — tools/fleet_top.py's replicas panel and
        the chaos drills' exact-counter verdicts both read this."""
        with self._cond:
            now = time.monotonic()
            members = {}
            for rid, m in self._members.items():
                rate = None
                marks = m["marks"]
                if len(marks) >= 2 and marks[-1][0] > marks[0][0] + 0.2:
                    rate = round((marks[-1][1] - marks[0][1])
                                 / (marks[-1][0] - marks[0][0]), 2)
                members[str(rid)] = {
                    "generation": m["generation"],
                    "lease_age": round(
                        max(0.0, now - (m["expires"]
                                        - self._lease_window())), 3),
                    "round": m["round"],
                    "renews": m["renews"],
                    "joining": rid in self._joining,
                    "updates_per_s": rate,
                }
            expected = max(1, int(self.params.replicas))
            return {
                "expected": expected,
                "members": members,
                "degraded": len(members) < expected,
                "generation": self._gen,
                "rounds_completed": self.rounds_completed,
                "degraded_completions": self.degraded_completions,
                "counters": {
                    "leases_granted": self.leases_granted,
                    "leases_expired": self.leases_expired,
                    "leases_released": self.leases_released,
                    "lease_fenced": self.lease_fenced,
                    "stale_grad_rejected": self.stale_grad_rejected,
                    "stale_prio_rejected": self.stale_prio_rejected,
                    "prio_merged_rows": self.prio_merged_rows,
                    "joins_completed": self.joins_completed,
                    "joins_timed_out": self.joins_timed_out,
                },
            }

    # -- wire dispatch (called by DcnGateway serve threads) ------------------

    def handle_lease(self, msg: dict) -> dict:
        action = str(msg.get("action", ""))
        try:
            rid = int(msg.get("replica"))
        except (TypeError, ValueError):
            return {"status": "error", "error": "bad replica id"}
        if action == "acquire":
            return self.acquire(rid, int(msg.get("incarnation", 0)))
        gen = int(msg.get("generation", -1))
        if action == "renew":
            r = msg.get("round")
            return self.renew(rid, gen,
                              int(r) if r is not None else None)
        if action == "release":
            return self.release(rid, gen)
        if action == "activate":
            es = msg.get("epoch_step")
            return self.activate(rid, gen,
                                 int(es) if es is not None else None)
        if action == "epoch":
            return self.note_epoch(rid, gen, int(msg.get("round", -1)),
                                   int(msg.get("step", -1)))
        return {"status": "error", "error": f"unknown action {action!r}"}

    def handle_round(self, payload: bytes) -> bytes:
        try:
            cols = _unpack_round(payload)
        except ValueError:
            return _pack_round_reply(RSTAT_STALE)  # malformed: reject
        rid, gen, rnd = (int(x) for x in cols["meta"])
        pidx, ptd = cols.get("pidx"), cols.get("ptd")
        res = self.submit(rid, gen, rnd, cols.get(
            "grad", np.zeros(0, np.float32)),
            ok=bool(cols.get("ok", [1])[0]),
            pidx=pidx, ptd=ptd)
        if res["status"] != RSTAT_OK:
            return _pack_round_reply(res["status"])
        return _pack_round_reply(
            RSTAT_OK, generation=gen, round_idx=res["round"],
            grad=res["grad"], members=res["members"],
            applied=res["applied"], epoch_due=res["epoch_due"],
            writebacks=res["writebacks"])

    def handle_prio(self, payload: bytes) -> dict:
        try:
            with np.load(io.BytesIO(payload)) as z:
                meta = z["meta"]
                pidx = z["pidx"]
                ptd = z["ptd"]
        except Exception as e:
            raise ConnectionError(f"unparseable RPRIO payload: {e!r}")
        return self.merge_prio(int(meta[0]), int(meta[1]), pidx, ptd)


class ReplicaFenced(RuntimeError):
    """This replica's lease is gone (expired, superseded, or the round
    reply said fenced): its generation can no longer write anything.
    The driver's recovery is rejoin-at-a-new-generation or a nonzero
    exit for the supervisor — never a silent continue."""


class LocalReplicaChannel:
    """In-process channel to a ReplicaRegistry — the lead learner runs
    in the gateway's own process, so its replica-plane traffic skips
    the wire (same surface as ReplicaClient; tests use it too)."""

    def __init__(self, registry: ReplicaRegistry, replica: int,
                 incarnation: Optional[int] = None):
        self.registry = registry
        self.replica = replica
        self.incarnation = (int(incarnation) if incarnation is not None
                            else time.time_ns() // 1_000_000)
        self.generation: Optional[int] = None
        self._granted_lease_s: Optional[float] = None
        self.fenced = threading.Event()
        self._renew_stop = threading.Event()
        self._renew_thread: Optional[threading.Thread] = None
        self._round = 0  # last round index reported on renews

    # -- surface shared with ReplicaClient -----------------------------------

    def acquire(self) -> dict:
        self.incarnation += 1
        reply = self.registry.acquire(self.replica, self.incarnation)
        if reply.get("status") != "ok":
            raise ReplicaFenced(
                f"replica {self.replica} lease refused: "
                f"{reply.get('error')}")
        self.generation = reply["generation"]
        # the renew cadence follows the SERVER'S lease window (it rides
        # the acquire reply): a client configured with a longer window
        # than the registry's would otherwise expire between renews
        self._granted_lease_s = float(reply.get("lease_s", 0.0)) or None
        self.fenced.clear()
        return reply

    def renew(self) -> dict:
        if self.generation is None:
            return {"status": "expired"}
        reply = self.registry.renew(self.replica, self.generation,
                                    self._round)
        if reply.get("status") != "ok":
            self.fenced.set()
        return reply

    def start_renewer(self, period: Optional[float] = None) -> None:
        if self._renew_thread is not None \
                and self._renew_thread.is_alive():
            return
        self._renew_stop.clear()
        p = period or (self.registry.params.renew_s
                       or (self._granted_lease_s
                           or self.registry._lease_window()) / 3.0)

        def _loop() -> None:
            while not self._renew_stop.wait(p):
                if self.fenced.is_set():
                    return
                self.renew()

        self._renew_thread = threading.Thread(
            target=_loop, name=f"replica-renew-{self.replica}",
            daemon=True)
        self._renew_thread.start()

    def submit_round(self, round_idx: int, grad: np.ndarray,
                     ok: bool = True,
                     pidx: Optional[np.ndarray] = None,
                     ptd: Optional[np.ndarray] = None) -> dict:
        if self.generation is None:
            raise ReplicaFenced(f"replica {self.replica} has no lease")
        self._round = round_idx
        res = self.registry.submit(self.replica, self.generation,
                                   round_idx, grad, ok=ok,
                                   pidx=pidx, ptd=ptd)
        if res["status"] in (RSTAT_FENCED, RSTAT_STALE):
            self.fenced.set()
        return res

    def merge_prio(self, pidx: np.ndarray, ptd: np.ndarray,
                   generation: Optional[int] = None) -> dict:
        g = self.generation if generation is None else generation
        if g is None:
            raise ReplicaFenced(f"replica {self.replica} has no lease")
        return self.registry.merge_prio(self.replica, g, pidx, ptd)

    def note_epoch(self, round_idx: int, step: int) -> dict:
        return self.registry.note_epoch(self.replica, self.generation,
                                        round_idx, step)

    def activate(self, epoch_step: Optional[int] = None) -> dict:
        return self.registry.activate(self.replica, self.generation,
                                      epoch_step)

    def members(self) -> List[int]:
        reply = self.renew()
        return list(reply.get("members", []))

    def wait_members(self, n: int, timeout: float = 30.0) -> bool:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if len(self.members()) >= n:
                return True
            time.sleep(0.05)
        return False

    def poll_join(self) -> Optional[dict]:
        return self.renew().get("join")

    def release(self) -> None:
        if self.generation is not None and not self.fenced.is_set():
            self.registry.release(self.replica, self.generation)

    def close(self) -> None:
        self._renew_stop.set()
        if self._renew_thread is not None:
            self._renew_thread.join(2.0)
            self._renew_thread = None


class ReplicaClient:
    """Wire twin of LocalReplicaChannel: one replica host's connection
    to the lead gateway's replica plane.  Two sockets — a control
    connection for the lease verbs (sessionless-adjacent: cheap JSON
    RPCs that must keep flowing while a round blocks) and a round
    connection whose T_RGRAD request parks server-side until the round
    completes.  Transport errors surface as ReplicaFenced after one
    redial attempt: the replica plane's recovery story is leases and
    rejoin, not transparent session resumption — a replica that cannot
    reach the registry for a lease window IS expired."""

    def __init__(self, address: Tuple[str, int], replica: int,
                 params=None, incarnation: Optional[int] = None):
        self.address = address
        self.replica = replica
        self.params = resolve_replica(params)
        self.incarnation = (int(incarnation) if incarnation is not None
                            else time.time_ns() // 1_000_000)
        self.generation: Optional[int] = None
        self._granted_lease_s: Optional[float] = None
        self.fenced = threading.Event()
        self._lease_lock = threading.Lock()
        self._round_lock = threading.Lock()
        self._lease_sock: Optional[socket.socket] = None
        self._round_sock: Optional[socket.socket] = None
        self._renew_stop = threading.Event()
        self._renew_thread: Optional[threading.Thread] = None
        self._round = 0

    def _lease_window(self) -> float:
        return max(0.05, float(self.params.lease_s))

    def _rpc(self, which: str, ftype: int, payload: bytes,
             timeout: float) -> Tuple[int, bytes]:
        lock = self._lease_lock if which == "lease" else self._round_lock
        attr = "_lease_sock" if which == "lease" else "_round_sock"
        with lock:
            for attempt in (0, 1):
                sock = getattr(self, attr)
                try:
                    if sock is None:
                        sock = socket.create_connection(
                            self.address, timeout=5.0)
                        sock.setsockopt(socket.IPPROTO_TCP,
                                        socket.TCP_NODELAY, 1)
                        bandwidth.register_socket(sock, "replica",
                                                  self.replica)
                        setattr(self, attr, sock)
                    sock.settimeout(timeout)
                    _send_frame(sock, ftype, payload)
                    return _recv_frame(sock)
                except (ConnectionError, OSError):
                    try:
                        if sock is not None:
                            sock.close()
                    except OSError:
                        pass
                    setattr(self, attr, None)
                    if attempt:
                        raise

    def _lease_rpc(self, msg: dict,
                   timeout: Optional[float] = None) -> dict:
        rtype, payload = self._rpc(
            "lease", T_RLEASE, json.dumps(msg).encode(),
            timeout or max(5.0, self._lease_window()))
        if rtype != T_RLEASE:
            raise ConnectionError(
                f"expected T_RLEASE reply, got frame type {rtype}")
        try:
            return json.loads(payload.decode())
        except (ValueError, UnicodeDecodeError) as e:
            raise ConnectionError(f"undecodable RLEASE reply: {e}")

    # -- surface (mirrors LocalReplicaChannel) -------------------------------

    def acquire(self) -> dict:
        self.incarnation += 1
        reply = self._lease_rpc({"action": "acquire",
                                 "replica": self.replica,
                                 "incarnation": self.incarnation})
        if reply.get("status") != "ok":
            raise ReplicaFenced(
                f"replica {self.replica} lease refused: "
                f"{reply.get('error')}")
        self.generation = reply["generation"]
        # the renew cadence follows the SERVER'S lease window (it rides
        # the acquire reply): a client configured with a longer window
        # than the registry's would otherwise expire between renews
        self._granted_lease_s = float(reply.get("lease_s", 0.0)) or None
        self.fenced.clear()
        return reply

    def renew(self) -> dict:
        if self.generation is None:
            return {"status": "expired"}
        try:
            reply = self._lease_rpc({"action": "renew",
                                     "replica": self.replica,
                                     "generation": self.generation,
                                     "round": self._round})
        except (ConnectionError, OSError):
            return {"status": "error"}
        if reply.get("status") == "expired":
            self.fenced.set()
        return reply

    def start_renewer(self, period: Optional[float] = None) -> None:
        if self._renew_thread is not None \
                and self._renew_thread.is_alive():
            return
        self._renew_stop.clear()
        p = period or (self.params.renew_s
                       or (self._granted_lease_s
                           or self._lease_window()) / 3.0)

        def _loop() -> None:
            while not self._renew_stop.wait(p):
                if self.fenced.is_set():
                    return
                self.renew()

        self._renew_thread = threading.Thread(
            target=_loop, name=f"replica-renew-{self.replica}",
            daemon=True)
        self._renew_thread.start()

    def submit_round(self, round_idx: int, grad: np.ndarray,
                     ok: bool = True,
                     pidx: Optional[np.ndarray] = None,
                     ptd: Optional[np.ndarray] = None) -> dict:
        if self.generation is None:
            raise ReplicaFenced(f"replica {self.replica} has no lease")
        self._round = round_idx
        timeout = (self.params.round_timeout_s
                   or 3.0 * self._lease_window() + 1.0) + 10.0
        rtype, payload = self._rpc(
            "round", T_RGRAD,
            _pack_round(self.replica, self.generation, round_idx, ok,
                        grad, pidx, ptd),
            timeout)
        if rtype != T_RGRAD:
            raise ConnectionError(
                f"expected T_RGRAD reply, got frame type {rtype}")
        res = _unpack_round_reply(payload)
        if res["status"] in (RSTAT_FENCED, RSTAT_STALE):
            self.fenced.set()
        return res

    def merge_prio(self, pidx: np.ndarray, ptd: np.ndarray,
                   generation: Optional[int] = None) -> dict:
        g = self.generation if generation is None else generation
        if g is None:
            raise ReplicaFenced(f"replica {self.replica} has no lease")
        rtype, payload = self._rpc(
            "lease", T_RPRIO, _pack_prio(self.replica, g, pidx, ptd),
            max(5.0, self._lease_window()))
        if rtype != T_RPRIO:
            raise ConnectionError(
                f"expected T_RPRIO reply, got frame type {rtype}")
        return json.loads(payload.decode())

    def note_epoch(self, round_idx: int, step: int) -> dict:
        return self._lease_rpc({"action": "epoch",
                                "replica": self.replica,
                                "generation": self.generation,
                                "round": round_idx, "step": step})

    def activate(self, epoch_step: Optional[int] = None) -> dict:
        return self._lease_rpc({"action": "activate",
                                "replica": self.replica,
                                "generation": self.generation,
                                "epoch_step": epoch_step})

    def members(self) -> List[int]:
        return list(self.renew().get("members", []))

    def wait_members(self, n: int, timeout: float = 30.0) -> bool:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if len(self.members()) >= n:
                return True
            time.sleep(0.05)
        return False

    def poll_join(self) -> Optional[dict]:
        return self.renew().get("join")

    def release(self) -> None:
        if self.generation is None or self.fenced.is_set():
            return
        try:
            self._lease_rpc({"action": "release",
                             "replica": self.replica,
                             "generation": self.generation})
        except (ConnectionError, OSError):
            pass

    def close(self) -> None:
        self._renew_stop.set()
        if self._renew_thread is not None:
            self._renew_thread.join(2.0)
            self._renew_thread = None
        for attr in ("_lease_sock", "_round_sock"):
            sock = getattr(self, attr)
            if sock is not None:
                try:
                    sock.close()
                except OSError:
                    pass
                setattr(self, attr, None)


# ---------------------------------------------------------------------------
# learner-host gateway
# ---------------------------------------------------------------------------

class DcnGateway:
    """Accepts remote-actor connections on the learner host.

    ``put_chunk`` receives decoded ``[(Transition, priority), ...]`` lists —
    wire it to the single-owner memory's spawn queue (``feed_queue_of``) so
    remote experience merges with local feeders on the learner's drain path.

    Slot registry: each remote actor slot maps to the (incarnation,
    connection) that owns it.  A reconnecting actor fences its own stale
    predecessor by arriving with a higher incarnation (see module
    docstring); connections idle past ``idle_deadline`` seconds — a
    multiple of the clients' heartbeat interval — are presumed dead and
    dropped, which frees their slots without waiting on TCP keepalive.
    """

    def __init__(self, param_store, clock, actor_stats,
                 put_chunk: Callable[[list], None],
                 host: str = "0.0.0.0", port: int = 0,
                 local_actors: int = 0,
                 idle_deadline: Optional[float] = None,
                 faults: Optional[FaultInjector] = None,
                 health: Optional[Callable[[], dict]] = None,
                 profiler: Optional[Callable[[dict], dict]] = None,
                 metrics_sink: Optional[Callable[[dict], int]] = None,
                 flow_params=None,
                 pressure: Optional[Callable[[], float]] = None,
                 flow_writer=None,
                 replicas: Optional[ReplicaRegistry] = None,
                 shards=None,
                 gateway_params=None,
                 log_dir: Optional[str] = None,
                 ha_role: str = "primary",
                 sync_from: Optional[Tuple[str, int]] = None,
                 ha_writer=None,
                 resume_term: Optional[int] = None):
        self.param_store = param_store
        self.clock = clock
        self.actor_stats = actor_stats
        self.put_chunk = put_chunk
        self.local_actors = local_actors
        self._idle_deadline = (_env_float("DCN_IDLE_DEADLINE", 60.0)
                               if idle_deadline is None else idle_deadline)
        self._faults = (faults if faults is not None
                        else FaultInjector.from_env("gateway"))
        # extra STATUS fields from the owning topology (replay fill,
        # queue depth, restart budget, learner rate — things only the
        # learner-host wiring can see); called per STATUS request
        self._health = health
        # on-demand profiling provider (utils/perf.run_profile_window
        # via the owning topology): T_PROFILE requests block their own
        # serve thread for the bounded window and reply with the trace
        # dir; no provider wired -> error reply, never a crash
        self._profiler = profiler
        self.profiles_served = 0
        # T_METRICS sink (utils/telemetry.MissionControl.ingest_remote
        # via the owning topology): receives one pushed batch dict and
        # returns rows absorbed; no sink wired -> counted error reply,
        # never a crash
        self._metrics_sink = metrics_sink
        self.metrics_batches = 0
        self.metrics_rows = 0
        # replica plane (ISSUE 15): the lease-fenced membership registry
        # + gradient-exchange coordinator for N data-parallel learner
        # replicas.  None on non-replicated fleets — the verbs then
        # answer counted errors, never crash a serve thread.
        self._replicas = replicas
        # shard plane (ISSUE 20): duck-typed handler for the shard
        # verbs — a memory.shard_plane.LocalShard on replay-shard
        # hosts, a ShardRegistry on the coordinator.  Duck-typed so
        # this module never imports the plane; None on unsharded
        # fleets — the verbs then answer counted errors, never crash
        # a serve thread, and STATUS carries no shards block at all.
        self._shards = shards
        self._tracer = tracing.get_tracer("gateway")
        self._recorder = flight_recorder.get_recorder("gateway")
        # flow-control plane (ISSUE 11, utils/flow.py): per-slot credit
        # grants on every ack, admission control + the brownout ladder.
        # Inert without a ``pressure`` provider (the governor never
        # leaves healthy, no credit field rides the wire), so bare
        # test/tool gateways behave exactly as before.
        self._flow = None
        if flow.resolve_flow(flow_params).enabled:
            self._flow = flow.GatewayFlow(
                flow_params, pressure=pressure,
                recorder=self._recorder, writer=flow_writer)
        self._born = time.monotonic()
        self._srv = socket.create_server((host, port))
        self._srv.settimeout(0.25)
        self.port = self._srv.getsockname()[1]
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self._slots: Dict[int, Tuple[int, socket.socket]] = {}
        self._tick_seq: Dict[int, int] = {}  # per-slot dedup high-water
        self._last_seen: Dict[int, float] = {}  # slot -> last frame (mono)
        self._slots_lock = threading.Lock()
        self._conns: Set[socket.socket] = set()
        self.connections = 0
        self.chunks_in = 0
        self.status_served = 0
        self.fenced = 0  # stale predecessors evicted by higher incarnations
        # health-sentinel ingest counters: schema-invalid EXP frames
        # rejected (counted warning + ack, never a session teardown) and
        # transitions quarantined per source slot — both surfaced by the
        # T_STATUS verb so fleet_top shows WHICH actor is poisoning
        self.frames_rejected = 0
        self.quarantined: Dict[str, int] = {}
        self._validators: Dict[str, Any] = {}
        # gateway HA plane (ISSUE 16): durable control journal + warm
        # standby + fenced promotion.  Entirely absent unless a resolved
        # GatewayParams enables it AND a log_dir exists to journal under
        # — the default single-gateway fleet stays byte-identical on the
        # wire (no term/sync fields, no TERM/WAL files, no STATUS block).
        self._gp = resolve_gateway(gateway_params)
        self._ha = bool(self._gp.enabled and log_dir)
        self._ha_log_dir = log_dir
        self._role = ("standby" if (self._ha and ha_role == "standby")
                      else "primary")
        # a standby refuses session verbs (counted) until promoted, so
        # failing-over clients land on the ConnectionError -> redial
        # path, never the terminal DcnRefused path
        self._serving = not (self._ha and self._role == "standby")
        self._sync_from = sync_from
        self._ha_writer = ha_writer
        self.term = 0
        self.promotions = 0
        self.gateway_term_fenced = 0  # writes rejected on a stale term
        self.standby_refused = 0
        self.failover_lost = 0  # acked-but-undrained rows lost in failover
        self.sync_served = 0
        self.promoted = threading.Event()
        self._term_fenced = False
        self._journal_dead = False
        self._term_checked = 0.0
        # re-read TERM.json at most this often on the write path: bounds
        # how long a fenced primary can run before noticing, well inside
        # the lease window that gates any promotion in the first place
        self._term_check_every = min(0.05, max(0.01, self._gp.lease_s / 10))
        self._journal: Optional[GatewayJournal] = None
        # absolute ingest totals carried across terms (seeded from the
        # journal / sync stream; own-plane counters add on top)
        self._ha_carry: Dict[str, int] = {}
        self._inc_floor: Dict[int, int] = {}  # journal-seeded slot fencing
        self._ha_thread: Optional[threading.Thread] = None
        self._ha_state_every = max(0.05, min(0.5, self._gp.sync_s))
        self._ha_state_last = 0.0
        self._sync_seq = 0
        self._sync_term = 0
        self._last_sync_ok = time.monotonic()
        if self._ha:
            self._ha_init(resume_term)
        # all state above must exist before the first connection lands
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name="dcn-accept", daemon=True)
        self._accept_thread.start()

    # -- server loops -------------------------------------------------------

    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                conn, addr = self._srv.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            # slot is unknown until HELLO; the serve loop re-registers
            bandwidth.register_socket(conn, "gateway")
            self.connections += 1
            with self._slots_lock:
                self._conns.add(conn)
            t = threading.Thread(target=self._serve, args=(conn, addr),
                                 name=f"dcn-conn-{addr}", daemon=True)
            t.start()
            # prune threads of departed peers — actor churn is expected
            self._threads = [x for x in self._threads if x.is_alive()]
            self._threads.append(t)

    def _clock_payload(self, slot: Optional[int] = None) -> bytes:
        msg = {
            "learner_step": int(self.clock.learner_step.value),
            "stop": bool(self.clock.stop.is_set()),
            # gateway wall clock: remote clients estimate their offset
            # to the learner host off the reply midpoint (NTP-style),
            # so tools/timeline.py can align cross-host events on one
            # clock.  Old peers ignore the extra key.
            "wall": time.time(),
        }
        if self._flow is not None and slot is not None:
            # flow control rides the ack (ISSUE 11): ``credits`` is how
            # many chunks this slot may send before its next grant
            # (absent while healthy = unlimited — old peers and calm
            # fleets see the exact pre-flow wire); ``brownout`` tells
            # the client host which shed tier the ladder is on.
            grant = self._flow.grant(slot)
            if grant is not None:
                msg["credits"] = grant
            tier = self._flow.governor.tier
            if tier:
                msg["brownout"] = tier
        return json.dumps(msg).encode()

    # -- gateway HA plane (ISSUE 16) ----------------------------------------

    def _ha_init(self, resume_term: Optional[int]) -> None:
        """Role-split HA bring-up.  Primary: recover the journal, bump +
        publish the term, warm-seed tick dedup / incarnation floors /
        ledger carry from the recovered records.  Standby: recover its
        own applied-copy journal (the resync offset) and start the sync
        loop.  ``resume_term`` is the drill hook for a RESURRECTED
        primary: it believes the stale term it is given and must
        discover the on-disk one through the fencing path — it never
        bumps, never writes TERM.json, never opens a WAL."""
        if self._role == "standby":
            self._journal = GatewayJournal(self._ha_log_dir, standby=True)
            _term, recs = self._journal.recover()
            self._seed_records(recs)
            self._sync_seq = self._journal.seq
            self._ha_thread = threading.Thread(
                target=self._ha_loop, name="dcn-ha-sync", daemon=True)
            self._ha_thread.start()
            return
        self._journal = GatewayJournal(self._ha_log_dir)
        if resume_term is not None:
            self.term = int(resume_term)
            return
        disk = self._journal.read_term()
        rec_term, recs = self._journal.recover()
        self.term = max(disk, rec_term) + 1
        self._journal.write_term(self.term)
        self._journal.start_term(self.term)
        self._seed_records(recs)
        self._ha_append("start", {"term": self.term})
        self._recorder.record("gateway-term", term=self.term,
                              warm=len(recs))

    def _ha_append(self, kind: str, data: Dict[str, Any]) -> None:
        if self._journal is None:
            return
        try:
            self._journal.append(kind, data)
        except OSError:
            # can't journal => can't lead: losing the shared log dir is
            # indistinguishable from being the partitioned side of a
            # split brain, so writes self-fence from here on (counted
            # per rejected frame in gateway_term_fenced)
            self._journal_dead = True

    def _ha_write_ok(self) -> bool:
        """May this gateway still apply session writes?  False once a
        HIGHER term is visible on disk (a standby promoted over us) or
        our own journal died — the structural split-brain guarantee."""
        if self._term_fenced or self._journal_dead:
            return False
        now = time.monotonic()
        if now - self._term_checked >= self._term_check_every:
            self._term_checked = now
            disk = self._journal.read_term() if self._journal else 0
            if disk > self.term:
                self._term_fenced = True
                self._recorder.record("gateway-fenced",
                                      term=self.term, disk=disk)
                print(f"[dcn] gateway term {self.term} fenced by "
                      f"on-disk term {disk}", flush=True)
                return False
        return True

    def _session_gate(self, ftype: int) -> None:
        """Pre-dispatch HA gate for SESSION verbs only (sessionless
        probes always answer).  An unpromoted standby refuses with a
        counted connection drop — the client's redial path then cycles
        to the next endpoint, never the terminal DcnRefused path — and
        a fenced stale-term gateway's writes/grants are counted rejects
        that are NEVER applied."""
        if ftype in (T_STATUS, T_PROFILE, T_METRICS, T_RLEASE,
                     T_RGRAD, T_RPRIO, T_SYNC, T_SSAMPLE, T_SMASS,
                     T_SPRIO, T_BYE):
            return
        if not self._serving:
            self.standby_refused += 1
            raise ConnectionError(
                "standby gateway: sessions refused before promotion")
        if not self._ha_write_ok():
            self.gateway_term_fenced += 1
            self._recorder.record("stale-term-write",
                                  ftype=ftype, term=self.term)
            raise ConnectionError("gateway term fenced")

    def _ha_ledger(self) -> Dict[str, int]:
        """ABSOLUTE cumulative ingest-side totals across terms: the
        journal carry (what previous terms accounted) plus this
        process's own counters — what the state records persist and the
        sync stream ships, so re-applying any suffix is idempotent."""
        led = {"ingested": int(self._ha_carry.get("ingested", 0)),
               "shed": int(self._ha_carry.get("shed", 0)),
               "quarantined": int(self._ha_carry.get("quarantined", 0)),
               "ingested_bytes":
                   int(self._ha_carry.get("ingested_bytes", 0)),
               "rejected_bytes":
                   int(self._ha_carry.get("rejected_bytes", 0)),
               "shed_bytes": int(self._ha_carry.get("shed_bytes", 0))}
        if self._flow is not None:
            led["ingested"] += int(self._flow.ingested_rows)
            led["shed"] += int(sum(self._flow.shed_rows.values()))
            # byte legs (ISSUE 18) ride the same absolute-cumulative
            # contract as the row legs, so re-applying any journal
            # suffix stays idempotent
            led["ingested_bytes"] += int(self._flow.ingested_bytes)
            led["rejected_bytes"] += int(self._flow.rejected_bytes)
            led["shed_bytes"] += int(self._flow.shed_bytes)
        with self._slots_lock:
            led["quarantined"] += int(sum(self.quarantined.values()))
        return led

    def _ha_note_state(self) -> None:
        """Rate-limited composite state record on the serve path: tick
        dedup high-waters, clock counters, the cumulative ledger and the
        failover-lost count — everything a warm restart or a promoting
        standby needs to continue the control plane without double
        counting.  One fsynced append per ``_ha_state_every`` window,
        amortized across every chunk in it."""
        if not self._serving or self._journal_dead or self._term_fenced:
            return
        now = time.monotonic()
        if now - self._ha_state_last < self._ha_state_every:
            return
        self._ha_state_last = now
        with self._slots_lock:
            ticks = {str(s): int(q) for s, q in self._tick_seq.items()}
        self._ha_append("state", {
            "tick_seq": ticks,
            "clock": {
                "learner_step": int(self.clock.learner_step.value),
                "actor_step": int(self.clock.actor_step.value)},
            "chunks_in": int(self._ha_carry.get("chunks_in", 0))
            + self.chunks_in,
            "lost": self.failover_lost,
            "ledger": self._ha_ledger()})

    def _seed_records(self, recs: List[Dict[str, Any]]) -> None:
        """Apply journal/sync records to local control state.  Every
        field is an ABSOLUTE value applied through max(), so any replay
        — a restarted standby re-pulling from an old offset, a recovery
        scan over a file containing duplicates — lands exactly once."""
        for rec in recs:
            kind, data = rec.get("kind"), rec.get("data") or {}
            if kind == "slot":
                s = int(data.get("slot", -1))
                inc = int(data.get("inc", -1))
                if s >= 0:
                    with self._slots_lock:
                        if inc > self._inc_floor.get(s, -1):
                            self._inc_floor[s] = inc
            elif kind == "state":
                with self._slots_lock:
                    for s, q in (data.get("tick_seq") or {}).items():
                        si = int(s)
                        if int(q) > self._tick_seq.get(si, -1):
                            self._tick_seq[si] = int(q)
                led = data.get("ledger") or {}
                for k in ("ingested", "shed", "quarantined",
                          "ingested_bytes", "rejected_bytes",
                          "shed_bytes"):
                    v = int(led.get(k, 0))
                    if v > self._ha_carry.get(k, 0):
                        self._ha_carry[k] = v
                ci = int(data.get("chunks_in", 0))
                if ci > self._ha_carry.get("chunks_in", 0):
                    self._ha_carry["chunks_in"] = ci
                lost = int(data.get("lost", 0))
                if lost > self.failover_lost:
                    self.failover_lost = lost

    def _apply_record(self, rec: Dict[str, Any]) -> None:
        """Standby side: digest-check one pulled record, persist it to
        the applied-copy journal (dup seqs are no-ops) and seed state."""
        try:
            if rec.get("sha") != _rec_digest(
                    int(rec["seq"]), rec["kind"], rec["data"]):
                return
        except (KeyError, TypeError, ValueError):
            return
        if self._journal is not None and not self._journal.apply(rec):
            return
        self._seed_records([rec])

    def _ha_emit(self, stale: float) -> None:
        """The standby's health scalar: ``gateway/sync_stale`` is 1.0
        while the primary is unreachable and 0.0 when healthy — the
        telemetry DEFAULT_RULES ``gateway_failover`` alert fires on
        sustained staleness and RESOLVES once the promoted standby keeps
        reporting 0.  Non-HA fleets never report the tag, so the rule is
        inert there (absence rules never fire for never-seen tags)."""
        if self._ha_writer is None:
            return
        try:
            wall = time.time()
            self._ha_writer.scalar("gateway/sync_stale", float(stale),
                                   step=self._sync_seq, wall=wall)
            self._ha_writer.scalar("gateway/term", float(self.term),
                                   step=self._sync_seq, wall=wall)
            self._ha_writer.flush()
        except Exception:  # noqa: BLE001 - telemetry must not kill HA
            pass

    def _sync_once(self) -> bool:
        """One sessionless T_SYNC pull from the primary; returns False
        on any wire/reply failure (the promotion clock's input)."""
        timeout = max(0.5, self._gp.sync_s * 4)
        try:
            sock = socket.create_connection(self._sync_from,
                                            timeout=timeout)
        except OSError:
            return False
        bandwidth.register_socket(sock, "sync")
        try:
            sock.settimeout(timeout)
            _send_frame(sock, T_SYNC,
                        json.dumps({"since": self._sync_seq}).encode())
            rtype, payload = _recv_frame(sock)
            if rtype != T_SYNC:
                return False
            reply = json.loads(payload.decode())
        except (ConnectionError, OSError, ValueError):
            return False
        finally:
            try:
                sock.close()
            except OSError:
                pass
        if reply.get("error"):
            return False
        self._sync_term = max(self._sync_term, int(reply.get("term", 0)))
        for rec in reply.get("records", []):
            self._apply_record(rec)
        self._sync_seq = max(self._sync_seq,
                             int(reply.get("seq", self._sync_seq)))
        return True

    def _promote(self) -> None:
        """Fenced promotion: CAS-bump the on-disk term above everything
        this standby has seen (disk, stream, self), open the new term's
        WAL continuing the global seq numbering, and start serving.  Any
        resurrected predecessor now reads a higher term and fences."""
        disk = self._journal.read_term() if self._journal else 0
        new_term = max(disk, self._sync_term, self.term) + 1
        jr = GatewayJournal(self._ha_log_dir)
        jr.seq = self._journal.seq if self._journal else 0
        try:
            jr.write_term(new_term)
            jr.start_term(new_term)
        except OSError:
            # no shared log dir => cannot prove leadership => stay a
            # (non-serving) standby rather than risk split brain
            self._journal_dead = True
            return
        old, self._journal = self._journal, jr
        if old is not None:
            old.close()
        self.term = new_term
        self.promotions += 1
        self._role = "primary"
        self._serving = True
        self._ha_append("promote", {"term": new_term})
        self._ha_note_state()
        self.promoted.set()
        self._recorder.record("gateway-promoted", term=new_term)
        print(f"[dcn] standby promoted to gateway term {new_term}",
              flush=True)

    def _ha_loop(self) -> None:
        """Warm-standby loop: pull the journal stream on the sync
        cadence; once the pull has failed for one lease window, promote.
        After promotion the loop keeps journaling state and emitting the
        healthy scalar so the ``gateway_failover`` alert resolves."""
        gp = self._gp
        while not self._stop.is_set():
            if self._serving:
                self._ha_note_state()
                self._ha_emit(0.0)
            elif self._sync_once():
                self._last_sync_ok = time.monotonic()
                self._ha_emit(0.0)
            else:
                self._ha_emit(1.0)
                if (time.monotonic() - self._last_sync_ok) > gp.lease_s:
                    self._promote()
            self._stop.wait(gp.sync_s)

    def note_failover_lost(self, rows: int) -> None:
        """Count acked-but-undrained rows that died with the old
        primary's ingest queue.  Only the wiring that discards that
        queue knows the number (the drill, or a fleet restart path) —
        counting it HERE keeps the conservation ledger exact across a
        failover instead of letting the rows silently vanish."""
        self.failover_lost += int(rows)
        self._recorder.record("failover-lost", rows=int(rows))

    @property
    def flow(self):
        """The gateway's GatewayFlow plane (None when disabled) — read
        by drills (tools/chaos_soak.py conservation verdict) and tests."""
        return self._flow

    @property
    def active_slots(self) -> Dict[int, int]:
        """Snapshot of {slot: incarnation} for supervision/chaos asserts."""
        with self._slots_lock:
            return {s: inc for s, (inc, _c) in self._slots.items()}

    def status_snapshot(self) -> dict:
        """The live health plane's one read: slot states + incarnations +
        heartbeat ages, clocks, gateway counters, and whatever the owning
        topology's ``health`` provider adds (replay fill, ingest queue
        depth, restart budget, learner step rate).  Slot fields are taken
        under the registry lock so the snapshot is internally consistent;
        the health extras are best-effort reads of a live system."""
        now = time.monotonic()
        with self._slots_lock:
            slots = {
                str(s): {
                    "incarnation": inc,
                    "heartbeat_age": round(
                        now - self._last_seen.get(s, now), 3),
                }
                for s, (inc, _c) in self._slots.items()
            }
        snap = {
            "wall": time.time(),
            "uptime": round(now - self._born, 3),
            "learner_step": int(self.clock.learner_step.value),
            "actor_step": int(self.clock.actor_step.value),
            "stop": bool(self.clock.stop.is_set()),
            "local_actors": self.local_actors,
            "slots": slots,
            "connections": self.connections,
            "chunks_in": self.chunks_in,
            "fenced": self.fenced,
            "metrics_batches": self.metrics_batches,
            "metrics_rows": self.metrics_rows,
            "frames_rejected": self.frames_rejected,
            "quarantined": dict(self.quarantined),
        }
        if self._flow is not None:
            # flow-control plane (ISSUE 11): overload state + brownout
            # tier, per-slot credits/shed/drop-share and the
            # conservation ledger — fleet_top's ``flow:`` panel line
            snap["flow"] = self._flow.status_block(
                quarantined=sum(snap["quarantined"].values()))
        wire_blk = bandwidth.status_block()
        if wire_blk is not None:
            # bandwidth X-ray (ISSUE 18): per-link byte/frame totals,
            # bytes/transition + bytes/round, and the byte-ledger
            # verdict joined from the flow block's conservation —
            # fleet_top's ``wire:`` panel line
            if self._flow is not None:
                cons = snap.get("flow", {}).get("conservation", {})
                wire_blk["ledger"] = {
                    k: cons[k] for k in (
                        "acked_bytes", "ingested_bytes",
                        "rejected_bytes", "shed_bytes",
                        "accounted_bytes", "bytes_balanced")
                    if k in cons}
            snap["wire"] = wire_blk
        if self._replicas is not None:
            # replica plane (ISSUE 15): membership/generation/lease ages
            # + the fencing ledger — fleet_top's ``replicas:`` panel
            # line and the chaos drills' exact-counter verdicts
            snap["replicas"] = self._replicas.status_block()
        if self._shards is not None and hasattr(self._shards,
                                                "status_block"):
            # shard plane (ISSUE 20): membership/mass-share/lease ages
            # + the degradation ledger — fleet_top's ``shards:`` panel
            # line and the shard drills' exact-counter verdicts.  Only
            # the coordinator's registry has a status_block; shard
            # HOSTS (a LocalShard handler) report through their lease
            # renews instead.  Absent with sharding off: unsharded
            # peers observe zero new fields anywhere.
            snap["shards"] = self._shards.status_block()
        if self._ha:
            # gateway HA plane (ISSUE 16): role/term/sync lag + the
            # failover ledger — fleet_top's ``gateway:`` panel line and
            # the failover drill's exact-counter verdicts.  Absent with
            # HA off: pre-HA peers observe zero new fields anywhere.
            snap["gateway"] = {
                "role": self._role,
                "term": self.term,
                "serving": self._serving,
                "fenced": bool(self._term_fenced or self._journal_dead),
                "term_fenced": self.gateway_term_fenced,
                "standby_refused": self.standby_refused,
                "promotions": self.promotions,
                "failover_lost": self.failover_lost,
                "sync_served": self.sync_served,
                "sync_seq": self._sync_seq,
                "sync_term": self._sync_term,
                "sync_age": round(now - self._last_sync_ok, 3),
                "journal_seq": (self._journal.seq
                                if self._journal else 0),
                "journal_appends": (self._journal.appends
                                    if self._journal else 0),
                "recover_warnings": (self._journal.recover_warnings
                                     if self._journal else 0),
                "carry": {k: int(v)
                          for k, v in self._ha_carry.items()},
            }
        if self._health is not None:
            try:
                snap.update(self._health() or {})
            except Exception as e:  # noqa: BLE001 - health is best-effort
                snap["health_error"] = repr(e)
        return snap

    def _claim_slot(self, ind: Optional[int], incarnation: int,
                    conn: socket.socket) -> Optional[str]:
        """Register a remote actor's global slot; returns an error string
        on a conflict.  A slot held by a LOWER incarnation is not a
        conflict — it is this actor's own half-open predecessor (a
        partition or mid-RPC gateway blip left it behind), so the old
        connection is fenced off and the slot re-keyed; without this a
        reconnecting actor crash-loops against its own ghost until the
        RestartBudget drains (utils/supervision.py docstring).  Equal or
        lower incarnations refuse: duplicate live actors silently skew
        the fleet-wide Ape-X epsilon schedule.

        Known limit of wall-clock incarnation bases: a MISCONFIGURED
        genuine duplicate (two hosts claiming overlapping slot ranges)
        that starts later carries a higher incarnation and evicts the
        live owner; the evicted side reconnects below the thief, is
        refused, and its supervisor respawns it with a fresh higher
        base — mutual eviction that drains both RestartBudgets and
        fails both hosts fast with nonzero exits.  Noisy fail-fast, not
        the silent epsilon skew: distinguishing a live duplicate from a
        dead predecessor's replacement would need gateway-side liveness
        probing, which the idle-deadline reaper only provides after the
        fact."""
        if ind is None:
            return None
        evict: Optional[socket.socket] = None
        with self._slots_lock:
            if ind < self.local_actors:
                return (f"actor slot {ind} is local to the learner host "
                        f"(local_actors={self.local_actors})")
            if self._ha and incarnation <= self._inc_floor.get(ind, -1):
                # journal-seeded fencing (ISSUE 16): a zombie actor
                # process dialing the PROMOTED gateway with an
                # incarnation at or below the floor the old primary
                # journaled is its own fenced predecessor — refusing
                # here is the slot-fencing contract surviving failover
                return (f"actor slot {ind} incarnation {incarnation} "
                        f"fenced by journaled floor "
                        f"{self._inc_floor[ind]}")
            held = self._slots.get(ind)
            if held is not None:
                held_inc, held_conn = held
                if incarnation <= held_inc:
                    return (f"actor slot {ind} already connected "
                            f"(incarnation {incarnation} <= {held_inc})")
                evict = held_conn
                self.fenced += 1
                self._recorder.record("fence", slot=ind,
                                      old=held_inc, new=incarnation)
            self._slots[ind] = (incarnation, conn)
            self._last_seen[ind] = time.monotonic()
            if self._ha and incarnation > self._inc_floor.get(ind, -1):
                self._inc_floor[ind] = incarnation
        if evict is not None:
            # outside the lock: unblock the predecessor's serve thread;
            # its release is identity-checked so it cannot free OUR claim
            try:
                evict.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
        self._recorder.record("slot-claimed", slot=ind,
                              incarnation=incarnation)
        return None

    def _quarantine(self, slot: Optional[int], items: list) -> list:
        """The DCN leg of the ingest quarantine (utils/health.py):
        validate a decoded chunk per-transition and divert offenders to
        ``{log_dir}/quarantine/`` with a per-slot counter, so remote
        experience gets exactly the same admission control as the local
        spawn-queue path — and ``fleet_top`` can name the poisoning
        actor.  Returns the clean remainder (possibly empty)."""
        from pytorch_distributed_tpu.utils import health

        if not items or not health.quarantine_active():
            return items
        src = f"slot{slot}" if slot is not None else "anon"
        validator = self._validators.get(src)
        if validator is None:
            validator = self._validators[src] = health.ChunkValidator()
        items, bad = validator.filter(items)
        if bad:
            with self._slots_lock:
                self.quarantined[src] = (self.quarantined.get(src, 0)
                                         + len(bad))
            self._recorder.record("chunk-quarantined", slot=slot,
                                  n=len(bad), reason=bad[0][2])
            health.get_quarantine(f"gateway-{src}").put(
                bad, trace_id=getattr(items, "trace_id", 0))
        return items

    def _fresh_tick(self, slot: Optional[int], seq: Optional[int]) -> bool:
        """Dedup retransmitted T_TICKs: a tick whose T_CLOCK ack was lost
        mid-blip is resent after reconnect, and applying it twice would
        inflate the fleet-wide actor-step count (the learner's
        max_replay_ratio gate) and the episode stats.  Seq numbers are
        wall-clock-based like incarnations, so a replacement process
        starts above its predecessor's high-water mark.  The map is not
        cleared on slot release — it must outlive fencing and reconnects
        — but a gateway RESTART forgets it: an ack lost across a restart
        is the one residual double-count window (failure model)."""
        if slot is None or seq is None:
            return True
        with self._slots_lock:
            if seq <= self._tick_seq.get(slot, -1):
                return False
            self._tick_seq[slot] = seq
            return True

    def _release_slot(self, slot: Optional[int],
                      conn: socket.socket) -> None:
        with self._slots_lock:
            self._conns.discard(conn)
            if slot is None:
                return
            held = self._slots.get(slot)
            if held is not None and held[1] is conn:
                del self._slots[slot]
                self._recorder.record("slot-released", slot=slot,
                                      incarnation=held[0])

    def _serve(self, conn: socket.socket, addr) -> None:
        slot: Optional[int] = None
        if self._idle_deadline and self._idle_deadline > 0:
            conn.settimeout(self._idle_deadline)
        try:
            with conn:
                while not self._stop.is_set():
                    ftype, payload = _recv_frame(conn)
                    if self._ha:
                        # HA gate first: an unpromoted standby or a
                        # fenced stale-term gateway must refuse session
                        # verbs BEFORE any of their side effects
                        self._session_gate(ftype)
                    if ftype not in (T_STATUS, T_PROFILE, T_METRICS,
                                     T_RLEASE, T_RGRAD, T_RPRIO, T_SYNC,
                                     T_SSAMPLE, T_SMASS, T_SPRIO):
                        # STATUS/PROFILE/METRICS probes and the replica
                        # plane are outside the wire fault plane: a
                        # monitor polling the gateway must neither shift
                        # a deterministic drill's frame schedule nor
                        # absorb a fault meant for session traffic, and
                        # replica drills inject at the replica driver
                        # (REPLICA_FAULTS) where kill/hang/crash are the
                        # real failure modes
                        payload = self._faults.frame(payload)
                    if slot is not None:
                        # plain GIL-atomic write: heartbeat-age reads in
                        # status_snapshot tolerate a one-frame race
                        self._last_seen[slot] = time.monotonic()
                    if ftype == T_BYE:
                        return
                    elif ftype == T_STATUS:
                        # health probe: answered before any HELLO — a
                        # monitoring CLI must never consume an actor slot
                        self.status_served += 1
                        _send_frame(conn, T_STATUS, json.dumps(
                            self.status_snapshot()).encode())
                    elif ftype == T_PROFILE:
                        # on-demand profiling, sessionless like STATUS.
                        # Blocking THIS serve thread for the bounded
                        # window is free concurrency-wise (one thread
                        # per connection); concurrent requests are
                        # refused by the provider's one-window lock.
                        msg = self._json(payload) if payload else {}
                        if self._profiler is None:
                            reply = {"error": "no profiler wired on "
                                              "this gateway"}
                        else:
                            try:
                                reply = self._profiler(msg) or {}
                            except Exception as e:  # noqa: BLE001
                                reply = {"error":
                                         f"profiler failed: {e!r}"}
                        self.profiles_served += 1
                        self._recorder.record(
                            "profile-served",
                            ok=("error" not in reply),
                            seconds=msg.get("seconds"))
                        _send_frame(conn, T_PROFILE,
                                    json.dumps(reply).encode())
                    elif ftype == T_METRICS:
                        # fleet-host scalar push, sessionless like
                        # STATUS.  The reply always carries the
                        # gateway's wall clock — the pusher's NTP-style
                        # offset estimator reads it off the RPC
                        # midpoint, which is what lets remote rows land
                        # on the learner host's time axis.
                        msg = self._json(payload) if payload else {}
                        if self._metrics_sink is None:
                            reply = {"accepted": 0,
                                     "error": "no metrics sink wired "
                                              "on this gateway"}
                        else:
                            try:
                                n = int(self._metrics_sink(msg) or 0)
                                reply = {"accepted": n}
                                self.metrics_rows += n
                            except Exception as e:  # noqa: BLE001
                                reply = {"accepted": 0,
                                         "error":
                                         f"metrics sink failed: {e!r}"}
                        self.metrics_batches += 1
                        reply["wall"] = time.time()
                        if self._flow is not None \
                                and self._flow.governor.tier >= 1:
                            # brownout tier 1: the telemetry rung.  The
                            # reply tells the pusher to shed ITS side
                            # (counted there) so metrics traffic stops
                            # competing with the experience plane.
                            reply["brownout"] = self._flow.governor.tier
                        _send_frame(conn, T_METRICS,
                                    json.dumps(reply).encode())
                    elif ftype == T_RLEASE:
                        # replica lease verbs (ISSUE 15), sessionless-
                        # adjacent like STATUS: no actor-slot claim —
                        # the lease TABLE is the membership
                        msg = self._json(payload) if payload else {}
                        if self._replicas is None:
                            reply = {"status": "error",
                                     "error": "no replica registry "
                                              "wired on this gateway"}
                        else:
                            try:
                                reply = self._replicas.handle_lease(msg)
                            except Exception as e:  # noqa: BLE001
                                reply = {"status": "error",
                                         "error": f"registry failed: "
                                                  f"{e!r}"}
                        _send_frame(conn, T_RLEASE,
                                    json.dumps(reply).encode())
                    elif ftype == T_RGRAD:
                        # the generation-stamped allreduce round:
                        # blocking THIS serve thread until the round
                        # completes (or fences) is free concurrency-wise
                        # — one thread per connection, and the registry
                        # bounds the wait with the round-stall rule
                        if self._replicas is None:
                            _send_frame(conn, T_RGRAD,
                                        _pack_round_reply(RSTAT_NOREG))
                        else:
                            _send_frame(conn, T_RGRAD,
                                        self._replicas.handle_round(
                                            payload))
                    elif ftype == T_RPRIO:
                        # out-of-round |TD| write-back merge with
                        # last-generation-wins fencing (the zombie
                        # replica's writes die HERE, counted)
                        if self._replicas is None:
                            reply = {"status": "error",
                                     "error": "no replica registry "
                                              "wired on this gateway"}
                        else:
                            reply = self._replicas.handle_prio(payload)
                        _send_frame(conn, T_RPRIO,
                                    json.dumps(reply).encode())
                    elif ftype == T_SSAMPLE:
                        # shard-local sample leg of the two-level draw
                        # (ISSUE 20), sessionless-adjacent like the
                        # replica verbs; the codec and the generation
                        # fence live in memory/shard_plane.py — the
                        # handler object owns both sides of the frame
                        if self._shards is None or not hasattr(
                                self._shards, "handle_ssample"):
                            _send_frame(conn, T_SSAMPLE,
                                        _pack_noshard_reply())
                        else:
                            _send_frame(conn, T_SSAMPLE,
                                        self._shards.handle_ssample(
                                            payload))
                    elif ftype == T_SMASS:
                        # shard membership verbs (coordinator) or the
                        # mass poll (shard host) — plain JSON either way
                        msg = self._json(payload) if payload else {}
                        if self._shards is None:
                            reply = {"status": "error",
                                     "error": "no shard plane wired "
                                              "on this gateway"}
                        else:
                            try:
                                reply = self._shards.handle_smass(msg)
                            except Exception as e:  # noqa: BLE001
                                reply = {"status": "error",
                                         "error": f"shard plane "
                                                  f"failed: {e!r}"}
                        _send_frame(conn, T_SMASS,
                                    json.dumps(reply).encode())
                    elif ftype == T_SPRIO:
                        # cross-shard |TD| write-back with
                        # last-generation-wins fencing (a zombie
                        # learner's writes die HERE, counted)
                        if self._shards is None or not hasattr(
                                self._shards, "handle_sprio"):
                            reply = {"status": "error",
                                     "error": "no shard plane wired "
                                              "on this gateway"}
                        else:
                            reply = self._shards.handle_sprio(payload)
                        _send_frame(conn, T_SPRIO,
                                    json.dumps(reply).encode())
                    elif ftype == T_SYNC:
                        # gateway HA control-plane pull (ISSUE 16),
                        # sessionless like STATUS: the warm standby asks
                        # for journal records past its applied offset
                        msg = self._json(payload) if payload else {}
                        if (not self._ha or self._journal is None
                                or not self._serving
                                or self._term_fenced):
                            reply = {"error":
                                     "no HA journal serving on this "
                                     "gateway"}
                        else:
                            since = int(msg.get("since", 0))
                            base, recs = \
                                self._journal.records_since(since)
                            reply = {"term": self.term,
                                     "seq": self._journal.seq,
                                     "base_seq": base,
                                     "records": recs,
                                     "wall": time.time()}
                        self.sync_served += 1
                        _send_frame(conn, T_SYNC,
                                    json.dumps(reply).encode())
                    elif ftype == T_EXP:
                        # byte-ledger granularity is the FRAME: every
                        # acked EXP payload lands in exactly one of
                        # {rejected, shed, ingested} byte buckets
                        # (quarantine is a row-level refinement inside
                        # the ingested frame).  Header-free, matching
                        # the client's acked_bytes count at encode.
                        exp_nbytes = len(payload)
                        try:
                            items = decode_chunk(payload)
                        except ConnectionError:
                            raise
                        except ValueError as e:
                            # WELL-FRAMED but schema-invalid (missing/
                            # truncated/wrong-dtype columns): a malformed
                            # peer.  Dropping the connection would only
                            # make it retransmit the same poison until
                            # its retransmit cap kills it — count, warn,
                            # ack, and drop the FRAME instead; the
                            # session survives.
                            self.frames_rejected += 1
                            if self._flow is not None:
                                # acked below — the frame's bytes must
                                # land in the rejected ledger bucket
                                self._flow.note_rejected_bytes(exp_nbytes)
                            self._recorder.record("frame-rejected",
                                                  slot=slot,
                                                  error=str(e)[:200])
                            if self.frames_rejected <= 3:
                                print(f"[dcn] rejected malformed EXP "
                                      f"frame from slot {slot}: {e}",
                                      flush=True)
                            _send_frame(conn, T_CLOCK,
                                        self._clock_payload(slot))
                            continue
                        except Exception as e:
                            # byte-level corruption np.load itself chokes
                            # on: drop the connection — the client's
                            # retransmit carries a clean copy (the wire
                            # failure model; never decode garbage)
                            raise ConnectionError(
                                f"undecodable EXP frame: {e!r}")
                        if isinstance(items, tracing.TracedChunk) \
                                and not (self._flow is not None
                                         and self._flow.governor.tier
                                         >= 2):
                            # actor flush -> gateway receipt: the wire
                            # hop.  Suppressed at brownout tier >= 2
                            # off the gateway's OWN governor (the
                            # process-local flow.trace_shed latch is
                            # only ever set by a DcnClient, which the
                            # gateway process doesn't host) — covers
                            # chunks from actors that haven't latched
                            # the tier yet.
                            self._tracer.record_hop("gateway", items.born,
                                                    items.trace_id)
                        admitted = (self._flow is None
                                    or self._flow.admit(
                                        slot, len(items),
                                        nbytes=exp_nbytes))
                        if admitted:
                            if self._flow is not None:
                                # ingested-BYTES counts the whole
                                # admitted frame even if quarantine
                                # empties it (the rows land in the
                                # quarantined row bucket; the bytes
                                # stay frame-granular)
                                self._flow.note_ingested_bytes(
                                    exp_nbytes)
                            items = self._quarantine(slot, items)
                        else:
                            # the gateway's ONE declared experience shed
                            # point (brownout tier 3, bucket dry —
                            # counted + recorded in GatewayFlow.admit):
                            # ack so the peer doesn't retransmit the
                            # very load being shed
                            items = []
                        if items:
                            bandwidth.note_transitions(len(items))
                            if self._flow is not None:
                                # ingested = admitted AND clean of the
                                # quarantine: each row lands in exactly
                                # one conservation bucket
                                self._flow.note_ingested(len(items))
                            try:
                                self.put_chunk(items)
                            except ValueError:
                                # memory queue already closed: the run is
                                # over; answer with the stop-carrying
                                # clock instead of dying with a traceback
                                pass
                        self.chunks_in += 1
                        _send_frame(conn, T_CLOCK, self._clock_payload(slot))
                        if self._ha:
                            self._ha_note_state()
                    elif ftype == T_GETP:
                        try:
                            (min_version,) = struct.unpack("!Q", payload)
                        except struct.error as e:
                            raise ConnectionError(
                                f"undecodable GETP frame: {e}")
                        got = self.param_store.fetch(min_version)
                        if got is None:
                            _send_frame(conn, T_PARAMS,
                                        struct.pack("!Q", 0))
                        else:
                            flat, version = got
                            _send_frame(
                                conn, T_PARAMS,
                                struct.pack("!Q", version)
                                + np.ascontiguousarray(
                                    flat, dtype=np.float32).tobytes())
                    elif ftype == T_PING:
                        # the ack carries the slot's fresh credit grant:
                        # heartbeats are how a credit-blocked client
                        # learns it may drain its ring again (throttled
                        # never reads as dead OR stays blocked forever)
                        _send_frame(conn, T_CLOCK, self._clock_payload(slot))
                    elif ftype == T_TICK:
                        msg = self._json(payload)
                        try:
                            steps = int(msg.get("actor_steps", 0))
                            seq = msg.get("seq")
                            seq = int(seq) if seq is not None else None
                            kv = {k: float(v) for k, v
                                  in (msg.get("stats") or {}).items()}
                        except (TypeError, ValueError) as e:
                            raise ConnectionError(
                                f"undecodable TICK frame: {e}")
                        if self._fresh_tick(slot, seq):
                            if steps:
                                self.clock.add_actor_steps(steps)
                            if kv:
                                self.actor_stats.add(**kv)
                        if self._flow is not None:
                            # cumulative client flow counters (minted/
                            # dropped/buffered) — idempotent outside the
                            # dedup gate, so a retransmitted tick can
                            # never double-count drops
                            self._flow.on_client_report(
                                slot, msg.get("flow"))
                        _send_frame(conn, T_CLOCK, self._clock_payload(slot))
                        if self._ha:
                            self._ha_note_state()
                    elif ftype == T_HELLO:
                        msg = self._json(payload)
                        try:
                            ind = msg.get("process_ind")
                            ind = int(ind) if ind is not None else None
                            inc = int(msg.get("incarnation", 0))
                        except (TypeError, ValueError) as e:
                            raise ConnectionError(
                                f"undecodable HELLO frame: {e}")
                        err = self._claim_slot(ind, inc, conn)
                        if err is not None:
                            reply = json.loads(self._clock_payload())
                            reply["error"] = err
                            _send_frame(conn, T_CLOCK,
                                        json.dumps(reply).encode())
                            return
                        slot = ind
                        # the accept loop registered this conn slotless
                        bandwidth.register_socket(conn, "gateway", slot)
                        if self._ha and ind is not None:
                            # journal the claim (absolute incarnation:
                            # idempotent) so the standby fences stale
                            # actor incarnations across a failover
                            self._ha_append("slot",
                                            {"slot": ind, "inc": inc})
                        _send_frame(conn, T_CLOCK, self._clock_payload(slot))
                    else:
                        raise ConnectionError(f"bad frame type {ftype}")
        except (ConnectionError, OSError):
            return  # peer went away (or idled out); churn is expected
        finally:
            self._release_slot(slot, conn)

    @staticmethod
    def _json(payload: bytes) -> dict:
        try:
            return json.loads(payload.decode())
        except (ValueError, UnicodeDecodeError) as e:
            raise ConnectionError(f"undecodable control frame: {e}")

    def close(self) -> None:
        self._stop.set()
        try:
            self._srv.close()
        except OSError:
            pass
        # the kernel only releases the listening port once the accept
        # thread leaves its accept() syscall — join it, or an immediate
        # rebind on the same port (restart_gateway) races into EADDRINUSE
        self._accept_thread.join(2.0)
        if self._ha_thread is not None:
            self._ha_thread.join(max(2.0, self._gp.sync_s * 4))
        if self._journal is not None:
            self._journal.close()
        with self._slots_lock:
            conns = list(self._conns)
        for c in conns:
            # unblock serve threads parked in recv so join() is prompt
            try:
                c.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
        for t in self._threads:
            t.join(1.0)


def feed_queue_of(memory_handles) -> Callable[[list], None]:
    """The gateway->memory bridge: single-owner learner-side memories
    (QueueOwner, DeviceReplayIngest) drain a spawn queue of
    ``[(Transition, priority)]`` chunks; remote chunks enter that same
    queue.  Multi-writer shared rings (SharedReplay/NativeRingReplay) take
    direct feeds — their ``feed`` is already cross-process safe."""
    learner_side = memory_handles.learner_side
    if getattr(learner_side, "_q", None) is not None:
        # late-bound: Topology._use_thread_queue may swap the queue object
        # between construction and run
        def _enqueue(items: list) -> None:
            learner_side._q.put(items)
        return _enqueue

    def _direct(items: list) -> None:
        if isinstance(items, tracing.TracedChunk):
            # multi-writer rings feed inline on the serve thread — the
            # "feed" hop collapses into the gateway receipt, record it so
            # the trace still closes for shared-ring memory types
            tracing.get_tracer("feeder").record_hop(
                "feed", items.born, items.trace_id)
        for t, p in items:
            learner_side.feed(t, p)
    return _direct


# ---------------------------------------------------------------------------
# health-plane client
# ---------------------------------------------------------------------------

def _sessionless_rpc(address: Tuple[str, int], ftype: int, payload: bytes,
                     timeout: float, what: str,
                     retry_after_send: bool = True) -> dict:
    """Shared core of the sessionless helpers (ISSUE 16 satellite):
    one bounded round-trip on a fresh connection, with a SINGLE retry
    so a monitor probing a half-dead gateway mid-failover — one that
    accepts the connection and never replies — gets a clean
    ConnectionError after ~2 timeouts instead of wedging forever.  The
    per-call ``settimeout`` bounds every recv; the retry opens a fresh
    connection (the promoted standby may be answering by then).
    ``retry_after_send`` False restricts the retry to connect-phase
    failures for verbs whose server-side work must not run twice
    (T_PROFILE holds the one-window profiler lock)."""
    last: Optional[BaseException] = None
    for attempt in (0, 1):
        try:
            sock = socket.create_connection(address, timeout=timeout)
            bandwidth.register_socket(sock, "probe")
        except OSError as e:
            last = e
            if attempt == 0:
                time.sleep(min(0.2, timeout / 10.0))
            continue
        sent = False
        try:
            sock.settimeout(timeout)
            _send_frame(sock, ftype, payload)
            sent = True
            rtype, reply = _recv_frame(sock)
            if rtype != ftype:
                raise ConnectionError(
                    f"expected {what} reply, got frame type {rtype}")
            try:
                return json.loads(reply.decode())
            except (ValueError, UnicodeDecodeError) as e:
                raise ConnectionError(f"undecodable {what} reply: {e}")
        except (ConnectionError, OSError) as e:
            last = e
            if attempt == 1 or (sent and not retry_after_send):
                raise
            time.sleep(min(0.2, timeout / 10.0))
        finally:
            try:
                sock.close()
            except OSError:
                pass
    raise ConnectionError(f"{what} request to {address} failed: {last!r}")


def fetch_status(address: Tuple[str, int], timeout: float = 5.0) -> dict:
    """One STATUS round-trip against a gateway — the read side of the
    live health plane (tools/fleet_top.py).  Deliberately sessionless:
    no HELLO, no slot claim, a fresh connection per probe so a monitor
    keeps working across gateway restarts exactly when it matters most.
    Every socket operation is bounded by ``timeout`` and the probe is
    retried ONCE on a fresh connection (a gateway mid-failover may
    accept and die before replying).  Raises ConnectionError/OSError
    when the gateway stays unreachable."""
    return _sessionless_rpc(address, T_STATUS, b"", timeout, "T_STATUS")


def fetch_profile(address: Tuple[str, int], seconds: float = 3.0,
                  label: Optional[str] = None, role: str = "learner",
                  timeout: Optional[float] = None) -> dict:
    """One T_PROFILE round-trip: trigger a bounded XLA profiler window
    on the learner host and return the reply ({"trace_dir", "seconds"}
    on success, {"error": ...} otherwise).  Sessionless like
    ``fetch_status`` — no HELLO, no slot claim — and sits OUTSIDE the
    fault-injection plane, so profiling a drilled fleet never shifts
    the drill schedule.  The reply wait covers the window plus generous
    slack: the process's FIRST-ever profiler session pays a one-time
    init that can exceed a minute on a saturated small host
    (utils/perf.prewarm_profiler amortizes it at fleet startup when
    the perf plane is enabled, but a bare fleet stays cold until the
    first request).  The server clamps ``seconds``
    (PerfParams.profile_window_max), so a typo'd duration errs on the
    reply arriving early, not never."""
    if timeout is None:
        timeout = float(seconds) + 180.0
    msg: Dict[str, Any] = {"seconds": float(seconds), "role": role}
    if label is not None:
        msg["label"] = str(label)
    # retry only covers the connect phase: once the request is on the
    # wire the server may already hold the one-window profiler lock, and
    # a blind retry would answer "profiler busy" instead of the result
    return _sessionless_rpc(address, T_PROFILE, json.dumps(msg).encode(),
                            timeout, "T_PROFILE", retry_after_send=False)


def push_metrics(address: Tuple[str, int], rows: list,
                 offset: Optional[float] = None,
                 host: Optional[str] = None,
                 timeout: float = 10.0) -> dict:
    """One T_METRICS round-trip: push a batch of scalar rows (the
    MetricsWriter JSONL schema — plain dicts) into the learner-host
    aggregator.  Sessionless like ``fetch_status`` — no HELLO, no slot
    claim — and OUTSIDE the fault-injection plane, so the telemetry
    path never shifts a drill schedule.  ``offset`` is the pusher's
    estimated clock offset to the gateway (seconds to ADD to this
    host's walls); the reply carries ``accepted`` and the gateway's
    ``wall`` for the next offset estimate
    (utils/telemetry.MetricsPusher owns the estimator and cadence)."""
    msg: Dict[str, Any] = {"rows": list(rows)}
    if offset is not None:
        msg["offset"] = float(offset)
    if host is not None:
        msg["host"] = str(host)
    # full single-retry: re-pushing the same rows is at worst a
    # duplicate scalar sample on the same wall clock, and the pusher's
    # own catch-up window already tolerates that; wedging the stats
    # thread on a half-dead gateway is the failure that matters
    return _sessionless_rpc(address, T_METRICS, json.dumps(msg).encode(),
                            timeout, "T_METRICS")


# ---------------------------------------------------------------------------
# actor-host client + adapters
# ---------------------------------------------------------------------------

def redial_backoff(rng, prev: float, cap: float = 1.0,
                   base: float = 0.05) -> float:
    """Decorrelated-jitter backoff (the AWS 'decorrelated jitter'
    scheme): next delay is uniform in ``[base, prev * 3]``, capped.
    Drawn from the CLIENT'S OWN seeded RNG stream — the fix for the
    reconnect thundering herd: the old deterministic doubling gave
    every client the identical redial schedule, so N replicas killed
    by one fault redialled the gateway in lockstep.  Seeding by slot
    keeps seeded ``DCN_FAULTS`` drills reproducible (the schedule is a
    pure function of the slot, not of wall clock) while two clients
    with different slots spread their redial times
    (tests/test_replicas.py asserts both properties)."""
    hi = max(prev * 3.0, base * 1.001)
    return float(min(cap, rng.uniform(base, hi)))


class DcnDisconnected(ConnectionError):
    """Terminal session loss: the reconnect budget is spent (or the
    client is closing).  Subclasses ConnectionError so transport-level
    best-effort paths (final flushes) swallow it, while the actor's main
    loop surfaces it as a nonzero exit for the RestartBudget."""


class DcnRefused(RuntimeError):
    """The gateway answered the HELLO with an error (slot conflict,
    local-slot claim).  A distinct type so supervisors can classify it
    as a session condition without catching unrelated RuntimeErrors —
    notably faults.InjectedCrash, which must never be mistaken for a
    network problem."""


class DcnClient:
    """One connection to the gateway, shared by the adapters of one actor
    process.  All requests are synchronous request/reply under a lock;
    every reply refreshes the cached learner clock.

    A send/recv failure mid-RPC enters the reconnect path: redial with
    exponential backoff (bounded by ``reconnect_timeout``), re-HELLO with
    a bumped incarnation — fencing off this client's own half-open
    predecessor on the gateway — then retransmit the one unacknowledged
    frame.  The caller never observes the blip; a terminal failure raises
    ``DcnDisconnected`` and latches ``disconnected``.

    ``stop`` and ``disconnected`` are disjoint: ``stop`` means the
    learner's clock declared the run over (exit 0); ``disconnected``
    means the session died (exit nonzero, supervision restarts us).

    A background heartbeat thread pings after ``heartbeat_interval`` idle
    seconds so a partitioned gateway is detected (and reconnected to)
    even while the actor is busy between RPCs, and so the gateway's idle
    deadline never reaps a healthy-but-quiet actor.
    """

    def __init__(self, address: Tuple[str, int], process_ind: int = 0,
                 connect_timeout: float = 60.0, retries: int = 20,
                 incarnation: Optional[int] = None,
                 heartbeat_interval: Optional[float] = None,
                 reply_deadline: Optional[float] = None,
                 reconnect_timeout: Optional[float] = None,
                 faults: Optional[FaultInjector] = None):
        # ordered endpoint list (ISSUE 16): a single ``(host, port)`` is
        # the pre-HA contract, byte-identical behaviour; a list (or a
        # "h:p,h:p" string) dials in order, and the redial path cycles
        # to the NEXT endpoint on failure — failover to the promoted
        # standby rides the exact PR-1 re-HELLO/incarnation/
        # unacked-resend machinery, and the PR-11 cumulative flow
        # counters make the resend idempotent across gateways.
        self.endpoints = parse_endpoints(address) or [address]
        self._ep = 0
        self.failovers = 0
        self.address = self.endpoints[0]
        self.process_ind = process_ind
        self._lock = threading.RLock()
        self.learner_step = 0
        self.stop = threading.Event()          # learner said stop (T_CLOCK)
        self.disconnected = threading.Event()  # session terminally lost
        # wall-clock-derived base so a REPLACEMENT process (fresh object,
        # no memory of its predecessor's count) still fences a half-open
        # slot left by the old incarnation; reconnects bump it by 1
        self.incarnation = (int(incarnation) if incarnation is not None
                            else time.time_ns() // 1_000_000)
        # tick dedup sequence, same wall-clock base trick: the gateway
        # drops a retransmitted tick (seq <= its per-slot high-water)
        # instead of double-counting actor steps/stats, and a replacement
        # process's fresh counter still lands above its predecessor's
        self._tick_seq = time.time_ns() // 1_000_000
        self.reconnects = 0
        # ---- flow control (ISSUE 11, utils/flow.py): ``credits`` is
        # the gateway's latest per-ack grant — None means the gateway
        # sent no credit field (healthy state, or a pre-flow gateway):
        # unlimited, the exact pre-ISSUE-11 behaviour.  At grant 0 the
        # client parks chunks in a bounded drop-oldest ring instead of
        # blocking the actor (newest experience wins; drops counted +
        # provenance-stamped) and keeps heartbeating, so throttled
        # never reads as dead and never deadlocks.
        self._flow_params = flow.resolve_flow()
        self.credits: Optional[int] = None
        self.flow_ring = flow.DropOldestRing(
            self._flow_params.client_ring, owner=process_ind)
        self.flow_minted_rows = 0   # rows offered to send_chunk
        self.flow_acked_rows = 0    # rows the wire acknowledged
        self.flow_acked_bytes = 0   # EXP payload bytes acknowledged
        self._flow_blocked_logged = False
        # estimated wall-clock offset to the gateway host (seconds to ADD
        # to local time.time() to land on the gateway's clock), derived
        # NTP-style from T_CLOCK replies' ``wall`` against the RPC
        # midpoint and EWMA-smoothed; recorded as ``clock_sync`` flight-
        # recorder events so tools/timeline.py can align this host's
        # blackbox/metrics rows onto the learner-host clock
        self.clock_offset: Optional[float] = None
        self._offset_logged: Optional[float] = None
        self._closed = False
        self._faults = (faults if faults is not None
                        else FaultInjector.from_env("client"))
        self._heartbeat_interval = (
            _env_float("DCN_HEARTBEAT_INTERVAL", 10.0)
            if heartbeat_interval is None else heartbeat_interval)
        self._reply_deadline = (
            _env_float("DCN_REPLY_DEADLINE", 180.0)
            if reply_deadline is None else reply_deadline)
        self._reconnect_timeout = (
            _env_float("DCN_RECONNECT_TIMEOUT", 30.0)
            if reconnect_timeout is None else reconnect_timeout)
        self._recorder = flight_recorder.get_recorder(
            f"dcn-client-{process_ind}")
        # slot-seeded redial jitter stream (see redial_backoff): each
        # slot's backoff schedule is deterministic in isolation but
        # decorrelated from its neighbours', so a mass disconnect never
        # redials the gateway in lockstep
        self._redial_rng = np.random.default_rng((0xDC2, process_ind))
        self._last_rpc = time.monotonic()
        deadline = time.monotonic() + connect_timeout
        delay = 0.1
        while True:
            try:
                self.address = self.endpoints[self._ep]
                self._sock = socket.create_connection(self.address,
                                                      timeout=30.0)
                bandwidth.register_socket(self._sock, "client",
                                          process_ind)
                break
            except OSError:
                if time.monotonic() > deadline or retries <= 0:
                    raise
                retries -= 1
                # cycle the endpoint list: the next dial may be the
                # standby already serving (no-op with one endpoint)
                self._ep = (self._ep + 1) % len(self.endpoints)
                time.sleep(delay)
                delay = min(delay * 2, 2.0)
        self._configure(self._sock)
        self._request(T_HELLO, self._hello_payload())
        self._hb_stop = threading.Event()
        self._hb_thread: Optional[threading.Thread] = None
        if self._heartbeat_interval and self._heartbeat_interval > 0:
            self._hb_thread = threading.Thread(
                target=self._heartbeat_loop,
                name=f"dcn-heartbeat-{process_ind}", daemon=True)
            self._hb_thread.start()

    # -- session plumbing ---------------------------------------------------

    def _configure(self, sock: socket.socket) -> None:
        # bounded reply wait: legitimate backpressure stalls under this
        # deadline; a frozen/partitioned peer trips it into the reconnect
        # path instead of stalling the actor forever (<=0 restores the
        # old unbounded-blocking behaviour)
        sock.settimeout(self._reply_deadline
                        if self._reply_deadline and self._reply_deadline > 0
                        else None)
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)

    def _hello_payload(self) -> bytes:
        return json.dumps({"role": "actor",
                           "process_ind": self.process_ind,
                           "incarnation": self.incarnation}).encode()

    def _handle_reply(self, rtype: int, rpayload: bytes,
                      rpc_mid: Optional[float] = None) -> None:
        if rtype != T_CLOCK:
            return
        msg = json.loads(rpayload.decode())
        if rpc_mid is not None and "wall" in msg:
            sample = float(msg["wall"]) - rpc_mid
            self.clock_offset = (sample if self.clock_offset is None
                                 else 0.9 * self.clock_offset
                                 + 0.1 * sample)
            if (self._offset_logged is None
                    or abs(self.clock_offset
                           - self._offset_logged) > 0.05):
                # logged on first estimate and on >50 ms drift — the
                # timeline reads the LAST clock_sync of the role's ring
                self._offset_logged = self.clock_offset
                self._recorder.record(
                    "clock_sync", offset=round(self.clock_offset, 6),
                    slot=self.process_ind)
        self.learner_step = int(msg["learner_step"])
        if self._flow_params.enabled:
            # absent credit field = healthy/legacy gateway = unlimited
            c = msg.get("credits")
            self.credits = int(c) if c is not None else None
            tier = int(msg.get("brownout", 0) or 0)
            if tier != flow.brownout_tier():
                # latch the ladder tier for this process's shed hooks
                # (RemoteStats / QueueFeeder trace minting)
                flow.set_brownout(tier)
                self._recorder.record("brownout", tier=tier,
                                      slot=self.process_ind)
        if msg.get("stop"):
            self.stop.set()
        if "error" in msg:  # e.g. actor-slot conflict at HELLO
            self.disconnected.set()
            raise DcnRefused(f"gateway refused: {msg['error']}")

    def _terminal(self, why: str) -> DcnDisconnected:
        # a close()-initiated abort is not a session LOSS: latching
        # ``disconnected`` here would let a heartbeat racing a clean
        # shutdown flip a run-complete exit into EXIT_DISCONNECTED
        # (fleet._remote_actor_main reads the flag after close())
        if not self._closed:
            self.disconnected.set()
            # the actor is about to exit EXIT_DISCONNECTED: leave the
            # post-mortem NOW, while the session history is still in
            # memory (utils/flight_recorder.py failure paths)
            self._recorder.record("dcn-terminal", slot=self.process_ind,
                                  why=why, reconnects=self.reconnects)
            flight_recorder.dump_all(
                f"DcnDisconnected slot {self.process_ind}: {why}")
        return DcnDisconnected(
            f"DCN session to {self.address} lost (slot "
            f"{self.process_ind}): {why}")

    def _reconnect(self) -> Tuple[int, bytes]:
        """Redial + re-HELLO under the request lock; returns the HELLO
        reply on success, raises DcnDisconnected when the budget is spent
        (or the client is stopping — with the run over or the process
        closing there is nothing left to deliver)."""
        try:
            self._sock.close()
        except OSError:
            pass
        deadline = time.monotonic() + self._reconnect_timeout
        delay = 0.05
        while True:
            if self._closed:
                raise self._terminal("client closed")
            if self.stop.is_set():
                raise self._terminal("stop already set")
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise self._terminal(
                    f"reconnect budget ({self._reconnect_timeout:.1f}s) "
                    f"exhausted")
            addr = self.endpoints[self._ep]
            try:
                sock = socket.create_connection(
                    addr, timeout=max(0.1, min(5.0, remaining)))
            except OSError:
                # failover (ISSUE 16): cycle to the next endpoint — a
                # dead primary's slot in the list is skipped within one
                # backoff step (no-op with a single endpoint)
                self._ep = (self._ep + 1) % len(self.endpoints)
                time.sleep(min(delay, max(0.0, remaining)))
                delay = redial_backoff(self._redial_rng, delay)
                continue
            # the HELLO exchange is budgeted by the reconnect deadline,
            # not the (much longer) reply deadline: a frozen gateway whose
            # kernel backlog still accepts connects must not stretch a
            # 30 s reconnect budget into a 180 s reply wait
            sock.settimeout(max(0.1, min(self._reply_deadline, remaining)
                                if self._reply_deadline
                                and self._reply_deadline > 0
                                else remaining))
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            bandwidth.register_socket(sock, "client", self.process_ind)
            self.incarnation += 1
            try:
                _send_frame(sock, T_HELLO, self._hello_payload())
                rtype, rpayload = _recv_frame(sock)
            except (ConnectionError, OSError):
                try:
                    sock.close()
                except OSError:
                    pass
                # an accepted-then-dropped HELLO is what an unpromoted
                # standby answers with — keep cycling until it promotes
                # (or the budget spends)
                self._ep = (self._ep + 1) % len(self.endpoints)
                time.sleep(min(delay, max(0.0, remaining)))
                delay = redial_backoff(self._redial_rng, delay)
                continue
            self._configure(sock)  # restore the steady-state reply deadline
            self._sock = sock
            self.reconnects += 1
            if addr != self.address:
                # the session moved gateways: the counted failover event
                self.failovers += 1
                self._recorder.record("failover", slot=self.process_ind,
                                      frm=list(self.address),
                                      to=list(addr))
                self.address = addr
            self._recorder.record("reconnect", slot=self.process_ind,
                                  incarnation=self.incarnation,
                                  count=self.reconnects)
            try:
                self._handle_reply(rtype, rpayload)
            except DcnRefused as e:
                # HELLO refused: the slot is held at >= our incarnation —
                # a live duplicate actor owns it; retrying cannot win
                raise self._terminal(str(e)) from e
            return rtype, rpayload

    # reconnect-then-REFUSAL cycles one RPC may consume before the frame
    # is declared poison: each counted cycle means the session redialled
    # FINE and the gateway then actively dropped this exact frame again
    # (a chunk it can never decode, a serve-side crash on apply) —
    # without a cap the actor livelocks forever instead of exiting for
    # the supervisor.  Reply TIMEOUTS never count: a deadline trip is
    # legitimate ingest backpressure (or a frozen peer, which the
    # reconnect budget handles) and must stall the actor, not kill it.
    _MAX_RETRANSMITS = 5

    def _request(self, ftype: int, payload: bytes) -> Tuple[int, bytes]:
        with self._lock:
            if self.disconnected.is_set() or self._closed:
                raise self._terminal("session already closed")
            retransmits = 0
            rpc_mid = None
            while True:
                try:
                    wire = self._faults.frame(payload)
                    t_send = time.time()
                    _send_frame(self._sock, ftype, wire)
                    rtype, rpayload = _recv_frame(self._sock)
                    rpc_mid = (t_send + time.time()) / 2.0
                    break
                except (ConnectionError, OSError) as e:
                    timed_out = isinstance(e, socket.timeout)
                    if (not timed_out
                            and retransmits >= self._MAX_RETRANSMITS):
                        raise self._terminal(
                            f"frame type {ftype} refused "
                            f"{retransmits}x across fresh sessions "
                            f"(poison frame?)")
                    reply = self._reconnect()
                    if ftype == T_HELLO:
                        # the reconnect's own HELLO already (re)established
                        # the session — retransmitting would bounce off the
                        # fresh claim as a same-incarnation duplicate
                        rtype, rpayload = reply
                        break
                    if not timed_out:
                        retransmits += 1
                    # loop retransmits the one unacked frame
            self._last_rpc = time.monotonic()
            self._handle_reply(rtype, rpayload, rpc_mid=rpc_mid)
            return rtype, rpayload

    # -- heartbeats ---------------------------------------------------------

    def _heartbeat_loop(self) -> None:
        interval = self._heartbeat_interval
        while not self._hb_stop.wait(min(interval / 4.0, 1.0)):
            if self.disconnected.is_set():
                return
            if time.monotonic() - self._last_rpc < interval:
                continue
            try:
                self.ping()
            except (ConnectionError, OSError):
                return  # terminal states are latched by the request path
            # anything else (notably faults.InjectedCrash) propagates:
            # a crash drill must die loudly, never as a quiet hb death

    def ping(self) -> int:
        """Heartbeat RPC; refreshes the cached learner clock."""
        self._request(T_PING, b"")
        return self.learner_step

    # -- RPC surface --------------------------------------------------------

    def _flow_blocked(self) -> bool:
        return (self._flow_params.enabled and self.credits is not None
                and self.credits <= 0)

    def _send_exp(self, items: list) -> None:
        """One credit-consuming EXP round-trip (the reply re-grants).

        Byte ledger (ISSUE 18): the payload is encoded ONCE and its
        bytes counted ONCE after the ack — ``_request``'s retransmits
        resend the same frame, so ``flow_acked_bytes`` is
        retransmit-idempotent by construction (exactly like the row
        count below)."""
        if self.credits is not None:
            self.credits -= 1
        payload = encode_chunk(items)
        self._request(T_EXP, payload)
        self.flow_acked_rows += len(items)
        self.flow_acked_bytes += len(payload)

    def send_chunk(self, items: list) -> None:
        """Ship one chunk, credit-aware (ISSUE 11).  With send credit
        (or a gateway that grants none — healthy/legacy) this is the
        usual synchronous RPC, draining any ring backlog first so
        experience stays ordered.  At grant 0 the chunk parks in the
        bounded drop-oldest ring and the call RETURNS — the actor keeps
        ticking (its heartbeats keep the session claimed and fetch the
        next grant), the ring's oldest rows are the counted,
        provenance-stamped cost of sustained overload."""
        self.flow_minted_rows += len(items)
        with self._lock:
            if self._flow_blocked():
                if self.flow_ring.put(items) and not self._flow_blocked_logged:
                    self._flow_blocked_logged = True
                    print(f"[dcn] slot {self.process_ind}: credit-blocked "
                          f"ring full — shedding oldest experience "
                          f"(counted; newest wins)", flush=True)
                return
            # drain the backlog first (oldest buffered chunk precedes
            # this one on the wire); every reply refreshes the grant,
            # so a re-throttle mid-drain parks the rest again
            while len(self.flow_ring):
                buffered = self.flow_ring.pop()
                if buffered is None:
                    break
                self._send_exp(buffered)
                if self._flow_blocked():
                    self.flow_ring.put(items)
                    return
            self._send_exp(items)

    def flow_report(self) -> Dict[str, int]:
        """Cumulative flow counters for the T_TICK report (idempotent
        by construction — the gateway's conservation ledger reads
        them)."""
        return {"minted": self.flow_minted_rows,
                "acked": self.flow_acked_rows,
                "acked_bytes": self.flow_acked_bytes,
                "dropped": self.flow_ring.dropped_rows,
                "buffered": self.flow_ring.buffered_rows}

    def get_params(self, min_version: int
                   ) -> Optional[Tuple[np.ndarray, int]]:
        _, payload = self._request(T_GETP, struct.pack("!Q", min_version))
        (version,) = struct.unpack("!Q", payload[:8])
        if version == 0:
            return None
        return np.frombuffer(payload[8:], dtype=np.float32).copy(), version

    def tick(self, actor_steps: int = 0,
             stats: Optional[Dict[str, float]] = None) -> int:
        msg: Dict[str, Any] = {"actor_steps": actor_steps}
        if stats:
            msg["stats"] = stats
        if self._flow_params.enabled and self.flow_minted_rows:
            # cumulative (not delta) flow counters: a retransmitted
            # tick re-ships the same totals, so the gateway-side
            # conservation ledger is dedup-proof by construction
            msg["flow"] = self.flow_report()
        with self._lock:
            # seq assigned under the request lock so ticks hit the wire
            # in seq order; a retransmit reuses the SAME payload bytes,
            # which is exactly what lets the gateway spot the duplicate
            self._tick_seq += 1
            msg["seq"] = self._tick_seq
            self._request(T_TICK, json.dumps(msg).encode())
        return self.learner_step

    def close(self) -> None:
        try:
            # best-effort final drain of the credit-blocked backlog:
            # whatever the grant allows ships, the rest stays counted
            # in the ring (``buffered`` in the last flow report)
            if len(self.flow_ring) and not self.disconnected.is_set():
                with self._lock:
                    while not self._flow_blocked():
                        buffered = self.flow_ring.pop()
                        if buffered is None:
                            break
                        self._send_exp(buffered)
        except (ConnectionError, OSError):
            pass
        self._closed = True
        if self._hb_thread is not None:
            self._hb_stop.set()
            self._hb_thread.join(2.0)
        try:
            with self._lock:
                _send_frame(self._sock, T_BYE, b"")
                self._sock.close()
        except (ConnectionError, OSError):
            pass


class _ChunkSink:
    """Duck-types the queue end QueueFeeder writes to: ``put(items)``
    becomes one EXP frame."""

    def __init__(self, client: DcnClient):
        self._client = client

    def put(self, items: list) -> None:
        self._client.send_chunk(items)


class RemoteMemory(QueueFeeder):
    """Actor-side feed endpoint over DCN: QueueFeeder's chunk buffering,
    with the spawn queue replaced by the wire."""

    def __init__(self, client: DcnClient, chunk: int = 64):
        super().__init__(_ChunkSink(client), chunk=chunk)


class RemoteParamStore:
    """Read surface of agents/param_store.py ParamStore over DCN."""

    def __init__(self, client: DcnClient):
        self._client = client

    def fetch(self, min_version: int = 0
              ) -> Optional[Tuple[np.ndarray, int]]:
        return self._client.get_params(min_version)

    # ParamStore.wait is written purely against self.fetch, so the poll
    # loop (startup blocking, stop-event handling, timeout) is shared
    # verbatim rather than re-implemented.
    wait = ParamStore.wait


class _StepShim:
    """Duck-types ``mp.Value`` for the clock's learner_step reads."""

    def __init__(self, client: DcnClient):
        self._client = client

    @property
    def value(self) -> int:
        return self._client.learner_step


class RemoteClock:
    """GlobalClock surface for remote actors.  ``add_actor_steps``
    accumulates locally and flushes to the gateway on a count/time cadence —
    a per-env-step RPC would put one RTT in the rollout hot loop; the
    learner-step view is refreshed by every flush (and by every experience
    chunk ack), so ``done()`` staleness is bounded by the cadence, matching
    the reference's tolerance for stale clock reads (reference
    dqn_actor.py:62 reads an unlocked mp.Value)."""

    def __init__(self, client: DcnClient, flush_every: int = 256,
                 max_age: float = 2.0):
        self._client = client
        self._flush_every = flush_every
        self._max_age = max_age
        self._pending = 0
        self._last_flush = time.monotonic()
        self.learner_step = _StepShim(client)
        # hang-watchdog progress board (utils/supervision.ProgressBoard),
        # attached by fleet._remote_actor_main so the actor-host
        # supervisor can see this worker's liveness — same duck surface
        # as GlobalClock.bump_progress
        self.progress = None

    def bump_progress(self, label: str, n: int = 1) -> None:
        if self.progress is not None:
            self.progress.bump(label, n)

    @property
    def stop(self) -> threading.Event:
        return self._client.stop

    def add_actor_steps(self, n: int = 1) -> int:
        self._pending += n
        now = time.monotonic()
        if (self._pending >= self._flush_every
                or now - self._last_flush > self._max_age):
            self.flush()
        return self._client.learner_step

    def flush(self) -> None:
        pending, self._pending = self._pending, 0
        self._last_flush = time.monotonic()
        try:
            self._client.tick(actor_steps=pending)
        except (ConnectionError, OSError):
            # terminal disconnect (transient ones retry inside the
            # client): keep the steps — they are the fleet-wide Ape-X
            # step count, and done() ends this loop via ``disconnected``,
            # so a dropped tick would silently undercount the run
            self._pending += pending

    def done(self, steps: int) -> bool:
        if (self._client.stop.is_set()
                or self._client.disconnected.is_set()):
            return True
        if time.monotonic() - self._last_flush > self._max_age:
            self.flush()
        return self._client.learner_step >= steps


class RemoteStats:
    """ActorStats.add surface: forwards accumulator increments inline —
    actors already batch their stats on the ``actor_freq`` cadence
    (agents/actor.py flush_stats), so one RPC per flush is the right
    granularity.  At brownout tier >= 1 (the telemetry rung of the
    ISSUE-11 ladder, latched off gateway replies) stat pushes are shed
    — counted via ``flow.note_shed`` — so reporting traffic yields to
    the experience plane first."""

    def __init__(self, client: DcnClient):
        self._client = client

    def add(self, **kv: float) -> None:
        if flow.telemetry_shed():
            flow.note_shed("stats", 1)
            return
        try:
            self._client.tick(stats={k: float(v) for k, v in kv.items()})
        except (ConnectionError, OSError):
            pass
