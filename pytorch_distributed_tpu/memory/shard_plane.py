"""Sharded prioritized replay: one fault-fenced priority plane across
gateway hosts (ISSUE 20).

Ape-X's single global prioritized replay stops scaling at one host's HBM
and ingest bandwidth; the INES topology (PAPERS.md "In-Network Experience
Sampling") samples where experience LANDS instead of shipping raw
transitions to a central buffer.  Here each gateway host owns a replay
ring SHARD — a whole ``PrioritizedReplay`` with its own sum/min trees —
and the learner samples through a TWO-LEVEL tree:

- **level 1, learner-side** (``ShardedReplayPlane``): a global
  priority-mass vector over the live shards.  One stratified draw over
  the GLOBAL mass (the same ``linspace`` + one ``rng.uniform`` call the
  single-host ``SumTree.sample`` makes, so the RNG stream is consumed
  identically) routes each sample value to the shard owning its mass
  stratum;
- **level 2, shard-local** (``LocalShard``): the existing sum-tree
  descent answers with rows + leaf priorities — the raw transitions
  never move except as sampled minibatch rows.

Fault tolerance is the first-class axis, not an afterthought:

- **Lease-fenced membership** (``ShardRegistry``, the PR-14
  ``ReplicaRegistry`` contract on the replay plane): every shard holds a
  renewable lease stamped with a monotonic GENERATION; renews carry the
  shard's mass/fill/ingest report.  A shard silent past one lease window
  is expired and FENCED — the global mass vector reconfigures and
  sampling continues over the survivors within one window.
- **Exact degradation ledger**: the expired shard's cumulative ingested
  rows move into the ``shard_lost`` bucket, so conservation stays exact
  through the loss: minted = Σ live ingested + shard_lost + dropped +
  shed + quarantined + buffered (the ISSUE-11 flow identity, extended).
- **Deterministic fenced write-back**: the plane stamps each sample with
  the per-shard generations it sampled under; |TD| write-backs are
  decoded to (shard, local-row) groups applied in ascending shard order,
  and a write-back to a shard whose generation moved (died, rejoined) is
  a COUNTED reject — never applied.  A zombie shard host can never
  resurrect stale priorities.
- **Slot-routed ingest rebalance**: transitions route to shards by actor
  slot over the live-member table; membership change rebuilds the route
  (counted), so ingest drains onto survivors without pausing.
- **Rejoin barrier** (the PR-14 epoch-barrier pattern): a REjoining
  shard re-leases at a fresh generation in a ``joining`` state — it
  receives routed ingest immediately but is excluded from the sample
  mass vector until it ``activate``s (its ring is warm), bounded by
  ``join_timeout_s``.

At ``ShardParams.shards <= 1`` the plane is off everywhere:
``factory.build_memory`` constructs the plain single-host PER, no
registry exists, no shard verb ever rides the wire, and STATUS carries
zero new fields.  A 1-shard plane, when constructed explicitly, is
BIT-identical to the single-host PER path (tests/test_shard_plane.py
oracle) — sampled indices, IS weights, priorities, and write-backs all
reduce to the same floats, which is what makes the degraded
(last-survivor) state trustworthy.

Pure stdlib+numpy — no jax — so tools/chaos_soak.py drills the whole
plane in milliseconds.  Wire codecs for the sessionless-adjacent shard
verbs (T_SSAMPLE/T_SMASS/T_SPRIO, parallel/dcn.py) live here; the
gateway stays ignorant of this module and dispatches to duck-typed
``handle_*`` methods on whatever ``shards=`` object it was wired with.
"""

from __future__ import annotations

import io
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from pytorch_distributed_tpu.memory.prioritized import PrioritizedReplay
from pytorch_distributed_tpu.utils import flight_recorder
from pytorch_distributed_tpu.utils.experience import (
    PROV_NONE, REPLAY_FIELDS, Batch, Transition,
)

# ---------------------------------------------------------------------------
# params + env plane
# ---------------------------------------------------------------------------


def resolve_shard(sp=None):
    """ShardParams + ``TPU_APEX_SHARD_<FIELD>`` env overrides — the same
    override-by-env contract as the health/perf/flow/replica planes.
    Returns a NEW instance; the input is never mutated (Options rides
    spawn pickles)."""
    import dataclasses

    from pytorch_distributed_tpu.config import ShardParams

    if sp is None:
        sp = ShardParams()
    changes: Dict[str, Any] = {}
    for f in dataclasses.fields(sp):
        raw = os.environ.get("TPU_APEX_SHARD_" + f.name.upper())
        if raw is None:
            continue
        cur = getattr(sp, f.name)
        if isinstance(cur, bool):
            changes[f.name] = raw.strip().lower() not in (
                "0", "false", "off", "no", "")
        elif isinstance(cur, int) and not isinstance(cur, bool):
            changes[f.name] = int(float(raw))
        elif isinstance(cur, float):
            changes[f.name] = float(raw)
        else:
            changes[f.name] = raw.strip()
    return dataclasses.replace(sp, **changes) if changes else sp


def export_shard_env(sp) -> None:
    """Export a RESOLVED ShardParams into the environment so spawn
    children (remote shard hosts, actor mains) resolve the same plane
    the topology configured.  setdefault: an operator's explicit env
    wins."""
    import dataclasses

    for f in dataclasses.fields(sp):
        val = getattr(sp, f.name)
        if val != f.default:
            os.environ.setdefault("TPU_APEX_SHARD_" + f.name.upper(),
                                  str(val))


def sharding_active(sp=None) -> bool:
    """The one predicate every integration point keys on: > 1 configured
    shards.  False = the pre-shard code path, bit-for-bit."""
    return resolve_shard(sp).shards > 1


# ---------------------------------------------------------------------------
# wire codecs (T_SSAMPLE / T_SPRIO payloads; T_SMASS is plain JSON)
# ---------------------------------------------------------------------------

# T_SSAMPLE reply status codes (int64 ``status`` column)
SSTAT_OK = 0      # answered; mass report (+ rows when values were sent)
SSTAT_STALE = 1   # request stamped a dead generation: counted reject
SSTAT_DEAD = 2    # shard host is draining/dead: caller treats as loss
SSTAT_NOSHARD = 3  # no shard host wired on this gateway

# every savez column the shard codecs may ship, either direction (the
# declared wire schema, same contract as dcn.REPLICA_WIRE_COLUMNS; the
# pack/unpack helpers below are the only writers/readers)
SHARD_WIRE_COLUMNS = REPLAY_FIELDS + (
    "meta", "values", "status", "generation", "total", "size",
    "min_leaf", "ingested", "stale_rejected", "idx", "leaves", "prov",
    "pidx", "ptd")


def _pack_ssample(shard: int, generation: int,
                  values: Optional[np.ndarray] = None) -> bytes:
    """Sample request: ``values`` are SHARD-LOCAL mass coordinates (the
    plane already subtracted the global stratum offset).  Empty values =
    a pure mass poll (the level-1 refresh)."""
    cols = {"meta": np.asarray([shard, generation], np.int64)}
    if values is not None and len(values):
        cols["values"] = np.ascontiguousarray(values, dtype=np.float64)
    out = io.BytesIO()
    np.savez(out, **cols)
    return out.getvalue()


def _unpack_ssample(payload: bytes) -> Tuple[int, int, np.ndarray]:
    try:
        with np.load(io.BytesIO(payload)) as z:
            meta = z["meta"]
            values = z["values"] if "values" in z.files else \
                np.zeros(0, np.float64)
    except Exception as e:
        raise ConnectionError(f"unparseable SSAMPLE payload: {e!r}")
    if meta.shape != (2,) or meta.dtype.kind not in "iu":
        raise ConnectionError("malformed SSAMPLE frame: bad meta column")
    return int(meta[0]), int(meta[1]), values


def _pack_ssample_reply(status: int, generation: int = 0,
                        mass: Optional[dict] = None,
                        rows: Optional[dict] = None) -> bytes:
    cols: Dict[str, np.ndarray] = {
        "status": np.asarray([status], np.int64),
        "generation": np.asarray([generation], np.int64),
    }
    if mass is not None:
        cols["total"] = np.asarray([mass["total"]], np.float64)
        cols["size"] = np.asarray([mass["size"]], np.int64)
        cols["min_leaf"] = np.asarray([mass["min_leaf"]], np.float64)
        cols["ingested"] = np.asarray([mass["ingested"]], np.int64)
        cols["stale_rejected"] = np.asarray([mass["stale_rejected"]],
                                            np.int64)
    if rows is not None:
        cols["idx"] = np.ascontiguousarray(rows["idx"], np.int64)
        cols["leaves"] = np.ascontiguousarray(rows["leaves"], np.float64)
        cols["prov"] = np.ascontiguousarray(rows["prov"], np.int64)
        for f in REPLAY_FIELDS:
            cols[f] = np.ascontiguousarray(rows[f])
    out = io.BytesIO()
    np.savez(out, **cols)
    return out.getvalue()


def _unpack_ssample_reply(payload: bytes) -> dict:
    try:
        with np.load(io.BytesIO(payload)) as z:
            cols = {k: z[k] for k in z.files}
    except Exception as e:
        raise ConnectionError(f"unparseable SSAMPLE reply: {e!r}")
    out: Dict[str, Any] = {
        "status": int(cols["status"][0]),
        "generation": int(cols.get("generation", [0])[0]),
    }
    if "total" in cols:
        out["mass"] = {
            "total": float(cols["total"][0]),
            "size": int(cols["size"][0]),
            "min_leaf": float(cols["min_leaf"][0]),
            "ingested": int(cols["ingested"][0]),
            "stale_rejected": int(cols["stale_rejected"][0]),
        }
    if "idx" in cols:
        out["rows"] = {k: cols[k] for k in
                       ("idx", "leaves", "prov") + REPLAY_FIELDS}
    return out


def _pack_sprio(shard: int, generation: int, pidx: np.ndarray,
                ptd: np.ndarray) -> bytes:
    out = io.BytesIO()
    np.savez(out,
             meta=np.asarray([shard, generation], np.int64),
             pidx=np.ascontiguousarray(pidx, dtype=np.int32),
             ptd=np.ascontiguousarray(ptd, dtype=np.float32))
    return out.getvalue()


# ---------------------------------------------------------------------------
# the shard itself (lives on a gateway host; served over T_SSAMPLE/T_SPRIO)
# ---------------------------------------------------------------------------

class LocalShard:
    """One host's replay shard: a whole PrioritizedReplay + the fencing
    state and ledger legs the fault plane needs.  Server-side handler
    for the shard verbs (the gateway dispatches ``handle_ssample`` /
    ``handle_sprio`` to whatever ``shards=`` object it holds) AND the
    in-process shard of a loopback plane (tests, the co-located
    shard-0 of a production learner host)."""

    # single-owner declaration (apexlint single-owner rule): the shard's
    # ring and trees mutate only through the plane's routed ingest, the
    # gateway's ingest path, and the fenced write-back — a second writer
    # forks the priority plane the whole design keeps singular
    __apex_mutators__ = ("feed", "write_prio", "restore")
    __apex_owner__ = ("memory.shard_plane", "parallel.dcn",
                      "agents.learner", "fleet", "tools.chaos_soak")

    def __init__(self, shard_id: int, per: PrioritizedReplay,
                 generation: int = 0):
        self.shard_id = int(shard_id)
        self.per = per
        # stamped by the registry at acquire (ShardLease/loopback build);
        # every write-back and sample request is checked against it
        self.generation = int(generation)
        # flipped by drills (and by a draining host) to model the crash:
        # a dead shard answers nothing, renews nothing, and expires
        self.alive = True
        self.ingested_rows = 0        # cumulative ledger leg
        self.stale_rejected = 0       # write-backs fenced HERE (rows)
        self._recorder = flight_recorder.get_recorder("shard")

    # -- mass report (level-1 refresh + lease renew payload) ----------------

    def mass(self) -> dict:
        return {
            "total": float(self.per.sum_tree.total),
            "size": int(self.per.size),
            "min_leaf": float(self.per.min_tree.min),
            "ingested": int(self.ingested_rows),
            "stale_rejected": int(self.stale_rejected),
        }

    # -- ingest (slot-routed by the plane / T_EXP on the shard gateway) -----

    def feed(self, transition: Transition,
             priority: Optional[float] = None) -> bool:
        if not self.alive:
            return False
        self.per.feed(transition, priority)
        self.ingested_rows += 1
        return True

    # -- level-2 sample: local find + row gather ----------------------------

    def find_rows(self, values: np.ndarray) -> dict:
        """Answer shard-local sample values with rows + leaf priorities.
        ``values`` are already in this shard's mass coordinates; the
        descent is the exact single-host ``SumTree.find``, so a 1-shard
        plane draws bit-identical indices."""
        idx = self.per.sum_tree.find(values)
        return {
            "idx": idx,
            "leaves": self.per.sum_tree.get(idx),
            "prov": self.per.prov[idx],
            **{f: getattr(self.per, f)[idx].copy()
               for f in REPLAY_FIELDS},
        }

    # -- fenced |TD| write-back --------------------------------------------

    def write_prio(self, indices: np.ndarray, priorities: np.ndarray,
                   generation: int) -> bool:
        """Apply a |TD| write-back IF ``generation`` still names this
        shard's live incarnation; a stale generation (the writer sampled
        before this shard died/rejoined) is a counted reject — the
        last-generation-wins contract, so a zombie writer can never
        resurrect pre-loss priorities."""
        if not self.alive or int(generation) != self.generation:
            self.stale_rejected += int(len(indices))
            self._recorder.record("stale-writeback-rejected",
                                  shard=self.shard_id,
                                  generation=int(generation),
                                  rows=int(len(indices)))
            return False
        self.per.update_priorities(indices, priorities)
        return True

    # -- checkpoint / oracle plumbing ---------------------------------------

    def snapshot(self) -> dict:
        return self.per.snapshot()

    def restore(self, data: dict) -> None:
        self.per.restore(data)

    # -- wire dispatch (called by DcnGateway serve threads) ------------------

    def handle_ssample(self, payload: bytes) -> bytes:
        sid, gen, values = _unpack_ssample(payload)
        if not self.alive:
            return _pack_ssample_reply(SSTAT_DEAD)
        if sid != self.shard_id:
            return _pack_ssample_reply(SSTAT_STALE)
        rows = self.find_rows(values) if len(values) else None
        return _pack_ssample_reply(SSTAT_OK, generation=self.generation,
                                   mass=self.mass(), rows=rows)

    def handle_sprio(self, payload: bytes) -> dict:
        try:
            with np.load(io.BytesIO(payload)) as z:
                meta = z["meta"]
                pidx = z["pidx"]
                ptd = z["ptd"]
        except Exception as e:
            raise ConnectionError(f"unparseable SPRIO payload: {e!r}")
        if not self.alive:
            return {"status": "dead"}
        ok = self.write_prio(pidx.astype(np.int64), ptd, int(meta[1]))
        return {"status": "ok" if ok else "stale",
                "rows": int(len(pidx))}

    def handle_smass(self, msg: dict) -> dict:
        # a shard HOST answers only the mass poll; membership actions
        # belong to the coordinator's ShardRegistry
        if str(msg.get("action", "mass")) == "mass":
            if not self.alive:
                return {"status": "dead"}
            return {"status": "ok", "shard": self.shard_id,
                    "generation": self.generation, **self.mass()}
        return {"status": "error",
                "error": "membership actions need the coordinator "
                         "gateway (this is a shard host)"}


# ---------------------------------------------------------------------------
# coordinator-side membership: lease-fenced, generation-stamped
# ---------------------------------------------------------------------------

class ShardRegistry:
    """Coordinator-side shard membership + the degradation ledger
    (ISSUE 20) — the PR-14 ``ReplicaRegistry`` lease contract on the
    replay plane, minus rounds (sampling has no barrier: the mass
    vector reconfigures and the next sample just runs over survivors).

    Leases are stamped with one monotonic GENERATION counter across the
    registry; renews carry the shard's mass/fill/ingest report, so the
    registry always holds the last-acked ledger legs.  Expiry moves the
    dead shard's cumulative ingested rows into ``shard_lost_rows`` —
    the bucket that keeps minted = ingested + dropped + shed +
    quarantined + shard_lost + buffered EXACT through the loss.  A
    rejoin (an id with a fenced past generation) enters ``joining``:
    routed ingest immediately, excluded from the sample mass vector
    until ``activate`` (the epoch-barrier pattern, replay-plane
    flavour), bounded by ``join_timeout_s``."""

    def __init__(self, params=None, writer=None):
        self.params = resolve_shard(params)
        self._cond = threading.Condition()
        self._gen = 0
        # shard -> {generation, incarnation, expires, joining, endpoint,
        #           capacity, renews, born, mass, size, fill, ingested,
        #           stale_rejected, join_deadline}
        self._members: Dict[int, Dict[str, Any]] = {}
        self._fenced_gen: Dict[int, int] = {}
        self._writer = writer
        self._last_emit = 0.0
        self._recorder = flight_recorder.get_recorder("shard-registry")
        # membership epoch: bumped on every acquire/expire/release/
        # activate — the plane rebuilds its route table when it moves
        self.route_epoch = 0
        # counters (the drill ledger: chaos_soak asserts these EXACTLY)
        self.leases_granted = 0
        self.leases_expired = 0
        self.leases_released = 0
        self.lease_fenced = 0            # double-lease evictions
        self.shard_lost_rows = 0         # ledger bucket, cumulative
        self.stale_writeback_rejected = 0  # rows fenced plane- or shard-side
        self.route_dropped = 0           # rows routed at a dead shard
        self.rebalances = 0              # membership-change route rebuilds
        self.joins_completed = 0
        self.joins_timed_out = 0

    # -- internals (all under self._cond) -----------------------------------

    def _lease_window(self) -> float:
        return max(0.05, float(self.params.lease_s))

    def _emit_locked(self, force: bool = False) -> None:
        """``replay/shard_*`` scalar rows for mission control: live
        member count (vs expected), mass skew (max shard share over the
        balanced share — 1.0 is perfect balance), and the 0/1 degraded
        flag the ``shard_membership`` DEFAULT_RULE watches.  Rate-
        limited; membership events force.  Fleets without sharding
        never construct a registry, so the series are never written and
        the rule stays silently inert there."""
        if self._writer is None:
            return
        now = time.monotonic()
        if not force and now - self._last_emit < 1.0:
            return
        self._last_emit = now
        wall = time.time()
        expected = max(1, int(self.params.shards))
        masses = [m["mass"] for m in self._members.values()
                  if not m["joining"]]
        total = float(sum(masses))
        n = max(1, len(masses))
        skew = (max(masses) / (total / n)) if total > 0 else 0.0
        try:
            self._writer.scalar("replay/shard_members",
                                float(len(self._members)), wall=wall)
            self._writer.scalar("replay/shard_mass_skew", round(skew, 4),
                                wall=wall)
            self._writer.scalar(
                "replay/shard_degraded",
                1.0 if len(self._members) < expected else 0.0, wall=wall)
            self._writer.flush()
        except Exception:  # noqa: BLE001 - telemetry is best-effort
            pass

    def _expire_locked(self, now: float) -> None:
        for sid, m in list(self._members.items()):
            if m["joining"] and now > m["join_deadline"]:
                # the rejoiner never warmed up: cancel the join so the
                # plane's route stops feeding a ghost
                del self._members[sid]
                self._fenced_gen[sid] = m["generation"]
                self.joins_timed_out += 1
                self.shard_lost_rows += int(m["ingested"])
                self.route_epoch += 1
                self.rebalances += 1
                self._recorder.record("join-timeout", shard=sid,
                                      generation=m["generation"])
                self._emit_locked(force=True)
                continue
            if now > m["expires"]:
                del self._members[sid]
                self._fenced_gen[sid] = m["generation"]
                self.leases_expired += 1
                # THE degradation ledger move: the dead shard's acked
                # transitions leave the live-ingested leg and land in
                # shard_lost in the same locked step — conservation is
                # exact at every quiescent point, not eventually
                self.shard_lost_rows += int(m["ingested"])
                self.route_epoch += 1
                self.rebalances += 1
                self._recorder.record("lease-expired", shard=sid,
                                      generation=m["generation"],
                                      lost_rows=int(m["ingested"]))
                print(f"[shard] lease expired: shard {sid} (generation "
                      f"{m['generation']}, {int(m['ingested'])} rows -> "
                      f"shard_lost)", flush=True)
                self._emit_locked(force=True)

    def _live(self, sid: int, generation: int) -> bool:
        m = self._members.get(sid)
        return m is not None and m["generation"] == generation

    # -- lease verbs ---------------------------------------------------------

    def acquire(self, shard: int, incarnation: int, endpoint: str = "",
                capacity: int = 0) -> dict:
        with self._cond:
            now = time.monotonic()
            self._expire_locked(now)
            held = self._members.get(shard)
            if held is not None:
                if incarnation <= held["incarnation"]:
                    return {"status": "refused",
                            "error": f"shard {shard} already leased "
                                     f"(incarnation {incarnation} <= "
                                     f"{held['incarnation']})"}
                # double-lease: newer incarnation fences its own
                # half-open predecessor (PR-1 slot fencing, PR-14
                # replica fencing — same contract, replay plane)
                self._fenced_gen[shard] = held["generation"]
                self.lease_fenced += 1
                self.shard_lost_rows += int(held["ingested"])
                self._recorder.record("lease-fenced", shard=shard,
                                      old=held["generation"])
            self._gen += 1
            g = self._gen
            # a shard id with a fenced PAST generation is a REJOIN: it
            # enters joining (routed ingest, no sample mass) until it
            # activates — the epoch-barrier pattern.  First-ever
            # acquires are full members at once: an empty fresh shard
            # carries zero mass, so the vector excludes it naturally.
            joining = shard in self._fenced_gen
            self._members[shard] = {
                "generation": g, "incarnation": int(incarnation),
                "expires": now + self._lease_window(),
                "joining": joining, "endpoint": str(endpoint),
                "capacity": int(capacity), "renews": 0, "born": now,
                "mass": 0.0, "size": 0, "fill": 0.0, "ingested": 0,
                "stale_rejected": 0, "min_leaf": float("inf"),
                "join_deadline": now + max(self.params.join_timeout_s,
                                           self._lease_window()),
            }
            self.leases_granted += 1
            self.route_epoch += 1
            self.rebalances += 1
            self._recorder.record("lease-granted", shard=shard,
                                  generation=g, joining=joining)
            self._emit_locked(force=True)
            self._cond.notify_all()
            return {"status": "ok", "generation": g,
                    "lease_s": self._lease_window(),
                    "joining": joining,
                    "members": sorted(self._members)}

    def renew(self, shard: int, generation: int,
              report: Optional[dict] = None) -> dict:
        with self._cond:
            now = time.monotonic()
            self._expire_locked(now)
            if not self._live(shard, generation):
                return {"status": "expired"}
            m = self._members[shard]
            m["expires"] = now + self._lease_window()
            m["renews"] += 1
            if report:
                for k in ("mass", "size", "fill", "ingested",
                          "stale_rejected", "min_leaf"):
                    if k in report:
                        m[k] = report[k]
            self._emit_locked()
            return {"status": "ok", "generation": generation,
                    "joining": m["joining"],
                    "members": sorted(self._members)}

    def release(self, shard: int, generation: int) -> dict:
        with self._cond:
            if self._live(shard, generation):
                m = self._members.pop(shard)
                self._fenced_gen[shard] = m["generation"]
                self.leases_released += 1
                # a graceful release still abandons the rows (the host
                # is going away): same ledger move as expiry, so the
                # conservation identity never depends on HOW a shard
                # left
                self.shard_lost_rows += int(m["ingested"])
                self.route_epoch += 1
                self.rebalances += 1
                self._recorder.record("lease-released", shard=shard,
                                      generation=generation)
                self._emit_locked(force=True)
                self._cond.notify_all()
            return {"status": "ok"}

    def activate(self, shard: int, generation: int) -> dict:
        """A rejoiner confirms its ring is warm: it leaves ``joining``
        and its mass enters the sample vector from the next refresh."""
        with self._cond:
            if not self._live(shard, generation):
                return {"status": "expired"}
            m = self._members[shard]
            if m["joining"]:
                m["joining"] = False
                m["expires"] = time.monotonic() + self._lease_window()
                self.joins_completed += 1
                self.route_epoch += 1
                self.rebalances += 1
                self._recorder.record("join-activated", shard=shard,
                                      generation=generation)
                self._emit_locked(force=True)
                self._cond.notify_all()
            return {"status": "ok", "members": sorted(self._members)}

    # -- plane-side reads + ledger notes -------------------------------------

    def live_members(self, include_joining: bool = False) -> List[dict]:
        """Ascending-shard-id list of live members (expiry applied
        first) — the level-1 route/mass order.  ``include_joining``
        True is the INGEST view (rejoiners receive routed transitions
        while still barred from the sample vector)."""
        with self._cond:
            self._expire_locked(time.monotonic())
            return [{"shard": sid, "generation": m["generation"],
                     "endpoint": m["endpoint"],
                     "joining": m["joining"]}
                    for sid, m in sorted(self._members.items())
                    if include_joining or not m["joining"]]

    def touch(self, shard: int, generation: int,
              report: Optional[dict] = None) -> bool:
        """An answered in-process poll/ingest is proof of life — the
        loopback plane renews THROUGH its channel traffic, exactly as a
        wire shard host renews on its ingest acks."""
        return self.renew(shard, generation, report)["status"] == "ok"

    def note_stale_writeback(self, shard: int, rows: int) -> None:
        with self._cond:
            self.stale_writeback_rejected += int(rows)
            self._recorder.record("stale-writeback-rejected",
                                  shard=shard, rows=int(rows))

    def note_route_dropped(self, shard: int, rows: int) -> None:
        with self._cond:
            self.route_dropped += int(rows)
            self._recorder.record("route-dropped", shard=shard,
                                  rows=int(rows))

    # -- observability -------------------------------------------------------

    def ledger(self) -> Dict[str, int]:
        """The conservation legs this registry owns: live-acked ingest
        per shard + the loss buckets.  chaos_soak asserts
        minted == sum(ingested) + shard_lost + route_dropped (+ the
        flow plane's dropped/shed/quarantined/buffered legs) EXACTLY."""
        with self._cond:
            return {
                "ingested": int(sum(m["ingested"]
                                    for m in self._members.values())),
                "shard_lost": int(self.shard_lost_rows),
                "route_dropped": int(self.route_dropped),
                "stale_writeback_rejected":
                    int(self.stale_writeback_rejected),
            }

    def status_block(self) -> dict:
        """The gateway STATUS ``shards`` block: membership with lease
        ages, per-shard fill + priority-mass share + the rejected-stale
        ledger — tools/fleet_top.py's shards panel and the chaos
        drills' exact-counter verdicts both read this."""
        with self._cond:
            now = time.monotonic()
            sampling = [m for m in self._members.values()
                        if not m["joining"]]
            total = float(sum(m["mass"] for m in sampling))
            members = {}
            for sid, m in sorted(self._members.items()):
                members[str(sid)] = {
                    "generation": m["generation"],
                    "lease_age": round(
                        max(0.0, now - (m["expires"]
                                        - self._lease_window())), 3),
                    "joining": m["joining"],
                    "fill": round(float(m["fill"]), 4),
                    "size": int(m["size"]),
                    "mass": round(float(m["mass"]), 6),
                    "mass_share": round(m["mass"] / total, 4)
                    if (total > 0 and not m["joining"]) else 0.0,
                    "ingested": int(m["ingested"]),
                    "stale_rejected": int(m["stale_rejected"]),
                    "renews": m["renews"],
                    "endpoint": m["endpoint"],
                }
            expected = max(1, int(self.params.shards))
            n = max(1, len(sampling))
            skew = (max(m["mass"] for m in sampling) / (total / n)
                    if (sampling and total > 0) else 0.0)
            return {
                "expected": expected,
                "members": members,
                "degraded": len(members) < expected,
                "generation": self._gen,
                "mass_total": round(total, 6),
                "mass_skew": round(skew, 4),
                "counters": {
                    "leases_granted": self.leases_granted,
                    "leases_expired": self.leases_expired,
                    "leases_released": self.leases_released,
                    "lease_fenced": self.lease_fenced,
                    "shard_lost_rows": self.shard_lost_rows,
                    "stale_writeback_rejected":
                        self.stale_writeback_rejected,
                    "route_dropped": self.route_dropped,
                    "rebalances": self.rebalances,
                    "joins_completed": self.joins_completed,
                    "joins_timed_out": self.joins_timed_out,
                },
            }

    # -- wire dispatch (T_SMASS on the coordinator gateway) ------------------

    def handle_smass(self, msg: dict) -> dict:
        action = str(msg.get("action", ""))
        if action == "status":
            return {"status": "ok", "shards": self.status_block(),
                    "members": self.live_members(include_joining=True)}
        if action == "stale":
            self.note_stale_writeback(int(msg.get("shard", -1)),
                                      int(msg.get("rows", 0)))
            return {"status": "ok"}
        try:
            sid = int(msg.get("shard"))
        except (TypeError, ValueError):
            return {"status": "error", "error": "bad shard id"}
        if action == "acquire":
            return self.acquire(sid, int(msg.get("incarnation", 0)),
                                endpoint=str(msg.get("endpoint", "")),
                                capacity=int(msg.get("capacity", 0)))
        gen = int(msg.get("generation", -1))
        if action == "renew":
            return self.renew(sid, gen, msg.get("report"))
        if action == "release":
            return self.release(sid, gen)
        if action == "activate":
            return self.activate(sid, gen)
        return {"status": "error", "error": f"unknown action {action!r}"}


# ---------------------------------------------------------------------------
# channels: one surface whether the shard is in-process or across the wire
# ---------------------------------------------------------------------------

class LoopbackShardChannel:
    """In-process channel to a LocalShard — the tier-1 path and
    the co-located shard of a learner host.  Every answered call renews
    the shard's lease through ``registry.touch`` (served traffic is
    proof of life, the wire analog of renew-on-ack), so a drill that
    flips ``shard.alive`` sees the lease expire within one window with
    no thread machinery at all."""

    def __init__(self, shard: LocalShard, registry: ShardRegistry):
        self.shard = shard
        self.registry = registry

    def _report(self) -> dict:
        m = self.shard.mass()
        # the registry's renew report names the priority-mass leg
        # "mass" (the status/skew vocabulary); the sampler's poll keeps
        # the tree vocabulary ("total")
        m["mass"] = m["total"]
        m["fill"] = (m["size"] / self.shard.per.capacity
                     if self.shard.per.capacity else 0.0)
        return m

    def poll(self) -> Optional[dict]:
        """Mass report + generation, None when the shard is dead."""
        if not self.shard.alive:
            return None
        rep = self._report()
        self.registry.touch(self.shard.shard_id, self.shard.generation,
                            rep)
        return {"generation": self.shard.generation, **rep}

    def sample_rows(self, values: np.ndarray) -> Optional[dict]:
        if not self.shard.alive:
            return None
        return self.shard.find_rows(values)

    def write_prio(self, indices: np.ndarray, priorities: np.ndarray,
                   generation: int) -> bool:
        if not self.shard.alive:
            return False
        return self.shard.write_prio(indices, priorities, generation)

    def feed(self, transition: Transition,
             priority: Optional[float]) -> bool:
        if not self.shard.feed(transition, priority):
            return False
        self.registry.touch(self.shard.shard_id, self.shard.generation,
                            self._report())
        return True


class RemoteShardChannel:
    """Wire channel to a shard host's gateway over the sessionless-
    adjacent shard verbs (one persistent connection; errors mark the
    channel dead and the caller falls back to membership).  Ingest does
    NOT ride this channel in production — actors stream T_EXP chunks at
    the shard host directly (experience samples where it LANDS; that is
    the point of INES) — but ``feed`` exists for completeness and
    drills, shipping a one-row chunk through the same gateway ingest
    path."""

    def __init__(self, address: Tuple[str, int], shard: int,
                 generation: int, timeout: float = 5.0):
        self.address = tuple(address)
        self.shard = int(shard)
        self.generation = int(generation)
        self.timeout = timeout
        self._sock = None
        self.dead = False

    def _conn(self):
        import socket as _socket

        from pytorch_distributed_tpu.utils import bandwidth

        if self._sock is None:
            self._sock = _socket.create_connection(self.address,
                                                   timeout=self.timeout)
            self._sock.settimeout(self.timeout)
            bandwidth.register_socket(self._sock, "shard-client")
        return self._sock

    def close(self) -> None:
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
            self._sock = None

    def _rpc(self, ftype: int, payload: bytes) -> bytes:
        from pytorch_distributed_tpu.parallel import dcn

        try:
            sock = self._conn()
            dcn._send_frame(sock, ftype, payload)
            rtype, reply = dcn._recv_frame(sock)
            if rtype != ftype:
                raise ConnectionError(f"expected {ftype}, got {rtype}")
            return reply
        except (ConnectionError, OSError):
            self.close()
            self.dead = True
            raise

    def poll(self) -> Optional[dict]:
        from pytorch_distributed_tpu.parallel import dcn

        try:
            rep = _unpack_ssample_reply(self._rpc(
                dcn.T_SSAMPLE, _pack_ssample(self.shard,
                                             self.generation)))
        except (ConnectionError, OSError):
            return None
        if rep["status"] != SSTAT_OK or "mass" not in rep:
            return None
        self.generation = rep["generation"]
        m = rep["mass"]
        m["fill"] = 0.0
        return {"generation": rep["generation"], **m}

    def sample_rows(self, values: np.ndarray) -> Optional[dict]:
        from pytorch_distributed_tpu.parallel import dcn

        try:
            rep = _unpack_ssample_reply(self._rpc(
                dcn.T_SSAMPLE, _pack_ssample(self.shard, self.generation,
                                             values)))
        except (ConnectionError, OSError):
            return None
        if rep["status"] != SSTAT_OK or "rows" not in rep:
            return None
        return rep["rows"]

    def write_prio(self, indices: np.ndarray, priorities: np.ndarray,
                   generation: int) -> bool:
        import json as _json

        from pytorch_distributed_tpu.parallel import dcn

        try:
            reply = self._rpc(dcn.T_SPRIO,
                              _pack_sprio(self.shard, generation,
                                          np.asarray(indices, np.int32),
                                          np.asarray(priorities,
                                                     np.float32)))
            return _json.loads(reply.decode()).get("status") == "ok"
        except (ConnectionError, OSError, ValueError):
            return False

    def feed(self, transition: Transition,
             priority: Optional[float]) -> bool:
        from pytorch_distributed_tpu.parallel import dcn

        try:
            sock = self._conn()
            dcn._send_frame(sock, dcn.T_EXP,
                            dcn.encode_chunk([(transition, priority)]))
            # the gateway acks EXP with its clock frame (the normal
            # ingest contract) — the ack is what makes renew-before-ack
            # exact: by the time we see T_CLOCK the shard host has fed
            # the row AND renewed its lease with the updated count
            rtype, _ = dcn._recv_frame(sock)
            if rtype != dcn.T_CLOCK:
                raise ConnectionError(
                    f"expected clock ack for EXP, got {rtype}")
            return True
        except (ConnectionError, OSError):
            self.close()
            self.dead = True
            return False


class ShardLease:
    """Client-side lease maintenance for a shard HOST against the
    coordinator gateway (sessionless T_SMASS round-trips — the PR-14
    lease verbs, replay flavour).  The host renews on its own cadence
    AND on every ingest ack (so the registry's per-shard ingested leg is
    exact at every quiescent point: a crash between acks loses only
    unacked — hence actor-counted — rows)."""

    def __init__(self, coordinator: Tuple[str, int], shard: int,
                 incarnation: int, endpoint: str = "",
                 capacity: int = 0, timeout: float = 5.0):
        self.coordinator = tuple(coordinator)
        self.shard = int(shard)
        self.incarnation = int(incarnation)
        self.endpoint = endpoint
        self.capacity = int(capacity)
        self.timeout = timeout
        self.generation = -1
        self.joining = False

    def _rpc(self, msg: dict) -> dict:
        import json as _json

        from pytorch_distributed_tpu.parallel import dcn

        return dcn._sessionless_rpc(
            self.coordinator, dcn.T_SMASS,
            _json.dumps(msg).encode(), self.timeout, "T_SMASS")

    def acquire(self) -> dict:
        rep = self._rpc({"action": "acquire", "shard": self.shard,
                         "incarnation": self.incarnation,
                         "endpoint": self.endpoint,
                         "capacity": self.capacity})
        if rep.get("status") != "ok":
            raise ConnectionError(f"shard lease refused: {rep}")
        self.generation = int(rep["generation"])
        self.joining = bool(rep.get("joining"))
        return rep

    def renew(self, report: Optional[dict] = None) -> bool:
        rep = self._rpc({"action": "renew", "shard": self.shard,
                         "generation": self.generation,
                         "report": report})
        return rep.get("status") == "ok"

    def activate(self) -> bool:
        rep = self._rpc({"action": "activate", "shard": self.shard,
                         "generation": self.generation})
        self.joining = False
        return rep.get("status") == "ok"

    def release(self) -> None:
        try:
            self._rpc({"action": "release", "shard": self.shard,
                       "generation": self.generation})
        except (ConnectionError, OSError):
            pass


# ---------------------------------------------------------------------------
# level 1: the learner-side two-level sampler
# ---------------------------------------------------------------------------

class ShardedReplayPlane:
    """Learner-side drop-in for ``PrioritizedReplay`` over N shard
    channels: the same ``Memory`` surface (feed/sample/
    update_priorities/snapshot/restore + the provenance and leaf reads),
    so ``QueueOwner`` wraps it unchanged and the learner loop never
    learns sharding exists.

    **Bit-parity contract** (the degraded-trust anchor): with ONE live
    shard, ``sample`` consumes the RNG identically to the single-host
    path (one ``rng.uniform`` over the same ``linspace`` strata of the
    same total mass), routes every value to that shard's unmodified
    ``SumTree.find``, and computes IS weights from the same
    size/min/total floats — so indices, weights, priorities, and
    write-backs are bit-identical to ``PrioritizedReplay`` (the
    tests/test_shard_plane.py oracle).  Global row ids are
    ``shard_id * shard_capacity + local_row`` (shard 0 = the identity),
    decoded back for the fenced write-back merge.

    **Fencing**: each sample stamps the per-shard generations it drew
    under; ``update_priorities`` groups rows by ascending shard id and
    applies each group only where the generation still stands — a group
    aimed at a died/rejoined shard is a counted reject
    (``stale_writeback_rejected``), never applied."""

    # single-owner declaration (apexlint single-owner rule): ingest and
    # priority write-back mutate N rings through one routed boundary —
    # the learner's QueueOwner drain and the learner step own it
    __apex_mutators__ = ("feed", "update_priorities", "restore")
    __apex_owner__ = ("memory.shard_plane", "memory.feeder",
                      "agents.learner", "tools.chaos_soak")

    def __init__(self, channels: Dict[int, Any], registry: ShardRegistry,
                 shard_capacity: int,
                 state_shape: Tuple[int, ...],
                 action_shape: Tuple[int, ...] = (),
                 state_dtype=np.uint8, action_dtype=np.int32,
                 importance_weight: float = 0.4,
                 importance_anneal_steps: int = 500000):
        self.channels = dict(channels)
        self.registry = registry
        self.shard_capacity = int(shard_capacity)
        expected = max(1, int(registry.params.shards))
        assert expected * self.shard_capacity < 2 ** 31, \
            "global row ids must fit the Batch.index int32 contract"
        self.state_shape = tuple(state_shape)
        self.action_shape = tuple(action_shape)
        self.state_dtype = np.dtype(state_dtype)
        self.action_dtype = np.dtype(action_dtype)
        self.beta0 = importance_weight
        self.beta_steps = importance_anneal_steps
        self._samples_drawn = 0
        self._feed_seq = 0
        self._mass: List[dict] = []       # ascending sid mass entries
        self._mass_at = 0.0
        self._sample_gens: Dict[int, int] = {}
        self._route: List[int] = []
        self._route_epoch = -1

    # -- membership-reactive plumbing ---------------------------------------

    def attach_channel(self, sid: int, channel) -> None:
        """Wire a (re)joined shard's channel — the loopback builder and
        the drill's rejoin leg call this; wire planes rebuild channels
        from membership endpoints instead."""
        self.channels[int(sid)] = channel

    def _refresh_route(self) -> None:
        # snapshot the epoch BEFORE listing members: a membership event
        # that lands between the two would otherwise be stamped as
        # already-routed (stale route, current epoch) and a rejoiner
        # could be starved of ingest forever — if the epoch moves while
        # we read, the stale stamp forces another refresh next feed
        epoch = self.registry.route_epoch
        if self._route_epoch == epoch:
            return
        live = self.registry.live_members(include_joining=True)
        self._route = [m["shard"] for m in live
                       if m["shard"] in self.channels]
        self._route_epoch = epoch

    def _refresh_mass(self, force: bool = False) -> None:
        """Rebuild the level-1 mass vector from the live members' polls.
        ``mass_refresh_s`` 0 (the default) refreshes at EVERY sample —
        exact priority proportions, and what the parity oracle needs;
        wire fleets may trade staleness for fewer round-trips."""
        now = time.monotonic()
        every = float(self.registry.params.mass_refresh_s)
        if not force and self._mass and every > 0 \
                and now - self._mass_at < every:
            return
        self._mass_at = now
        entries: List[dict] = []
        for m in self.registry.live_members():
            ch = self.channels.get(m["shard"])
            if ch is None:
                continue
            rep = ch.poll()
            if rep is None:
                # dead-but-not-yet-expired: excluded from THIS vector;
                # the lease window owns the actual membership verdict
                continue
            entries.append({"shard": m["shard"],
                            "generation": rep["generation"],
                            "total": rep["total"], "size": rep["size"],
                            "min_leaf": rep["min_leaf"]})
        self._mass = entries

    # -- Memory surface ------------------------------------------------------

    @property
    def size(self) -> int:
        self._refresh_mass(force=True)
        return int(sum(e["size"] for e in self._mass))

    @property
    def capacity(self) -> int:
        return self.shard_capacity * max(1, int(
            self.registry.params.shards))

    @property
    def beta(self) -> float:
        frac = min(1.0, self._samples_drawn / max(1, self.beta_steps))
        return self.beta0 + (1.0 - self.beta0) * frac

    def feed(self, transition: Transition,
             priority: Optional[float] = None) -> None:
        """Slot-routed ingest: the actor slot (provenance column 0, or
        an arrival counter for unattributed rows) picks a live shard
        from the route table, which rebuilds on every membership change
        (the rebalance leg).  Rows routed at a shard that died inside
        its lease window are counted ``route_dropped`` — the loopback
        analog of an unacked wire chunk."""
        self._refresh_route()
        seq = self._feed_seq
        self._feed_seq += 1
        if not self._route:
            self.registry.note_route_dropped(-1, 1)
            return
        prov = getattr(transition, "prov", None)
        slot = int(prov[0]) if prov is not None and int(prov[0]) >= 0 \
            else seq
        sid = self._route[slot % len(self._route)]
        ch = self.channels.get(sid)
        if ch is None or not ch.feed(transition, priority):
            self.registry.note_route_dropped(sid, 1)
            # the failed channel is stale until the registry notices:
            # force a route re-check on the next feed
            self._route_epoch = -1

    def sample(self, batch_size: int, rng: np.random.Generator) -> Batch:
        self._refresh_mass()
        live = self._mass
        totals = [e["total"] for e in live]
        global_total = totals[0] if len(totals) == 1 \
            else float(np.sum(np.asarray(totals, np.float64)))
        assert global_total > 0, \
            "cannot sample from an empty shard plane"
        # ONE stratified uniform draw over the global mass — the exact
        # RNG consumption of the single-host SumTree.sample, so a
        # 1-shard plane replays its stream bit-for-bit
        bounds = np.linspace(0.0, global_total, batch_size + 1)
        values = rng.uniform(bounds[:-1], bounds[1:])
        self._samples_drawn += 1
        offsets = np.concatenate(
            [[0.0], np.cumsum(np.asarray(totals, np.float64))])
        pos = np.searchsorted(offsets[1:], values, side="right")
        pos = np.minimum(pos, len(live) - 1)
        local_values = values - offsets[pos]
        idx = np.empty(batch_size, np.int64)
        leaves = np.empty(batch_size, np.float64)
        cols: Dict[str, Optional[np.ndarray]] = {
            f: None for f in REPLAY_FIELDS}
        prov = np.tile(PROV_NONE, (batch_size, 1))
        gens: Dict[int, int] = {}
        for k, entry in enumerate(live):
            mask = pos == k
            if not mask.any():
                continue
            ch = self.channels.get(entry["shard"])
            rep = None if ch is None else ch.sample_rows(
                local_values[mask])
            if rep is None:
                # the shard died between the mass poll and the row
                # fetch (sub-lease-window race): fall back to a fresh
                # vector — sampling must degrade, never deadlock
                self._refresh_mass(force=True)
                assert self._mass, "all shards lost mid-sample"
                return self.sample(batch_size, rng)
            gens[entry["shard"]] = entry["generation"]
            idx[mask] = (entry["shard"] * self.shard_capacity
                         + rep["idx"])
            leaves[mask] = rep["leaves"]
            prov[mask] = rep["prov"]
            for f in REPLAY_FIELDS:
                if cols[f] is None:
                    arr = np.asarray(rep[f])
                    cols[f] = np.empty((batch_size,) + arr.shape[1:],
                                       dtype=arr.dtype)
                cols[f][mask] = rep[f]
        probs = leaves / global_total
        size = int(sum(e["size"] for e in live))
        beta = self.beta
        weights = (size * probs) ** (-beta)
        min_prob = min(e["min_leaf"] for e in live) / global_total
        max_weight = (size * min_prob) ** (-beta)
        weights = (weights / max_weight).astype(np.float32)
        self._sample_gens = gens
        self._last_prov = prov
        return Batch(
            state0=cols["state0"], action=cols["action"],
            reward=cols["reward"], gamma_n=cols["gamma_n"],
            state1=cols["state1"], terminal1=cols["terminal1"],
            weight=weights, index=idx.astype(np.int32))

    def update_priorities(self, indices: np.ndarray,
                          priorities: np.ndarray) -> None:
        """Deterministic cross-shard |TD| write-back merge: rows decode
        to (shard, local) and apply in ascending shard id (a fixed
        order, so every replayer of this write-back sequence converges);
        groups aimed at a generation that moved are counted rejects."""
        indices = np.asarray(indices)
        priorities = np.asarray(priorities)
        sids = indices // self.shard_capacity
        local = indices % self.shard_capacity
        live = {m["shard"]: m["generation"]
                for m in self.registry.live_members(
                    include_joining=True)}
        for sid in np.unique(sids):
            mask = sids == sid
            rows = int(mask.sum())
            gen = self._sample_gens.get(int(sid))
            if gen is None or live.get(int(sid)) != gen:
                # fenced: the shard died or rejoined since this batch
                # was sampled — its rows belong to a dead incarnation
                self.registry.note_stale_writeback(int(sid), rows)
                continue
            ch = self.channels.get(int(sid))
            if ch is None or not ch.write_prio(
                    local[mask], priorities[mask], gen):
                self.registry.note_stale_writeback(int(sid), rows)

    def provenance_of(self, indices: np.ndarray) -> np.ndarray:
        """(B, 4) provenance of the LAST sampled batch's rows (the
        learner's telemetry gathers right after sample; a cross-shard
        random gather would need another round-trip for no consumer)."""
        prov = getattr(self, "_last_prov", None)
        if prov is not None and len(prov) == len(np.asarray(indices)):
            return prov
        return np.tile(PROV_NONE, (len(np.asarray(indices)), 1))

    def priority_leaves(self) -> np.ndarray:
        """Live shards' valid leaves, ascending shard id — the priority
        X-ray's input; reduces to the single ring's leaves at 1 shard."""
        out = []
        for e in self._mass or []:
            ch = self.channels.get(e["shard"])
            if isinstance(ch, LoopbackShardChannel):
                out.append(ch.shard.per.priority_leaves())
        return (np.concatenate(out) if out
                else np.zeros(0, np.float64))

    # -- checkpoint / oracle plumbing ---------------------------------------

    def snapshot(self) -> dict:
        self._refresh_mass(force=True)
        shards = {}
        for e in self._mass:
            ch = self.channels.get(e["shard"])
            if isinstance(ch, LoopbackShardChannel):
                shards[str(e["shard"])] = ch.shard.snapshot()
        return {"sharded": np.int64(1),
                "samples_drawn": np.int64(self._samples_drawn),
                "shards": shards}

    def restore(self, data: dict) -> None:
        self._samples_drawn = int(data.get("samples_drawn", 0))
        for key, snap in data.get("shards", {}).items():
            ch = self.channels.get(int(key))
            if isinstance(ch, LoopbackShardChannel):
                ch.shard.restore(snap)


# ---------------------------------------------------------------------------
# builders
# ---------------------------------------------------------------------------

def build_loopback_plane(params=None, capacity: int = 1024,
                         state_shape: Tuple[int, ...] = (4,),
                         action_shape: Tuple[int, ...] = (),
                         state_dtype=np.float32, action_dtype=np.int32,
                         priority_exponent: float = 0.6,
                         importance_weight: float = 0.4,
                         importance_anneal_steps: int = 500000,
                         shard_ids: Optional[List[int]] = None,
                         writer=None):
    """N in-process shards + registry + plane — the tier-1/
    co-located topology (and the substrate the wire drill's shard hosts
    reuse one shard at a time).  ``capacity`` is the GLOBAL transition
    budget, split evenly across the expected shard count; at shards=1
    the single shard gets all of it, which is what makes the plane
    bit-identical to a ``PrioritizedReplay(capacity)``."""
    sp = resolve_shard(params)
    n = max(1, int(sp.shards))
    ids = list(shard_ids) if shard_ids is not None else list(range(n))
    shard_capacity = max(1, -(-int(capacity) // n))
    registry = ShardRegistry(sp, writer=writer)
    channels: Dict[int, LoopbackShardChannel] = {}
    shards: Dict[int, LocalShard] = {}
    for sid in ids:
        per = PrioritizedReplay(
            capacity=shard_capacity, state_shape=state_shape,
            action_shape=action_shape, state_dtype=state_dtype,
            action_dtype=action_dtype,
            priority_exponent=priority_exponent,
            importance_weight=importance_weight,
            importance_anneal_steps=importance_anneal_steps)
        shard = LocalShard(sid, per)
        grant = registry.acquire(sid, incarnation=1,
                                 capacity=shard_capacity)
        shard.generation = int(grant["generation"])
        channels[sid] = LoopbackShardChannel(shard, registry)
        shards[sid] = shard
    plane = ShardedReplayPlane(
        channels, registry, shard_capacity,
        state_shape=state_shape, action_shape=action_shape,
        state_dtype=state_dtype, action_dtype=action_dtype,
        importance_weight=importance_weight,
        importance_anneal_steps=importance_anneal_steps)
    return plane, shards, registry
