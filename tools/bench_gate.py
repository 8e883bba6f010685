#!/usr/bin/env python
"""Bench regression gate: diff two bench JSONs, keep history, exit
nonzero on regression — perf as a CI check, not an offline artifact.

``bench.py`` prints one JSON line per run; until this tool the only
consumer was a human eyeballing BENCH_r0N files.  The gate makes the
comparison mechanical and schema-aware:

- **What is compared**: a fixed spec table of throughput keys (higher is
  better) and overhead fractions (lower is better, absolute tolerance),
  spanning every bench section — micro headline, per-family rows,
  sampler, actor pipeline, e2e, health/perf overhead, and the ``--smoke``
  section.  Keys missing on EITHER side are skipped (an e2e-less candidate
  is not a regression), and ``bench_schema`` must match — a key whose
  MEANING changed between schemas (the round-3 lesson bench.py documents)
  must never be numerically compared across them
  (``--allow-schema-drift`` overrides, for deliberate migrations).
- **Tolerances**: per-section relative slack (dispatch timing is
  noisy; e2e carries actor jitter), overridable with
  repeatable ``--tol SECTION=FRAC``.  Overhead fractions use an absolute
  band instead — a 0.001 -> 0.002 "2x regression" on a noise-floor
  number is not a finding.
- **History**: ``--record FILE`` appends one JSONL row per gate run
  (wall clock, schema, headline, verdict, per-key outcomes), building
  the same-machine longitudinal record absolute rates need
  (``BENCH_HISTORY.jsonl`` at the repo root by convention).

Usage:
    python bench.py --smoke | python tools/bench_gate.py - \
        --against BENCH_SMOKE_BASELINE.json --record BENCH_HISTORY.jsonl
    python tools/bench_gate.py BENCH_r04.json --against BENCH_r03.json

Exit codes: 0 pass, 1 regression, 2 usage/schema error.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

# ---------------------------------------------------------------------------
# comparison spec: (dotted path, direction, section)
#
# direction "higher" — candidate must stay within (1 - tol) * baseline;
# direction "lower_abs" — candidate must stay under baseline + tol
# (absolute: these are overhead FRACTIONS living near the noise floor).
# A "*" path segment fans out over the keys present in BOTH dicts.
# ---------------------------------------------------------------------------

SPECS: List[Tuple[str, str, str]] = [
    ("updates_per_sec", "higher", "micro"),
    ("updates_per_sec_peak", "higher", "micro"),
    ("chip_bound_updates_per_sec", "higher", "micro"),
    ("families.*.updates_per_sec", "higher", "families"),
    # ISSUE-13 megabatch capability rows: the flat families' widened-
    # gather fused rate (bench_families MEGABATCH_FAMILIES leg) and the
    # smoke twin — the MLP-family wins this campaign lands would
    # otherwise be unprotected
    ("families.*.updates_per_sec_megabatch", "higher", "families"),
    ("sampler.xla_draws_per_sec", "higher", "sampler"),
    ("sampler.pallas_draws_per_sec", "higher", "sampler"),
    ("act_ab.act_ms_host", "lower_rel", "act"),
    ("actor_pipeline.inline.frames_per_sec", "higher", "actor"),
    ("actor_pipeline.pipelined.frames_per_sec", "higher", "actor"),
    ("actor_pipeline.env_only_frames_per_sec", "higher", "actor"),
    ("e2e_frames_per_sec", "higher", "e2e"),
    ("e2e_paced_updates_per_sec", "higher", "e2e"),
    ("health_overhead.health_overhead_frac", "lower_abs", "overhead"),
    ("perf_overhead.perf_overhead_frac", "lower_abs", "overhead"),
    ("provenance_overhead.provenance_overhead_frac", "lower_abs",
     "overhead"),
    ("metrics_overhead.metrics_overhead_frac", "lower_abs", "overhead"),
    ("flow_overhead.flow_overhead_frac", "lower_abs", "overhead"),
    ("replica_overhead.replica_overhead_frac", "lower_abs", "overhead"),
    ("gateway_ha_overhead.gateway_ha_overhead_frac", "lower_abs",
     "overhead"),
    # ISSUE-18 wire byte economics: deterministic counts (savez layout
    # at fixed geometry), so a regression here is a wire-format change
    # — the compression campaign must move these DOWN, never up
    ("wire.bytes_per_transition", "lower_rel", "wire"),
    ("wire.replica_bytes_per_round", "lower_rel", "wire"),
    ("wire_overhead.wire_overhead_frac", "lower_abs", "overhead"),
    # ISSUE-20 sharded-replay plane: per-shard-count sample latency
    # (loopback, so plane arithmetic — regressions are tree/merge
    # changes, not socket noise) and the mass-refresh+route cost held
    # inside the overhead band
    ("shard.sample_ms_1shard", "lower_rel", "shard"),
    ("shard.sample_ms_2shard", "lower_rel", "shard"),
    ("shard.sample_ms_4shard", "lower_rel", "shard"),
    ("shard_overhead.shard_overhead_frac", "lower_abs", "overhead"),
    ("device_env.host_frames_per_sec", "higher", "device_env"),
    ("device_env.device_frames_per_sec", "higher", "device_env"),
    ("device_env.fused_frames_per_sec", "higher", "device_env"),
    ("device_env.speedup_vs_host", "higher", "device_env"),
    ("anakin.frames_per_sec", "higher", "anakin"),
    ("anakin.updates_per_sec", "higher", "anakin"),
    ("anakin.speedup_vs_device", "higher", "anakin"),
    ("smoke.updates_per_sec", "higher", "smoke"),
    ("smoke.updates_per_sec_megabatch", "higher", "smoke"),
    ("smoke.device_env_frames_per_sec", "higher", "smoke"),
    ("smoke.anakin_frames_per_sec", "higher", "smoke"),
]

# Per-section default tolerance.  Relative for rates (sized to the
# window noise each section's docstring documents), ABSOLUTE for the
# overhead fractions.
DEFAULT_TOL: Dict[str, float] = {
    "micro": 0.15,
    "families": 0.20,
    "sampler": 0.20,
    "act": 0.30,
    "actor": 0.25,
    "e2e": 0.30,
    "overhead": 0.02,   # absolute band on a <2%-by-contract fraction
    # env-fleet rates: XLA dispatch + host scheduling noise on small
    # hosts; the speedup ratio divides out most machine noise but
    # keeps the same band for simplicity
    "device_env": 0.30,
    # closed-loop pair rate + its split-process speedup (ISSUE 12):
    # same dispatch-noise profile as device_env, and the split leg
    # adds spawn-queue scheduling jitter on loaded hosts
    "anakin": 0.30,
    "smoke": 0.40,      # CPU-host scheduling noise is large at small K
    # byte counts are layout-deterministic; the slack only covers savez
    # header drift across numpy versions
    "wire": 0.10,
    # loopback sample latency: pure python/numpy tree walks measured
    # best-of-chunks, but a gate host running the full check.sh chain
    # is LOADED — a genuine regression (an accidental linear scan in
    # the two-level walk) blows past 2x, scheduler contention doesn't
    "shard": 1.00,
}


def _lookup(d: dict, path: str) -> Any:
    cur: Any = d
    for part in path.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur


def _expand(path: str, cand: dict, base: dict) -> List[str]:
    """Expand one '*' segment over keys present in BOTH sides."""
    if "*" not in path:
        return [path]
    head, _, tail = path.partition(".*.")
    c, b = _lookup(cand, head), _lookup(base, head)
    if not isinstance(c, dict) or not isinstance(b, dict):
        return []
    return [f"{head}.{k}.{tail}" for k in sorted(c.keys() & b.keys())]


def compare(candidate: dict, baseline: dict,
            tol: Optional[Dict[str, float]] = None) -> dict:
    """Schema-aware diff.  Returns a report dict with ``checked`` (every
    key compared, with values and verdicts), ``regressions`` (the failed
    subset) and ``improvements`` (informational)."""
    tols = dict(DEFAULT_TOL)
    tols.update(tol or {})
    checked, regressions, improvements = [], [], []
    for spec_path, direction, section in SPECS:
        for path in _expand(spec_path, candidate, baseline):
            c, b = _lookup(candidate, path), _lookup(baseline, path)
            if not isinstance(c, (int, float)) \
                    or not isinstance(b, (int, float)):
                continue  # missing/errored on either side: not comparable
            t = tols.get(section, 0.2)
            if direction == "higher":
                bad = c < b * (1.0 - t)
                better = c > b * (1.0 + t)
            elif direction == "lower_rel":
                bad = c > b * (1.0 + t)
                better = c < b * (1.0 - t)
            else:  # lower_abs
                bad = c > b + t
                better = c < b - t
            row = {"key": path, "candidate": c, "baseline": b,
                   "direction": direction, "tolerance": t,
                   "section": section,
                   "verdict": ("regression" if bad else
                               "improvement" if better else "ok")}
            checked.append(row)
            if bad:
                regressions.append(row)
            elif better:
                improvements.append(row)
    return {"checked": checked, "regressions": regressions,
            "improvements": improvements}


def record_history(path: str, candidate: dict, against: str,
                   report: dict) -> None:
    """One append-only JSONL row per gate run — the same-machine
    longitudinal record.  Append is a single atomic line write, same
    contract as the metrics stream (utils/metrics.py)."""
    row = {
        "wall": time.time(),
        "bench_schema": candidate.get("bench_schema"),
        "metric": candidate.get("metric"),
        "value": candidate.get("value"),
        "device_kind": candidate.get("device_kind"),
        "mode": candidate.get("mode", "full"),
        "against": against,
        "checked": len(report["checked"]),
        "regressions": [r["key"] for r in report["regressions"]],
        "improvements": [r["key"] for r in report["improvements"]],
        "pass": not report["regressions"],
    }
    with open(path, "a") as f:
        f.write(json.dumps(row) + "\n")


def _load(source: str) -> dict:
    """A bench artifact: a JSON file, or '-' for stdin.  bench.py prints
    exactly one JSON line on stdout, but artifacts saved from noisy
    runs may carry stray stderr lines — take the LAST parseable object
    line."""
    text = sys.stdin.read() if source == "-" else open(source).read()
    last_err: Optional[Exception] = None
    try:
        return json.loads(text)
    except ValueError as e:
        last_err = e
    for line in reversed(text.splitlines()):
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            return json.loads(line)
        except ValueError as e:
            last_err = e
    raise ValueError(f"no JSON object found in {source!r}: {last_err}")


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="tools/bench_gate.py",
        description="diff two bench JSONs; exit 1 on regression")
    ap.add_argument("candidate",
                    help="candidate bench JSON (file path, or '-' to "
                         "read bench.py's output from stdin)")
    ap.add_argument("--against", required=True, metavar="BASELINE.json",
                    help="baseline bench JSON to gate against")
    ap.add_argument("--tol", action="append", default=[],
                    metavar="SECTION=FRAC",
                    help="per-section tolerance override (repeatable), "
                         f"sections: {', '.join(sorted(DEFAULT_TOL))}")
    ap.add_argument("--record", type=str, default=None,
                    metavar="HISTORY.jsonl",
                    help="append this gate run to a JSONL history file")
    ap.add_argument("--json", action="store_true",
                    help="print the full machine-readable report")
    ap.add_argument("--allow-schema-drift", action="store_true",
                    help="compare across differing bench_schema values "
                         "(keys may have changed MEANING — only for "
                         "deliberate migrations)")
    args = ap.parse_args(argv)

    tol: Dict[str, float] = {}
    for kv in args.tol:
        k, _, v = kv.partition("=")
        if k not in DEFAULT_TOL:
            ap.error(f"unknown tolerance section {k!r} "
                     f"(know: {', '.join(sorted(DEFAULT_TOL))})")
        try:
            tol[k] = float(v)
        except ValueError:
            ap.error(f"bad tolerance value in {kv!r}")

    try:
        candidate = _load(args.candidate)
        baseline = _load(args.against)
    except (OSError, ValueError) as e:
        print(f"bench_gate: {e}", file=sys.stderr)
        return 2

    cs, bs = candidate.get("bench_schema"), baseline.get("bench_schema")
    if cs != bs and not args.allow_schema_drift:
        print(f"bench_gate: bench_schema mismatch (candidate {cs!r} vs "
              f"baseline {bs!r}) — keys may have changed meaning; "
              f"re-baseline or pass --allow-schema-drift",
              file=sys.stderr)
        return 2

    report = compare(candidate, baseline, tol)
    if args.record:
        record_history(args.record, candidate, args.against, report)
    if args.json:
        print(json.dumps(report, indent=2))
    else:
        if not report["checked"]:
            print("bench_gate: no comparable keys between candidate and "
                  "baseline", file=sys.stderr)
        for row in report["checked"]:
            mark = {"ok": " ok ", "regression": "FAIL",
                    "improvement": " ++ "}[row["verdict"]]
            print(f"[{mark}] {row['key']}: {row['candidate']:g} vs "
                  f"baseline {row['baseline']:g} "
                  f"(tol {row['tolerance']:g}, {row['direction']})")
    if report["regressions"]:
        print(f"bench_gate: {len(report['regressions'])} regression(s) "
              f"out of {len(report['checked'])} checked", file=sys.stderr)
        return 1
    print(f"bench_gate: pass ({len(report['checked'])} checked, "
          f"{len(report['improvements'])} improved)", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
