"""Resolve a cell name to its files.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``: a configuration
under a traffic mix.  Everything that belongs to one configuration, one
traffic mix, one runner or one per-layer metric lives in a file of its own,
found here BY NAME, so a later PR adds a cell by adding files and entries and
edits nothing that exists:

    configs/<config>.json        sizes, source, overrides (the `file` of the entry)
    traffic/<traffic>.json       runner name + its parameters
    runners/<runner>.py          one way of driving the program: run(cell, args)
    layer_metrics/<metric>.py    METRIC declaration + read(ctx)
    families/<family>.py         what depends on the model family: seeded rows,
                                 step program, check, FLOPs count (families/__init__.py)
    reference/<reference>.py     the configuration's plain float32 reference
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
import re
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)   # the checkout: holds BENCHMARK.json


class ManifestError(ValueError):
    """BENCHMARK.json or a file it names is missing or malformed."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]        # configs/<config>.json
    traffic: Dict[str, Any]       # traffic/<traffic>.json
    end_to_end: List[Dict[str, Any]]   # this cell's entries of BENCHMARK.json
    per_layer: List[Dict[str, Any]]


def _load_json(path: str) -> Dict[str, Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise ManifestError(f"no such file: {path}") from None
    except json.JSONDecodeError as e:
        raise ManifestError(f"{path}: {e}") from None


def _for_cell(metrics: List[Dict[str, Any]], cell: str) -> List[Dict[str, Any]]:
    """A metric without ``workloads`` exists in every cell."""
    return [m for m in metrics
            if "workloads" not in m or cell in m["workloads"]]


def load_cell(workload: str) -> Cell:
    manifest = _load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next((w for w in manifest["workloads"]
                  if w["name"] == workload), None)
    if entry is None:
        names = ", ".join(w["name"] for w in manifest["workloads"])
        raise ManifestError(f"unknown workload {workload!r}; known: {names}")
    cfg_entry = next((c for c in manifest["configs"]
                      if c["name"] == entry["config"]), None)
    if cfg_entry is None:
        raise ManifestError(
            f"workload {workload!r} names config {entry['config']!r}, "
            f"which BENCHMARK.json does not list")
    return Cell(
        name=workload,
        chips=int(entry["chips"]),
        config=_load_json(os.path.join(ROOT, cfg_entry["file"])),
        traffic=_load_json(os.path.join(
            BENCH_DIR, "traffic", f"{entry['traffic']}.json")),
        end_to_end=_for_cell(manifest["end_to_end"], workload),
        per_layer=_for_cell(manifest["per_layer"], workload),
    )


def load_module(kind: str, name: str):
    """Import ``<kind>/<name>.py`` of this benchmark (kind is one of
    runners, layer_metrics, families, reference)."""
    if not re.fullmatch(r"[A-Za-z0-9_]+", name):
        raise ManifestError(f"bad {kind} name {name!r}")
    try:
        return importlib.import_module(f"..{kind}.{name}", __package__)
    except ModuleNotFoundError as e:
        if e.name and e.name.endswith(f"{kind}.{name}"):
            raise ManifestError(
                f"no file {kind}/{name}.py in {BENCH_DIR}") from None
        raise
