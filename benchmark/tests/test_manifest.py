"""BENCHMARK.json against the contract, and against the benchmark's files."""

import json
import os
import re

import pytest

from benchmark.harness import manifest

with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as f:
    B = json.load(f)
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_keys_and_limits():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["paths"] == ["benchmark"]
    assert len(B["command"]) <= 32 and B["command"][-1].startswith(
        "benchmark/")
    assert isinstance(B["run_seconds"], int) and 1 <= B["run_seconds"] <= 51
    assert 2 <= len(B["workloads"]) <= 24 and 1 <= len(B["configs"]) <= 24
    assert 1 <= len(B["end_to_end"]) <= 16 and 1 <= len(B["per_layer"]) <= 128
    assert os.path.getsize(os.path.join(manifest.ROOT,
                                        "BENCHMARK.json")) <= 64 * 1024
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in B[k]]
    assert len(names) == len(set(names)) and all(map(NAME.match, names))
    assert all(len(x["why"]) <= 200 for x in B["configs"] + B["workloads"])


def test_a_full_check_fits_with_24_cells():
    runs = 2 + 14 * 24
    assert runs * (B["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_cells_configs_and_chip_shares():
    configs = {c["name"]: c for c in B["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in B["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert {w["config"] for w in B["workloads"]} == set(configs)
    files = [c["file"] for c in B["configs"]]
    assert len(files) == len(set(files))
    for c in B["configs"]:
        assert c["file"].startswith("benchmark/")
        body = json.load(open(os.path.join(manifest.ROOT, c["file"])))
        assert body["reduced"] == c["reduced"]
        assert body["chips"] in (1, 4) and "reference" in body
        assert not any(k.endswith(("_dim", "_rank")) for k in c["reduced"])
    four = [w for w in B["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in B["workloads"])
    assert len(four) <= max(1, len(B["workloads"]) // 4)
    for w in B["workloads"]:
        assert configs[w["config"]] and manifest.load_cell(
            w["name"]).config["chips"] == w["chips"]


def test_metrics_of_every_cell():
    e2e = {m["name"]: m for m in B["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.1
    for m in B["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1 and m["better"] in ("higher",
                                                              "lower")
    for m in B["per_layer"]:
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert "bound" not in m
    cells = {w["name"] for w in B["workloads"]}
    for m in B["end_to_end"] + B["per_layer"]:
        assert set(m.get("workloads", ())) <= cells
    for w in B["workloads"]:
        cell = manifest.load_cell(w["name"])
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        moved = [m for m in cell.per_layer if m["moves"] in names]
        assert moved, w["name"]


@pytest.mark.parametrize("metric", B["per_layer"], ids=lambda m: m["name"])
def test_each_per_layer_metric_is_a_file_that_agrees(metric):
    reader = manifest.load_module("layer_metrics", metric["name"])
    assert callable(reader.read)
    for key in ("layer", "unit", "better", "source", "moves"):
        assert reader.METRIC[key] == metric[key], (metric["name"], key)


@pytest.mark.parametrize("workload", B["workloads"], ids=lambda w: w["name"])
def test_each_cell_resolves_to_its_files(workload):
    cell = manifest.load_cell(workload["name"])
    runner = manifest.load_module("runners", cell.traffic["runner"])
    assert callable(runner.run)
    reference = manifest.load_module("reference", cell.config["reference"])
    assert callable(reference.update) and callable(reference.batch_of)
    family = manifest.load_module("families", cell.config["family"])
    assert all(callable(getattr(family, name)) for name in (
        "update_flops", "seed_chunk", "update_priorities", "build_step",
        "agrees"))
    assert isinstance(cell.config["fill_chunk"], int)
    assert set(cell.config["tolerance"]) >= {"loss_rel", "td_p90_over_mean",
                                             "grad_cosine", "why"}


def test_unknown_names_are_refused():
    with pytest.raises(manifest.ManifestError, match="unknown workload"):
        manifest.load_cell("no.such_cell")
    with pytest.raises(manifest.ManifestError):
        manifest.load_module("runners", "no_such_runner")
    with pytest.raises(manifest.ManifestError, match="families/ddpg.py"):
        manifest.load_module("families", "ddpg")
    with pytest.raises(manifest.ManifestError, match="bad"):
        manifest.load_module("runners", "../run")
