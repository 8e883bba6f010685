"""Every cell end to end at a tiny size on the CPU backend: the same runners,
harness, readers and check as on the chip.  (The Pallas sampler cannot be
steered into interpret mode from outside the program -- the ring picks it by
platform -- so the rehearsals sample through the XLA path; the compiled
kernel is checked on the chip by tools/kernel_check.py and by every real
run's ``correct``.)"""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness.trace import reduce as T_reduce
from conftest import REPO, TINY_CELLS, rehearse

LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def last_line(proc) -> dict:
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def detail_of(proc) -> dict:
    row = next(l for l in proc.stderr.splitlines()
               if l.startswith("[benchmark] detail: "))
    return json.loads(row.split(": ", 1)[1])


@pytest.mark.parametrize("trace", (0, 1))
@pytest.mark.parametrize("cell", TINY_CELLS, ids=lambda c: c[0])
def test_cell_rehearsal(tiny_root, cell, trace):
    name, _config, _traffic, chips, _like = cell
    proc = rehearse(tiny_root, name, chips=chips, trace=trace, seconds=2.0)
    line = last_line(proc)
    assert set(line) == LINE_KEYS               # no breakdown off the chip
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"] == {"platform": "cpu", "kind": "cpu",
                              "count": chips, "memory_peak_bytes": 0}
    # a CPU result carries no number under a device metric's name
    assert line["metrics"] == {}
    detail = detail_of(proc)
    assert detail["compiles_in_window"] == 0 and detail["check"]["ok"]
    assert "end_to_end_while_traced" not in detail
    produced = set(detail["rehearsal_metric_names"])
    with open(os.path.join(tiny_root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    if trace:
        listed = {m["name"] for m in manifest["per_layer"]
                  if name in m.get("workloads", (name,))}
        assert produced <= listed
        # what needs no device: the set-up phases and the compiler's account
        assert {"setup_backend_s", "setup_build_s", "setup_fill_s",
                "setup_warm_s", "setup_compile_s",
                "step_scratch_gb"} <= produced
    else:
        expected = {m["name"] for m in manifest["end_to_end"]
                    if name in m.get("workloads", (name,))}
        assert produced == expected == {"updates_per_s", "hbm_peak_gb",
                                        "setup_s"}


GB = 10 ** 9


@pytest.mark.parametrize("in_use, peak, scratch, want", [
    # one chip, as apex_pong.learner_only read on the chip (PR 22): the fill
    # peaked at 11.355 GB, but 5.692 allocated + 9.015 of step scratch is more
    ([5.692], [11.355], 9.015, 14.707),
    # dp4: _alloc's whole arrays on device 0 outweigh any chip's step
    ([2.872, 2.871, 2.871, 2.871], [12.748, 4.486, 4.486, 4.486], 4.510,
     12.748),
    # no scratch known (the compiler gave no analysis): the allocator's peak
    ([5.692], [11.355], 0.0, 11.355),
], ids=["step-binds", "alloc-binds", "no-scratch"])
def test_hbm_peak_is_the_larger_of_allocator_peak_and_step_need(
        in_use, peak, scratch, want):
    from benchmark.harness.cell import hbm_peak_bytes

    at_close = {"in_use": [int(x * GB) for x in in_use],
                "peak": [int(x * GB) for x in peak]}
    assert hbm_peak_bytes(at_close, int(scratch * GB)) == pytest.approx(
        want * GB)


@pytest.mark.parametrize("traced", (False, True))
def test_the_line_of_a_run_on_a_chip(monkeypatch, tmp_path, traced):
    """What only a chip run puts together (values under metric names,
    ``busy_s``, ``window_s``, ``breakdown``), on a stand-in for the chip and
    for the runner: the recorded TPU trace and readings as the chip gave
    them (PR 22)."""
    import shutil
    import types

    from benchmark.harness import cell, manifest

    trace_dir = tmp_path / "trace"
    (trace_dir / "plugins" / "profile" / "t").mkdir(parents=True)
    shutil.copy(os.path.join(manifest.BENCH_DIR, "testdata",
                             "tiny_tpu.xplane.pb"),
                trace_dir / "plugins" / "profile" / "t" / "x.xplane.pb")
    chip = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    at_close = {"in_use": [5_692_000_000], "peak": [11_355_126_272]}

    def run(c, args):
        args.phases.lap("build")
        return cell.RunResult(
            attempted=3136, failed=0, setup_s=20.1, compiles_in_window=0,
            end_to_end={"updates_per_s": 304.03, "hbm_peak_gb":
                        cell.hbm_peak_bytes(at_close, 9_014_941_696) / 1e9},
            check={"ok": True}, memory_peak_bytes=max(at_close["peak"]),
            updates_per_dispatch=3, trace_dir=str(trace_dir),
            notes={"state_shape": [4, 84, 84], "num_actions": 6,
                   "setup_compile_s": 0.7,
                   "step_memory": {"scratch_bytes": 9_014_941_696}})

    load = manifest.load_module
    monkeypatch.setattr(cell, "_devices", lambda c, need: [chip])
    monkeypatch.setattr(
        "pytorch_distributed_tpu.utils.helpers.enable_compile_cache",
        lambda: False)
    monkeypatch.setattr(
        manifest, "load_module", lambda kind, name: types.SimpleNamespace(
            run=run) if kind == "runners" else load(kind, name))
    # the recorded trace's step program is not one the traffic file names
    monkeypatch.setattr(
        cell.trace_mod, "reduce", lambda t, step_modules: T_reduce(
            t, step_modules=["jit_tiny_step"]))
    line = cell.run("apex_pong.learner_only", 1, 10.0, traced, 0.0)

    assert line["correct"] is True and line["attempted"] == 3136
    device = line["device"]
    assert (device["platform"], device["kind"], device["count"],
            device["memory_peak_bytes"]) == ("tpu", "TPU v5 lite", 1,
                                             11_355_126_272)
    units = {name: m["unit"] for name, m in line["metrics"].items()}
    if not traced:
        assert set(line) == LINE_KEYS and set(device) == {
            "platform", "kind", "count", "memory_peak_bytes"}
        assert units == {"updates_per_s": "updates/s", "hbm_peak_gb": "GB",
                         "setup_s": "s"}
        assert line["metrics"]["hbm_peak_gb"]["value"] == pytest.approx(
            14.706941696)
        return
    assert set(line) == LINE_KEYS | {"breakdown"}
    assert device["busy_s"] == pytest.approx(3_788e-9)
    assert device["window_s"] == pytest.approx(10_145_980e-9)
    assert line["breakdown"]["device_ops"][0] == [
        "fusion.8", pytest.approx(1746e-9)]
    assert line["breakdown"]["idle_gaps"][0] == [
        "pause", pytest.approx(4_720_248e-9)]
    assert len(line["breakdown"]["device_ops"]) <= 10
    # every per-layer metric of the cell but the two with nothing to read
    # here: one chip has no collective, the stand-in laps no fill or warm-up
    with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"] for m in json.load(f)["per_layer"]}
    assert set(units) == listed - {"collective_exposed_share",
                                   "setup_fill_s", "setup_warm_s"}
    assert line["metrics"]["step_scratch_gb"]["value"] == 9.014941696
    assert "updates_per_s" not in units


def test_the_real_entry_refuses_to_run_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", "apex_pong.learner_only", "--seed", "1", "--seconds",
         "1", "--trace", "0"], cwd=REPO, env=env, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""            # no result line
    assert "no accelerator" in proc.stderr


def test_a_cell_on_the_wrong_number_of_chips_is_refused(tiny_root):
    proc = rehearse(tiny_root, "tiny_apex_dp4.tiny_learner_only", chips=2)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "defined on 4 chip(s)" in proc.stderr
