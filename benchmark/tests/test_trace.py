"""The reduction from a trace to numbers, on the small trace recorded on the
chip (benchmark/testdata/tiny_tpu.xplane.pb, made by testdata/record.py) and
on hand-made events.

The recorded trace, times in ns from its first device op (PR 22):

  three runs of ``jit_tiny_step``; the first ends before the host span
  ``bench/window`` opens (the device clock reads about 1.4 ms behind the
  host's in this trace), so the window [1306455, 11452435] holds two:

  run 2  copy-start 3682740-3682745   copy-done 3682745-3683248
         while 3683250-3684220 around  copy.11 22, fusion.8 291, copy.11 24,
         fusion.8 291, copy.11 23, fusion.8 291      reduce 3684220-3684636
  run 3  copy-start 6730289-6730294   copy-done 6730294-6730798
         while 6730800-6731770 around  copy.11 22, fusion.8 291, copy.11 23,
         fusion.8 291, copy.11 23, fusion.8 291      reduce 6731772-6732187
"""

import os
import types

import pytest

from benchmark.harness import manifest, trace as T

TINY = os.path.join(manifest.BENCH_DIR, "testdata", "tiny_tpu.xplane.pb")


@pytest.fixture(scope="module")
def tiny():
    return T.load(TINY)


def test_the_recorded_trace_is_what_the_reduction_expects(tiny):
    assert [d.plane for d in tiny.devices] == ["/device:TPU:0"]
    dev = tiny.devices[0]
    assert len(dev.ops) == 30 and len(dev.modules) == 3
    assert {m[0] for m in dev.modules} == {"jit_tiny_step"}
    assert {o[0] for o in dev.ops} == {"copy-start", "copy-done", "while",
                                       "copy.11", "fusion.8", "reduce"}
    assert [s[0] for s in tiny.host_spans] == [
        "bench/window"] + ["bench/dispatch", "bench/pause"] * 3


def test_reduction_of_the_recorded_trace_by_hand(tiny):
    s = T.reduce(tiny, step_modules=["jit_tiny_step"])
    assert s.chips == 1
    assert s.window_s == pytest.approx(10_145_980e-9, abs=1e-12)
    # busy: run 2 is 508 + 970 + 416, run 3 is 509 + 970 + 415
    assert s.busy_s == pytest.approx(3_788e-9, abs=1e-12)
    assert s.idle_share == pytest.approx(1 - 3_788 / 10_145_980)
    # self times: the whiles keep 970-942 and 970-941 for themselves
    assert [(n, round(t * 1e9)) for n, t in s.device_ops] == [
        ("fusion.8", 1746), ("copy-done", 1007), ("reduce", 831),
        ("copy.11", 137), ("while", 57), ("copy-start", 10)]
    assert sum(t for _, t in s.device_ops) == pytest.approx(s.busy_s)
    assert s.op_self_s == dict(s.device_ops)    # six ops: all among the top
    # the three long gaps: after run 3, between the runs, before run 2;
    # the host slept (bench/pause) through most of each
    assert [(n, round(t * 1e9)) for n, t in s.idle_gaps[:3]] == [
        ("pause", 4_720_248), ("pause", 3_045_653), ("pause", 2_376_285)]
    assert [round(t * 1e9) for _, t in s.idle_gaps[3:]] == [2, 2, 2]
    assert s.busy_s + sum(t for _, t in s.idle_gaps) == pytest.approx(
        s.window_s)
    assert s.step_module == "jit_tiny_step"
    assert [round(ms * 1e6) for ms in s.step_ms] == [1902, 1905]
    assert [round(ms * 1e6) for ms in s.step_gaps_ms] == [3_045_647]
    assert round((s.step_starts_s[1] - s.step_starts_s[0]) * 1e9) \
        == 3_047_549                                # 6730283 - 3682734
    assert s.collective_exposed_s == 0.0


def test_every_trace_reader_reads_the_recorded_trace(tiny):
    """The readers against the reduction's fields, with the chip's peaks and
    a configuration's family count, as a traced run on the chip puts them
    together (the CPU rehearsals have no device trace to hand them)."""
    import json

    from benchmark.harness import cell, peaks

    with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as f:
        listed = [m["name"] for m in json.load(f)["per_layer"]
                  if m["source"] == "device_trace"]
    s = T.reduce(tiny, step_modules=["jit_tiny_step"])
    ctx = cell.Ctx(
        cell=manifest.load_cell("apex_pong.learner_only"),
        result=types.SimpleNamespace(updates_per_dispatch=3, notes={
            "state_shape": [4, 84, 84], "num_actions": 6}),
        phases={}, device_count=1, peaks=peaks.peaks_of("TPU v5 lite"),
        trace=s)
    got = {name: manifest.load_module("layer_metrics", name).read(ctx)
           for name in listed}
    assert got.pop("collective_exposed_share") is None      # one chip
    assert got["device_idle_share"] == pytest.approx(
        100 * (1 - 3_788 / 10_145_980))
    assert got["step_device_ms"] == pytest.approx((1902 + 1905) / 2e6 / 3)
    assert got["dispatch_gap_ms"] == pytest.approx(3.045647)
    # copy.11 137 + copy-done 1007 + copy-start 10 ns of self time
    assert got["copy_op_share"] == pytest.approx(100 * 1154 / 10_145_980)
    # 3 updates per 3,047,549 ns x 9.57 GFLOP over 197 TFLOP/s
    assert got["mfu"] == pytest.approx(
        100 * 3 / 3_047_549e-9 * 128 * 4 * 18_692_096 / 197e12)
    assert set(got) == {"device_idle_share", "step_device_ms", "mfu",
                        "copy_op_share", "dispatch_gap_ms"}


def test_names_are_cut_to_the_op_and_the_module():
    assert T.op_name("%fusion.12 = f32[8]{0:T(128)} fusion(f32[8] %p)") \
        == "fusion.12"
    assert T.op_name("copy.3") == "copy.3"
    assert T.module_name("jit_multi(6991644180013392575)") == "jit_multi"


def test_interval_arithmetic():
    merged = T.union([(5, 9), (0, 3), (2, 4), (9, 10)])
    assert merged == [(0, 4), (5, 10)]
    assert T.total(merged) == 9
    assert T.clip(merged, 3, 7) == [(3, 4), (5, 7)]
    assert T.gaps(T.clip(merged, 1, 12), 1, 12) == [(4, 5), (10, 12)]
    assert T.gaps([], 0, 4) == [(0, 4)]
    assert T.median([4, 1, 3]) == 3 and T.median([1, 2, 3, 4]) == 2.5
    assert T.percentile(list(range(1, 21)), 0.95) == 19


def test_self_time_with_two_levels_of_nesting():
    events = [("outer", 0, 100), ("inner", 10, 60), ("leaf", 20, 30),
              ("leaf", 40, 45), ("tail", 70, 90), ("alone", 120, 130)]
    assert T.self_times(events) == {"outer": 30, "inner": 35, "leaf": 15,
                                    "tail": 20, "alone": 10}


def two_chip_trace():
    """Chip 0: a step of 100 with an all-reduce of 30 in it, then idle 50,
    then a step of 100.  Chip 1: the same, but its all-reduce waits 50."""
    def chip(n, wait):
        ops = [("fusion.1", 0, 100 - wait), ("all-reduce.2", 100 - wait, 100),
               ("fusion.1", 150, 250 - wait),
               ("all-reduce.2", 250 - wait, 250)]
        return T.DeviceTrace(f"/device:TPU:{n}", ops,
                             [("jit_multi", 0, 100), ("jit_multi", 150, 250),
                              ("jit_other", 100, 101)])
    spans = [("bench/window", 0, 300), ("bench/drain", 100, 140),
             ("bench/dispatch", 140, 150), ("bench/wait_slot", 250, 300)]
    return T.Trace([chip(0, 30), chip(1, 50)], spans)


def test_chips_are_averaged_and_collectives_exposed():
    s = T.reduce(two_chip_trace(), step_modules=["jit_multi"])
    assert s.chips == 2 and s.window_s == pytest.approx(300e-9)
    assert s.busy_s == pytest.approx(200e-9)           # both chips 200 of 300
    assert s.collective_exposed_s == pytest.approx((60 + 100) / 2 * 1e-9)
    assert dict(s.device_ops) == pytest.approx(
        {"fusion.1": 120e-9, "all-reduce.2": 80e-9})
    assert s.idle_gaps == [("drain", pytest.approx(50e-9)),
                           ("wait_slot", pytest.approx(50e-9))]
    assert s.step_ms == pytest.approx([100e-6, 100e-6])
    assert s.step_gaps_ms == pytest.approx([50e-6])
    assert s.step_starts_s == pytest.approx([0.0, 150e-9])


def test_no_module_stands_in_for_a_step_module_that_did_not_run():
    """After a rename in the program the step readers report nothing; they
    do not read whichever program is heaviest."""
    s = T.reduce(two_chip_trace(), step_modules=["jit_absent"])
    assert s.step_module is None
    assert s.step_ms == s.step_gaps_ms == s.step_starts_s == []
    ctx = types.SimpleNamespace(
        trace=s, peaks=object(), device_count=2,
        result=types.SimpleNamespace(updates_per_dispatch=32))
    for name in ("step_device_ms", "dispatch_gap_ms", "mfu"):
        assert manifest.load_module("layer_metrics", name).read(ctx) is None


def test_copy_op_share_sums_every_copy_op_not_the_heaviest_ten():
    """Twelve ops heavier than the two copies: the share still holds them."""
    ops, at = [], 0
    for i in range(12):
        ops.append((f"fusion.{i}", at, at + 50)); at += 50
    ops += [("copy.7", at, at + 20), ("copy-done", at + 20, at + 30)]
    trace = T.Trace([T.DeviceTrace("/device:TPU:0", ops, [])],
                    [("bench/window", 0, 1000)])
    s = T.reduce(trace)
    assert len(s.device_ops) == 10
    assert not any(n.startswith("copy") for n, _ in s.device_ops)
    reader = manifest.load_module("layer_metrics", "copy_op_share")
    assert reader.read(types.SimpleNamespace(trace=s)) == pytest.approx(3.0)


def test_a_trace_without_device_ops_is_refused():
    with pytest.raises(ValueError, match="no device op"):
        T.reduce(T.Trace([T.DeviceTrace("/device:TPU:0", [], [])], []))
