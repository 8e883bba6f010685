"""harness/phases.py: the xplane decoder and the per-phase reduction, on the
two traces recorded on the chip and on hand-made planes.

``tiny_tpu_scoped.xplane.pb`` (testdata/record_scoped.py, PR 24): three runs
of ``jit_tiny_scoped_step``; the host span ``bench/window`` opens after the
first, so the window holds runs 2 and 3.  Per run, ns from its module
event's start (run 2 / run 3), with the ``tf_op`` path of each op:

  no tf_op      custom-call.1-3 6.172 / 6.250, copy-start + copy-start.2
                12.422 / 12.422, copy-done 517.422 / 522.500, copy-done.2
                156.250 / 146.250, custom-call 1.250 / 1.172, copy-start.1
                6.172 / 6.250, copy-done.1 2.656 / 39.922
  no tf_op      while.5 1212.500 / 1212.578 around the forward body
                (children 1167.656 / 1168.906: self 44.844 / 43.672),
                while.6 2206.172 / 2206.172 around the backward body
                (children 2183.360 / 2183.282: self 22.812 / 22.890)
  jit(..)/jvp(train.online)/while...      copy.30 x3 70.000 / 71.328,
                bitcast_dynamic-update-slice_fusion.5 x3 87.500 / 87.422,
                broadcast_multiply_fusion.2 x3 867.500 / 866.328,
                bitcast_dynamic-update-slice_fusion.6 x3 142.656 / 143.828
  jit(..)/jvp(train.online)/reduce_sum    fusion.9 415.078 / 415.000
  jit(..)/transpose(jvp(train.online))/while...   copy.31 x3 71.250 /
                72.344, fusion.23 x3 1206.016 / 1205.938, fusion.24 x3
                906.094 / 905.000
  jit(..)/train.optimizer/sub             multiply_subtract_fusion
                466.250 / 463.594
  x:  [data formatting]                   copy (the unscoped transpose)
                600.000 / 601.250
"""

import os
import shutil
import types

import pytest

from benchmark.harness import manifest, phases as P, trace as T

TESTDATA = os.path.join(manifest.BENCH_DIR, "testdata")
SCOPED = os.path.join(TESTDATA, "tiny_tpu_scoped.xplane.pb")
UNSCOPED = os.path.join(TESTDATA, "tiny_tpu.xplane.pb")
READERS = ("draw", "gather", "target", "online", "optimizer", "writeback",
           "relayout", "unnamed")
# per update = (run 2 + run 3) / 2 updates, in ns
ONLINE_NS = (70.000 + 87.500 + 867.500 + 142.656 + 415.078
             + 71.250 + 1206.016 + 906.094
             + 71.328 + 87.422 + 866.328 + 143.828 + 415.000
             + 72.344 + 1205.938 + 905.000) / 2
OPTIMIZER_NS = (466.250 + 463.594) / 2
RELAYOUT_NS = (600.000 + 601.250) / 2
UNNAMED_NS = (6.172 + 12.422 + 517.422 + 156.250 + 1.250 + 6.172 + 2.656
              + 44.844 + 22.812
              + 6.250 + 12.422 + 522.500 + 146.250 + 1.172 + 6.250 + 39.922
              + 43.672 + 22.890) / 2


@pytest.mark.parametrize("path, step", [(SCOPED, "jit_tiny_scoped_step"),
                                        (UNSCOPED, "jit_tiny_step")],
                         ids=["scoped", "unscoped"])
def test_the_decoder_reads_what_jax_reads(path, step):
    """Event for event against ``jax.profiler.ProfileData`` (through
    trace.load), which hands out names and times but no metadata stats."""
    devices, window = P.load(path)
    tr = T.load(path)
    assert [d.name for d in devices] == [d.plane for d in tr.devices]
    dev, ref = devices[0], tr.devices[0]
    assert [T.op_name(dev.meta[m].name) for m, _, _ in dev.ops] \
        == [name for name, _, _ in ref.ops]
    # ProfileData cuts the picoseconds off a start and off a duration
    for (_, s, e), (_, rs, re_) in zip(dev.ops, ref.ops):
        assert abs(s - rs) < 1.0 and abs(e - re_) < 2.0
    assert {T.module_name(dev.meta[m].name) for m, _, _ in dev.modules} \
        == {step}
    assert window == pytest.approx(T._window(tr))
    # the stats ProfileData does not hand out
    program = int(dev.meta[dev.modules[0][0]].name.split("(")[1][:-1])
    ops = [dev.meta[m] for m, _, _ in dev.ops]
    assert {m.program_id for m in ops} == {program}
    assert all(m.category for m in ops)


def test_the_scoped_trace_by_hand():
    devices, window = P.load(SCOPED)
    got = P.per_update_ms(devices, window, ["jit_tiny_scoped_step"], 1)
    assert got == pytest.approx({
        "online": ONLINE_NS / 1e6, "optimizer": OPTIMIZER_NS / 1e6,
        "relayout": RELAYOUT_NS / 1e6, "unnamed": UNNAMED_NS / 1e6},
        abs=1e-9)
    # forward and backward of the scope are one phase, the layout copies
    # inside the scope's loops are the scope's, and the phases add up to
    # the busy time of the two whole steps
    s = T.reduce(T.load(SCOPED), step_modules=["jit_tiny_scoped_step"])
    assert len(s.step_ms) == 2
    assert 2 * sum(got.values()) == pytest.approx(
        sum(s.op_self_s.values()) * 1e3, rel=1e-3)
    # K updates per dispatch divide it
    assert P.per_update_ms(devices, window, ["jit_tiny_scoped_step"], 4) \
        == pytest.approx({k: v / 4 for k, v in got.items()})
    # no module of that name ran: nothing to read, no other stands in
    assert P.per_update_ms(devices, window, ["jit_multi"], 1) == {}


def test_a_trace_without_scopes_reads_nothing_never_zero():
    """The parent of PR 24, and the first recorded trace: ops carry
    ``tf_op`` paths (``jit(tiny_step)/while:``) but none stands under a
    scope of the vocabulary, so no reader reports, ``unnamed`` included."""
    devices, window = P.load(UNSCOPED)
    assert any(m.tf_op for m in devices[0].meta.values())
    assert P.per_update_ms(devices, window, ["jit_tiny_step"], 3) == {}


@pytest.mark.parametrize("tf_op, category, want", [
    ("jit(multi)/while/body/closed_call/replay.draw/cumsum:", "x", "draw"),
    ("jit(multi)/while/body/closed_call/train.online/jvp(DqnCnnModel)/"
     "Conv_0/conv_general_dilated:", "convolution fusion", "online"),
    ("jit(multi)/train.online/transpose(jvp(DqnCnnModel))/Conv_0/conv:",
     "convolution fusion", "online"),
    ("jit(one)/transpose(jvp(train.online))/mul:", "x", "online"),
    # the innermost name wins: the target pass inside the online gradient
    ("jit(multi)/train.online/jvp(train.target)/DqnCnnModel/Dense_1/dot:",
     "x", "target"),
    ("jit(multi)/while/body/closed_call/train.target/unroll/while/body/"
     "closed_call/train.target/DrqnCnnModel/Conv_1/conv:", "x", "target"),
    ("jit(multi)/replay.gather/vmap(replay.draw)/gather:", "x", "draw"),
    ("jit(multi)/replay.writeback/scatter:", "x", "writeback"),
    ("jit(multi)/train.optimizer/jit(_where)/select_n:", "x", "optimizer"),
    # a layout copy inherits the path of the op it serves
    ("jit(multi)/while/body/closed_call/replay.gather/gather:",
     "data formatting", "gather"),
    # under no phase: the compiler's category decides
    ("jit(multi)/while:", "data formatting", "relayout"),
    (None, "data formatting", "relayout"),
    (None, "while", "unnamed"),
    ("jit(multi)/while/body/dynamic_slice:", "loop fusion", "unnamed"),
    # a name is a whole path component
    ("jit(f)/not.train.online/x:", "x", "unnamed"),
    ("jit(f)/train.onlineish/x:", "x", "unnamed"),
    # the feed is the program's word too, but no metric of the step
    ("jit(per_feed)/replay.feed/scatter:", "x", "unnamed"),
])
def test_phase_of_a_path(tf_op, category, want):
    assert P.phase_of(tf_op, category) == want


def test_the_vocabulary_is_the_programs():
    from pytorch_distributed_tpu.utils import profiling

    assert set(P.PHASES.values()) == set(profiling.DEVICE_PHASES) - {
        profiling.PHASE_FEED}
    assert set(READERS) == set(P.PHASES) | {P.RELAYOUT, P.UNNAMED}


def planes(chips=2, stop_artefact=True):
    """Two chips, two whole steps of 100 ns each (K = 2): per step
    online 40, an all-reduce under online's backward 20 (chip 1 waits 10
    longer inside a 110 ns step), optimizer 30, a copy under no scope 10;
    then the nanosecond step event the profiler's stop leaves behind."""
    meta = {
        1: P.OpMeta("%fusion.1 = f32[] fusion()",
                    "jit(multi)/train.online/jvp(M)/dot:", "convolution"),
        2: P.OpMeta("%all-reduce.2 = f32[] all-reduce()",
                    "jit(multi)/train.online/transpose(jvp(M))/dot:",
                    "all-reduce"),
        3: P.OpMeta("%fusion.3 = f32[] fusion()",
                    "jit(multi)/train.optimizer/add:", "loop fusion"),
        4: P.OpMeta("%copy.4 = f32[] copy()", None, "data formatting"),
        9: P.OpMeta("jit_multi(77)"), 8: P.OpMeta("jit_feed(78)"),
    }
    out = []
    for chip in range(chips):
        wait = 10.0 * chip
        ops, modules = [], []
        for t0 in (0.0, 200.0):
            ops += [(4, t0, t0 + 10), (1, t0 + 10, t0 + 50),
                    (2, t0 + 50, t0 + 70 + wait),
                    (3, t0 + 70 + wait, t0 + 100 + wait)]
            modules.append((9, t0, t0 + 100 + wait))
        ops.append((3, 150.0, 160.0))          # the feed program's op
        modules.append((8, 150.0, 160.0))
        if stop_artefact:
            modules.append((9, 390.0, 390.001))
        out.append(P.DevicePlane(f"/device:TPU:{chip}", ops, modules, meta))
    return out


@pytest.mark.parametrize("stop_artefact", (True, False))
def test_chips_are_averaged_and_the_stop_artefact_is_no_step(stop_artefact):
    got = P.per_update_ms(planes(stop_artefact=stop_artefact), (0.0, 400.0),
                          ["jit_multi"], 2)
    ns = {k: v * 1e6 for k, v in got.items()}
    # per update = per step / 2; the all-reduce is 20 on chip 0, 30 on chip 1
    assert ns == pytest.approx({"online": (40 + 25) / 2, "optimizer": 15.0,
                                "relayout": 5.0})
    # a step cut by the window's edge is no whole step
    one = P.per_update_ms(planes(), (0.0, 250.0), ["jit_multi"], 2)
    assert {k: v * 1e6 for k, v in one.items()} == pytest.approx(ns)


def test_the_wire_format_as_far_as_xplane_uses_it():
    # field 1 varint 300, field 2 bytes "ab", field 3 fixed64, field 4 fixed32
    buf = memoryview(bytes([0x08, 0xAC, 0x02, 0x12, 0x02, 0x61, 0x62,
                            0x19, 1, 0, 0, 0, 0, 0, 0, 0,
                            0x25, 2, 0, 0, 0]))
    assert [(n, w, bytes(v) if w == 2 else v) for n, w, v in P.fields(buf)] \
        == [(1, 0, 300), (2, 2, b"ab"), (3, 1, 1), (4, 5, 2)]
    with pytest.raises(ValueError, match="wire type"):
        list(P.fields(memoryview(bytes([0x0B]))))      # a group: not used
    # XStats: a string, a reference to a string, an unsigned integer
    assert P._stat(memoryview(bytes([0x08, 0x07, 0x2A, 0x02, 0x61, 0x62]))) \
        == (7, "ab")
    assert P._stat(memoryview(bytes([0x08, 0x07, 0x38, 0x09]))) \
        == (7, ("ref", 9))
    assert P._stat(memoryview(bytes([0x08, 0x07, 0x18, 0xAC, 0x02]))) \
        == (7, 300)


def ctx_for(trace_dir, traced=True, K=1, step="jit_tiny_scoped_step"):
    cell = types.SimpleNamespace(traffic={"step_modules": [step]})
    return types.SimpleNamespace(
        cell=cell, trace=object() if traced else None,
        result=types.SimpleNamespace(trace_dir=trace_dir,
                                     updates_per_dispatch=K))


def put(tmp_path, source):
    folder = tmp_path / "trace" / "plugins" / "profile" / "t"
    folder.mkdir(parents=True)
    shutil.copy(source, folder / "x.xplane.pb")
    return str(tmp_path / "trace")


@pytest.mark.parametrize("reader", READERS)
def test_each_reader_on_both_traces(tmp_path, reader):
    read = manifest.load_module("layer_metrics", f"phase_{reader}_ms").read
    want = {"online": ONLINE_NS, "optimizer": OPTIMIZER_NS,
            "relayout": RELAYOUT_NS, "unnamed": UNNAMED_NS}.get(reader)
    got = read(ctx_for(put(tmp_path / "scoped", SCOPED)))
    if want is None:        # a phase the program does not contain
        assert got is None
    else:
        assert got == pytest.approx(want / 1e6, abs=1e-9)
    # nothing to read: a program without scopes, an untraced or CPU run,
    # a run that left no trace
    assert read(ctx_for(put(tmp_path / "bare", UNSCOPED),
                        step="jit_tiny_step")) is None
    assert read(ctx_for(put(tmp_path / "cpu", SCOPED), traced=False)) is None
    assert read(ctx_for(None)) is None
    assert read(ctx_for(str(tmp_path / "nowhere"))) is None


def test_the_line_of_a_traced_run_holds_the_phases(monkeypatch, tmp_path):
    """As test_cells.py puts a chip run's line together on a stand-in, with
    the scoped trace: the phases the step program contains are in the line
    beside the metrics the benchmark had, the others are left out."""
    from benchmark.harness import cell

    trace_dir = put(tmp_path, SCOPED)
    chip = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")

    def run(c, args):
        args.phases.lap("build")
        return cell.RunResult(
            attempted=64, failed=0, setup_s=20.0, compiles_in_window=0,
            end_to_end={"updates_per_s": 300.0, "hbm_peak_gb": 14.7},
            check={"ok": True}, memory_peak_bytes=1, updates_per_dispatch=1,
            trace_dir=trace_dir,
            notes={"state_shape": [4, 84, 84], "num_actions": 6,
                   "setup_compile_s": 0.7,
                   "step_memory": {"scratch_bytes": 9_000_000_000}})

    load = manifest.load_module
    monkeypatch.setattr(cell, "_devices", lambda c, need: [chip])
    monkeypatch.setattr(
        "pytorch_distributed_tpu.utils.helpers.enable_compile_cache",
        lambda: False)
    monkeypatch.setattr(
        manifest, "load_module", lambda kind, name: types.SimpleNamespace(
            run=run) if kind == "runners" else load(kind, name))
    real = manifest.load_cell

    def load_cell(name):        # the recorded step program's module name
        c = real(name)
        return manifest.Cell(**{**c.__dict__, "traffic": dict(
            c.traffic, step_modules=["jit_tiny_scoped_step"])})

    monkeypatch.setattr(manifest, "load_cell", load_cell)
    line = cell.run("apex_pong.learner_only", 1, 10.0, True, 0.0)
    values = {k: m["value"] for k, m in line["metrics"].items()}
    assert {k: values[k] for k in values if k.startswith("phase_")} \
        == pytest.approx({
            "phase_online_ms": ONLINE_NS / 1e6,
            "phase_optimizer_ms": OPTIMIZER_NS / 1e6,
            "phase_relayout_ms": RELAYOUT_NS / 1e6,
            "phase_unnamed_ms": UNNAMED_NS / 1e6}, abs=1e-9)
    assert all(line["metrics"][k]["unit"] == "ms" for k in values
               if k.startswith("phase_"))
    assert {"step_device_ms", "copy_op_share", "device_idle_share",
            "dispatch_gap_ms", "mfu", "step_scratch_gb"} <= set(values)
    # one update per dispatch: the phases add up to the step's device time
    # less what its ops leave idle inside it
    assert sum(v for k, v in values.items() if k.startswith("phase_")) \
        <= values["step_device_ms"]
