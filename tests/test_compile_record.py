"""The compile-path record (utils/profiling.py ``CompileRecord``): JAX's
trace, lower and compile-or-load spans folded per program name, the
benchmark's six set-up readers over it, and what installing it must not
change.  Spans are emitted by hand where the CPU path cannot make them (it
runs without a persistent cache, so it never loads)."""

import functools
import json
import os
import subprocess
import sys
import threading

import jax
import jax.monitoring as monitoring
import pytest

from pytorch_distributed_tpu.utils import profiling
from pytorch_distributed_tpu.utils.profiling import (BACKEND_EVENT,
                                                     CACHE_HIT_EVENT,
                                                     LOWER_EVENT, TRACE_EVENT,
                                                     CompileRecord)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
READERS = ("setup_trace_s", "setup_lower_s", "setup_traces",
           "step_trace_s", "step_lower_s", "step_load_s")


def _span(rec, event, name, start, end, emit=None):
    """One span as JAX reports it: its start announced, then its end."""
    if emit is None:
        rec.on_start(event, start, fun_name=name)
        rec.on_span(event, start, end, fun_name=name)
    else:
        emit.record_scalar(event, start, fun_name=name)
        emit.record_event_time_span(event, start, end, fun_name=name)


@pytest.fixture
def listening():
    """A fresh record on jax.monitoring for one test.  Every hand-made
    span below is closed and every hit consumed, so the process's own
    record (which hears them too) is left with no open span."""
    rec = CompileRecord().listen()
    try:
        yield rec
    finally:
        monitoring.unregister_scalar_listener(rec.on_start)
        monitoring.unregister_event_time_span_listener(rec.on_span)
        monitoring.unregister_event_listener(rec.on_event)


def test_a_nested_trace_counts_once_in_seconds_and_each_time_in_count(
        listening):
    m = monitoring
    m.record_scalar(TRACE_EVENT, 100.0, fun_name="_cr_outer")
    _span(None, TRACE_EVENT, "_cr_inner", 100.5, 101.0, emit=m)
    _span(None, TRACE_EVENT, "_cr_inner", 101.5, 102.5, emit=m)
    m.record_event_time_span(TRACE_EVENT, 100.0, 104.0,
                             fun_name="_cr_outer")
    t = listening.totals()
    assert t.traces == 3
    assert t.trace_s == pytest.approx(4.0)
    inner = listening.program("_cr_inner")
    assert inner.traces == 2 and inner.trace_s == 0.0   # never outermost
    assert inner.first_trace is None
    assert listening.program("_cr_outer").trace_s == pytest.approx(4.0)


def test_parent_links_and_self_time():
    rec = CompileRecord()
    rec.on_start(TRACE_EVENT, 10.0, fun_name="f")
    rec.on_start(TRACE_EVENT, 11.0, fun_name="g")
    _span(rec, TRACE_EVENT, "h", 11.5, 12.0)      # inside g, inside f
    rec.on_span(TRACE_EVENT, 11.0, 13.0, fun_name="g")
    # an eager compile made while f traces: f's child, not a trace
    _span(rec, BACKEND_EVENT, "jit(k)", 13.0, 14.0)
    rec.on_span(TRACE_EVENT, 10.0, 20.0, fun_name="f")
    f, g, h = (rec.program(n) for n in "fgh")
    assert h.self_s == pytest.approx(0.5)
    assert g.self_s == pytest.approx(1.5)          # 2 s less h's 0.5
    assert f.self_s == pytest.approx(7.0)          # 10 s less g 2, k 1
    assert (f.trace_s, g.trace_s, h.trace_s) == (10.0, 0.0, 0.0)
    assert rec.program("k").compiles == 1
    assert rec.totals().trace_s == pytest.approx(10.0)
    assert not any(th.open for th in rec._threads.values())


def test_spans_of_two_threads_do_not_nest():
    rec = CompileRecord()
    rec.on_start(TRACE_EVENT, 0.0, fun_name="main_f")

    def other():
        _span(rec, TRACE_EVENT, "worker_g", 1.0, 2.0)

    t = threading.Thread(target=other)
    t.start()
    t.join(10)
    assert not t.is_alive()
    rec.on_span(TRACE_EVENT, 0.0, 3.0, fun_name="main_f")
    assert rec.program("worker_g").trace_s == 1.0  # outermost on its thread
    assert rec.program("main_f").self_s == 3.0


def test_a_trace_made_while_lowering_counts_in_the_lowering_alone():
    """A lowering rule traces functions of its own (a ``pallas_call`` body
    is traced while its caller lowers): such a trace is the lowering's
    child, so its seconds are not in ``trace_s`` as well."""
    rec = CompileRecord()
    _span(rec, TRACE_EVENT, "step", 0.0, 10.0)
    rec.on_start(LOWER_EVENT, 10.0, fun_name="jit(step)")
    for start in (11.0, 13.0):
        _span(rec, TRACE_EVENT, "wrapped", start, start + 1.5)
    rec.on_span(LOWER_EVENT, 10.0, 16.0, fun_name="jit(step)")
    t = rec.totals()
    assert (t.trace_s, t.lower_s, t.traces) == (10.0, 6.0, 3)
    kernel = rec.program("wrapped")
    assert kernel.traces == 2 and kernel.trace_s == 0.0
    assert kernel.self_s == pytest.approx(3.0)
    assert kernel.first_trace is None
    assert not any(th.open for th in rec._threads.values())


def test_the_setup_line_names_each_programs_trace_self_lower_and_ready():
    rec = _stand_in()
    line = rec.setup_line(top=2)
    assert line.startswith("[setup] trace 26.00 s, lower 6.00 s, "
                           "compile 10.50 s, load 8.50 s, 5 traces; ")
    # one: 15 s outermost, 14 s its own equations (add's 1 s inside)
    assert "top (trace/self/lower/ready): one x1 15.00/14.00/4.50/9.50s, " \
        "reference x1 10.00/10.00/1.00/9.00s" in line


def test_executables_are_filed_by_the_thread_that_made_them():
    rec = CompileRecord()
    _span(rec, BACKEND_EVENT, "jit(act)", 0.0, 1.0)
    here = threading.get_ident()
    worker = []

    def other():
        worker.append(threading.get_ident())
        _span(rec, BACKEND_EVENT, "jit(act)", 1.0, 2.0)
        _span(rec, BACKEND_EVENT, "jit(act)", 2.0, 3.0)

    t = threading.Thread(target=other)
    t.start()
    t.join(10)
    assert rec.program("act").ready == 3
    assert rec.ready_on("act", here) == 1
    assert rec.ready_on("act", worker[0]) == 2
    assert rec.ready_on("no_such_program", here) == 0


def test_a_hit_then_a_backend_span_is_a_load_and_without_a_hit_a_compile(
        listening):
    m = monitoring
    m.record_scalar(BACKEND_EVENT, 1.0, fun_name="jit(_cr_prog)")
    m.record_event(CACHE_HIT_EVENT)
    m.record_event_time_span(BACKEND_EVENT, 1.0, 3.0,
                             fun_name="jit(_cr_prog)")
    _span(None, BACKEND_EVENT, "jit(_cr_prog)", 4.0, 9.0, emit=m)
    p = listening.program("_cr_prog")
    assert (p.loads, p.load_s, p.compiles, p.compile_s) == (1, 2.0, 1, 5.0)
    assert p.ready == 2
    assert listening.totals().load_s == 2.0
    assert listening.totals().compile_s == 5.0


def test_jax_names_one_program_three_ways():
    assert profiling.program_name("jit(multi)") == "multi"
    assert profiling.program_name("multi") == "multi"
    from benchmark.harness.compile_spans import program_of

    assert program_of("jit_multi") == "multi"
    assert program_of("jit_multi_mega") == "multi_mega"


def _stand_in():
    """Set-up of a cell as the record sees it: a feed program, the step
    program ``one`` (traced with a nested ``add``, lowered with a kernel
    body traced inside the lowering, loaded), then what the check compiles
    after the window."""
    rec = CompileRecord()
    _span(rec, TRACE_EVENT, "feed", 0.0, 1.0)
    _span(rec, LOWER_EVENT, "jit(feed)", 1.0, 1.5)
    _span(rec, BACKEND_EVENT, "jit(feed)", 1.5, 2.0)
    rec.on_start(TRACE_EVENT, 3.0, fun_name="one")
    _span(rec, TRACE_EVENT, "add", 4.0, 5.0)
    rec.on_span(TRACE_EVENT, 3.0, 18.0, fun_name="one")
    rec.on_start(LOWER_EVENT, 18.0, fun_name="jit(one)")
    _span(rec, TRACE_EVENT, "wrapped", 19.0, 21.0)
    rec.on_span(LOWER_EVENT, 18.0, 22.5, fun_name="jit(one)")
    rec.on_start(BACKEND_EVENT, 22.5, fun_name="jit(one)")
    rec.on_event(CACHE_HIT_EVENT)
    rec.on_span(BACKEND_EVENT, 22.5, 31.0, fun_name="jit(one)")
    # after the step is ready: the check's programs, a second step
    _span(rec, TRACE_EVENT, "reference", 40.0, 50.0)
    _span(rec, LOWER_EVENT, "jit(reference)", 50.0, 51.0)
    _span(rec, BACKEND_EVENT, "jit(reference)", 51.0, 60.0)
    _span(rec, BACKEND_EVENT, "jit(one)", 61.0, 62.0)
    return rec


def test_setup_ends_when_the_step_program_is_ready():
    rec = _stand_in()
    one = rec.first_ready_of(["multi", "multi_mega", "one"])
    assert one.name == "one" and one.loads == 1 and one.compiles == 1
    assert one.at_ready == profiling.CompileTotals(
        trace_s=16.0, lower_s=5.0, compile_s=0.5, load_s=8.5, traces=4)
    assert rec.totals().traces == 5 and rec.totals().trace_s == 26.0
    # whichever step module was ready FIRST ends set-up
    _span(rec, BACKEND_EVENT, "jit(multi)", 0.5, 0.6)
    assert rec.first_ready_of(["one", "multi"]).name == "multi"
    assert rec.first_ready_of(["no_such_program"]) is None


class _Ctx:
    def __init__(self, modules):
        from benchmark.harness.manifest import Cell

        self.cell = Cell(name="x.y", chips=1, config={},
                         traffic={"step_modules": modules},
                         end_to_end=[], per_layer=[])


def _read(name, ctx):
    from benchmark.harness import manifest

    return manifest.load_module("layer_metrics", name).read(ctx)


def test_the_six_readers_on_a_stand_in_record(monkeypatch):
    rec = _stand_in()
    monkeypatch.setattr(profiling, "compile_record", lambda: rec)
    ctx = _Ctx(["jit_multi", "jit_multi_mega", "jit_one"])
    got = {name: _read(name, ctx) for name in READERS}
    assert got == pytest.approx({
        "setup_trace_s": 16.0, "setup_lower_s": 5.0, "setup_traces": 4,
        "step_trace_s": 15.0, "step_lower_s": 4.5, "step_load_s": 8.5})


@pytest.mark.parametrize("case", ["no_step_program", "no_record",
                                  "program_without_record"])
def test_the_readers_read_nothing_where_there_is_nothing(monkeypatch, case):
    rec = _stand_in()
    modules = ["jit_one"]
    if case == "no_step_program":
        modules = ["jit_multi"]
        monkeypatch.setattr(profiling, "compile_record", lambda: rec)
    elif case == "no_record":
        monkeypatch.setattr(profiling, "compile_record", lambda: None)
    else:   # the parent's program: the readers must not raise there
        monkeypatch.delattr(profiling, "compile_record")
    ctx = _Ctx(modules)
    assert all(_read(name, ctx) is None for name in READERS)


def test_installing_twice_is_a_no_op():
    first = profiling.install_compile_record()
    assert profiling.install_compile_record() is first
    assert profiling.compile_record() is first
    before = first.totals().lower_s
    _span(None, LOWER_EVENT, "jit(_cr_twice)", 5.0, 7.0, emit=monitoring)
    # heard once: a second install registered no second listener
    assert first.totals().lower_s - before == pytest.approx(2.0)
    assert first.program("_cr_twice").lowers == 1


def test_a_real_jit_nests_its_jnp_traces_and_a_cached_call_says_nothing():
    rec = profiling.install_compile_record()

    def _cr_fused(x, y):
        return jax.numpy.sin(x) * 2 + jax.numpy.cos(y).sum()

    f = jax.jit(_cr_fused)
    x = jax.numpy.ones(3)
    before = rec.totals()
    f(x, x)
    after = rec.totals()
    p = rec.program("_cr_fused")
    assert (p.traces, p.lowers, p.compiles) == (1, 1, 1)
    assert after.traces - before.traces >= 3       # sin, cos, ... inside
    assert p.self_s < p.trace_s
    f(x, x)
    assert rec.totals() == after


def test_a_pallas_body_is_traced_inside_its_callers_lowering():
    """JAX traces a ``pallas_call`` body while the jit around it lowers
    (the chip's kernels, here in interpret mode): the record files that
    trace under the lowering, never as an outermost trace."""
    from jax.experimental import pallas as pl

    rec = profiling.install_compile_record()

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2 + 1

    def _cr_kernel_step(x):
        return pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=True)(x).sum()

    def wrapped():
        p = rec.program("wrapped")
        return (p.traces, p.trace_s) if p is not None else (0, 0.0)

    x = jax.numpy.ones((8, 128))
    before, kernel_before = rec.totals(), wrapped()
    jax.jit(_cr_kernel_step)(x)
    after, kernel_after = rec.totals(), wrapped()
    assert kernel_after[0] > kernel_before[0]          # the body was traced
    assert kernel_after[1] == kernel_before[1]         # ... never outermost
    step = rec.program("_cr_kernel_step")
    assert after.trace_s - before.trace_s == pytest.approx(step.trace_s)


def test_a_partial_names_its_module_after_its_trace():
    """``jit(partial(f, ...))`` lowers a module JAX calls ``jit(<unknown>)``;
    it is filed under the function its trace span names."""
    rec = profiling.install_compile_record()

    def _cr_scaled(a, x):
        return a * x

    jax.jit(functools.partial(_cr_scaled, 2.0))(jax.numpy.ones(2))
    p = rec.program("_cr_scaled")
    assert (p.traces, p.lowers, p.compiles) == (1, 1, 1)


_LOWER_TWICE = r"""
import json, sys
import jax
from benchmark.harness import program
from pytorch_distributed_tpu.utils import profiling

opt = program.build_opt({"row": 12, "overrides": {
    "memory_size": 512, "batch_size": 8, "steps_per_dispatch": 4}},
    3, sys.argv[1], "lowering")
lrn = program.build_learner(opt)
fused = program.build_fused(lrn)
keys = jax.random.split(jax.random.PRNGKey(0), lrn.K)
beta = jax.numpy.float32(0.4)

assert profiling.compile_record() is None
texts = []
for install in (False, True):     # one call site: its line is in the text
    if install:
        record = profiling.install_compile_record()
        jax.clear_caches()
    texts.append(fused.lower(lrn.state, lrn.replay.state, keys,
                             beta).as_text(debug_info=True))
print(json.dumps({"same": texts[0] == texts[1],
                  "traced": record.program("multi").traces}))
"""


def test_the_record_changes_no_lowered_text(tmp_path):
    """The compile cache's key holds the programs' metadata (PR 24): a
    listener that put a frame on a traced stack would move every key."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    proc = subprocess.run(
        [sys.executable, "-c", _LOWER_TWICE, str(tmp_path / "run")],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got == {"same": True, "traced": 1}


def test_state_grows_with_programs_not_spans():
    rec = CompileRecord()
    names = [f"p{i}" for i in range(10)]
    t = 0.0
    for i in range(10 ** 5 // 4):
        name = names[i % 10]
        rec.on_start(TRACE_EVENT, t, fun_name=name)
        _span(rec, TRACE_EVENT, "add", t + 0.1, t + 0.2)
        rec.on_span(TRACE_EVENT, t, t + 0.5, fun_name=name)
        _span(rec, LOWER_EVENT, f"jit({name})", t + 0.5, t + 0.6)
        _span(rec, BACKEND_EVENT, f"jit({name})", t + 0.6, t + 0.7)
        t += 1.0
    assert len(rec._programs) == 11 and len(rec._threads) == 1
    assert not rec._threads[threading.get_ident()].open
    assert rec.totals().traces == 2 * (10 ** 5 // 4)
