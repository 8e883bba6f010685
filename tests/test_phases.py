"""The program's phases (ISSUE 24): every learner step program stands under
the device-phase vocabulary of utils/profiling.py, the scopes are metadata
(the lowered program is the same without them), ``StepTimer.phase`` is a
profiler span as well as a timer, and ``clock.learner_done`` counts
completions behind ``clock.learner_step``'s enqueues."""

import contextlib
import re
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from pytorch_distributed_tpu import factory, runtime
from pytorch_distributed_tpu.config import build_options
from pytorch_distributed_tpu.utils import profiling
from pytorch_distributed_tpu.utils.metrics import read_scalars
from pytorch_distributed_tpu.utils.profiling import (
    DEVICE_PHASES, PHASE_DRAW, PHASE_FEED, PHASE_GATHER, PHASE_ONLINE,
    PHASE_OPTIMIZER, PHASE_TARGET, PHASE_WRITEBACK, StepTimer,
)

TRAIN = (PHASE_TARGET, PHASE_ONLINE, PHASE_OPTIMIZER)
PER = (PHASE_DRAW, PHASE_GATHER, *TRAIN, PHASE_WRITEBACK)
UNIFORM = (PHASE_DRAW, PHASE_GATHER, *TRAIN)
# id: CONFIGS row, memory_type, steps_per_dispatch, megabatch, its phases
STEP_PROGRAMS = {
    "per-one": (1, "device-per", 1, 0, PER),
    "per-multi": (1, "device-per", 4, 0, PER),
    "per-multi_mega": (1, "device-per", 4, 2, PER),
    "sequence-multi": (13, "device-sequence", 2, 0, PER),
    "uniform-k1": (1, "device", 1, 0, UNIFORM),
    "uniform-multi": (1, "device", 4, 0, UNIFORM),
    "uniform-multi_mega": (1, "device", 4, 2, UNIFORM),
    "uniform-multi-ddpg": (2, "device", 4, 0, UNIFORM),
}
_A_PHASE = re.compile(
    r"(?<![\w.])(" + "|".join(map(re.escape, DEVICE_PHASES)) + r")(?![\w.])")


def build_step(tmp_path, row, memory_type, K, megabatch):
    """The step program ``run_learner`` builds for this configuration, at
    a tiny size, and arguments to trace it with."""
    kw = dict(root_dir=str(tmp_path), memory_type=memory_type,
              memory_size=256, batch_size=4, steps_per_dispatch=K,
              visualize=False)
    if row == 13:
        kw.update(seq_len=8, seq_overlap=4, burn_in=2, nstep=2)
    if megabatch:
        kw["megabatch"] = megabatch
    opt = build_options(row, **kw)
    spec = factory.probe_env(opt)
    model = factory.build_model(opt, spec)
    params = factory.init_params(opt, spec, model, seed=0)
    state, step_fn = factory.build_train_state_and_step(opt, spec, model,
                                                        params, mesh=None)
    replay = factory.build_memory(opt, spec).learner_side.attach(mesh=None)
    B = opt.agent_params.batch_size
    mega = {}
    M, K_mb = factory.resolve_megabatch(opt, K)
    if M > 1:
        K = K_mb
        mega = dict(megabatch=M, megabatch_step=(
            factory.build_megabatch_train_step(opt, model)))
    keys = (jax.random.split(jax.random.PRNGKey(0), K) if K > 1
            else jax.random.PRNGKey(0))
    if hasattr(replay, "build_fused_step"):
        fused = replay.build_fused_step(step_fn, B, donate=False,
                                        steps_per_call=K, **mega)
        return fused, (state, replay.state, keys, jnp.float32(0.4))
    from pytorch_distributed_tpu.memory.device_replay import (
        build_uniform_fused_step, sample_rows,
    )

    if K > 1:
        fused = build_uniform_fused_step(step_fn, B, steps_per_call=K,
                                         donate=False, **mega)
    else:       # the K=1 program of run_learner
        fused = jax.jit(lambda ts, rs, key: step_fn(
            ts, sample_rows(rs, key, B)))
    return fused, (state, replay.state, keys)


def leaf_paths(jaxpr, outer=""):
    """(name path, primitive) of every equation that holds no inner
    program: the path the lowering gives the op, outer scopes first."""
    for eqn in jaxpr.eqns:
        path = f"{outer}/{eqn.source_info.name_stack}"
        inner = [v for value in eqn.params.values()
                 for v in (value if isinstance(value, (tuple, list))
                           else (value,))
                 if hasattr(v, "eqns") or hasattr(v, "jaxpr")]
        if not inner:
            yield path, eqn.primitive.name
        for sub in inner:
            yield from leaf_paths(getattr(sub, "jaxpr", sub), path)


@pytest.mark.parametrize("case", STEP_PROGRAMS, ids=list(STEP_PROGRAMS))
def test_every_op_of_a_step_program_stands_under_a_phase(tmp_path, case):
    *build, family = STEP_PROGRAMS[case]
    fused, args = build_step(tmp_path, *build)
    text = fused.lower(*args).as_text(debug_info=True)
    assert {p for p in DEVICE_PHASES if p in text} == set(family)
    jaxpr = jax.make_jaxpr(fused)(*args).jaxpr
    outside = sorted({(path, prim) for path, prim in leaf_paths(jaxpr)
                      if not _A_PHASE.search(path)})
    # sampling, the train step and the write-back leave nothing unnamed:
    # what a trace shows under no phase is the compiler's own
    assert outside == []
    # the backward pass is filed under the phase of its forward
    assert any(PHASE_ONLINE in path and "transpose(" in path
               for path, _ in leaf_paths(jaxpr))


@pytest.mark.parametrize("case", STEP_PROGRAMS, ids=list(STEP_PROGRAMS))
def test_scopes_are_metadata(tmp_path, monkeypatch, case):
    """The lowered program without debug info (what the compile-cache key
    is computed from) is the same with every scope taken away."""
    *build, _family = STEP_PROGRAMS[case]
    fused, args = build_step(tmp_path, *build)
    scoped = fused.lower(*args).as_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    fused, args = build_step(tmp_path, *build)
    bare = fused.lower(*args)
    assert not any(p in bare.as_text(debug_info=True)
                   for p in DEVICE_PHASES)
    assert bare.as_text() == scoped


@pytest.mark.parametrize("memory_type", ["device", "device-per",
                                         "device-sequence"])
def test_the_ring_feed_is_a_phase(tmp_path, memory_type):
    row = 13 if memory_type == "device-sequence" else 1
    kw = dict(seq_len=8, seq_overlap=4, burn_in=2, nstep=2) \
        if row == 13 else {}
    opt = build_options(row, root_dir=str(tmp_path), memory_size=64,
                        memory_type=memory_type, visualize=False, **kw)
    replay = factory.build_memory(
        opt, factory.probe_env(opt)).learner_side.attach(mesh=None)
    if row == 13:
        from pytorch_distributed_tpu.memory.device_sequence import (
            SegmentChunk as Chunk,
        )
        fields = Chunk._fields
    else:
        from pytorch_distributed_tpu.utils.experience import (
            REPLAY_FIELDS as fields, Transition as Chunk,
        )
    # four rows of the (empty) ring stand in for a chunk of the same schema
    chunk = Chunk(*(getattr(replay.state, f)[:4] for f in fields))
    jaxpr = jax.make_jaxpr(replay._feed_fn)(replay.state, chunk).jaxpr
    paths = list(leaf_paths(jaxpr))
    assert paths and all(PHASE_FEED in path for path, _ in paths)


@pytest.mark.parametrize("kind", ["device", "device-per"])
def test_the_pack_is_feed_and_the_unpack_is_gather(kind):
    """Pixel rows are stored packed (memory/device_replay.py RowCodec):
    the shifts that pack a chunk stand under ``replay.feed``, the shifts
    that unpack a batch under ``replay.gather``, nothing under no phase."""
    import numpy as np

    from pytorch_distributed_tpu.memory import DeviceReplay
    from pytorch_distributed_tpu.memory.device_per import DevicePerReplay
    from pytorch_distributed_tpu.utils.experience import Transition

    cls = DevicePerReplay if kind == "device-per" else DeviceReplay
    ring = cls(16, (4, 12, 12), state_dtype=np.uint8)
    assert ring.codec.words
    n = 4
    chunk = Transition(
        state0=np.zeros((n, 4, 12, 12), np.uint8),
        action=np.zeros(n, np.int32), reward=np.zeros(n, np.float32),
        gamma_n=np.ones(n, np.float32),
        state1=np.zeros((n, 4, 12, 12), np.uint8),
        terminal1=np.zeros(n, np.float32))
    feed = list(leaf_paths(
        jax.make_jaxpr(ring._feed_fn)(ring.state, chunk).jaxpr))
    assert all(PHASE_FEED in path for path, _ in feed)
    assert any(prim == "shift_left" for _, prim in feed)
    extra = {"beta": jnp.float32(0.4)} if kind == "device-per" else {}
    sample = list(leaf_paths(jax.make_jaxpr(
        lambda st, key: ring._sample_fn(st, key, batch_size=4, **extra))(
            ring.state, jax.random.PRNGKey(0)).jaxpr))
    assert all(_A_PHASE.search(path) for path, _ in sample)
    # one unpack a column (the draw's random bits shift too, under draw)
    assert sum(prim == "shift_right_logical" and PHASE_GATHER in path
               for path, prim in sample) == 2


# ---------------------------------------------------------------------------
# host phases
# ---------------------------------------------------------------------------

class RecordedSpan:
    """Stands in for ``jax.profiler.TraceAnnotation``."""
    log = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))


@pytest.mark.parametrize("prefix, phases", [
    ("learner", ("drain", "dispatch", "stats")),
    ("actor", ("act", "act")),
    ("x", ("p",)),
])
def test_a_phase_is_a_trace_annotation_of_its_name(monkeypatch, prefix,
                                                   phases):
    monkeypatch.setattr(profiling, "_trace_annotation",
                        lambda: RecordedSpan)
    monkeypatch.setattr(RecordedSpan, "log", [])
    timer = StepTimer(prefix)
    for name in phases:
        with timer.phase(name):
            time.sleep(0.001)
    assert RecordedSpan.log == [
        (edge, f"{prefix}/{name}") for name in phases
        for edge in ("enter", "exit")]
    assert timer.last_s >= 0.001
    rows = timer.drain()
    # the rows it drained before it wrote spans: five per phase
    assert set(rows) == {f"{prefix}/time_{name}_{col}" for name in phases
                         for col in ("ms", "max_ms", "calls", "total_ms",
                                     "last_wall")}
    for name in set(phases):
        assert rows[f"{prefix}/time_{name}_calls"] == phases.count(name)
        assert rows[f"{prefix}/time_{name}_total_ms"] >= 1.0
    assert timer.drain() == {}


def test_a_role_without_jax_keeps_its_timer(monkeypatch):
    monkeypatch.setattr(profiling, "_trace_annotation", lambda: None)
    timer = StepTimer("logger")
    with timer.phase("write"):
        pass
    assert timer.drain()["logger/time_write_calls"] == 1.0


def test_a_phase_shows_in_a_profiler_trace(tmp_path):
    """The real annotation, in a real (CPU) trace: a host span named
    ``<prefix>/<phase>`` on the profiler's clock."""
    from jax.profiler import ProfileData

    timer = StepTimer("learner")
    with profiling.trace("t", log_dir=str(tmp_path)) as path:
        with timer.phase("dispatch"):
            jnp.ones((8,)).block_until_ready()
    found = sorted((tmp_path / "t").rglob("*.xplane.pb"))
    assert path is not None and found
    names = {event.name
             for plane in ProfileData.from_file(str(found[-1])).planes
             for line in plane.lines for event in line.events}
    assert "learner/dispatch" in names


# ---------------------------------------------------------------------------
# the learner's loop: learner_done, dispatch and step
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("memory_type, K", [
    ("shared", 1), ("device", 4), ("device-per", 4)],
    ids=["host", "uniform-fused", "per-fused"])
def test_learner_done_follows_learner_step(tmp_path, memory_type, K):
    opt = build_options(
        1, root_dir=str(tmp_path), refs="done", memory_type=memory_type,
        steps_per_dispatch=K, num_actors=1, steps=120, learn_start=16,
        batch_size=8, memory_size=512, learner_freq=20,
        evaluator_nepisodes=0, visualize=False, max_replay_ratio=0.0)
    topo = runtime.Topology(opt)
    clock = topo.clock
    seen = []

    def watch():
        # done is read FIRST: step only grows, so done <= step must hold
        while not clock.stop.is_set():
            seen.append((clock.learner_done.value,
                         clock.learner_step.value))
            time.sleep(0.001)

    watcher = threading.Thread(target=watch, daemon=True)
    watcher.start()
    topo.run(backend="thread")
    clock.stop.set()
    watcher.join(10.0)
    assert not watcher.is_alive()
    assert clock.learner_step.value >= 120
    # it reaches learner_step once the last dispatch is done ...
    assert clock.learner_done.value == clock.learner_step.value
    # ... never leads it, and moves in whole dispatches
    assert seen and all(done <= step for done, step in seen)
    assert all(done % K == 0 for done, _ in seen)

    rows = read_scalars(opt.log_dir)
    total = lambda tag: sum(r["value"] for r in rows if r["tag"] == tag)
    dispatches = 120 // K
    assert total("learner/time_dispatch_calls") == dispatches
    assert total("learner/time_step_calls") == dispatches
    # a dispatch's step runs from its enqueue to its completion, so it
    # holds its dispatch phase (the enqueue alone)
    assert total("learner/time_step_total_ms") >= total(
        "learner/time_dispatch_total_ms") > 0.0
    # the learn span is the same clock reading as the step phase
    learn = [r for r in rows if r["tag"] == "trace/learner/learn_ms"]
    assert sum(r["count"] for r in learn) == dispatches
    assert sum(r["mean"] * r["count"] for r in learn) == pytest.approx(
        total("learner/time_step_total_ms"), rel=1e-6)
    # every stretch of the loop has a name
    tags = {r["tag"] for r in rows}
    assert {"learner/time_stats_ms", "learner/time_dispatch_ms",
            "learner/time_step_ms"} <= tags
    if memory_type != "shared":
        assert {"learner/time_keys_ms", "learner/time_drain_ms"} <= tags


def test_pacing_and_checkpoints_are_phases(tmp_path):
    opt = build_options(
        1, root_dir=str(tmp_path), refs="paced", memory_type="device-per",
        steps_per_dispatch=4, num_actors=1, steps=40, learn_start=16,
        batch_size=8, memory_size=512, learner_freq=20,
        checkpoint_freq=20, evaluator_nepisodes=0, visualize=False,
        # 50 frames per sampled row: the learner waits for the actor
        max_replay_ratio=0.02)
    topo = runtime.train(opt, backend="thread")
    assert topo.clock.learner_done.value == topo.clock.learner_step.value
    tags = {r["tag"] for r in read_scalars(opt.log_dir)}
    assert {"learner/time_pace_ms", "learner/time_checkpoint_ms"} <= tags


def test_the_compile_cache_key_holds_the_names(monkeypatch):
    """A cached executable carries its tree's names into every later
    trace, so on the platform that caches, the key includes the metadata
    (utils/helpers.enable_compile_cache says what that costs)."""
    import types

    from pytorch_distributed_tpu.utils import helpers

    was = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_compilation_cache_include_metadata_in_key)
    try:
        assert helpers.enable_compile_cache() is None       # CPU: no cache
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.setattr(helpers.jax, "devices", lambda: [
            types.SimpleNamespace(platform="tpu")])
        assert helpers.enable_compile_cache() == helpers.compile_cache_dir()
        assert jax.config.jax_compilation_cache_include_metadata_in_key
    finally:
        jax.config.update("jax_compilation_cache_dir", was[0])
        jax.config.update(
            "jax_compilation_cache_include_metadata_in_key", was[1])
