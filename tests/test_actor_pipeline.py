"""Actor hot-loop pipeline (ISSUE 4): schedule equivalence + plumbing.

The contract under test: ``actor_backend`` changes WHEN work happens,
never WHAT is computed.  ``pipelined`` (the default) dispatches tick
k+1's fused act while the host feeds tick k; ``batched`` moves the
forward to a shared InferenceServer; ``inline`` is the serial reference.
All three must produce bit-identical action/transition streams under a
fixed seed, because per-tick randomness is a pure function of
(actor, tick, env row) — models/policies.tick_keys — and the weight-sync
point is schedule-invariant (agents/actor._drive_actor_loop docstring).

Everything here runs in-process on CPU via
``agents.actor.bounded_actor_run`` (one fixed published param snapshot, a
recording sink, a tick-bounded clock) — fast tier, no spawns.
"""

from __future__ import annotations

import numpy as np
import pytest

from pytorch_distributed_tpu.config import build_options
from pytorch_distributed_tpu.agents.actor import bounded_actor_run
from pytorch_distributed_tpu.utils.profiling import install_compile_record


def _opt(cfg, tmp_path, backend, **kw):
    kw.setdefault("num_actors", 2)
    kw.setdefault("num_envs_per_actor", 3)
    # no mid-run flush: leaves the StepTimer intact for phase asserts
    kw.setdefault("actor_freq", 10 ** 9)
    return build_options(cfg, root_dir=str(tmp_path), refs=f"t_{backend}",
                         actor_backend=backend, visualize=False, **kw)


def _assert_streams_equal(a, b):
    assert len(a) == len(b), (len(a), len(b))
    for (t1, p1), (t2, p2) in zip(a, b):
        assert type(t1) is type(t2)
        for f in t1._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(t1, f)), np.asarray(getattr(t2, f)),
                err_msg=f"field {f}")
        if p1 is None or p2 is None:
            assert p1 is None and p2 is None
        else:
            assert p1 == p2


# ---------------------------------------------------------------------------
# determinism: pipelined == inline, bit for bit
# ---------------------------------------------------------------------------


def test_pipelined_matches_inline_dqn(tmp_path):
    runs = {b: bounded_actor_run(_opt(1, tmp_path, b), 60)
            for b in ("inline", "pipelined")}
    assert runs["inline"]["stream"], "no transitions collected"
    _assert_streams_equal(runs["inline"]["stream"],
                          runs["pipelined"]["stream"])


def test_pipelined_matches_inline_dqn_per_priorities(tmp_path):
    """With PER on, the actor-computed initial priorities ride the
    stream too — the q_sel/q_max alignment across the one-tick holding
    pen must survive the reordered schedule."""
    runs = {b: bounded_actor_run(
        _opt(1, tmp_path, b, memory_type="prioritized"), 60)
        for b in ("inline", "pipelined")}
    priorities = [p for _, p in runs["inline"]["stream"]]
    assert any(p is not None for p in priorities)
    _assert_streams_equal(runs["inline"]["stream"],
                          runs["pipelined"]["stream"])


def test_pipelined_matches_inline_ddpg(tmp_path):
    """OU noise is sampled host-side at collect time in BOTH schedules,
    so the noise stream — and with it every continuous action — lines
    up."""
    runs = {b: bounded_actor_run(_opt(2, tmp_path, b), 50)
            for b in ("inline", "pipelined")}
    assert runs["inline"]["stream"]
    _assert_streams_equal(runs["inline"]["stream"],
                          runs["pipelined"]["stream"])


@pytest.mark.parametrize("cfg", [13, 15], ids=["drqn-lstm", "dtqn"])
def test_pipelined_matches_inline_recurrent(tmp_path, cfg):
    """Recurrent actors: the pipelined loop keeps the carry
    device-resident and resets rows via the fused act's reset mask; the
    serial loop drives the same engine.  Segment streams — including the
    stored carry_before rows around episode resets — must match
    exactly."""
    # eps=1.0: fully random actions — a random walk is what actually
    # reaches the chain's terminal under untrained weights, and episode
    # ends are the point of this test (carry resets).  Seeded, so the
    # terminal hits reproduce exactly.
    kw = dict(seq_len=8, seq_overlap=4, eps=1.0)
    runs = {b: bounded_actor_run(_opt(cfg, tmp_path, b, **kw), 120)
            for b in ("inline", "pipelined")}
    segs = runs["inline"]["stream"]
    assert segs, "no segments collected"
    # the chain env terminates inside 60 ticks: carry resets were hit
    assert any(np.asarray(s.terminal).any() for s, _ in segs)
    _assert_streams_equal(segs, runs["pipelined"]["stream"])


# ---------------------------------------------------------------------------
# overlap smoke: the async schedule never reorders advance vs env resets
# ---------------------------------------------------------------------------


def test_pipelined_no_reorder_against_env_resets(tmp_path):
    """With nstep=1 every transition is a raw (s, a, s') edge: walking
    one env's stream, state0 must chain from the previous transition's
    state1 — except across a terminal, where it must chain from the
    RESET observation.  A pipelined loop that fed tick k after
    dispatching on tick k+1's post-reset obs out of order would break
    the chain."""
    # eps=1.0: random-walk actions so the chain terminal is actually hit
    # (greedy under untrained weights may never reach it); seeded.
    opt = _opt(1, tmp_path, "pipelined", num_envs_per_actor=1, nstep=1,
               eps=1.0)
    stream = bounded_actor_run(opt, 250)["stream"]
    assert stream
    reset_obs = np.zeros(8, np.float32)
    reset_obs[0] = 1.0
    terminals = 0
    prev = None
    for t, _p in stream:
        if prev is not None:
            if prev.terminal1:
                np.testing.assert_array_equal(np.asarray(t.state0),
                                              reset_obs)
                terminals += 1
            else:
                np.testing.assert_array_equal(np.asarray(t.state0),
                                              np.asarray(prev.state1))
        prev = t
    assert terminals >= 1, "no episode reset inside the window"


# ---------------------------------------------------------------------------
# CI throughput smoke: overlap exists, and nothing retraces per tick
# ---------------------------------------------------------------------------


def _act_builds(run):
    """``run()`` and the executables JAX made ready meanwhile for a
    program named ``act`` (utils/profiling's compile-path record)."""
    record = install_compile_record()

    def ready():
        p = record.program("act")
        return p.ready if p is not None else 0

    before = ready()
    res = run()
    return res, ready() - before


def test_pipelined_throughput_smoke(tmp_path):
    """A few hundred pipelined ticks on CPU: (a) the jitted fused act
    compiled exactly ONCE — a traced-vs-static slip on the tick counter
    would recompile every tick and this counter would explode; (b) every
    tick ran host feed work while a dispatch was in flight (dispatch
    precedes advance in the schedule), i.e. the overlap the pipeline
    exists for is nonzero."""
    ticks = 300
    res, builds = _act_builds(
        lambda: bounded_actor_run(_opt(1, tmp_path, "pipelined"), ticks))
    assert builds == 1, "fused act retraced mid-run (per-tick recompilation)"
    t = res["timer_ms"]
    # one dispatch per tick (+ the pipeline-priming one), one sync each
    assert t["actor/time_dispatch_calls"] == ticks + 1
    assert t["actor/time_sync_calls"] == ticks
    # the overlapped host work is real, not a zero-length no-op
    assert t["actor/time_advance_calls"] == ticks
    overlapped_ms = t["actor/time_advance_ms"] * ticks
    assert overlapped_ms > 0.0


def test_recurrent_pipelined_no_retrace(tmp_path):
    """The recurrent fused act takes the reset mask + tick as traced
    args — neither may trigger per-tick recompiles."""
    _res, builds = _act_builds(lambda: bounded_actor_run(
        _opt(13, tmp_path, "pipelined", seq_len=8, seq_overlap=4), 80))
    assert builds == 1


# ---------------------------------------------------------------------------
# batched backend: the shared inference server serves identical streams
# ---------------------------------------------------------------------------


def _server_for(opt, spec):
    from pytorch_distributed_tpu.factory import build_model, init_params
    from pytorch_distributed_tpu.agents.inference import InferenceServer
    from pytorch_distributed_tpu.agents.param_store import (
        ParamStore, make_flattener,
    )

    model = build_model(opt, spec)
    flat0, _ = make_flattener(init_params(opt, spec, model, seed=0))
    store = ParamStore(flat0.size)
    store.publish(flat0)
    return InferenceServer(opt, spec, store)


def test_batched_backend_matches_inline(tmp_path):
    """On a same-device (CPU) server the SEED-style batched backend is
    bit-identical to the local loops: per-row fold_in keys make action
    randomness independent of batching, and the server runs the same
    jitted program over the same published weights."""
    from pytorch_distributed_tpu.factory import probe_env

    opt_b = _opt(1, tmp_path, "batched")
    spec = probe_env(opt_b)
    server = _server_for(opt_b, spec)
    client = server.make_client(0)
    server.start()
    try:
        batched = bounded_actor_run(opt_b, 50, spec=spec,
                                    inference=client)
    finally:
        server.stop()
    inline = bounded_actor_run(_opt(1, tmp_path, "inline"), 50, spec=spec)
    _assert_streams_equal(inline["stream"], batched["stream"])
    assert server.stats["batches"] > 0
    assert server.stats["rows"] >= 50 * 3


def test_batched_backend_multi_client_rows(tmp_path):
    """Two clients coalesced into one sweep still get their own rows
    back: submit both before the server drains, forcing the
    concat/pad/scatter path at least once."""
    from pytorch_distributed_tpu.factory import probe_env
    from pytorch_distributed_tpu.models.policies import apex_epsilons
    from pytorch_distributed_tpu.utils.rngs import process_key

    opt = _opt(1, tmp_path, "batched")
    spec = probe_env(opt)
    server = _server_for(opt, spec)
    c0, c1 = server.make_client(0), server.make_client(1)
    for ind, c in ((0, c0), (1, c1)):
        c.begin_session(
            base_key=np.asarray(process_key(opt.seed, "actor", ind)),
            eps=apex_epsilons(ind, 2, 3))
    obs = np.zeros((3, 8), np.float32)
    obs[:, 0] = 1.0
    # enqueue both requests BEFORE the server thread starts draining
    h0 = c0.submit(obs, 0)
    h1 = c1.submit(obs, 0)
    server.start()
    try:
        p0 = c0.collect(h0, timeout=120.0)
        p1 = c1.collect(h1, timeout=120.0)
    finally:
        server.stop()
    assert p0.shape == (3, 3) and p1.shape == (3, 3)
    # rows from the same obs under the same weights: q_max must agree
    # across clients; actions may differ (per-client keys/eps)
    np.testing.assert_allclose(p0[2], p1[2], rtol=1e-6)


def test_batched_client_frame_packing():
    """The client elects the frame-packed wire mode exactly when the
    roll property holds: first submit full (seeds the server stack),
    rolled ticks packed (only the newest HxW frame ships), any broken
    roll — an env reset — full again."""
    from pytorch_distributed_tpu.agents.inference import InferenceClient

    sent = []

    import queue

    class _Q:
        def put(self, item):
            sent.append(item)

    c = InferenceClient(0, "dqn", _Q(), queue.Queue())
    c.begin_session(base_key=np.zeros(2, np.uint32),
                    eps=np.zeros(2, np.float32))
    obs0 = np.arange(2 * 4 * 3 * 3, dtype=np.uint8).reshape(2, 4, 3, 3)
    c.submit(obs0, 0)
    rolled = np.concatenate(
        [obs0[:, 1:], np.full((2, 1, 3, 3), 7, np.uint8)], axis=1)
    c.submit(rolled, 1)
    reset = np.zeros_like(obs0)  # env reset: fresh stack, roll broken
    c.submit(reset, 2)
    rolled2 = np.concatenate(
        [reset[:, 1:], np.full((2, 1, 3, 3), 9, np.uint8)], axis=1)
    c.submit(rolled2, 3)
    modes = [req[3] for req in sent]
    assert modes == ["full", "packed", "full", "packed"]
    assert sent[1][4].shape == (2, 3, 3)  # newest frame only
    np.testing.assert_array_equal(sent[1][4], np.full((2, 3, 3), 7))
    assert sent[2][4].shape == obs0.shape  # reset re-ships the stack


def test_batched_backend_frame_packed_pixels(tmp_path):
    """End-to-end packed path on the real rolling-stack env (pong-sim
    pixels): the server reconstructs stacks on device from newest-frame
    uploads, and the stream still matches the inline oracle bit for bit
    — including across episode resets, which force full re-uploads."""
    from pytorch_distributed_tpu.factory import probe_env

    kw = dict(num_envs_per_actor=2, early_stop=12)  # quick resets
    opt_b = _opt(4, tmp_path, "batched", **kw)
    spec = probe_env(opt_b)
    server = _server_for(opt_b, spec)
    client = server.make_client(0)
    server.start()
    try:
        batched = bounded_actor_run(opt_b, 30, spec=spec,
                                    inference=client)
    finally:
        server.stop()
    inline = bounded_actor_run(_opt(4, tmp_path, "inline", **kw), 30,
                               spec=spec)
    _assert_streams_equal(inline["stream"], batched["stream"])


def test_resolve_actor_backend_downgrades(tmp_path):
    from pytorch_distributed_tpu.factory import resolve_actor_backend

    opt = _opt(1, tmp_path, "batched")
    with pytest.warns(UserWarning, match="no InferenceClient"):
        assert resolve_actor_backend(opt, None) == "pipelined"
    opt_r = _opt(13, tmp_path, "batched", seq_len=8, seq_overlap=4)
    with pytest.warns(UserWarning, match="recurrent"):
        assert resolve_actor_backend(opt_r, object()) == "pipelined"
    opt_bad = _opt(1, tmp_path, "pipelined")
    opt_bad.env_params.actor_backend = "warp"
    with pytest.raises(ValueError, match="warp"):
        resolve_actor_backend(opt_bad)
    assert resolve_actor_backend(_opt(1, tmp_path, "inline")) == "inline"


# ---------------------------------------------------------------------------
# param prefetcher: swaps never block, remote stores still poll
# ---------------------------------------------------------------------------


def test_param_prefetcher_basic():
    import time

    from pytorch_distributed_tpu.agents.param_store import (
        ParamPrefetcher, ParamStore,
    )

    store = ParamStore(4)
    v1 = store.publish(np.arange(4, dtype=np.float32))
    pf = ParamPrefetcher(store, lambda f: f * 2.0, start_version=v1,
                         poll_secs=0.01)
    try:
        assert pf.take() is None  # nothing newer than v1
        v2 = store.publish(np.ones(4, dtype=np.float32))
        deadline = time.monotonic() + 5.0
        got = None
        while got is None and time.monotonic() < deadline:
            got = pf.take()
            time.sleep(0.01)
        assert got is not None
        tree, version = got
        assert version == v2
        np.testing.assert_array_equal(tree, np.full(4, 2.0, np.float32))
        assert pf.take() is None  # consumed
    finally:
        pf.close()


def test_param_prefetcher_versionless_store():
    """A DCN RemoteParamStore exposes no cheap ``version`` property —
    the fetch itself is the probe.  The prefetcher must still deliver."""
    import time

    from pytorch_distributed_tpu.agents.param_store import (
        ParamPrefetcher, ParamStore,
    )

    inner = ParamStore(2)

    class _RemoteLike:
        def fetch(self, min_version=0):
            return inner.fetch(min_version)

    pf = ParamPrefetcher(_RemoteLike(), lambda f: f, start_version=0,
                         remote_poll_secs=0.01)
    try:
        inner.publish(np.array([3.0, 4.0], np.float32))
        deadline = time.monotonic() + 5.0
        got = None
        while got is None and time.monotonic() < deadline:
            got = pf.take()
            time.sleep(0.01)
        assert got is not None and got[1] == 1
    finally:
        pf.close()


# ---------------------------------------------------------------------------
# apex_epsilons: the fleet exploration ladder (previously untested)
# ---------------------------------------------------------------------------


def test_apex_epsilons_formula():
    """env j of actor i takes slot i*N+j of the num_actors*N ladder,
    each slot getting eps ** (1 + slot/(total-1) * alpha) — the Ape-X
    schedule (Horgan et al. 2018; reference dqn_actor.py:33-36)."""
    from pytorch_distributed_tpu.models.policies import (
        apex_epsilon, apex_epsilons,
    )

    eps, alpha = 0.4, 7.0
    A, N = 4, 3
    total = A * N
    for i in range(A):
        got = apex_epsilons(i, A, N, eps, alpha)
        assert got.shape == (N,) and got.dtype == np.float32
        for j in range(N):
            slot = i * N + j
            expect = eps ** (1.0 + slot / (total - 1) * alpha)
            np.testing.assert_allclose(got[j], expect, rtol=1e-6)
            np.testing.assert_allclose(
                got[j], apex_epsilon(slot, total, eps, alpha), rtol=1e-6)
    # monotone: later fleet slots explore less
    ladder = np.concatenate([apex_epsilons(i, A, N, eps, alpha)
                             for i in range(A)])
    assert np.all(np.diff(ladder) < 0)


def test_apex_epsilons_stable_across_reshape():
    """The FLEET ladder depends only on num_actors * num_envs: reshaping
    4x3 into 6x2 or 12x1 yields the same 12 epsilons in the same global
    slot order — so retopologizing a fleet never changes its exploration
    mix."""
    from pytorch_distributed_tpu.models.policies import apex_epsilons

    def ladder(A, N):
        return np.concatenate([apex_epsilons(i, A, N) for i in range(A)])

    ref = ladder(4, 3)
    np.testing.assert_allclose(ladder(6, 2), ref, rtol=1e-7)
    np.testing.assert_allclose(ladder(12, 1), ref, rtol=1e-7)
    np.testing.assert_allclose(ladder(1, 12), ref, rtol=1e-7)


def test_apex_epsilons_single_actor_debug_value():
    """num_actors*num_envs == 1 keeps the reference's 0.1 debug branch."""
    from pytorch_distributed_tpu.models.policies import apex_epsilons

    np.testing.assert_allclose(apex_epsilons(0, 1, 1), [0.1])
