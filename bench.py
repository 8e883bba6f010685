#!/usr/bin/env python
"""Benchmark: the framework's throughput numbers on this chip.

Three measurements, merged into ONE printed JSON line:

1. **micro** — learner update throughput on the compute-critical loop
   (SURVEY.md §3.3) exactly as the flagship TPU config (CONFIGS row 8) runs
   it in production: replay resident in device HBM
   (memory/device_replay.py), uniform sampling fused into the train step,
   ``steps_per_dispatch`` update steps scanned inside one dispatched XLA
   program — the full DQN training step (Nature-CNN forward+backward, Adam,
   target update) at the reference's default batch 128 on 84x84x4 uint8
   states (reference utils/options.py:135, shared_memory.py:19-24).
   Measured at TWO fusion factors — the production K=32 and the peak
   K=256 (headline) — with a two-point fit of the per-dispatch overhead
   and the chip-bound asymptote, per-window p50/p90 so dispatch noise
   is visible in the artifact, an XLA-derived
   flops/update and the achieved FLOP/s (with an MFU estimate when the
   chip's peak is known).

2. **families** — one on-chip updates/s + FLOPs row for EVERY other
   shipped model family's learner program (dqn-mlp, ddpg-mlp, drqn-mlp,
   drqn-cnn, dtqn-mlp, dtqn-moe, dtqn-pipe) at its drive-validated
   geometry, under the ``families`` key — each measured PRODUCTION-SHAPED:
   the family's train step fused over an HBM ring (uniform transition ring
   for the flat families, the prioritized segment ring for the sequence
   families) at ``steps_per_dispatch`` = 8, so the figures are K-amortised
   program rates, not the latency of one unamortised dispatch
   (bench_families docstring).

3. **sampler** — Pallas hierarchical sampler vs the flat XLA
   cumsum+searchsorted draw on the production 50k-row PER priority
   vector (TPU only): a compile/perf regression in the Pallas path
   (memory/device_per.py's production draw on unsharded TPU rings) shows
   up here instead of only inside a north-star run.

4. **act A/B** — batch-16 actor forward on the host CPU vs on the
   accelerator (full-stack upload AND frame-packed upload variants):
   the measurement behind the "rollout inference is pinned to the host"
   design decision (agents/actor.py), re-taken on whatever hardware runs
   this bench so the decision is data, not folklore.

5. **actor_pipeline** — the ISSUE-4 actor hot loop, serial vs
   software-pipelined, on the production 16-env Nature-CNN shape:
   per-phase tick breakdown, frames/s for both schedules, the env-only
   ceiling, and ``overlap_efficiency`` (hidden device time / total
   device time — how much of the serial ``act`` cost the pipeline
   hides under host work).

6. **device_env** — the ISSUE-7 on-device env fleet: env frames/s of
   the host Python ``VectorEnv`` vs the native C++ stepper vs the
   pure-JAX device env (one scan advancing N envs per dispatch) at
   N in {64, 256, 1024}, plus the fused rollout engine
   (env+policy+n-step+replay-ring in ONE donated program) with the
   engine-cost (linear) and production (CNN) policies, and the
   ``speedup_vs_host`` headline the ROADMAP open item 1 tracks.

7. **e2e** — the BASELINE.md north-star accounting: env frames/sec with
   live actors + learner.  Runs the real config-8 topology (process
   backend, native batched pong stepper, HBM replay, replay-ratio
   pacing, and the ISSUE-4 actor plane: pipelined actors, or the
   SEED-style batched-inference backend when an accelerator hosts the
   learner — ``e2e_actor_backend`` records which) for a short
   wall-clock window and reads ``actor/total_nframes`` /
   ``learner/counter`` off the run's scalars — the same accounting as
   reference core/single_processes/dqn_logger.py:42.  Frames are agent
   steps (x4 emulated frames each, reference atari_env.py:95).
   ``e2e_actor_tick_ms`` carries the actors' phase medians (sync =
   blocked on the in-flight forward, dispatch = issue cost, param_swap
   = weight-refresh stall) and ``e2e_overlap_efficiency`` the fraction
   of per-tick device/server wait hidden under host work.

The merged line carries ``bench_schema`` (round-3 advisor finding: the
headline key's meaning changed once — K=256 peak -> K=32 production —
without a version marker; longitudinal consumers should key on the
schema).  Schema 2 = production-K headline + fused families rows +
sampler/act-A/B sections.

``vs_baseline`` compares micro updates/s against 250 updates/s — a
representative figure for this exact workload (batch-128 Nature-DQN Adam
step) on the single consumer CUDA GPU class the reference targets.  The
reference publishes no throughput numbers (BASELINE.md "published
frames/sec: none"), so this basis is self-declared; the ``*_basis`` field
says so explicitly.

Two rider sections measure the in-graph/host guards' cost on the fused
flagship program: ``health_overhead`` (the ISSUE-5 in-jit finite guard)
and ``perf_overhead`` (the ISSUE-6 live PerfMonitor doing its production
accounting) — both must stay <2% of median step time.

``--smoke`` is a separate seconds-scale CPU-safe mode (the dqn-mlp fused
program only) whose one-line JSON feeds ``tools/bench_gate.py --against
BENCH_SMOKE_BASELINE.json`` and ``BENCH_HISTORY.jsonl`` — the perf
regression gate CI runs (TESTING.md "Bench regression gate").

Usage: ``python bench.py [--mode micro|families|e2e|both] [--smoke]``
(default both = all three).
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import tempfile
import time

import numpy as np

BASELINE_UPDATES_PER_SEC = 250.0

# micro-bench geometry: batch per update / update steps per dispatched
# XLA program.  Two fusion factors are measured: K=32 is the production
# flagship value (the learner's TPU auto setting — kept small so publish/
# checkpoint cadences stay fine-grained and actor weight staleness stays
# bounded), K=256 is the peak-capability point (neither value has been
# measured on a directly attached chip; tuning them is a perf_opt
# issue's job).  The headline
# ``updates_per_sec`` is the PRODUCTION K=32 figure — what the learner
# actually runs — and the K=256 capability is published separately as
# ``updates_per_sec_peak`` (round-2 advisor finding: downstream consumers
# of the one-line JSON read the headline as production throughput).
MICRO_BATCH = 128
MICRO_DISPATCH = 32
MICRO_DISPATCH_PEAK = 256

# Peak FLOP/s table + the XLA cost-analysis FLOPs extraction now live in
# utils/perf.py (the live perf plane shares them with this bench and
# tools/mfu_probe.py — previously three inline copies).
from pytorch_distributed_tpu.utils.perf import (  # noqa: E402
    PEAK_FLOPS, flops_of_compiled, peak_flops_of as _peak_flops,
)


def bench_micro() -> dict:
    """Learner updates/s on the fused HBM-replay hot loop, at the
    production fusion factor (K=32) and the peak one (K=256), plus the
    two-point dispatch-overhead fit."""
    import jax

    from pytorch_distributed_tpu.memory.device_replay import (
        DeviceReplay, build_uniform_fused_step, round_capacity,
    )
    from pytorch_distributed_tpu.models import DqnCnnModel
    from pytorch_distributed_tpu.ops.losses import (
        build_dqn_train_step, init_train_state, make_optimizer,
    )
    from pytorch_distributed_tpu.parallel.mesh import make_mesh
    from pytorch_distributed_tpu.utils.experience import Transition

    B = MICRO_BATCH
    # NCHW rows, like production (factory.device_ring_channels_last is
    # False from measurement: the NHWC-resident variant A/B'd ~13% slower
    # on the v5 lite — TPU tiling pads the 4-wide channel minor dim)
    model = DqnCnnModel(action_space=6, norm_val=255.0)
    obs = np.zeros((1, 4, 84, 84), dtype=np.uint8)
    params = model.init(jax.random.PRNGKey(0), obs)
    tx = make_optimizer(lr=1e-4)
    state = init_train_state(params, tx)
    step = build_dqn_train_step(model.apply, tx, target_model_update=250)

    # multi-chip: ring rows shard over the mesh dp axis, train state
    # replicates, and XLA inserts the gradient all-reduce over ICI
    n_dev = len(jax.devices())
    mesh = make_mesh() if n_dev > 1 else None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        state = jax.device_put(state, NamedSharding(mesh, P()))

    # HBM ring filled once — the learner hot loop samples on device and
    # never re-transfers host pages (ingest runs between dispatches in
    # production, off this loop's critical path).  2048 rows keep the
    # fill's H2D cost down while sampling exactly like the production
    # 50k buffer
    ring = DeviceReplay(capacity=round_capacity(2048, mesh),
                        state_shape=(4, 84, 84),
                        state_dtype=np.uint8, mesh=mesh)
    rng = np.random.default_rng(0)
    C = 512
    for _ in range(ring.capacity // C):
        ring.feed_chunk(Transition(
            state0=rng.integers(0, 255, size=(C, 4, 84, 84)).astype(
                np.uint8),
            action=rng.integers(0, 6, size=C).astype(np.int32),
            reward=rng.normal(size=C).astype(np.float32),
            gamma_n=np.full(C, 0.99 ** 5, dtype=np.float32),
            state1=rng.integers(0, 255, size=(C, 4, 84, 84)).astype(
                np.uint8),
            terminal1=(rng.random(C) < 0.1).astype(np.float32)))

    key = jax.random.PRNGKey(0)
    flops_per_update = None

    def drain(m):
        # every window ends with a scalar device_get off the last step's
        # metrics, which the data dependency chains behind the whole
        # window's updates — a value fetch bounds the window whatever
        # the backend's block_until_ready does.  (The fetch-instead-of-
        # block rule was adopted on a remote backend that no longer
        # exists; whether it still matters on a directly attached chip
        # is not measured.)
        return float(jax.device_get(m["learner/critic_loss"]))

    def measure(K: int):
        """Fetch-bounded update rates at fusion factor K (median of
        independent windows: one long window would let a single stall
        skew the figure)."""
        nonlocal key, state, flops_per_update
        fused = build_uniform_fused_step(step, B, steps_per_call=K)

        def keymat():
            nonlocal key
            key, sub = jax.random.split(key)
            return jax.random.split(sub, K)

        # Compile explicitly so the flops of THIS executable can be read
        # off its cost analysis (exact for the HLO, no hand model).
        # XLA's cost analysis counts a scan/while body ONCE (verified:
        # identical flops for K=1/8/64), so the figure is per-update.
        compiled = fused.lower(state, ring.state, keymat()).compile()
        if flops_per_update is None:
            flops_per_update = flops_of_compiled(compiled)

        # warmup: the first dispatches pay one-time set-up
        for _ in range(10):
            state, metrics = compiled(state, ring.state, keymat())
        drain(metrics)

        # Key splits are pre-dispatched OUTSIDE the window (the
        # production learner amortizes one split per 64 dispatches,
        # agents/learner.py key_buf) so the timed loop issues exactly
        # the production program stream.
        # constant updates-per-window across K so the end-of-window drain
        # fetch is amortized identically (short windows would tax high-K
        # rates with a full fetch RTT per ~0.3s of work)
        windows, iters = 8, max(7680 // K, 1)
        rates, enq_rates = [], []
        for _ in range(windows):
            keysets = [keymat() for _ in range(iters)]
            jax.block_until_ready(keysets[-1])
            t0 = time.perf_counter()
            for ks in keysets:
                state, metrics = compiled(state, ring.state, ks)
            t_enq = time.perf_counter() - t0
            drain(metrics)
            rates.append(iters * K / (time.perf_counter() - t0))
            enq_rates.append(iters * K / t_enq)
        return rates, enq_rates

    rates32, enq32 = measure(MICRO_DISPATCH)
    rates_pk, _ = measure(MICRO_DISPATCH_PEAK)

    k32 = float(np.median(rates32))
    peak_rate = float(np.median(rates_pk))
    out = {
        # headline: the PRODUCTION fusion factor (the learner's TPU auto
        # K=32) — what config 8 actually dispatches
        "updates_per_sec": round(k32, 2),
        "updates_per_sec_min": round(float(np.min(rates32)), 2),
        "updates_per_sec_p90": round(float(np.percentile(rates32, 90)),
                                     2),
        "updates_per_sec_windows": [round(r, 1) for r in rates32],
        "steps_per_dispatch": MICRO_DISPATCH,
        # peak-fusion capability point (K=256, ~91% of the fitted
        # dispatch-overhead asymptote)
        "updates_per_sec_peak": round(peak_rate, 2),
        "updates_per_sec_peak_p90": round(float(np.percentile(rates_pk,
                                                              90)), 2),
        "steps_per_dispatch_peak": MICRO_DISPATCH_PEAK,
        # how fast dispatches ENQUEUE: the gap to the fetch-bounded
        # rates is work still in flight when the host timer stopped
        "updates_per_sec_enqueue": round(float(np.median(enq32)), 2),
        "batch_size": B,
    }
    # two-point fit of rate(K) = K / (K * t_update + t_dispatch): how
    # much of the gap to the chip-bound asymptote each K leaves
    k_a, k_b = MICRO_DISPATCH, MICRO_DISPATCH_PEAK
    t_a, t_b = k_a / k32, k_b / peak_rate
    t_update = (t_b - t_a) / (k_b - k_a)
    t_dispatch = t_a - k_a * t_update
    if t_update > 0 and t_dispatch > 0:
        # both positive or the fit is noise (e.g. a stall during the
        # K=32 windows) — omit rather than publish nonsense
        out["dispatch_overhead_ms"] = round(1e3 * t_dispatch, 3)
        out["chip_bound_updates_per_sec"] = round(1.0 / t_update, 1)
    if flops_per_update:
        achieved = k32 * flops_per_update
        achieved_pk = peak_rate * flops_per_update
        out["flops_per_update"] = round(flops_per_update)
        out["achieved_flops_per_sec"] = round(achieved)
        peak = _peak_flops(jax.devices()[0])
        out["mfu"] = round(achieved / peak, 4) if peak else None
        out["mfu_peak"] = round(achieved_pk / peak, 4) if peak else None
        # what bound the MFU in r03 (a note, not a measurement of this run)
        out["mfu_bound"] = _mfu_bound_note()
    return out


def _mfu_bound_note() -> str:
    """The micro section's ``mfu_bound`` string: the r03 trace finding
    (2026-07-31, v5 lite; batch- and dtype-invariant, channels-last A/B'd
    slower), taken over a link that no longer exists.  What bounds the
    learner on the directly attached chip is in PERF.md, per program
    phase, from a traced run of a benchmark cell."""
    return ("narrow conv channels (4/32/64) underfill the 128-lane "
            "MXU; batch- and dtype-invariant, channels-last A/B'd "
            "slower; ~25% of device time is XLA's own re-tiling "
            "(r03; see PERF.md for the directly attached chip)")


FAMILY_DISPATCH = 8  # steps per dispatched program in the family rows


def bench_families() -> dict:
    """On-chip updates/s + FLOPs for EVERY shipped model family's learner
    program (SURVEY §3.3 applied per family) — not just the flagship CNN.

    Each row builds the exact train step the factory gives the learner for
    that CONFIGS row and measures it PRODUCTION-SHAPED: fused over an HBM
    ring at ``FAMILY_DISPATCH`` update steps per dispatched XLA program —
    the uniform transition ring (memory/device_replay.py) for the flat
    families, the prioritized segment ring (memory/device_sequence.py,
    sampling + priority write-back fused in) for the sequence/transformer
    families.  One-update-per-dispatch figures measure dispatch latency,
    not the model, so every row carries its ``steps_per_dispatch``, and
    windows end on the same ``drain()``-style value fetch as
    bench_micro.  The flagship dqn-cnn fused row stays in bench_micro.
    """
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.config import build_options
    from pytorch_distributed_tpu.factory import (
        build_model, build_train_state_and_step, init_params, lstm_dim_of,
        probe_env, sequence_pack_frames,
    )
    from pytorch_distributed_tpu.memory.device_replay import (
        DeviceReplay, build_uniform_fused_step,
    )
    from pytorch_distributed_tpu.memory.device_sequence import (
        DeviceSequenceReplay, SegmentChunk,
    )
    from pytorch_distributed_tpu.utils.experience import Transition

    rng = np.random.default_rng(0)
    K = FAMILY_DISPATCH

    def fill_flat_ring(spec, capacity=1024):
        S = spec.state_shape
        img = len(S) == 3
        ring = DeviceReplay(
            capacity, S, spec.action_shape,
            state_dtype=np.uint8 if img else np.float32,
            action_dtype=spec.action_dtype)
        C = 256
        obs = ((lambda n: rng.integers(0, 255, (n, *S)).astype(np.uint8))
               if img else
               (lambda n: rng.normal(size=(n, *S)).astype(np.float32)))
        act = ((lambda n: rng.integers(0, spec.num_actions, n).astype(
                    np.int32)) if spec.discrete else
               (lambda n: rng.uniform(-1, 1, (n, spec.action_dim)).astype(
                    np.float32)))
        for _ in range(capacity // C):
            ring.feed_chunk(Transition(
                state0=obs(C), action=act(C),
                reward=rng.normal(size=C).astype(np.float32),
                gamma_n=np.full(C, 0.99 ** 5, np.float32),
                state1=obs(C),
                terminal1=(rng.random(C) < 0.1).astype(np.float32)))
        return ring

    def fill_seq_ring(opt, spec, capacity=256):
        L = opt.agent_params.seq_len
        S = spec.state_shape
        pack = sequence_pack_frames(opt)
        img = len(S) == 3
        dt = np.uint8 if img else np.float32
        ring = DeviceSequenceReplay(
            capacity, L, S, lstm_dim_of(opt), state_dtype=dt,
            priority_exponent=opt.memory_params.priority_exponent,
            importance_weight=opt.memory_params.priority_weight,
            pack_frames=pack)
        C = 64
        oshape = (L + pack, *S[1:]) if pack else (L + 1, *S)
        for _ in range(capacity // C):
            obs = (rng.integers(0, 255, (C, *oshape)).astype(np.uint8)
                   if img else
                   rng.normal(size=(C, *oshape)).astype(np.float32))
            ring.feed_chunk(SegmentChunk(
                obs=obs,
                action=rng.integers(0, max(spec.num_actions, 2),
                                    (C, L)).astype(np.int32),
                reward=rng.normal(size=(C, L)).astype(np.float32),
                terminal=np.zeros((C, L), np.float32),
                mask=np.ones((C, L), np.float32),
                c0=np.zeros((C, ring.lstm_dim), np.float32),
                h0=np.zeros((C, ring.lstm_dim), np.float32)))
        return ring

    # family -> (CONFIGS row, batch, option overrides); seq rows use the
    # drive-validated seq_len 16 geometry
    FAMILIES = [
        ("dqn-mlp", 1, 128, {}),
        ("ddpg-mlp", 2, 64, {}),
        ("drqn-mlp", 13, 32, dict(seq_len=16, burn_in=4)),
        ("drqn-cnn", 14, 32, dict(seq_len=16, burn_in=4)),
        ("dtqn-mlp", 15, 32, dict(seq_len=16)),
        ("dtqn-moe", 17, 32, dict(seq_len=16)),
        ("dtqn-pipe", 18, 32, dict(seq_len=16)),
    ]
    # ISSUE-13 megabatch leg for the dispatch-bound flat families: same
    # geometry, fused at megabatch M (K/M widened-gather groups per
    # dispatch) — the row's ``updates_per_sec_megabatch`` is the
    # campaign's gated capability figure, ``updates_per_sec`` stays the
    # sequential production default
    MEGABATCH_FAMILIES = {"dqn-mlp": 8, "ddpg-mlp": 8}

    peak = _peak_flops(jax.devices()[0])
    out = {}
    for name, cfg, B, over in FAMILIES:
        opt = build_options(cfg, batch_size=B, **over)
        spec = probe_env(opt)
        model = build_model(opt, spec)
        params = init_params(opt, spec, model, seed=0)
        state, step = build_train_state_and_step(opt, spec, model, params,
                                                 mesh=None)
        is_seq = opt.model_type.startswith(("drqn", "dtqn"))
        key = jax.random.PRNGKey(0)

        def keymat():
            nonlocal key
            key, sub = jax.random.split(key)
            return jax.random.split(sub, K)

        if is_seq:
            ring = fill_seq_ring(opt, spec)
            fused = ring.build_fused_step(step, B, steps_per_call=K)
            beta = jnp.asarray(0.6, jnp.float32)
            rs = ring.state
            compiled = fused.lower(state, rs, keymat(), beta).compile()

            def dispatch():
                nonlocal state, rs
                state, rs, metrics = compiled(state, rs, keymat(), beta)
                return metrics
        else:
            ring = fill_flat_ring(spec)
            fused = build_uniform_fused_step(step, B, steps_per_call=K)
            compiled = fused.lower(state, ring.state, keymat()).compile()

            def dispatch():
                nonlocal state
                state, metrics = compiled(state, ring.state, keymat())
                return metrics

        # scan bodies are counted once by cost_analysis (verified in
        # bench_micro across K=1/8/64), so this is per-update
        flops = flops_of_compiled(compiled)
        for _ in range(5):  # warmup + link settle
            metrics = dispatch()
        float(jax.device_get(metrics["learner/critic_loss"]))
        windows, iters, rates = 5, max(64 // K, 8), []
        for _ in range(windows):
            t0 = time.perf_counter()
            for _ in range(iters):
                metrics = dispatch()
            # fetch-bounded: the device_get chains behind the window
            float(jax.device_get(metrics["learner/critic_loss"]))
            rates.append(iters * K / (time.perf_counter() - t0))
        row = {
            "updates_per_sec": round(float(np.median(rates)), 2),
            "batch_size": B,
            "steps_per_dispatch": K,
            "megabatch": 1,
            "replay_fused": "device-sequence" if is_seq else "device",
        }
        if is_seq:
            row["seq_len"] = opt.agent_params.seq_len
        if flops:
            row["flops_per_update"] = round(flops)
            if peak:
                row["mfu"] = round(
                    float(np.median(rates)) * flops / peak, 4)
        M = MEGABATCH_FAMILIES.get(name, 0)
        if M > 1:
            from pytorch_distributed_tpu.factory import (
                build_megabatch_train_step,
            )
            from pytorch_distributed_tpu.memory.device_replay import (
                build_uniform_fused_step as _fuse,
            )

            # fresh params: the sequential leg's donating dispatches
            # consumed the original state's buffers, so re-init rather
            # than alias them
            mparams = init_params(opt, spec, model, seed=0)
            mstate, _ = build_train_state_and_step(opt, spec, model,
                                                   mparams, mesh=None)
            mega = build_megabatch_train_step(opt, model)
            mfused = _fuse(step, B, steps_per_call=K, megabatch=M,
                           megabatch_step=mega)
            mcompiled = mfused.lower(mstate, ring.state,
                                     keymat()).compile()
            for _ in range(5):
                mstate, mmetrics = mcompiled(mstate, ring.state,
                                             keymat())
            float(jax.device_get(mmetrics["learner/critic_loss"]))
            mrates = []
            for _ in range(windows):
                t0 = time.perf_counter()
                for _ in range(iters):
                    mstate, mmetrics = mcompiled(mstate, ring.state,
                                                 keymat())
                float(jax.device_get(mmetrics["learner/critic_loss"]))
                mrates.append(iters * K / (time.perf_counter() - t0))
            row["updates_per_sec_megabatch"] = round(
                float(np.median(mrates)), 2)
            row["megabatch_k"] = M
            row["megabatch_speedup"] = round(
                row["updates_per_sec_megabatch"]
                / max(row["updates_per_sec"], 1e-9), 3)
        out[name] = row
        print(f"[bench_families] {name}: {row}", file=sys.stderr,
              flush=True)
    return {"families": out}


def bench_sampler() -> dict:
    """Pallas hierarchical sampler vs flat XLA cumsum+searchsorted on the
    production PER geometry (50k-row priority vector, 128 draws) — the
    regression canary for memory/device_per.py's production draw path.
    TPU only: the Pallas kernel targets the TPU vector unit; on CPU the
    XLA scheme IS the production path and there is nothing to compare.

    Both schemes scan 32 draw batches inside one dispatched program so
    the figure compares kernel cost, not dispatch RTT; windows end with a
    value fetch (the async-dispatch guard bench_micro documents)."""
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        return {}
    from pytorch_distributed_tpu.ops.pallas_sampling import (
        hierarchical_sample,
    )

    N, B, SCAN = 50048, 128, 32
    rng = np.random.default_rng(0)
    p = jnp.asarray(rng.gamma(1.0, 1.0, N).astype(np.float32))

    def xla_draw(prio, key):
        cdf = jnp.cumsum(prio)
        u = jax.random.uniform(key, (B,)) * cdf[-1]
        return jnp.clip(jnp.searchsorted(cdf, u, side="right"),
                        0, N - 1).astype(jnp.int32)

    def pallas_draw(prio, key):
        idx, _probs = hierarchical_sample(prio, key, B)
        return idx

    def scanned(draw):
        def many(prio, keys):
            def body(acc, k):
                return acc + jnp.sum(draw(prio, k)), None
            acc, _ = jax.lax.scan(body, jnp.int32(0), keys)
            return acc
        return jax.jit(many)

    out = {}
    key = jax.random.PRNGKey(0)
    for label, draw in (("xla", xla_draw), ("pallas", pallas_draw)):
        try:
            fn = scanned(draw)
            keys = jax.random.split(key, SCAN)
            int(jax.device_get(fn(p, keys)))  # compile + warm
            rates = []
            for _ in range(5):
                key, sub = jax.random.split(key)
                keys = jax.random.split(sub, SCAN)
                t0 = time.perf_counter()
                int(jax.device_get(fn(p, keys)))  # fetch-bounded
                rates.append(SCAN / (time.perf_counter() - t0))
            out[f"{label}_draws_per_sec"] = round(float(np.median(rates)),
                                                  1)
        except Exception as e:  # noqa: BLE001 - publish the failure
            out[f"{label}_error"] = str(e)[:200]
    out.update(n_rows=N, batch_size=B)
    return {"sampler": out}


def bench_act_ab() -> dict:
    """Host-CPU vs on-device batched actor forward.

    The production actor pins rollout inference to the host CPU
    (agents/actor.py, utils/helpers.pin_to_cpu) — a decision that has
    never been measured on a directly attached chip.  This measures
    all three candidate paths at the production vector width (16 envs,
    Nature-CNN flagship) so the pin is justified by numbers on WHATEVER
    hardware runs the bench:

    - ``act_ms_host``: jitted CPU forward on host-pinned params — the
      production path (reference analogue: the actor's own CUDA replica,
      reference dqn_actor.py:84-85).
    - ``act_ms_device``: obs batch up (full 4-stack, uint8), forward on
      the accelerator, actions down.
    - ``act_ms_device_packed``: only the NEWEST frame ships (16x84x84);
      a device-resident rolling stack rebuilds the 4-stack on chip
      (donated buffer) — the frame-packed upload variant.
    """
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.models import DqnCnnModel
    from pytorch_distributed_tpu.models.policies import (
        build_epsilon_greedy_act,
    )
    from pytorch_distributed_tpu.utils.helpers import pin_to_cpu

    NV = 16  # production env-vector width
    model = DqnCnnModel(action_space=6, norm_val=255.0)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((1, 4, 84, 84), np.uint8))
    act = build_epsilon_greedy_act(model.apply)
    rng = np.random.default_rng(0)
    frames = rng.integers(0, 255, (64, NV, 84, 84)).astype(np.uint8)
    obs_host = np.repeat(frames[0][:, None], 4, axis=1)  # (NV, 4, 84, 84)
    eps = np.full(NV, 0.1, np.float32)

    def timed(tick, n=40, warm=5):
        for _ in range(warm):
            tick(0)
        t0 = time.perf_counter()
        for i in range(n):
            tick(i)
        return round(1e3 * (time.perf_counter() - t0) / n, 3)

    out = {}
    # --- host path (production): CPU-committed params, numpy obs --------
    cparams = pin_to_cpu(params)
    ckey = pin_to_cpu(jax.random.PRNGKey(1))
    ceps = pin_to_cpu(jnp.asarray(eps))

    def host_tick(i):
        a, _q, _m = act(cparams, obs_host, ckey, ceps)
        np.asarray(a)  # actions down (actors consume numpy)
    out["act_ms_host"] = timed(host_tick)

    dev = jax.devices()[0]
    if dev.platform != "cpu":
        dparams = jax.device_put(params, dev)
        dkey = jax.device_put(jax.random.PRNGKey(1), dev)
        deps = jax.device_put(jnp.asarray(eps), dev)

        # --- full-stack upload: obs up per tick, actions down -----------
        def dev_tick(i):
            o = jax.device_put(obs_host, dev)
            a, _q, _m = act(dparams, o, dkey, deps)
            np.asarray(a)
        out["act_ms_device"] = timed(dev_tick)

        # --- frame-packed upload: newest frame up, stack rolls on chip --
        @functools.partial(jax.jit, donate_argnums=(1,))
        def packed_act(p, stack, new, key, e):
            stack = jnp.concatenate([stack[:, 1:], new[:, None]], axis=1)
            a, q, m = act(p, stack, key, e)
            return a, stack
        stack_box = [jax.device_put(jnp.asarray(obs_host), dev)]

        def packed_tick(i):
            new = jax.device_put(frames[i % len(frames)], dev)
            a, stack_box[0] = packed_act(dparams, stack_box[0], new, dkey,
                                         deps)
            np.asarray(a)
        out["act_ms_device_packed"] = timed(packed_tick)
        out["act_device_kind"] = getattr(dev, "device_kind", "?")
    return {"act_ab": out} if out else {}


def bench_health_overhead(windows: int = 6,
                          updates_per_window: int = 512) -> dict:
    """Health-sentinel guard cost (ISSUE 5 acceptance): the SAME fused
    flagship learner program (batch-128 Nature-CNN over an HBM ring,
    K=32 scanned updates per dispatch) measured with the in-jit finite
    guard ON (production default: loss/grad/TD checked in-graph, state
    select per leaf) vs OFF.  The guard must stay in-graph — no host
    syncs on the hot path — so the acceptance bar is
    ``health_overhead_frac`` < 0.02 of median step time.  Both variants
    use the fetch-bounded window timing bench_micro documents."""
    import jax

    from pytorch_distributed_tpu.memory.device_replay import (
        DeviceReplay, build_uniform_fused_step, round_capacity,
    )
    from pytorch_distributed_tpu.models import DqnCnnModel
    from pytorch_distributed_tpu.ops.losses import (
        build_dqn_train_step, init_train_state, make_optimizer,
    )
    from pytorch_distributed_tpu.utils.experience import Transition

    B, K = MICRO_BATCH, MICRO_DISPATCH
    model = DqnCnnModel(action_space=6, norm_val=255.0)
    obs = np.zeros((1, 4, 84, 84), dtype=np.uint8)
    params = model.init(jax.random.PRNGKey(0), obs)
    tx = make_optimizer(lr=1e-4)

    ring = DeviceReplay(capacity=round_capacity(2048, None),
                        state_shape=(4, 84, 84), state_dtype=np.uint8)
    rng = np.random.default_rng(0)
    C = 512
    for _ in range(ring.capacity // C):
        ring.feed_chunk(Transition(
            state0=rng.integers(0, 255, (C, 4, 84, 84)).astype(np.uint8),
            action=rng.integers(0, 6, C).astype(np.int32),
            reward=rng.normal(size=C).astype(np.float32),
            gamma_n=np.full(C, 0.99 ** 5, dtype=np.float32),
            state1=rng.integers(0, 255, (C, 4, 84, 84)).astype(np.uint8),
            terminal1=(rng.random(C) < 0.1).astype(np.float32)))

    key = jax.random.PRNGKey(0)

    def measure(guard: bool) -> float:
        nonlocal key
        step = build_dqn_train_step(model.apply, tx,
                                    target_model_update=250, guard=guard)
        fused = build_uniform_fused_step(step, B, steps_per_call=K,
                                         donate=False)
        state = init_train_state(params, tx)

        def keymat():
            nonlocal key
            key, sub = jax.random.split(key)
            return jax.random.split(sub, K)

        compiled = fused.lower(state, ring.state, keymat()).compile()
        for _ in range(5):
            state, metrics = compiled(state, ring.state, keymat())
        float(jax.device_get(metrics["learner/critic_loss"]))
        iters, rates = max(updates_per_window // K, 2), []
        for _ in range(windows):
            keysets = [keymat() for _ in range(iters)]
            jax.block_until_ready(keysets[-1])
            t0 = time.perf_counter()
            for ks in keysets:
                state, metrics = compiled(state, ring.state, ks)
            float(jax.device_get(metrics["learner/critic_loss"]))
            rates.append(iters * K / (time.perf_counter() - t0))
        return float(np.median(rates))

    unguarded = measure(False)
    guarded = measure(True)
    frac = (unguarded - guarded) / unguarded if unguarded > 0 else None
    out = {
        "updates_per_sec_guarded": round(guarded, 2),
        "updates_per_sec_unguarded": round(unguarded, 2),
        # clamped at 0: window noise routinely makes the guarded run
        # measure FASTER on a noisy host; negative overhead is noise
        "health_overhead_frac": (round(max(frac, 0.0), 4)
                                 if frac is not None else None),
        "steps_per_dispatch": K,
        "batch_size": B,
    }
    print(f"[bench_health_overhead] {out}", file=sys.stderr, flush=True)
    return {"health_overhead": out}


def _mlp_fused_program(B: int, K: int, megabatch: int = 1):
    """The dqn-mlp learner program fused over a small uniform ring —
    the CPU-safe geometry shared by ``bench_smoke`` and the smoke
    variant of ``bench_perf_overhead`` (the flagship CNN takes minutes
    to compile on a CPU host; the MLP takes seconds).  Returns
    ``(fused, state, ring)``.  ``megabatch`` M > 1 builds the ISSUE-13
    megabatched variant (K/M widened-gather groups per dispatch)."""
    from pytorch_distributed_tpu.config import build_options
    from pytorch_distributed_tpu.factory import (
        build_model, build_train_state_and_step, init_params, probe_env,
    )
    from pytorch_distributed_tpu.memory.device_replay import (
        DeviceReplay, build_uniform_fused_step,
    )
    from pytorch_distributed_tpu.utils.experience import Transition

    opt = build_options(1, batch_size=B)  # dqn-mlp on the fake chain env
    spec = probe_env(opt)
    model = build_model(opt, spec)
    params = init_params(opt, spec, model, seed=0)
    state, step = build_train_state_and_step(opt, spec, model, params,
                                             mesh=None)
    rng = np.random.default_rng(0)
    ring = DeviceReplay(256, spec.state_shape, spec.action_shape,
                        state_dtype=np.float32,
                        action_dtype=spec.action_dtype)
    C = 64
    for c in range(ring.capacity // C):
        # rows carry provenance (two fake actors, version 1) so the
        # provenance-overhead bench's telemetry leg computes on REAL
        # stamps, not an all-sentinel fast path
        prov = np.stack([np.array([j % 2, j % 8, 1, c * C + j],
                                  np.int32) for j in range(C)])
        ring.feed_chunk(Transition(
            state0=rng.normal(size=(C, *spec.state_shape)).astype(
                np.float32),
            action=rng.integers(0, spec.num_actions, C).astype(np.int32),
            reward=rng.normal(size=C).astype(np.float32),
            gamma_n=np.full(C, 0.99 ** 5, np.float32),
            state1=rng.normal(size=(C, *spec.state_shape)).astype(
                np.float32),
            terminal1=(rng.random(C) < 0.1).astype(np.float32),
            prov=prov))
    mb_kw = {}
    if megabatch > 1:
        from pytorch_distributed_tpu.factory import (
            build_megabatch_train_step,
        )

        mb_kw = dict(megabatch=megabatch,
                     megabatch_step=build_megabatch_train_step(opt, model))
    fused = build_uniform_fused_step(step, B, steps_per_call=K,
                                     donate=False, **mb_kw)
    return fused, state, ring


def bench_perf_overhead(windows: int = 6,
                        updates_per_window: int = 512,
                        smoke: bool = False) -> dict:
    """Perf-plane monitor cost (ISSUE 6 acceptance): the SAME fused
    flagship learner program as bench_micro (batch-128 Nature-CNN over
    an HBM ring, K=32 scanned updates per dispatch) measured with a live
    ``utils/perf.PerfMonitor`` doing its production accounting — one
    ``note_updates`` per dispatch plus a ``drain()`` + JSONL flush per
    window, exactly the learner's stats-cadence wiring — vs bare.  The
    monitor's hot-path surface is one integer add, so the acceptance
    bar is ``perf_overhead_frac`` < 0.02 of median step time.  Both
    variants use the fetch-bounded window timing bench_micro documents.

    ``smoke=True`` swaps in the CPU-safe dqn-mlp geometry (shared with
    ``bench_smoke``) so the measurement logic itself is CI-exercisable —
    the flagship CNN program takes minutes to compile on a CPU host."""
    import jax

    from pytorch_distributed_tpu.config import PerfParams
    from pytorch_distributed_tpu.utils import perf
    from pytorch_distributed_tpu.utils.metrics import MetricsWriter

    if smoke:
        B, K = 32, 8
        fused, state0, ring = _mlp_fused_program(B, K)
    else:
        from pytorch_distributed_tpu.memory.device_replay import (
            DeviceReplay, build_uniform_fused_step, round_capacity,
        )
        from pytorch_distributed_tpu.models import DqnCnnModel
        from pytorch_distributed_tpu.ops.losses import (
            build_dqn_train_step, init_train_state, make_optimizer,
        )
        from pytorch_distributed_tpu.utils.experience import Transition

        B, K = MICRO_BATCH, MICRO_DISPATCH
        model = DqnCnnModel(action_space=6, norm_val=255.0)
        params = model.init(jax.random.PRNGKey(0),
                            np.zeros((1, 4, 84, 84), dtype=np.uint8))
        tx = make_optimizer(lr=1e-4)
        ring = DeviceReplay(capacity=round_capacity(2048, None),
                            state_shape=(4, 84, 84), state_dtype=np.uint8)
        rng = np.random.default_rng(0)
        C = 512
        for _ in range(ring.capacity // C):
            ring.feed_chunk(Transition(
                state0=rng.integers(0, 255, (C, 4, 84, 84)).astype(
                    np.uint8),
                action=rng.integers(0, 6, C).astype(np.int32),
                reward=rng.normal(size=C).astype(np.float32),
                gamma_n=np.full(C, 0.99 ** 5, dtype=np.float32),
                state1=rng.integers(0, 255, (C, 4, 84, 84)).astype(
                    np.uint8),
                terminal1=(rng.random(C) < 0.1).astype(np.float32)))
        step = build_dqn_train_step(model.apply, tx,
                                    target_model_update=250)
        fused = build_uniform_fused_step(step, B, steps_per_call=K,
                                         donate=False)
        state0 = init_train_state(params, tx)

    key = jax.random.PRNGKey(0)

    def keymat():
        nonlocal key
        key, sub = jax.random.split(key)
        return jax.random.split(sub, K)

    # ONE compile shared by both variants (donate=False keeps state0
    # reusable): the measurement is of the monitor, not the compiler
    compiled = fused.lower(state0, ring.state, keymat()).compile()
    flops = flops_of_compiled(compiled)

    def measure(monitored: bool) -> float:
        state = state0
        monitor, writer, mstep = None, None, 0
        if monitored:
            monitor = perf.PerfMonitor(
                "bench", PerfParams(enabled=True), prefix="learner")
            # immune to ambient TPU_APEX_PERF=0 (resolve() lets env
            # override the explicit params): a disabled monitor would
            # measure bare-vs-bare and report a vacuous 0% overhead
            monitor.enabled = True
            monitor.flops_per_update = flops
            monitor.register_jit("fused_step",
                                 getattr(fused, "_cache_size", None))
            writer = MetricsWriter(
                tempfile.mkdtemp(prefix="bench_perf_"),
                enable_tensorboard=False, role="learner")
            monitor.drain()  # anchor
        for _ in range(5):
            state, metrics = compiled(state, ring.state, keymat())
        float(jax.device_get(metrics["learner/critic_loss"]))
        iters, rates = max(updates_per_window // K, 2), []
        for _ in range(windows):
            keysets = [keymat() for _ in range(iters)]
            jax.block_until_ready(keysets[-1])
            t0 = time.perf_counter()
            for ks in keysets:
                state, metrics = compiled(state, ring.state, ks)
                if monitored:
                    monitor.note_updates(K)
            if monitored:
                mstep += iters * K
                writer.scalars(monitor.drain(step=mstep), step=mstep)
            float(jax.device_get(metrics["learner/critic_loss"]))
            rates.append(iters * K / (time.perf_counter() - t0))
        if writer is not None:
            writer.close()
        return float(np.median(rates))

    bare = measure(False)
    monitored = measure(True)
    frac = (bare - monitored) / bare if bare > 0 else None
    out = {
        "updates_per_sec_monitored": round(monitored, 2),
        "updates_per_sec_bare": round(bare, 2),
        # clamped at 0: window noise routinely makes the monitored run
        # measure FASTER on a noisy host; negative overhead is noise
        "perf_overhead_frac": (round(max(frac, 0.0), 4)
                               if frac is not None else None),
        "steps_per_dispatch": K,
        "batch_size": B,
        "geometry": "smoke-mlp" if smoke else "flagship-cnn",
    }
    print(f"[bench_perf_overhead] {out}", file=sys.stderr, flush=True)
    return {"perf_overhead": out}


def bench_provenance_overhead(windows: int = 5,
                              smoke: bool = False) -> dict:
    """Provenance-column cost on the fused hot paths (ISSUE 8
    acceptance): the data-plane X-ray must be <2% on both fused
    programs, enforced by the bench gate's absolute overhead band.

    Two legs, each instrumented-vs-bare on the SAME compiled jit:

    - **rollout** — the fused device rollout (emit="replay", linear
      policy: engine cost, not CNN FLOPs) dispatched WITH a provenance
      stamp (the (3,) int32 arg scattered as 4 extra int32 columns per
      emitted row) vs WITHOUT (columns written as the -1 sentinel —
      the write itself is schema-resident either way, so this measures
      the stamp's broadcast + the real column traffic).
    - **learner** — the fused learner step loop with the learner's
      stats-cadence telemetry running (one 256-row provenance gather
      D2H + the staleness/age/share numpy math + histogram rows per
      window, exactly agents/learner.py's wiring) vs bare.

    ``smoke=True`` shrinks N/windows to seconds-scale for CI; the
    measurement logic is identical.  Overhead fracs are clamped at 0 —
    negative overhead is window noise on a small host."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.config import build_options
    from pytorch_distributed_tpu.envs.device_env import build_device_env
    from pytorch_distributed_tpu.memory.device_replay import (
        DeviceReplay, provenance_sample,
    )
    from pytorch_distributed_tpu.models.policies import (
        build_fused_rollout, init_rollout_carry,
    )
    from pytorch_distributed_tpu.utils import health as health_mod
    from pytorch_distributed_tpu.utils.metrics import MetricsWriter

    N, K = (32, 8) if smoke else (256, 8)
    opt = build_options(4, visualize=False)
    env = build_device_env(opt.env_params, 0, N)
    apply_fn, params = _device_env_linear_policy(env.state_shape)
    roll = build_fused_rollout(apply_fn, env, nstep=5, gamma=0.99,
                               rollout_ticks=K, emit="replay")
    eps = jnp.full((N,), 0.1, jnp.float32)
    key = jnp.asarray(jax.random.PRNGKey(0))
    prov3 = jnp.asarray(np.array([0, 1, 0], np.int32))

    def rollout_rate(with_prov: bool) -> float:
        import gc

        gc.collect()
        # fresh ring per leg: the rollout DONATES the ring state, so a
        # leg must never reuse the other leg's consumed buffers
        ring = DeviceReplay(capacity=max(2 * K * N, 2048),
                            state_shape=env.state_shape,
                            state_dtype=np.uint8)
        box = [init_rollout_carry(env, 5), ring.state, jnp.int32(0)]

        def tick():
            carry, rs, tick0 = box
            if with_prov:
                carry, rs, stats = roll(params, carry, rs, key, tick0,
                                        eps, prov3)
            else:
                carry, rs, stats = roll(params, carry, rs, key, tick0,
                                        eps)
            int(jax.device_get(stats.fed))  # fetch-bounded
            box[:] = [carry, rs, tick0 + K]

        tick()  # warm/compile
        ticks = max(1, (512 if smoke else 2048) // (K * N))
        rates = []
        for _ in range(windows):
            t0 = time.perf_counter()
            for _ in range(ticks):
                tick()
            rates.append(N * K * ticks / (time.perf_counter() - t0))
        return float(np.median(rates))

    roll_bare = rollout_rate(False)
    roll_prov = rollout_rate(True)
    roll_frac = ((roll_bare - roll_prov) / roll_bare
                 if roll_bare > 0 else None)

    # ---- learner leg: fused step loop ± the stats-cadence telemetry ----
    B, LK = (32, 8)
    fused, state0, lring = _mlp_fused_program(B, LK)
    lkey = jax.random.PRNGKey(0)

    def keymat():
        nonlocal lkey
        lkey, sub = jax.random.split(lkey)
        return jax.random.split(sub, LK)

    compiled = fused.lower(state0, lring.state, keymat()).compile()
    prov_jit = jax.jit(provenance_sample, static_argnames="n")
    tel_key = jax.random.PRNGKey(7)

    def learner_rate(instrumented: bool) -> float:
        state = state0
        writer = None
        if instrumented:
            writer = MetricsWriter(
                tempfile.mkdtemp(prefix="bench_prov_"),
                enable_tensorboard=False, role="learner")
        for _ in range(5):
            state, metrics = compiled(state, lring.state, keymat())
        float(jax.device_get(metrics["learner/critic_loss"]))
        iters = max((128 if smoke else 512) // LK, 2)
        rates, mstep = [], 0
        for _ in range(windows):
            keysets = [keymat() for _ in range(iters)]
            jax.block_until_ready(keysets[-1])
            t0 = time.perf_counter()
            for ks in keysets:
                state, metrics = compiled(state, lring.state, ks)
            if instrumented:
                mstep += iters * LK
                pr, _fill = prov_jit(
                    lring.state, jax.random.fold_in(tel_key, mstep),
                    n=256)
                # the EXACT production computation (agents/learner.py
                # calls the same helper) — the bench must not drift
                # from what the learner actually pays per cadence
                ds = health_mod.provenance_stats(np.asarray(pr), 1,
                                                 mstep)
                if ds is not None:
                    writer.histogram("learner/staleness",
                                     ds["staleness"].tolist(),
                                     step=mstep)
                    writer.histogram("learner/sample_age",
                                     ds["age"].tolist(), step=mstep)
                    writer.histogram("replay/actor_share",
                                     ds["shares"].tolist(), step=mstep)
            float(jax.device_get(metrics["learner/critic_loss"]))
            rates.append(iters * LK / (time.perf_counter() - t0))
        if writer is not None:
            writer.close()
        return float(np.median(rates))

    learn_bare = learner_rate(False)
    learn_instr = learner_rate(True)
    learn_frac = ((learn_bare - learn_instr) / learn_bare
                  if learn_bare > 0 else None)
    fracs = [f for f in (roll_frac, learn_frac) if f is not None]
    out = {
        "rollout_frames_per_sec_bare": round(roll_bare, 1),
        "rollout_frames_per_sec_prov": round(roll_prov, 1),
        "rollout_overhead_frac": (round(max(roll_frac, 0.0), 4)
                                  if roll_frac is not None else None),
        "learner_updates_per_sec_bare": round(learn_bare, 2),
        "learner_updates_per_sec_instr": round(learn_instr, 2),
        "learner_overhead_frac": (round(max(learn_frac, 0.0), 4)
                                  if learn_frac is not None else None),
        # the gate's single number: worst of the two fused paths
        "provenance_overhead_frac": (round(max(max(fracs), 0.0), 4)
                                     if fracs else None),
        "rollout_envs": N,
        "geometry": "smoke" if smoke else "full",
    }
    print(f"[bench_provenance_overhead] {out}", file=sys.stderr,
          flush=True)
    return {"provenance_overhead": out}


def bench_metrics_overhead(windows: int = 6,
                           updates_per_window: int = 512,
                           smoke: bool = False) -> dict:
    """Mission-control plane cost (ISSUE 10 acceptance): the fused
    dqn-mlp learner loop with its per-window stats rows (the bare
    stats cadence both legs pay) vs the same loop with the FULL
    telemetry path live — a MissionControl tailing + ingesting the run
    dir and evaluating an alert rule per window (the gateway-host leg),
    plus a MetricsPusher tailing the same stream and pushing the
    window's scalar deltas to a local gateway over T_METRICS (the
    fleet-host leg, including its wire round-trip and the gateway-side
    aggregator ingest).  Both legs land in ONE number because a real
    fleet host pays one or the other; paying both here is the
    conservative bound.  Everything runs on the stats cadence — the
    dispatch hot loop itself is untouched by the plane — so the
    acceptance bar is ``metrics_overhead_frac`` < 0.02 of median step
    time (the bench_gate absolute overhead band).

    ``smoke=True`` shrinks windows/iters to seconds-scale for CI; the
    measurement logic is identical."""
    import jax

    from pytorch_distributed_tpu.agents.clocks import (
        ActorStats, GlobalClock,
    )
    from pytorch_distributed_tpu.agents.param_store import ParamStore
    from pytorch_distributed_tpu.config import AlertParams, MetricsParams
    from pytorch_distributed_tpu.parallel.dcn import DcnGateway
    from pytorch_distributed_tpu.utils import telemetry
    from pytorch_distributed_tpu.utils.metrics import MetricsWriter

    B, K = 32, 8
    if smoke:
        # windows stay SECONDS-wide even in smoke: the plane's cost is
        # per-cadence, so a too-narrow window measures timer noise, not
        # the plane (a 128-update window is ~0.3 s on this class of
        # host — one 15 ms scheduler hiccup reads as 5% "overhead")
        windows = min(windows, 4)
        updates_per_window = min(updates_per_window, 384)
    fused, state0, ring = _mlp_fused_program(B, K)
    key = jax.random.PRNGKey(0)

    def keymat():
        nonlocal key
        key, sub = jax.random.split(key)
        return jax.random.split(sub, K)

    # ONE compile shared by both legs (donate=False keeps state0
    # reusable): the measurement is of the telemetry plane, not XLA
    compiled = fused.lower(state0, ring.state, keymat()).compile()

    log_dir = tempfile.mkdtemp(prefix="bench_metrics_")
    writer = MetricsWriter(log_dir, enable_tensorboard=False,
                           role="learner")
    # gateway-side aggregator behind a REAL gateway socket: the push
    # leg pays the wire, the decode, and the ingest
    sink = telemetry.MissionControl(
        None, MetricsParams(enabled=True), AlertParams(enabled=False))
    gw = DcnGateway(ParamStore(4), GlobalClock(), ActorStats(),
                    put_chunk=lambda items: None,
                    host="127.0.0.1", port=0,
                    metrics_sink=sink.ingest_remote)
    # local leg: tail + ingest + one quiet-threshold rule pass
    mission = telemetry.MissionControl(
        log_dir, MetricsParams(enabled=True),
        AlertParams(rules="slow: learner/updates_per_s < 1 for 60s"))
    pusher = telemetry.MetricsPusher(("127.0.0.1", gw.port), log_dir,
                                     MetricsParams(enabled=True))

    state = state0
    for _ in range(5):
        state, metrics = compiled(state, ring.state, keymat())
    float(jax.device_get(metrics["learner/critic_loss"]))
    pusher.push_once()  # offset handshake + pipe warmup, outside timing

    # INTERLEAVED windows (bare, instrumented, bare, ...): this host
    # class drifts ±10% between back-to-back runs (VM steal/freq
    # noise), which back-to-back legs read as fake overhead; pairing
    # windows makes each leg sample the same host weather.  The GATE
    # number is NOT the rate difference (a difference of two noisy
    # medians reads scheduler hiccups as multi-% "overhead" on a
    # loaded 2-vCPU host — observed flaking the tier-1 smoke gate):
    # the plane runs on a seconds-scale CADENCE, so its honest cost is
    # the DIRECTLY TIMED tail+ingest+alert-eval+push work as a
    # fraction of the wall span it amortizes over — one cadence every
    # other ~1 s window ≈ the production poll_s/push_s density.  The
    # A/B rates stay in the output as context.
    iters = max(updates_per_window // K, 2)
    rates = {False: [], True: []}
    plane_s = 0.0
    total_s = 0.0
    mstep = 0
    for w in range(windows * 2):
        instrumented = bool(w % 2)
        keysets = [keymat() for _ in range(iters)]
        jax.block_until_ready(keysets[-1])
        t0 = time.perf_counter()
        for ks in keysets:
            state, metrics = compiled(state, ring.state, ks)
        mstep += iters * K
        # the bare stats cadence BOTH legs pay: one scalar flush per
        # window (what agents/learner.py does)
        writer.scalars({"learner/updates_per_s": float(iters * K),
                        "learner/ingest_queue_util": 0.0}, step=mstep)
        if instrumented:
            tp = time.perf_counter()
            mission.poll()        # tail + ingest + alert eval
            pusher.push_once()    # T_METRICS push of the deltas
            plane_s += time.perf_counter() - tp
        float(jax.device_get(metrics["learner/critic_loss"]))
        dt = time.perf_counter() - t0
        total_s += dt
        rates[instrumented].append(iters * K / dt)
    writer.close()
    pushed_rows = pusher.pushed_rows
    mission.stop()
    gw.close()

    bare = float(np.median(rates[False]))
    instr = float(np.median(rates[True]))
    frac = plane_s / total_s if total_s > 0 else None
    out = {
        "updates_per_sec_bare": round(bare, 2),
        "updates_per_sec_metrics": round(instr, 2),
        # the gate number: cadence work / wall span it amortizes over
        "metrics_overhead_frac": (round(frac, 4)
                                  if frac is not None else None),
        "plane_ms_per_cadence": round(plane_s / max(windows, 1) * 1e3,
                                      2),
        "pushed_rows": int(pushed_rows),
        "steps_per_dispatch": K,
        "batch_size": B,
        "geometry": "smoke-mlp" if smoke else "mlp",
    }
    print(f"[bench_metrics_overhead] {out}", file=sys.stderr, flush=True)
    return {"metrics_overhead": out}


def bench_flow_overhead(chunks: int = 600, rows: int = 16,
                        smoke: bool = False) -> dict:
    """Flow-control plane cost on the ingest hot path (ISSUE 11
    acceptance): a real DcnClient→DcnGateway wire ingest loop with the
    plane at its production default (enabled, healthy — no credits on
    the wire) measures the per-chunk ingest span, and the plane's
    per-chunk adds — ``GatewayFlow.admit`` (time-gated governor
    refresh + token-bucket meter) plus the ``grant`` read riding the
    ack — are DIRECTLY timed in isolation.  The gate number
    ``flow_overhead_frac`` is flow-work-per-chunk over ingest-span-
    per-chunk, held under the 0.02 absolute band by bench_gate — the
    PR-10 lesson applies verbatim: a difference of two noisy wire
    throughputs on this loaded 2-vCPU host would read scheduler
    hiccups as multi-% fake overhead, so the rate difference is never
    the gate number.

    ``smoke=True`` shrinks the loop to sub-second for CI; the
    measurement logic is identical."""
    from pytorch_distributed_tpu.agents.clocks import (
        ActorStats, GlobalClock,
    )
    from pytorch_distributed_tpu.agents.param_store import ParamStore
    from pytorch_distributed_tpu.parallel.dcn import DcnClient, DcnGateway
    from pytorch_distributed_tpu.utils.experience import Transition

    flow_iters = 20_000
    if smoke:
        chunks = min(chunks, 250)
        flow_iters = 8_000
    z = np.zeros(4, dtype=np.float32)
    t = Transition(state0=z, action=np.int32(0), reward=np.float32(0.0),
                   gamma_n=np.float32(0.99), state1=z,
                   terminal1=np.float32(0.0))
    chunk = [(t, 1.0)] * rows
    store = ParamStore(4)
    store.publish(np.zeros(4, dtype=np.float32))
    gw = DcnGateway(store, GlobalClock(), ActorStats(),
                    put_chunk=lambda items: None, host="127.0.0.1",
                    port=0, pressure=lambda: 0.0)
    assert gw.flow is not None, "flow plane off at its production default"
    client = DcnClient(("127.0.0.1", gw.port), process_ind=0)
    for _ in range(30):  # session + validator + allocator warmup
        client.send_chunk(chunk)
    t0 = time.perf_counter()
    for _ in range(chunks):
        client.send_chunk(chunk)
    span = time.perf_counter() - t0
    # the plane's per-chunk work, timed directly: the serve loop pays
    # admit() per EXP frame and grant() inside every ack payload
    t0 = time.perf_counter()
    for _ in range(flow_iters):
        gw.flow.admit(0, rows)
        gw.flow.grant(0)
    flow_s = time.perf_counter() - t0
    client.close()
    gw.close()
    per_chunk = span / max(chunks, 1)
    per_flow = flow_s / max(flow_iters, 1)
    out = {
        "chunks_per_sec_ingest": round(chunks / span, 1),
        "chunk_ingest_us": round(per_chunk * 1e6, 2),
        "flow_us_per_chunk": round(per_flow * 1e6, 3),
        # the gate number: per-chunk flow work / per-chunk ingest span
        "flow_overhead_frac": round(per_flow / per_chunk, 4),
        "chunk_rows": rows,
        "geometry": "smoke-wire" if smoke else "wire",
    }
    print(f"[bench_flow_overhead] {out}", file=sys.stderr, flush=True)
    return {"flow_overhead": out}


def bench_replica_overhead(rounds: int = 200, grad_dim: int = 65536,
                           smoke: bool = False) -> dict:
    """Replica-plane cost on the learner hot path (ISSUE 15
    acceptance): a real ReplicaClient→gateway→ReplicaRegistry wire loop
    at N=1 (the solo-degenerate case every replicated learner passes
    through) measures the per-round exchange span at a production-ish
    gradient size (64k fp32 ≈ the dqn-mlp tree), and the plane's
    per-round adds — the generation-stamp validate + round bookkeeping
    (``submit`` fast path) and one lease ``renew`` (an upper bound:
    production renews every lease_s/3, not every round) — are DIRECTLY
    timed in isolation against the registry.  The gate number
    ``replica_overhead_frac`` is plane-work-per-round over
    exchange-span-per-round, held under the 0.02 absolute band by
    bench_gate — the PR-10 lesson applies verbatim: differencing two
    noisy round rates on this loaded host would read scheduler hiccups
    as fake overhead, so the rate difference is never the gate number.

    ``smoke=True`` shrinks the loop to sub-second for CI; the
    measurement logic is identical."""
    from pytorch_distributed_tpu.agents.clocks import (
        ActorStats, GlobalClock,
    )
    from pytorch_distributed_tpu.agents.param_store import ParamStore
    from pytorch_distributed_tpu.config import ReplicaParams
    from pytorch_distributed_tpu.parallel.dcn import (
        DcnGateway, LocalReplicaChannel, ReplicaClient, ReplicaRegistry,
    )

    plane_iters = 6_000
    if smoke:
        rounds = min(rounds, 80)
        plane_iters = 2_500
    registry = ReplicaRegistry(ReplicaParams(replicas=1, lease_s=30.0))
    store = ParamStore(4)
    store.publish(np.zeros(4, dtype=np.float32))
    gw = DcnGateway(store, GlobalClock(), ActorStats(),
                    put_chunk=lambda items: None, host="127.0.0.1",
                    port=0, replicas=registry)
    client = ReplicaClient(("127.0.0.1", gw.port), 0)
    client.acquire()
    grad = np.zeros(grad_dim, dtype=np.float32)
    for r in range(10):  # session + allocator warmup
        client.submit_round(r, grad)
    t0 = time.perf_counter()
    for r in range(10, 10 + rounds):
        client.submit_round(r, grad)
    span = time.perf_counter() - t0
    # the plane's own work, timed directly against a second registry:
    # the stamp/validate + completion bookkeeping of an N=1 submit
    # (tiny grad — the reduce over real bytes is already inside the
    # wire span above) and the renew path
    reg2 = ReplicaRegistry(ReplicaParams(replicas=1, lease_s=30.0))
    ch = LocalReplicaChannel(reg2, 0)
    ch.acquire()
    tiny = np.zeros(4, dtype=np.float32)
    t0 = time.perf_counter()
    for i in range(plane_iters):
        ch.submit_round(i, tiny)
    stamp_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(plane_iters):
        ch.renew()
    renew_s = time.perf_counter() - t0
    client.release()
    client.close()
    ch.release()
    ch.close()
    gw.close()
    per_round = span / max(rounds, 1)
    per_stamp = stamp_s / max(plane_iters, 1)
    per_renew = renew_s / max(plane_iters, 1)
    out = {
        "rounds_per_sec_wire": round(rounds / span, 1),
        "round_exchange_us": round(per_round * 1e6, 2),
        "stamp_us_per_round": round(per_stamp * 1e6, 3),
        "renew_us": round(per_renew * 1e6, 3),
        # the gate number: per-round plane work (stamp + one renew,
        # the conservative bound) / per-round exchange span
        "replica_overhead_frac": round(
            (per_stamp + per_renew) / per_round, 4),
        "grad_dim": grad_dim,
        "geometry": "smoke-wire" if smoke else "wire",
    }
    print(f"[bench_replica_overhead] {out}", file=sys.stderr, flush=True)
    return {"replica_overhead": out}


def bench_gateway_ha_overhead(chunks: int = 600, rows: int = 16,
                              smoke: bool = False) -> dict:
    """Gateway HA-plane cost on the ingest hot path (ISSUE 16
    acceptance): a real DcnClient→DcnGateway wire ingest loop with the
    HA plane ON (journaling its control state to a WAL) measures the
    per-chunk ingest span, and the plane's adds are DIRECTLY timed in
    isolation — the per-frame session gate (term check, rate-limited
    TERM re-read amortized in), one fsynced journal ``append`` (paid
    once per state window, never per chunk — charged at the measured
    append count), and one primary-side sync-stream serve (charged at
    the production sync_s cadence, standby or not).  The gate number
    ``gateway_ha_overhead_frac`` is HA-work-per-chunk over
    ingest-span-per-chunk, held under the 0.02 absolute band by
    bench_gate — the PR-10 lesson applies verbatim: differencing an
    HA-on wire rate against an HA-off one on this loaded host would
    read scheduler hiccups as multi-% fake overhead, so the rate
    difference is never the gate number.

    ``smoke=True`` shrinks the loop to sub-second for CI; the
    measurement logic is identical."""
    import shutil
    import tempfile

    from pytorch_distributed_tpu.agents.clocks import (
        ActorStats, GlobalClock,
    )
    from pytorch_distributed_tpu.agents.param_store import ParamStore
    from pytorch_distributed_tpu.config import GatewayParams
    from pytorch_distributed_tpu.parallel.dcn import (
        DcnClient, DcnGateway, GatewayJournal, T_EXP,
    )
    from pytorch_distributed_tpu.utils.experience import Transition

    gate_iters = 20_000
    append_iters = 120
    sync_iters = 4_000
    if smoke:
        chunks = min(chunks, 250)
        gate_iters = 8_000
        append_iters = 50
        sync_iters = 1_500
    gp = GatewayParams(enabled=True)  # production lease/sync defaults
    tmp = tempfile.mkdtemp(prefix="bench-gw-ha-")
    z = np.zeros(4, dtype=np.float32)
    t = Transition(state0=z, action=np.int32(0), reward=np.float32(0.0),
                   gamma_n=np.float32(0.99), state1=z,
                   terminal1=np.float32(0.0))
    chunk = [(t, 1.0)] * rows
    store = ParamStore(4)
    store.publish(np.zeros(4, dtype=np.float32))
    gw = DcnGateway(store, GlobalClock(), ActorStats(),
                    put_chunk=lambda items: None, host="127.0.0.1",
                    port=0, gateway_params=gp, log_dir=tmp)
    client = DcnClient(("127.0.0.1", gw.port), process_ind=0)
    for _ in range(30):  # session + validator + allocator warmup
        client.send_chunk(chunk)
    appends_before = gw.status_snapshot()["gateway"]["journal_appends"]
    t0 = time.perf_counter()
    for _ in range(chunks):
        client.send_chunk(chunk)
    span = time.perf_counter() - t0
    appends_during = (gw.status_snapshot()["gateway"]["journal_appends"]
                      - appends_before)
    # the plane's own work, timed directly: the per-frame gate...
    t0 = time.perf_counter()
    for _ in range(gate_iters):
        gw._session_gate(T_EXP)
    gate_s = time.perf_counter() - t0
    # ...one fsynced state append against a second journal (same dir =
    # same storage medium; the wire span above amortizes the SAME cost
    # across every chunk in a state window)...
    j = GatewayJournal(os.path.join(tmp, "direct"))
    j.start_term(1)
    state = {"tick_seq": {"0": 999}, "clock": {"learner_step": 10 ** 6,
                                               "actor_step": 10 ** 7},
             "chunks_in": 10 ** 6, "lost": 0,
             "ledger": {"ingested": 10 ** 7, "shed": 0,
                        "quarantined": 0}}
    t0 = time.perf_counter()
    for _ in range(append_iters):
        j.append("state", state)
    append_s = time.perf_counter() - t0
    # ...and one primary-side sync serve (steady state: the standby's
    # incremental pull finds the tail it already has)
    t0 = time.perf_counter()
    for _ in range(sync_iters):
        base, recs = j.records_since(max(0, j.seq - 1))
        json.dumps({"term": 1, "seq": j.seq, "base_seq": base,
                    "records": recs})
    sync_s_total = time.perf_counter() - t0
    j.close()
    client.close()
    gw.close()
    shutil.rmtree(tmp, ignore_errors=True)
    per_chunk = span / max(chunks, 1)
    per_gate = gate_s / max(gate_iters, 1)
    per_append = append_s / max(append_iters, 1)
    per_sync = sync_s_total / max(sync_iters, 1)
    # HA work charged per chunk: every frame pays the gate; the
    # measured append count amortizes the fsync across the loop; the
    # sync stream is charged at its production cadence over the span
    ha_per_chunk = (per_gate
                    + per_append * appends_during / max(chunks, 1)
                    + per_sync * (span / max(gp.sync_s, 1e-3))
                    / max(chunks, 1))
    out = {
        "chunks_per_sec_ingest": round(chunks / span, 1),
        "chunk_ingest_us": round(per_chunk * 1e6, 2),
        "gate_us_per_chunk": round(per_gate * 1e6, 3),
        "journal_append_us": round(per_append * 1e6, 2),
        "journal_appends_during": appends_during,
        "sync_serve_us": round(per_sync * 1e6, 3),
        # the gate number: per-chunk HA work / per-chunk ingest span
        "gateway_ha_overhead_frac": round(ha_per_chunk / per_chunk, 4),
        "chunk_rows": rows,
        "geometry": "smoke-wire" if smoke else "wire",
    }
    print(f"[bench_gateway_ha_overhead] {out}", file=sys.stderr,
          flush=True)
    return {"gateway_ha_overhead": out}


def _shard_bench_plane(shards: int, capacity: int = 4096,
                       fill: int = 2048):
    """A warmed loopback shard plane: ``fill`` slot-routed rows over
    ``shards`` in-process shards (capacity split evenly), ready to
    sample."""
    from pytorch_distributed_tpu.config import ShardParams
    from pytorch_distributed_tpu.memory.shard_plane import (
        build_loopback_plane,
    )
    from pytorch_distributed_tpu.utils.experience import (
        Transition, make_prov,
    )

    plane, _, registry = build_loopback_plane(
        ShardParams(shards=shards, lease_s=120.0), capacity=capacity,
        state_shape=(4,))
    z = np.zeros(4, dtype=np.float32)
    for i in range(fill):
        t = Transition(state0=z, action=np.int32(0),
                       reward=np.float32(i % 7),
                       gamma_n=np.float32(0.99), state1=z,
                       terminal1=np.float32(0.0),
                       prov=make_prov(i % 8, 0, 0, i))
        plane.feed(t, float(1.0 + (i % 13)))
    return plane, registry


def bench_shard(samples: int = 400, batch: int = 64,
                smoke: bool = False) -> dict:
    """Sharded-replay sample latency vs shard count (ISSUE 20
    acceptance): the SAME global capacity and fill, sampled through the
    two-level tree at 1, 2, and 4 in-process (loopback) shards — the
    1-shard figure is the plane's degenerate case (bit-identical
    draws to a plain ``PrioritizedReplay``, the tier-1 parity oracle),
    so the 2/4-shard columns read as the pure cost of the stratified
    mass routing + per-shard local draws + the |TD| write-back merge.
    Loopback isolates plane arithmetic from socket noise; the wire
    path's per-verb cost is ISSUE-18's accountant's to report.

    ``smoke=True`` shrinks the loop to sub-second for CI; the
    measurement logic is identical."""
    if smoke:
        samples = min(samples, 120)
    out: dict = {"batch": batch, "samples": samples,
                 "geometry": "smoke-loopback" if smoke else "loopback"}
    reps = 5  # best-of-reps: scheduler hiccups inflate a mean, not a min
    chunk = max(1, samples // reps)
    for n in (1, 2, 4):
        plane, _ = _shard_bench_plane(n)
        rng = np.random.default_rng(0)
        for _ in range(10):  # tree/route warmup
            b = plane.sample(batch, rng)
            plane.update_priorities(b.index, np.abs(b.reward) + 0.5)
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(chunk):
                b = plane.sample(batch, rng)
                plane.update_priorities(b.index, np.abs(b.reward) + 0.5)
            best = min(best, time.perf_counter() - t0)
        out[f"sample_ms_{n}shard"] = round(best / chunk * 1e3, 4)
    print(f"[bench_shard] {out}", file=sys.stderr, flush=True)
    return {"shard": out}


def bench_shard_overhead(samples: int = 400, batch: int = 64,
                         smoke: bool = False) -> dict:
    """Shard-plane cost on the sample hot path (ISSUE 20 acceptance):
    the per-sample span at the production-shaped 4-shard loopback
    geometry, with the plane's own adds — one forced level-1
    mass-vector rebuild (the per-sample refresh at the exact-proportions
    default ``mass_refresh_s=0``) and one cold route rebuild (the
    every-feed epoch check's worst case) — DIRECTLY timed in isolation.
    The gate number ``shard_overhead_frac`` is plane-work-per-sample
    over sample-span, held under the 0.02 absolute band by bench_gate —
    the PR-10 lesson applies verbatim: differencing two noisy sample
    rates on a loaded host would read scheduler hiccups as fake
    overhead, so the rate difference is never the gate number."""
    plane_iters = 4_000
    if smoke:
        samples = min(samples, 120)
        plane_iters = 1_500
    plane, _ = _shard_bench_plane(4)
    rng = np.random.default_rng(0)
    for _ in range(10):
        b = plane.sample(batch, rng)
        plane.update_priorities(b.index, np.abs(b.reward) + 0.5)
    t0 = time.perf_counter()
    for _ in range(samples):
        b = plane.sample(batch, rng)
        plane.update_priorities(b.index, np.abs(b.reward) + 0.5)
    span = time.perf_counter() - t0
    # the plane's own work, timed directly: the mass rebuild every
    # sample pays (poll each live shard + rebuild the level-1 vector)
    # and the cold route rebuild a membership event would force
    t0 = time.perf_counter()
    for _ in range(plane_iters):
        plane._refresh_mass(force=True)
    mass_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(plane_iters):
        plane._route_epoch = -1
        plane._refresh_route()
    route_s = time.perf_counter() - t0
    per_sample = span / max(samples, 1)
    per_mass = mass_s / max(plane_iters, 1)
    per_route = route_s / max(plane_iters, 1)
    out = {
        "sample_ms": round(per_sample * 1e3, 4),
        "mass_refresh_us": round(per_mass * 1e6, 3),
        "route_rebuild_us": round(per_route * 1e6, 3),
        # the gate number: per-sample plane work (mass rebuild + cold
        # route rebuild, the conservative bound) / per-sample span
        "shard_overhead_frac": round(
            (per_mass + per_route) / per_sample, 4),
        "shards": 4,
        "geometry": "smoke-loopback" if smoke else "loopback",
    }
    print(f"[bench_shard_overhead] {out}", file=sys.stderr, flush=True)
    return {"shard_overhead": out}


def bench_wire(rows: int = 400, chunk_rows: int = 25,
               grad_dim: int = 65536, smoke: bool = False) -> dict:
    """Wire byte economics (ISSUE 18): the bandwidth X-ray's measured
    baseline for the ROADMAP-4 compression campaign.  Three numbers,
    all read off the LinkAccountant over REAL client→gateway wires:

    - ``legacy_bytes_per_transition`` — one transition per EXP frame
      (the pre-PR-4 upload shape: every tick ships its own savez
      envelope + 9-byte header);
    - ``bytes_per_transition`` — the production frame-packed shape
      (``actor_freq``-row chunks, envelope amortized across the chunk)
      — the headline every compression leg will be gated against;
    - ``replica_bytes_per_round`` — the ISSUE-15 replica exchange at
      N=1 with a production-ish 64k-fp32 gradient.

    Byte counts are deterministic (savez layout, fixed geometry), so
    the gate band is tight — a change here is a wire-format change,
    not noise."""
    from pytorch_distributed_tpu.agents.clocks import (
        ActorStats, GlobalClock,
    )
    from pytorch_distributed_tpu.agents.param_store import ParamStore
    from pytorch_distributed_tpu.config import ReplicaParams
    from pytorch_distributed_tpu.parallel.dcn import (
        DcnClient, DcnGateway, ReplicaClient, ReplicaRegistry,
    )
    from pytorch_distributed_tpu.utils import bandwidth
    from pytorch_distributed_tpu.utils.experience import Transition

    rounds = 6 if smoke else 20
    if smoke:
        rows = min(rows, 100)
    rows -= rows % chunk_rows  # same row count on both legs
    z = np.zeros(4, dtype=np.float32)
    t = Transition(state0=z, action=np.int32(0), reward=np.float32(0.0),
                   gamma_n=np.float32(0.99), state1=z,
                   terminal1=np.float32(0.0))

    def ingest_leg(per_frame: int) -> float:
        bandwidth.reset_for_tests()
        store = ParamStore(4)
        store.publish(np.zeros(4, dtype=np.float32))
        gw = DcnGateway(store, GlobalClock(), ActorStats(),
                        put_chunk=lambda items: None, host="127.0.0.1",
                        port=0, pressure=lambda: 0.0)
        client = DcnClient(("127.0.0.1", gw.port), process_ind=0)
        chunk = [(t, 1.0)] * per_frame
        for _ in range(rows // per_frame):
            client.send_chunk(chunk)
        acct = bandwidth.get_accountant()
        bpt = acct.bytes_per_transition()
        client.close()
        gw.close()
        return bpt

    legacy = ingest_leg(1)
    packed = ingest_leg(chunk_rows)

    # the replica exchange leg: N=1 rounds with a 64k-fp32 gradient
    bandwidth.reset_for_tests()
    registry = ReplicaRegistry(ReplicaParams(replicas=1, lease_s=30.0))
    store = ParamStore(4)
    store.publish(np.zeros(4, dtype=np.float32))
    gw = DcnGateway(store, GlobalClock(), ActorStats(),
                    put_chunk=lambda items: None, host="127.0.0.1",
                    port=0, replicas=registry)
    rclient = ReplicaClient(("127.0.0.1", gw.port), 0)
    rclient.acquire()
    grad = np.zeros(grad_dim, dtype=np.float32)
    acct = bandwidth.get_accountant()
    for r in range(2):  # session setup pays a one-off extra frame
        rclient.submit_round(r, grad)
    base_b = sum(acct.totals(link="gateway", verb=v)[0]
                 for v in ("rlease", "rgrad", "rprio"))
    base_rounds = acct.rounds
    for r in range(2, 2 + rounds):
        rclient.submit_round(r, grad)
    meas_b = sum(acct.totals(link="gateway", verb=v)[0]
                 for v in ("rlease", "rgrad", "rprio"))
    bpr = (meas_b - base_b) / max(acct.rounds - base_rounds, 1)
    rclient.release()
    rclient.close()
    gw.close()
    bandwidth.reset_for_tests()

    out = {
        # the headline: the production frame-packed upload shape
        "bytes_per_transition": round(packed, 1),
        "legacy_bytes_per_transition": round(legacy, 1),
        "packing_ratio": round(legacy / packed, 2) if packed else None,
        "replica_bytes_per_round": round(bpr, 1),
        "chunk_rows": chunk_rows,
        "rows": rows,
        "grad_dim": grad_dim,
        "geometry": "smoke-wire" if smoke else "wire",
    }
    print(f"[bench_wire] {out}", file=sys.stderr, flush=True)
    return {"wire": out}


def bench_wire_overhead(chunks: int = 600, rows: int = 16,
                        smoke: bool = False) -> dict:
    """Bandwidth-accountant cost on the ingest hot path (ISSUE 18
    acceptance): a real DcnClient→DcnGateway wire ingest loop with the
    plane at its production default (enabled) measures the per-chunk
    ingest span, and the plane's per-chunk adds — the four
    ``note_frame`` stamps an EXP round-trip pays (exp tx/rx + ack
    tx/rx, each a weak socket lookup + one dict get + two int adds
    under the lock) plus the ``note_transitions`` row count and the
    flow ledger's byte legs — are DIRECTLY timed in isolation.  The
    gate number ``wire_overhead_frac`` is plane-work-per-chunk over
    ingest-span-per-chunk, held under the 0.02 absolute band by
    bench_gate — the PR-10 lesson applies verbatim: differencing two
    noisy wire throughputs reads scheduler hiccups as fake overhead,
    so the rate difference is never the gate number.

    ``smoke=True`` shrinks the loop to sub-second for CI; the
    measurement logic is identical."""
    import socket as socket_mod

    from pytorch_distributed_tpu.agents.clocks import (
        ActorStats, GlobalClock,
    )
    from pytorch_distributed_tpu.agents.param_store import ParamStore
    from pytorch_distributed_tpu.parallel.dcn import (
        T_CLOCK, T_EXP, DcnClient, DcnGateway,
    )
    from pytorch_distributed_tpu.utils import bandwidth
    from pytorch_distributed_tpu.utils.experience import Transition

    wire_iters = 20_000
    if smoke:
        chunks = min(chunks, 250)
        wire_iters = 8_000
    z = np.zeros(4, dtype=np.float32)
    t = Transition(state0=z, action=np.int32(0), reward=np.float32(0.0),
                   gamma_n=np.float32(0.99), state1=z,
                   terminal1=np.float32(0.0))
    chunk = [(t, 1.0)] * rows
    bandwidth.reset_for_tests()
    store = ParamStore(4)
    store.publish(np.zeros(4, dtype=np.float32))
    gw = DcnGateway(store, GlobalClock(), ActorStats(),
                    put_chunk=lambda items: None, host="127.0.0.1",
                    port=0, pressure=lambda: 0.0)
    acct = bandwidth.get_accountant()
    assert acct is not None, "wire plane off at its production default"
    client = DcnClient(("127.0.0.1", gw.port), process_ind=0)
    for _ in range(30):  # session + validator + allocator warmup
        client.send_chunk(chunk)
    t0 = time.perf_counter()
    for _ in range(chunks):
        client.send_chunk(chunk)
    span = time.perf_counter() - t0
    # the plane's per-chunk work, timed directly on a registered live
    # socket (the weak side-table lookup is part of the cost)
    s1, s2 = socket_mod.socketpair()
    acct.register_socket(s1, "client", 0)
    nb = 4096
    t0 = time.perf_counter()
    for _ in range(wire_iters):
        acct.note_frame(s1, T_EXP, nb, "tx")
        acct.note_frame(s1, T_EXP, nb, "rx")
        acct.note_frame(s1, T_CLOCK, 64, "tx")
        acct.note_frame(s1, T_CLOCK, 64, "rx")
        acct.note_transitions(rows)
        gw.flow.note_ingested_bytes(nb)
    wire_s = time.perf_counter() - t0
    s1.close()
    s2.close()
    client.close()
    gw.close()
    bandwidth.reset_for_tests()
    per_chunk = span / max(chunks, 1)
    per_wire = wire_s / max(wire_iters, 1)
    out = {
        "chunks_per_sec_ingest": round(chunks / span, 1),
        "chunk_ingest_us": round(per_chunk * 1e6, 2),
        "wire_us_per_chunk": round(per_wire * 1e6, 3),
        # the gate number: per-chunk accountant work / per-chunk
        # ingest span
        "wire_overhead_frac": round(per_wire / per_chunk, 4),
        "chunk_rows": rows,
        "geometry": "smoke-wire" if smoke else "wire",
    }
    print(f"[bench_wire_overhead] {out}", file=sys.stderr, flush=True)
    return {"wire_overhead": out}


def bench_smoke(updates: int = 384) -> dict:
    """Seconds-scale, CPU-safe bench for CI gating (ISSUE 6 satellite):
    the dqn-mlp learner program fused over a small uniform HBM-style
    ring — tiny enough to compile and run in seconds on a CPU host,
    production-shaped enough (fused sample+train scan, fetch-bounded
    windows, XLA-derived flops) that a real regression in the core
    train-step machinery moves it.  The output feeds
    ``tools/bench_gate.py --against BENCH_SMOKE_BASELINE.json`` and is
    recorded into ``BENCH_HISTORY.jsonl`` — perf as a CI check, not an
    offline artifact.  Absolute rates are machine-dependent; gate smoke
    runs against a SAME-MACHINE baseline/history (the checked-in
    baseline documents this image's figures)."""
    import jax

    B, K = 32, 8
    fused, state, ring = _mlp_fused_program(B, K)
    key = jax.random.PRNGKey(0)

    def keymat():
        nonlocal key
        key, sub = jax.random.split(key)
        return jax.random.split(sub, K)

    t_compile = time.perf_counter()
    compiled = fused.lower(state, ring.state, keymat()).compile()
    t_compile = time.perf_counter() - t_compile
    flops = flops_of_compiled(compiled)
    for _ in range(3):
        state, metrics = compiled(state, ring.state, keymat())
    float(jax.device_get(metrics["learner/critic_loss"]))
    windows, rates = 4, []
    iters = max(updates // (4 * K), 1)
    for _ in range(windows):
        keysets = [keymat() for _ in range(iters)]
        jax.block_until_ready(keysets[-1])
        t0 = time.perf_counter()
        for ks in keysets:
            state, metrics = compiled(state, ring.state, ks)
        float(jax.device_get(metrics["learner/critic_loss"]))
        rates.append(iters * K / (time.perf_counter() - t0))
    out = {
        "updates_per_sec": round(float(np.median(rates)), 2),
        "batch_size": B,
        "steps_per_dispatch": K,
        "compile_seconds": round(t_compile, 2),
    }
    if flops:
        out["flops_per_update"] = round(flops)

    # ISSUE-13 megabatch leg: the same dqn-mlp program fused as ONE
    # M=32 widened-gather group per dispatch — the smoke gate's
    # regression canary for the megabatch machinery (additive key,
    # schema stays 4)
    MB = 32
    mfused, mstate, mring = _mlp_fused_program(B, MB, megabatch=MB)
    mkey = jax.random.PRNGKey(0)

    def mkeymat():
        nonlocal mkey
        mkey, sub = jax.random.split(mkey)
        return jax.random.split(sub, MB)

    mcompiled = mfused.lower(mstate, mring.state, mkeymat()).compile()
    for _ in range(3):
        mstate, mmetrics = mcompiled(mstate, mring.state, mkeymat())
    float(jax.device_get(mmetrics["learner/critic_loss"]))
    mrates = []
    miters = max(updates // (4 * MB), 1)
    for _ in range(4):
        keysets = [mkeymat() for _ in range(miters)]
        jax.block_until_ready(keysets[-1])
        t0 = time.perf_counter()
        for ks in keysets:
            mstate, mmetrics = mcompiled(mstate, mring.state, ks)
        float(jax.device_get(mmetrics["learner/critic_loss"]))
        mrates.append(miters * MB / (time.perf_counter() - t0))
    out["updates_per_sec_megabatch"] = round(float(np.median(mrates)), 2)
    out["megabatch_k"] = MB
    print(f"[bench_smoke] {out}", file=sys.stderr, flush=True)
    return {"smoke": out}


def bench_actor_pipeline(envs: int = 16, ticks: int = 300) -> dict:
    """Actor hot-loop section (ISSUE 4): serial vs software-pipelined
    schedules on the production actor shape (pong-sim vector, Nature-CNN
    forward on the host CPU — the inline/pipelined backends always run
    inference host-side; the accelerator-served ``batched`` backend is
    measured by the e2e section, where a learner process owns the chip).

    Reported per schedule: per-tick phase breakdown (ms; the jit-compile
    tick is excluded by dropping each phase's max before averaging) and
    the implied frames/s.  Plus:

    - ``env_only_frames_per_sec`` — the ceiling if inference were free:
      the bare env vector stepped with constant actions;
    - ``overlap_efficiency`` — hidden device time / total device time:
      of the act time the serial schedule pays (``act`` = dispatch +
      blocked sync), the fraction the pipelined schedule hides under
      host work, ``(act_serial - sync - dispatch) / act_serial``.  On a
      one-core host CPU compute cannot actually overlap host python — so
      this number is ALSO the honest measure of how much of the "act"
      cost was dispatch/transfer latency rather than compute.
    """
    from pytorch_distributed_tpu.config import build_options
    from pytorch_distributed_tpu.factory import build_env_vector
    from pytorch_distributed_tpu.agents.actor import bounded_actor_run

    root = tempfile.mkdtemp(prefix="bench_actor_")

    def adjusted(timer_ms, phase):
        """Per-call ms with the single worst call (the compile) dropped."""
        mean = timer_ms.get(f"actor/time_{phase}_ms")
        if mean is None:
            return None
        mx = timer_ms[f"actor/time_{phase}_max_ms"]
        n = timer_ms[f"actor/time_{phase}_calls"]
        if n <= 1:
            return round(mean, 3)
        return round((mean * n - mx) / (n - 1), 3)

    out = {"envs": envs, "ticks": ticks}
    for backend in ("inline", "pipelined"):
        opt = build_options(
            4, root_dir=root, refs=f"actor_{backend}", num_actors=1,
            num_envs_per_actor=envs, actor_backend=backend,
            visualize=False,
            # no mid-run flush/sync: the timer must hold the whole run
            actor_freq=10 ** 9, actor_sync_freq=10 ** 9)
        res = bounded_actor_run(opt, ticks)
        t = res["timer_ms"]
        phases = {p: adjusted(t, p)
                  for p in ("act", "sync", "dispatch", "env", "advance")
                  if adjusted(t, p) is not None}
        host = (("sync", "dispatch", "env", "advance")
                if backend == "pipelined" else ("act", "env", "advance"))
        tick_ms = sum(phases[p] for p in host if p in phases)
        out[backend] = {
            "tick_ms": round(tick_ms, 3),
            "frames_per_sec": round(envs / tick_ms * 1e3, 1) if tick_ms
            else None,
            "phases_ms": phases,
        }
        print(f"[bench_actor_pipeline] {backend}: {out[backend]}",
              file=sys.stderr, flush=True)
    # env-only ceiling: the same vector stepped with constant actions
    opt = build_options(4, root_dir=root, refs="actor_env_only",
                        num_envs_per_actor=envs, visualize=False)
    env = build_env_vector(opt, 0, envs)
    env.train()
    env.reset()
    acts = np.zeros(envs, dtype=np.int64)
    for _ in range(10):
        env.step(acts)
    t0 = time.perf_counter()
    for _ in range(ticks):
        env.step(acts)
    env_tick = (time.perf_counter() - t0) / ticks
    out["env_only_frames_per_sec"] = round(envs / env_tick, 1)
    act_serial = out["inline"]["phases_ms"].get("act")
    pip = out["pipelined"]["phases_ms"]
    if act_serial:
        hidden = act_serial - pip.get("sync", 0.0) - pip.get("dispatch",
                                                             0.0)
        out["overlap_efficiency"] = round(
            min(max(hidden / act_serial, 0.0), 1.0), 4)
    if out["inline"].get("frames_per_sec") and \
            out["pipelined"].get("frames_per_sec"):
        out["pipeline_speedup"] = round(
            out["pipelined"]["frames_per_sec"]
            / out["inline"]["frames_per_sec"], 3)
    return {"actor_pipeline": out}


def _device_env_linear_policy(state_shape):
    """A fixed random linear Q-head over the flattened obs: the
    cheapest policy that still exercises the rollout engine's full
    per-tick structure (forward -> eps-greedy -> env -> n-step ->
    ring).  Engine-cost rows use it so the section separates what the
    ROLLOUT PLANE costs from what the configured model costs (on a CPU
    host the Nature CNN forward alone caps any actor plane at ~1k
    frames/s; on a TPU it is noise)."""
    import jax.numpy as jnp

    dim = int(np.prod(state_shape))
    w = jnp.asarray(np.random.default_rng(0).normal(
        size=(dim, 6)).astype(np.float32) * 0.01)

    def apply_fn(params, obs):
        x = obs.reshape((obs.shape[0], -1)).astype(jnp.float32) / 255.0
        return x @ params

    return apply_fn, w


def bench_device_env(ns=(64, 256, 1024), scan_ticks: int = 8,
                     smoke: bool = False) -> dict:
    """The ISSUE-7 device env fleet section: env frames/s of the three
    env backends at N in ``ns`` plus the fused rollout engine.

    - ``ladder`` — env-STEPPING throughput per backend: the Python
      ``VectorEnv`` (the reference-shaped host path), the C++ batched
      stepper (when the toolchain builds it), and the device env (one
      jitted scan advancing all N pure-JAX envs ``scan_ticks`` ticks
      per dispatch).  All three produce the full 84x84 uint8 stacked
      observation per tick; actions are held fixed, as in the
      actor-pipeline section's env-only ceiling.
    - ``fused`` — the COMPLETE device actor plane per dispatch
      (models/policies.build_fused_rollout, emit="replay"): policy
      forward + eps-greedy + env + on-device n-step assembly +
      transitions scattered straight into a device replay ring with
      zero host round-trip.  Two policies: ``linear`` (engine cost —
      what the rollout plane itself costs) and ``cnn`` (the production
      Nature-CNN policy; on CPU hosts its forward dominates, which the
      row's ``policy_bound`` flag says explicitly).
    - ``speedup_vs_host`` — device ladder row over the Python host row
      at the widest N: the acceptance figure (>= 10x on this image's
      CPU: the host plane pays ~N Python frames per tick, the device
      plane one dispatch).

    Window timing is fetch-bounded like every other section (a value
    fetch chains behind the dispatched work).
    """
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.config import build_options
    from pytorch_distributed_tpu.envs.device_env import build_device_env
    from pytorch_distributed_tpu.envs.vector import VectorEnv
    from pytorch_distributed_tpu.envs.pong_sim import PongSimEnv
    from pytorch_distributed_tpu.memory.device_replay import DeviceReplay
    from pytorch_distributed_tpu.models.policies import (
        build_fused_rollout, init_rollout_carry,
    )

    if smoke:
        ns = (32,)
    opt = build_options(4, visualize=False)
    K = scan_ticks
    out: dict = {"n_ladder": list(ns), "scan_ticks": K, "ladder": {}}

    def median_windows(tick_fn, frames_per_tick: int, ticks: int,
                       windows: int = 5):
        """Median frames/s over independent windows (the bench-wide
        convention: one scheduler stall must not skew a row), with a
        gc pass first so a previous row's teardown is not billed
        here."""
        import gc

        gc.collect()
        tick_fn()  # warm (compile / allocator settle)
        rates = []
        for _ in range(windows):
            t0 = time.perf_counter()
            for _ in range(ticks):
                tick_fn()
            rates.append(frames_per_tick * ticks
                         / (time.perf_counter() - t0))
        return float(np.median(rates))

    def host_row(N: int):
        env = VectorEnv([PongSimEnv(opt.env_params, j) for j in range(N)])
        env.reset()
        acts = np.zeros(N, dtype=np.int64)
        return median_windows(lambda: env.step(acts), N,
                              ticks=max(2, 1024 // N))

    def native_row(N: int):
        try:
            from pytorch_distributed_tpu.envs.native_pong import (
                NativePongVectorEnv, get_lib,
            )

            get_lib()
        except Exception:  # noqa: BLE001 - no toolchain: row omitted
            return None
        env = NativePongVectorEnv(opt.env_params, 0, N)
        env.reset()
        acts = np.zeros(N, dtype=np.int64)
        return median_windows(lambda: env.step(acts), N,
                              ticks=max(2, 4096 // N))

    def device_row(N: int):
        env = build_device_env(opt.env_params, 0, N)
        acts = jnp.zeros((N,), jnp.int32)

        @functools.partial(jax.jit, donate_argnums=(0,))
        def scan_steps(state):
            def body(s, _):
                s, out_ = env.step(s, acts)
                return s, out_.reward

            s, r = jax.lax.scan(body, state, None, length=K)
            return s, r

        box = [env.init()]

        def tick():
            box[0], r = scan_steps(box[0])
            float(jax.device_get(r[-1][0]))  # fetch-bounded
        return median_windows(tick, N * K,
                              ticks=max(1, 8192 // (K * N)))

    for N in ns:
        row = {"host_frames_per_sec": round(host_row(N), 1)}
        nat = native_row(N)
        if nat is not None:
            row["native_frames_per_sec"] = round(nat, 1)
        row["device_frames_per_sec"] = round(device_row(N), 1)
        out["ladder"][str(N)] = row
        print(f"[bench_device_env] N={N}: {row}", file=sys.stderr,
              flush=True)

    # ---- fused rollout engine (emit="replay": zero-copy into HBM) ----
    def fused_row(N: int, policy: str):
        env = build_device_env(opt.env_params, 0, N)
        if policy == "linear":
            apply_fn, params = _device_env_linear_policy(env.state_shape)
        else:
            from pytorch_distributed_tpu.models import DqnCnnModel

            model = DqnCnnModel(action_space=6, norm_val=255.0)
            params = model.init(jax.random.PRNGKey(0),
                                np.zeros((1, 4, 84, 84), np.uint8))
            apply_fn = model.apply
        ring = DeviceReplay(capacity=max(2 * K * N, 2048),
                            state_shape=env.state_shape,
                            state_dtype=np.uint8)
        roll = build_fused_rollout(apply_fn, env, nstep=5, gamma=0.99,
                                   rollout_ticks=K, emit="replay")
        eps = jnp.full((N,), 0.1, jnp.float32)
        key = jnp.asarray(jax.random.PRNGKey(0))
        box = [init_rollout_carry(env, 5), ring.state, jnp.int32(0)]

        def tick():
            carry, rs, tick0 = box
            carry, rs, stats = roll(params, carry, rs, key, tick0, eps)
            int(jax.device_get(stats.fed))  # fetch-bounded
            box[:] = [carry, rs, tick0 + K]

        return median_windows(
            tick, N * K,
            ticks=max(1, (2048 if policy == "linear" else 256)
                      // (K * N)),
            windows=3 if policy == "linear" else 2)

    out["fused"] = {}
    fused_ns = ns if not smoke else (32,)
    for N in fused_ns:
        row = {"linear_frames_per_sec": round(fused_row(N, "linear"), 1)}
        if not smoke:
            row["cnn_frames_per_sec"] = round(fused_row(N, "cnn"), 1)
            # on CPU hosts the Nature-CNN forward alone is the wall;
            # flag it so the row is read as a model cost, not an
            # engine cost
            row["policy_bound"] = bool(
                row["cnn_frames_per_sec"]
                < 0.5 * row["linear_frames_per_sec"])
        out["fused"][str(N)] = row
        print(f"[bench_device_env] fused N={N}: {row}", file=sys.stderr,
              flush=True)

    top = str(max(ns))
    host = out["ladder"][top]["host_frames_per_sec"]
    dev = out["ladder"][top]["device_frames_per_sec"]
    out["host_frames_per_sec"] = host
    out["device_frames_per_sec"] = dev
    out["fused_frames_per_sec"] = out["fused"][top][
        "linear_frames_per_sec"]
    if host:
        out["speedup_vs_host"] = round(dev / host, 2)
    # the ROADMAP open-item-1 read: with the env fleet on device, the
    # actor plane stops being bound by the host env step — what binds
    # next is the policy forward (CPU) or the ingest plane (TPU)
    out["host_step_bound"] = False
    return {"device_env": out}


def bench_anakin(pairs: int = 10, envs: int = 16, ticks: int = 8,
                 smoke: bool = False) -> dict:
    """The ISSUE-12 closed-loop section: the co-located Anakin driver
    (agents/anakin.py — env fleet + learner in ONE process, the fused
    rollout scattering straight into the HBM PER ring, zero host work
    on the experience path) against the split-process ``device``
    backend's host plumbing driving the SAME XLA programs (chunk D2H
    -> per-row feeder -> spawn queue -> ingest drain -> fused learner
    step — the ~56 KB/transition wall BENCH_r03 measured).

    Both legs run the same strict-alternation schedule (one rollout
    dispatch, one learner dispatch, ``pairs`` times) on the same
    geometry, so ``speedup_vs_device`` is purely the host plumbing the
    co-location deletes.  ``duty_cycle`` is the rollout share of busy
    time (the ``anakin/duty_cycle`` telemetry tag's exact definition);
    frames/s counts ALL env frames over the pair wall clock — the
    e2e-loop rate, not the rollout-only ceiling the device_env section
    reports.  ``smoke=True`` shrinks the fleet to seconds-scale and
    skips the split leg (one compile instead of three); the smoke
    output rides ``smoke.anakin_frames_per_sec`` into the gate."""
    import jax

    from pytorch_distributed_tpu.agents.anakin import AnakinDriver
    from pytorch_distributed_tpu.agents.clocks import (
        ActorStats, GlobalClock, LearnerStats,
    )
    from pytorch_distributed_tpu.config import build_options
    from pytorch_distributed_tpu.agents.param_store import (
        ParamStore, make_flattener,
    )
    from pytorch_distributed_tpu.factory import (
        build_memory, build_model, init_params, probe_env,
    )

    if smoke:
        pairs, envs, ticks = 4, 8, 6

    def make_opt(root, **over):
        # config 12 (pong-sim + HBM PER ring) with the mlp head: the
        # cnn forward would drown the plumbing delta on a CPU host (the
        # device_env section's policy_bound flag), and the ring schema
        # pins uint8 to match the device env's frames (the config-12
        # cnn default; the mlp default would flip it to float32)
        base = dict(
            root_dir=root, refs="bench_anakin", num_actors=1,
            num_envs_per_actor=envs, actor_backend="anakin",
            visualize=False, model_type="dqn-mlp", state_dtype="uint8",
            nstep=4, memory_size=4096, learn_start=64, batch_size=32,
            steps=10 ** 9, early_stop=50, actor_freq=10 ** 9,
            learner_freq=10 ** 9, param_publish_freq=10 ** 9,
            checkpoint_freq=10 ** 9)
        base.update(over)
        opt = build_options(config=12, **base)
        opt.env_params.device_rollout_ticks = ticks
        return opt

    # ---- leg A: the co-located driver ----
    root_a = tempfile.mkdtemp(prefix="bench_anakin_")
    opt = make_opt(root_a)
    spec = probe_env(opt)
    handles = build_memory(opt, spec)
    model = build_model(opt, spec)
    flat0, _ = make_flattener(init_params(opt, spec, model,
                                          seed=opt.seed))
    drv = AnakinDriver(opt, spec, handles.learner_side,
                       ParamStore(flat0.size), GlobalClock(),
                       LearnerStats(), actor_stats=ActorStats())
    drv.dispatch_rollout()   # compile both programs outside the window
    drv.dispatch_learn()
    drv._roll_s = drv._learn_s = 0.0
    t0 = time.perf_counter()
    for _ in range(pairs):
        drv.dispatch_rollout()
        drv.dispatch_learn()
    jax.block_until_ready(drv.state.params)
    wall = time.perf_counter() - t0
    frames = pairs * ticks * envs
    updates = pairs * drv.K_learn
    busy = drv._roll_s + drv._learn_s
    out = {
        "frames_per_sec": round(frames / wall, 1),
        "updates_per_sec": round(updates / wall, 2),
        "duty_cycle": round(drv._roll_s / busy, 4) if busy else None,
        "pairs": pairs,
        "geometry": f"dqn-mlp head, {envs} envs x {ticks} ticks, "
                    f"uint8 HBM PER ring (config 12)",
    }
    drv.writer.close()
    handles.learner_side.close()
    print(f"[bench_anakin] co-located: {out}", file=sys.stderr,
          flush=True)

    if not smoke:
        out["split_frames_per_sec"] = _anakin_split_leg(
            make_opt, pairs, envs, ticks)
        out["speedup_vs_device"] = round(
            out["frames_per_sec"] / out["split_frames_per_sec"], 2)
        print(f"[bench_anakin] split-process: "
              f"{out['split_frames_per_sec']} f/s "
              f"(speedup {out['speedup_vs_device']}x)",
              file=sys.stderr, flush=True)
    return {"anakin": out}


def _anakin_split_leg(make_opt, pairs: int, envs: int,
                      ticks: int) -> float:
    """The split-process ``actor_backend="device"`` loop's pieces in
    one process, driven to the same strict-alternation schedule as the
    co-located leg: chunk-emit rollout -> device_get -> per-row feeder
    (the device actor loop's exact feed path) -> spawn queue -> ingest
    drain -> fused learner step."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.factory import (
        build_device_env, build_memory, build_model,
        build_train_state_and_step, init_params, probe_env,
    )
    from pytorch_distributed_tpu.models.policies import (
        apex_epsilons, build_fused_rollout, init_rollout_carry,
    )
    from pytorch_distributed_tpu.utils.experience import (
        Transition, make_prov,
    )
    from pytorch_distributed_tpu.utils.rngs import np_rng, process_key

    root = tempfile.mkdtemp(prefix="bench_anakin_split_")
    opt = make_opt(root, actor_backend="device")
    ap = opt.agent_params
    spec = probe_env(opt)
    ingest = build_memory(opt, spec).learner_side
    model = build_model(opt, spec)
    params = init_params(opt, spec, model, seed=opt.seed)
    state, step_fn = build_train_state_and_step(opt, spec, model, params)
    ring = ingest.attach()
    fused = ring.build_fused_step(step_fn, ap.batch_size,
                                  donate=opt.parallel_params.donate,
                                  steps_per_call=1)
    device_key = jax.random.PRNGKey(
        np_rng(opt.seed, "learner", 0).integers(2 ** 31))
    env = build_device_env(opt, 0, envs)
    roll = build_fused_rollout(model.apply, env, nstep=ap.nstep,
                               gamma=ap.gamma, rollout_ticks=ticks,
                               emit="chunk")
    carry = init_rollout_carry(env, ap.nstep)
    base_key = jnp.asarray(process_key(opt.seed, "actor", 0))
    eps = jnp.asarray(apex_epsilons(0, 1, envs, ap.eps, ap.eps_alpha),
                      jnp.float32)
    feeder = ingest.make_feeder()
    tick0 = jnp.int32(0)
    fed_expected = 0

    def pair(k):
        nonlocal carry, tick0, state, device_key, fed_expected
        carry, chunk = roll(state.params, carry, base_key, tick0, eps)
        tick0 = tick0 + ticks
        ch = jax.device_get(chunk)   # the split path's chunk D2H
        valid = np.asarray(ch.valid)
        for t in range(ticks):
            for j in range(envs):
                if not valid[t, j]:
                    continue
                feeder.feed(Transition(
                    state0=ch.state0[t, j], action=ch.action[t, j],
                    reward=ch.reward[t, j], gamma_n=ch.gamma_n[t, j],
                    state1=ch.state1[t, j],
                    terminal1=ch.terminal1[t, j],
                    prov=make_prov(0, j, 0, k)), None)
                fed_expected += 1
        feeder.flush()
        # drain until THIS dispatch's transitions have all landed in
        # the ring — the freshness the co-located loop gives by
        # construction (each learn samples the rollout it just ran).
        # Letting the queue lag instead hides the plumbing behind the
        # learner's XLA time on an idle core, at the price of sampling
        # stale data — exactly the Podracer trade this section exists
        # to measure.  The geometry keeps every dispatch's emission
        # count a multiple of the smallest feeder chunk (64) so the
        # drain can fully settle.
        deadline = time.monotonic() + 30.0
        while ingest._fed_total < fed_expected \
                and time.monotonic() < deadline:
            ingest.drain()
            time.sleep(0.001)
        keys = jax.random.split(device_key, 2)
        device_key = keys[0]
        beta = jax.device_put(np.float32(ring.beta(k)))
        new_state, ring.state, _m = fused(state, ring.state, keys[1],
                                          beta)
        return new_state

    state = pair(0)   # compile outside the window
    t0 = time.perf_counter()
    for k in range(pairs):
        state = pair(k + 1)
    jax.block_until_ready(state.params)
    wall = time.perf_counter() - t0
    ingest.close()
    return round(pairs * ticks * envs / wall, 1)


def bench_e2e(seconds: float = 60.0, actors: int = 1,
              envs_per_actor: int = 16,
              actor_backend: str | None = None) -> dict:
    """North-star accounting: env frames/s + paced updates/s with the full
    config-8 topology live (actors -> feeder -> HBM replay -> learner).

    ``actors``/``envs_per_actor`` reshape the fleet: the default 1x16 is
    the production topology for few-CPU hosts (the actor tick is ~94%
    jitted CNN inference, so one process with a wider batch beats N
    processes time-slicing a core — measured 143 -> 250+ agent steps/s on
    the 1-CPU image, 2026-07-31); ``--e2e-actors 16 --e2e-envs 1`` is the
    reference-scale fan-out drive (reference main.py:68-80 spawns
    num_actors processes), converting the many-actor architecture claim
    into a measured aggregate rate on whatever host runs this."""
    import jax

    from pytorch_distributed_tpu import runtime
    from pytorch_distributed_tpu.config import build_options
    from pytorch_distributed_tpu.utils.metrics import read_scalars

    if actor_backend is None:
        # with an accelerator present the learner parent owns it and can
        # host the SEED-style inference batcher — actor ticks stop being
        # host-CPU convnet forwards (ISSUE 4); CPU-only hosts run the
        # ISSUE-12 CLOSED loop: env fleet + learner co-located in one
        # process, zero spawn-queue/D2H work on the experience path
        # (the config-8 pong-sim env has a device implementation and
        # the config-8 memory is the HBM ring anakin scatters into)
        actor_backend = ("batched"
                         if jax.devices()[0].platform != "cpu"
                         else "anakin")

    t_start = time.perf_counter()

    def mark(stage: str) -> None:
        print(f"[bench_e2e +{time.perf_counter() - t_start:.1f}s] {stage}",
              file=sys.stderr, flush=True)

    root = tempfile.mkdtemp(prefix="bench_e2e_")
    opt = build_options(
        8, root_dir=root, refs="bench_e2e", num_actors=actors,
        num_envs_per_actor=envs_per_actor, batch_size=128, visualize=False,
        learn_start=1000, max_replay_ratio=8.0, logger_freq=5,
        actor_backend=actor_backend,
        evaluator_nepisodes=0,  # no evaluator process in the bench
        steps=10 ** 9, max_seconds=seconds + 45.0)
    if actor_backend == "anakin" and jax.devices()[0].platform == "cpu":
        # duty-cycle setpoint for the CPU image: the split-process
        # backends' actors free-run while the CNN learner trails far
        # behind (BENCH_r03: ~470 f/s against ~1 update/s — replay
        # ratio << 1), so the comparable anakin schedule is the same
        # data-rich regime, ~4 frames collected per sampled-batch row.
        # Strict alternation (ratio 0, the default) is the TPU
        # operating point: there the learn dispatch is ms-scale and
        # alternation keeps the chip saturated either way.
        opt.anakin_params = dataclasses.replace(
            opt.anakin_params, rollout_ratio=4.0 * opt.agent_params.
            batch_size)

    # The topology (and its child processes) write progress to fd 1; the
    # driver contract is ONE JSON line on stdout, so point fd 1 at stderr
    # for the duration and restore it for the final print.
    saved_stdout = os.dup(1)
    mark("starting topology")
    try:
        sys.stdout.flush()
        os.dup2(2, 1)
        runtime.train(opt, backend="process")
    finally:
        sys.stdout.flush()  # buffered worker prints must NOT hit real fd 1
        os.dup2(saved_stdout, 1)
        os.close(saved_stdout)
    mark("topology done")

    rows = read_scalars(os.path.join(root, "logs", "bench_e2e"))
    frames = [(r["wall"], r["value"]) for r in rows
              if r["tag"] == "actor/total_nframes"]
    lrates = [(r["wall"], r["value"]) for r in rows
              if r["tag"] == "learner/steps_per_sec"]
    if len(frames) < 3:
        return {"e2e_error": "too few logger windows"}
    # drop the first quarter of the wall span: children are still paying
    # jax import + compile there, which is startup, not throughput
    t0, t1 = frames[0][0], frames[-1][0]
    cut = t0 + 0.25 * (t1 - t0)
    kept = [(w, v) for w, v in frames[1:] if w >= cut]  # [1:]: deltas
    span = kept[-1][0] - kept[0][0] if len(kept) > 1 else 0.0
    agent_steps = sum(v for _, v in kept[1:])
    out = {
        "e2e_frames_per_sec": round(agent_steps / span, 1) if span else None,
        "e2e_emulator_frames_per_sec":
            round(4 * agent_steps / span, 1) if span else None,
        "e2e_seconds": round(t1 - t0, 1),
        "e2e_actors": f"{actors}x{envs_per_actor} envs",
        "e2e_num_actors": actors,
        "e2e_actor_backend": actor_backend,
    }
    lr = [v for w, v in lrates if w >= cut]
    if lr:
        out["e2e_paced_updates_per_sec"] = round(float(np.median(lr)), 2)
    # Actor-plane wall-time breakdown (SURVEY §7 hard part "batch-1 actor
    # inference latency"): the actors' StepTimer scalars say where each
    # tick goes — jitted act() forward, env.step, or the python feed path
    # (advance).  Medians over the kept window, ms per vector tick.
    breakdown = {}
    for tag in ("actor/time_act_ms", "actor/time_env_ms",
                "actor/time_advance_ms", "actor/time_sync_ms",
                "actor/time_dispatch_ms", "actor/time_param_swap_ms",
                "actor/time_rollout_ms", "actor/time_emit_ms"):
        vals = [r["value"] for r in rows
                if r["tag"] == tag and r["wall"] >= cut]
        if vals:
            breakdown[tag.split("/")[-1]] = round(float(np.median(vals)), 3)
    if breakdown:
        out["e2e_actor_tick_ms"] = breakdown
    # pipelined/batched actors: overlap efficiency = the host work the
    # in-flight dispatch hid / the device-wait it couldn't hide + that
    # hidden work — per-tick, from the actors' own phase timers.  1.0
    # means every device/server microsecond was covered by env stepping
    # and feed work; 0 means the pipeline never hid anything (the serial
    # loop's behaviour by construction).
    if "time_sync_ms" in breakdown:
        hidden = breakdown.get("time_env_ms", 0.0) + breakdown.get(
            "time_advance_ms", 0.0)
        wait = breakdown["time_sync_ms"] + breakdown.get(
            "time_dispatch_ms", 0.0)
        if hidden + wait > 0:
            out["e2e_overlap_efficiency"] = round(
                hidden / (hidden + wait), 4)
    if actor_backend == "device":
        # the ISSUE-7 read: the actor plane has NO host env step — its
        # tick breakdown is the fused device dispatch (rollout), the
        # once-per-dispatch chunk fetch (emit) and the replay feed
        # (advance); time_env_ms cannot appear by construction
        out["e2e_host_env_step_ms"] = 0.0
        out["e2e_actor_plane"] = (
            "device rollout (fused env+policy+nstep scan) — actor "
            "plane no longer bound by the host env step")
    elif actor_backend == "anakin":
        # the ISSUE-12 read: there is no actor PROCESS at all — the
        # learner process hosts the env fleet and alternates the fused
        # rollout (scattering in-graph into its own HBM ring) with the
        # fused learner step; no host env step, no spawn queue, no
        # D2H on the experience path.  What binds e2e now is the
        # learner-side FLOPs (rollout forward + train step) alone.
        out["e2e_host_env_step_ms"] = 0.0
        out["e2e_actor_plane"] = (
            "anakin co-located loop (env fleet in the learner "
            "process, in-graph replay scatter) — e2e is "
            "learner-FLOPs-bound, zero experience-path transfers")
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("micro", "e2e", "both", "families",
                                       "sampler", "act", "actor",
                                       "health", "perf", "device_env",
                                       "provenance", "metrics", "flow",
                                       "anakin", "replica",
                                       "gateway", "wire", "shard"),
                    default="both")
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale CPU-safe bench (the dqn-mlp "
                         "fused learner program only) for CI gating: "
                         "pipe the JSON into tools/bench_gate.py "
                         "--against BENCH_SMOKE_BASELINE.json")
    ap.add_argument("--e2e-seconds", type=float, default=60.0)
    ap.add_argument("--e2e-actors", type=int, default=1)
    ap.add_argument("--e2e-envs", type=int, default=16)
    ap.add_argument("--e2e-actor-backend", type=str, default=None,
                    choices=("inline", "pipelined", "batched", "device",
                             "anakin"),
                    help="override the e2e actor schedule (default: "
                         "batched on accelerator hosts, else the "
                         "ISSUE-12 co-located anakin loop)")
    ap.add_argument("--actor-envs", type=int, default=16,
                    help="env-vector width for the actor-pipeline section")
    ap.add_argument("--actor-ticks", type=int, default=300)
    args = ap.parse_args()

    import jax

    from pytorch_distributed_tpu.utils.helpers import enable_compile_cache

    # the repo's one cache rule (helpers.compile_cache_dir)
    enable_compile_cache()

    result = {}
    if args.smoke:
        result.update(bench_smoke())
        # seconds-scale device-env engine row (N=32, linear policy)
        # so the gate covers the ISSUE-7 actor plane from day one
        dev = bench_device_env(smoke=True)["device_env"]
        result["smoke"]["device_env_frames_per_sec"] = \
            dev["fused"]["32"]["linear_frames_per_sec"]
        result["smoke"]["device_env_host_frames_per_sec"] = \
            dev["ladder"]["32"]["host_frames_per_sec"]
        # ISSUE-10 telemetry-plane overhead rides the smoke output so
        # the pre-PR gate holds the <2% band continuously (additive
        # key — existing keys keep their meaning, so no schema bump)
        result.update(bench_metrics_overhead(smoke=True))
        # ISSUE-11 flow-plane overhead rides the smoke output the same
        # way (additive key, schema stays 4)
        result.update(bench_flow_overhead(smoke=True))
        # ISSUE-15 replica-plane overhead (lease renew + generation
        # stamp vs the round-exchange span): additive key, schema
        # stays 4; tools/check.sh stage 2c fails on its absence
        result.update(bench_replica_overhead(smoke=True))
        # ISSUE-16 gateway HA-plane overhead (journal append + sync
        # serve + per-frame term gate vs the wire ingest span):
        # additive key, schema stays 4; tools/check.sh stage 2d fails
        # on its absence
        result.update(bench_gateway_ha_overhead(smoke=True))
        # ISSUE-18 wire byte economics (legacy vs frame-packed
        # bytes/transition, replica bytes/round) and the accountant's
        # hot-path cost: additive keys, schema stays 4; tools/check.sh
        # stage 2e fails on their absence
        result.update(bench_wire(smoke=True))
        result.update(bench_wire_overhead(smoke=True))
        # ISSUE-20 sharded-replay plane: sample latency at 1/2/4
        # loopback shards and the mass-refresh+route cost vs the
        # sample span: additive keys, schema stays 4; tools/check.sh
        # stage 2f fails on their absence
        result.update(bench_shard(smoke=True))
        result.update(bench_shard_overhead(smoke=True))
        # ISSUE-12 co-located loop: the closed rollout+learn pair rate
        # on a tiny fleet (additive key, schema stays 4; the full
        # section with the split-process comparison runs under --mode
        # anakin/both)
        result["smoke"]["anakin_frames_per_sec"] = \
            bench_anakin(smoke=True)["anakin"]["frames_per_sec"]
        out = {
            "bench_schema": 4,
            "metric": "smoke_updates_per_sec",
            "value": result["smoke"]["updates_per_sec"],
            "unit": ("updates/s (dqn-mlp fused x8, smoke geometry — "
                     "machine-local figure, gate against same-machine "
                     "history)"),
            "mode": "smoke",
            "device_kind": getattr(jax.devices()[0], "device_kind", "?"),
        }
        out.update(result)
        print(json.dumps(out))
        return
    if args.mode in ("micro", "both"):
        result.update(bench_micro())
    if args.mode in ("both", "families"):
        result.update(bench_families())
    if args.mode in ("both", "sampler"):
        result.update(bench_sampler())
    if args.mode in ("both", "act"):
        result.update(bench_act_ab())
    if args.mode in ("both", "health"):
        result.update(bench_health_overhead())
    if args.mode in ("both", "perf"):
        result.update(bench_perf_overhead())
    if args.mode in ("both", "provenance"):
        result.update(bench_provenance_overhead())
    if args.mode in ("both", "metrics"):
        result.update(bench_metrics_overhead())
    if args.mode in ("both", "flow"):
        result.update(bench_flow_overhead())
    if args.mode in ("both", "replica"):
        result.update(bench_replica_overhead())
    if args.mode in ("both", "gateway"):
        result.update(bench_gateway_ha_overhead())
    if args.mode in ("both", "wire"):
        result.update(bench_wire())
        result.update(bench_wire_overhead())
    if args.mode in ("both", "shard"):
        result.update(bench_shard())
        result.update(bench_shard_overhead())
    if args.mode in ("both", "actor"):
        result.update(bench_actor_pipeline(args.actor_envs,
                                           args.actor_ticks))
    if args.mode in ("both", "device_env"):
        result.update(bench_device_env())
    if args.mode in ("both", "anakin"):
        result.update(bench_anakin())
    if args.mode in ("e2e", "both"):
        result.update(bench_e2e(args.e2e_seconds, args.e2e_actors,
                                args.e2e_envs, args.e2e_actor_backend))

    headline = result.get("updates_per_sec")
    n_dev = len(jax.devices())
    if headline is not None:
        metric = "dqn_cnn_learner_updates_per_sec"
        value = headline
        unit = (f"updates/s (batch {MICRO_BATCH}, "
                f"production fused x{MICRO_DISPATCH}, "
                f"HBM replay, {n_dev} device(s), "
                f"{jax.devices()[0].platform})")
    elif args.mode in ("e2e", "both"):
        # e2e ran (value may be None on an error path — keep the e2e
        # metric label either way so consumers see what failed)
        metric, value, unit = ("e2e_frames_per_sec",
                               result.get("e2e_frames_per_sec"),
                               "agent steps/s")
    elif "families" in result:  # families-only: summarize the table
        fams = result["families"]
        rates = [v["updates_per_sec"] for v in fams.values()
                 if "updates_per_sec" in v]
        metric = "family_learner_updates_per_sec_median"
        value = round(float(np.median(rates)), 2) if rates else None
        unit = f"updates/s (median of {len(rates)} model families)"
    else:  # sampler/act-only invocations have no throughput headline
        metric, value, unit = f"bench_{args.mode}", None, "see section keys"
    out = {
        # schema 4: adds the ISSUE-7 device_env section (on-device env
        # fleet ladder + fused rollout engine) and the e2e default
        # actor plane on CPU hosts becomes actor_backend=device (no
        # host env step — e2e_frames_per_sec is not comparable to
        # schema-3 rows measured with pipelined host-env actors;
        # e2e_actor_backend says which plane ran).  Schema 3: e2e runs
        # the ISSUE-4 actor plane (pipelined/batched), actor_pipeline
        # section, e2e_overlap_efficiency.  Schema 2 (r3):
        # production-K headline, fused families rows, sampler +
        # act-A/B sections.  Bump whenever a key's MEANING changes so
        # longitudinal consumers never compare across semantics
        # (round-3 advisor finding).
        "bench_schema": 4,
        "metric": metric,
        "value": value,
        "unit": unit,
        "vs_baseline": round(headline / BASELINE_UPDATES_PER_SEC, 3)
                       if headline is not None else None,
        "vs_baseline_basis": "self-declared 250 updates/s (consumer-GPU "
                             "class for this workload); reference "
                             "publishes no throughput figures",
        "device_kind": getattr(jax.devices()[0], "device_kind", "?"),
    }
    out.update(result)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
