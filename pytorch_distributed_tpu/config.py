"""Configuration system.

TPU-native re-design of the reference's config layer (``utils/options.py`` in
the reference repo: the ``CONFIGS`` 5-tuple table at :10-14 and the
``Params``/``EnvParams``/``MemoryParams``/``ModelParams``/``AgentParams``/
``Options`` class hierarchy at :16-175).

Differences from the reference, on purpose:

- Plain frozen-by-convention dataclasses instead of mutually-inheriting
  classes with class-attribute singletons; an ``Options`` instance is an
  explicit value that is passed around (and pickled across process spawns).
- A real CLI (``--config``, ``--mode``, ``--num-actors``, ...) in
  ``main.py`` on top of the table — the reference is edit-the-file only
  (reference ``README.md:41-49``).
- Hyperparameter *values* mirror the reference defaults exactly
  (reference ``utils/options.py:108-168``) so learning behaviour is
  comparable; each is annotated with its reference source.
"""

from __future__ import annotations

import dataclasses
import os
import time
from dataclasses import dataclass, field
from typing import Any, Optional, Tuple

# ---------------------------------------------------------------------------
# The CONFIGS table: each row bundles compatible component choices, exactly
# like reference utils/options.py:10-14 —
#   [agent_type, env_type, game, memory_type, model_type]
# Rows 0 is the reference's only row (dqn/atari/pong/shared/dqn-cnn).  The
# extra rows cover the driver BASELINE.json tracked configs plus self-
# contained debug envs that need no ALE install.  Rows 12, 14 and 20
# are the benchmark's configurations (benchmark/configs/); rows 15, 17 and
# 18 are toy-sized families on the host sequence replay.
# ---------------------------------------------------------------------------
CONFIGS = [
    # agent_type, env_type,    game,          memory_type, model_type
    ["dqn",       "atari",     "pong",        "shared",    "dqn-cnn"],   # 0 (reference row 0)
    ["dqn",       "fake",      "chain",       "shared",    "dqn-mlp"],   # 1 smoke/debug
    ["ddpg",      "classic",   "pendulum",    "shared",    "ddpg-mlp"],  # 2
    ["dqn",       "classic",   "cartpole",    "shared",    "dqn-mlp"],   # 3
    ["dqn",       "pong-sim",  "pong",        "shared",    "dqn-cnn"],   # 4 ALE-free Pong clone
    ["dqn",       "atari",     "breakout",    "shared",    "dqn-cnn"],   # 5
    ["dqn",       "pong-sim",  "pong",        "prioritized", "dqn-cnn"], # 6 PER
    ["dqn",       "atari",     "pong",        "prioritized", "dqn-cnn"], # 7 PER on ALE
    ["dqn",       "pong-sim",  "pong",        "device",      "dqn-cnn"], # 8 HBM replay (flagship TPU)
    ["ddpg",      "gym",       "halfcheetah", "shared",      "ddpg-mlp"],# 9  (BASELINE config 4; needs gym+mujoco)
    ["ddpg",      "gym",       "humanoid",    "shared",      "ddpg-mlp"],# 10 (BASELINE config 5; needs gym+mujoco)
    ["dqn",       "atari",     "breakout",    "device",      "dqn-cnn"], # 11 Atari-57 sweep row (needs ALE)
    ["dqn",       "pong-sim",  "pong",        "device-per",  "dqn-cnn"], # 12 HBM PER, fully fused
    ["r2d2",      "fake",      "chain",       "sequence",    "drqn-mlp"],# 13 recurrent smoke
    ["r2d2",      "pong-sim",  "pong",        "device-sequence", "drqn-cnn"],# 14 R2D2 pixels, HBM segment ring
    ["r2d2",      "fake",      "chain",       "sequence",    "dtqn-mlp"],# 15 transformer Q (DTQN)
    ["ddpg",      "classic",   "reacher",     "shared",      "ddpg-mlp"],# 16 multi-dim continuous control
    ["r2d2",      "fake",      "chain",       "sequence",    "dtqn-moe"],# 17 MoE transformer Q (expert parallel)
    ["r2d2",      "fake",      "chain",       "sequence",    "dtqn-pipe"],# 18 staged transformer Q (pipeline parallel)
    ["dqn",       "pong-sim",  "pong",        "device-per",  "dqn-cnn-wide"],# 19 MXU-filling wide torso (ISSUE 13)
    ["r2d2",      "pong-sim",  "pong",        "device-sequence", "dtqn-hybrid"],# 20 state-space / sparse-expert / grouped-query trunk (models/hybrid.py)
    ["r2d2",      "pong-sim",  "pong",        "device-sequence", "dtqn-hybrid"],# 21 gated-delta-rule / 512-expert / gated-attention trunk (ROW_DEFAULTS)
    ["r2d2",      "pong-sim",  "pong",        "device-sequence", "dtqn-hybrid"],# 22 channel-gated delta rule / latent attention / 256-expert trunk (ROW_DEFAULTS)
    ["r2d2",      "pong-sim",  "pong",        "device-sequence", "dtqn-hybrid"],# 23 gated short convolution / grouped-query / 32-expert trunk (ROW_DEFAULTS)
]

# What a row sets beside its five selectors, before the caller's overrides.
ROW_DEFAULTS = {21: {"hybrid_preset": "qwen3-next-4"},
                22: {"hybrid_preset": "kimi-linear-5"},
                23: {"hybrid_preset": "lfm2-moe-5"}}


# ---------------------------------------------------------------------------
# The env-knob declaration table (ISSUE 9).  Every TPU_APEX_* / *_FAULTS
# environment variable the fleet reads MUST have a row here — (name,
# where-read, one-line doc) — and a matching row in the knob tables of
# README.md and TESTING.md.  ``tools/apexlint.py`` (knob-registry rule)
# mechanically diffs this table against the env reads it finds in code
# and against both docs, in BOTH directions: an undeclared read, a
# declared-but-never-read row, and an undocumented knob are each
# findings.  Names ending in ``*`` declare a family (per-field override
# planes built from a prefix constant); ``*_FAULTS`` is the per-plane
# fault-injection suffix family.  Plain string tuples on purpose: the
# linter parses this literal via ast, no import.
# ---------------------------------------------------------------------------
KNOBS = (
    ("TPU_APEX_PERF", "utils/perf.py",
     "master perf-plane switch (shorthand for TPU_APEX_PERF_ENABLED)"),
    ("TPU_APEX_PERF_*", "utils/perf.py",
     "per-field PerfParams overrides (e.g. TPU_APEX_PERF_PEAK_FLOPS)"),
    ("TPU_APEX_TRACE", "utils/tracing.py",
     "chunk tracing on/off (default on; 0 ships plain chunks)"),
    ("TPU_APEX_TRACE_SAMPLE", "utils/tracing.py",
     "per-event span row sampling rate"),
    ("TPU_APEX_QUARANTINE", "utils/health.py",
     "process-wide ingest-quarantine kill switch"),
    ("TPU_APEX_HEALTH_*", "utils/health.py",
     "per-field HealthParams overrides (e.g. TPU_APEX_HEALTH_HANG_DEADLINE)"),
    ("TPU_APEX_PROFILE", "utils/profiling.py",
     "directory for TensorBoard-viewable device traces"),
    ("TPU_APEX_BLACKBOX_DIR", "utils/flight_recorder.py",
     "blackbox dump directory, exported to spawn children"),
    ("TPU_APEX_RUN_ID", "utils/flight_recorder.py",
     "run id stamped on blackbox dumps + quarantine files"),
    ("DCN_FAULTS_*", "utils/faults.py",
     "wire-role fault specs (DCN_FAULTS_CLIENT / DCN_FAULTS_GATEWAY)"),
    ("*_FAULTS", "utils/faults.py",
     "per-plane fault specs (CKPT_/FEEDER_/LEARNER_/ACTOR_FAULTS)"),
    ("DCN_IDLE_DEADLINE", "parallel/dcn.py",
     "gateway idle-connection reap deadline, seconds"),
    ("TPU_APEX_METRICS", "utils/telemetry.py",
     "mission-control metrics plane switch (shorthand for "
     "TPU_APEX_METRICS_ENABLED)"),
    ("TPU_APEX_METRICS_*", "utils/telemetry.py",
     "per-field MetricsParams overrides (e.g. "
     "TPU_APEX_METRICS_OPENMETRICS, TPU_APEX_METRICS_PUSH_S)"),
    ("TPU_APEX_ALERT_*", "utils/telemetry.py",
     "per-field AlertParams overrides (e.g. TPU_APEX_ALERT_RULES)"),
    ("TPU_APEX_FLOW", "utils/flow.py",
     "flow-control plane switch (shorthand for TPU_APEX_FLOW_ENABLED)"),
    ("TPU_APEX_FLOW_*", "utils/flow.py",
     "per-field FlowParams overrides (e.g. TPU_APEX_FLOW_LOCAL_POLICY, "
     "TPU_APEX_FLOW_CLIENT_RING)"),
    ("TPU_APEX_ANAKIN_*", "agents/anakin.py",
     "per-field AnakinParams overrides (e.g. TPU_APEX_ANAKIN_ROLLOUT_RATIO, "
     "TPU_APEX_ANAKIN_DOUBLE_BUFFER)"),
    ("TPU_APEX_MXU_*", "utils/perf.py",
     "per-field LearnerPerfParams overrides — the ISSUE-13 MFU-campaign "
     "levers (e.g. TPU_APEX_MXU_MEGABATCH, TPU_APEX_MXU_PALLAS_TORSO)"),
    ("TPU_APEX_REPLICA_*", "parallel/dcn.py",
     "per-field ReplicaParams overrides — the ISSUE-15 multi-learner "
     "replica plane (e.g. TPU_APEX_REPLICA_REPLICAS, "
     "TPU_APEX_REPLICA_LEASE_S)"),
    ("TPU_APEX_GATEWAY_*", "parallel/dcn.py",
     "per-field GatewayParams overrides — the ISSUE-16 gateway "
     "high-availability plane (e.g. TPU_APEX_GATEWAY_ENABLED, "
     "TPU_APEX_GATEWAY_LEASE_S, TPU_APEX_GATEWAY_ENDPOINTS)"),
    ("TPU_APEX_WIRE", "utils/bandwidth.py",
     "bandwidth-accounting plane switch (shorthand for "
     "TPU_APEX_WIRE_ENABLED)"),
    ("TPU_APEX_WIRE_*", "utils/bandwidth.py",
     "per-field BandwidthParams overrides — the ISSUE-18 byte-exact "
     "wire/ring/checkpoint accountant (e.g. TPU_APEX_WIRE_SPAWN, "
     "TPU_APEX_WIRE_RATE_FLOOR_S)"),
    ("TPU_APEX_SHARD_*", "memory/shard_plane.py",
     "per-field ShardParams overrides — the ISSUE-20 sharded "
     "prioritized-replay plane (e.g. TPU_APEX_SHARD_SHARDS, "
     "TPU_APEX_SHARD_LEASE_S, TPU_APEX_SHARD_COORDINATOR)"),
)


def _default_refs() -> str:
    """Run signature ``{machine}_{timestamp}`` keying checkpoints and logs
    (reference utils/options.py:37-51)."""
    machine = os.uname().nodename.split(".")[0] or "machine"
    return f"{machine}_{time.strftime('%y%m%d%H%M%S')}"


@dataclass
class EnvParams:
    """Env-layer knobs (reference utils/options.py:54-69)."""

    env_type: str = "atari"
    game: str = "pong"
    seed: int = 100
    # State layout: ``state_cha`` is the history length (stacked frames for
    # CNNs, 1 for MLPs); hei/wid are the per-frame spatial dims.
    state_cha: int = 4
    state_hei: int = 84
    state_wid: int = 84
    # Max emulator frames per episode before truncation
    # (reference utils/options.py:69; "early_stop").
    early_stop: int = 12500
    # Life-loss-as-terminal & action-repeat semantics toggled by mode
    # (reference core/env.py:29-35).
    action_repetition: int = 4
    # Vector-env width per actor process.  The reference asserts this to 1
    # (utils/options.py:32, atari_env.py:15); here >1 is supported by the
    # sim envs and batched inference.
    num_envs_per_actor: int = 1
    # Actor hot-loop schedule/placement (ISSUE 4 + ISSUE 7):
    #   "pipelined" — two-stage software pipeline (default): the jitted
    #                 act for tick k+1 is dispatched asynchronously while
    #                 the host feeds tick k; bit-identical streams to
    #                 "inline" under a fixed seed.
    #   "inline"    — the serial dispatch-sync-step-feed loop; the
    #                 fallback and the determinism reference.
    #   "batched"   — SEED-style shared inference: actors hold no model
    #                 and submit obs to the InferenceServer thread in the
    #                 accelerator-owning process (agents/inference.py).
    #                 dqn/ddpg with a co-located server only; downgrades
    #                 to "pipelined" otherwise (factory.
    #                 resolve_actor_backend).
    #   "device"    — Sebulba/Anakin on-device env fleet (ISSUE 7): the
    #                 env itself is a pure-JAX program
    #                 (envs/device_env.py) and ONE donated scan advances
    #                 all N envs x device_rollout_ticks ticks fused with
    #                 the policy forward and on-device n-step assembly
    #                 (models/policies.build_fused_rollout) — no host
    #                 env step at all; one D2H per dispatch ships the
    #                 finished transition chunk.  dqn families with a
    #                 device env implementation only (pong-sim);
    #                 downgrades to "pipelined" otherwise.
    #   "anakin"    — the CLOSED Anakin loop (ISSUE 12): the env fleet
    #                 lives IN the learner process and one driver
    #                 alternates the donated fused rollout (emit=
    #                 "replay", scattering straight into the device
    #                 replay ring) with the fused learner dispatch
    #                 against the same HBM ring — no actor processes,
    #                 no spawn queue, no D2H on the experience path at
    #                 all (agents/anakin.py).  The acting params ARE the
    #                 train state's params (the published version is the
    #                 acting version by construction).  dqn + a device
    #                 env implementation + a device replay ring
    #                 (memory_type "device"/"device-per") only;
    #                 downgrades to "device" otherwise.  Knobs:
    #                 AnakinParams.
    actor_backend: str = "pipelined"
    # Ticks per fused device rollout dispatch (actor_backend="device"):
    # K env steps of all N envs run inside one XLA program, amortizing
    # dispatch latency and the chunk D2H over K*N frames.  Weight-sync
    # and stat cadences quantize to K ticks.
    device_rollout_ticks: int = 8
    # Device env family selector: "auto" derives it from env_type
    # (pong-sim -> the "pong" device port).  Naming a family explicitly
    # pins/documents the choice and must MATCH the env_type's own
    # device family (a family can never substitute a different game
    # than the host config runs — mismatches raise).
    # envs/device_env.DEVICE_ENV_FAMILIES.
    device_env_family: str = "auto"
    render: bool = False
    # Step sim envs through the first-party C++ batched stepper
    # (native/pong_batch.cpp) when the toolchain builds it; the Python
    # per-env loop is the fallback either way.
    native_env: bool = True

    @property
    def state_shape(self) -> Tuple[int, ...]:
        if self.state_hei > 1 or self.state_cha > 1:
            return (self.state_cha, self.state_hei, self.state_wid)
        return (self.state_wid,)


@dataclass
class MemoryParams:
    """Replay-memory knobs (reference utils/options.py:72-94)."""

    memory_type: str = "shared"
    memory_size: int = 50000           # reference utils/options.py:78-80
    enable_per: bool = False           # reference leaves PER unfinished (":82 TODO")
    # uint8 states for image observations, float32 for low-dim
    # (reference utils/options.py:84-91).
    state_dtype: str = "uint8"
    # PER exponents (reference utils/options.py:92-94; Ape-X paper values).
    priority_exponent: float = 0.6
    priority_weight: float = 0.4
    # Save/restore replay CONTENTS with the train-state checkpoint (the
    # resume leg the reference lacks, SURVEY.md §5).  Off by default:
    # image replays serialize to large files; written once at run end.
    checkpoint_replay: bool = False
    # NOTE: device-resident (HBM) replay is selected via
    # ``memory_type="device"`` (CONFIGS row 8), not a flag here: the buffer
    # is sharded across the learner mesh's dp axis and sampled on device
    # fused into the train step (memory/device_replay.py).


@dataclass
class ModelParams:
    """Model knobs (reference utils/options.py:97-105 is empty; we add the
    few things the models actually need)."""

    model_type: str = "dqn-cnn"
    hidden_dim: int = 256              # dqn-mlp width (reference dqn_mlp_model.py:18-26)
    lstm_dim: int = 256                # recurrent core width (drqn-* models)
    # transformer Q-net (dtqn-*) geometry
    tf_dim: int = 128
    tf_heads: int = 4
    tf_depth: int = 2
    # dqn-cnn-wide (ISSUE 13): base channel width of the MXU-filling
    # IMPALA-deep torso — multiples of 128 fill the 128-lane MXU the
    # Nature CNN's 4/32/64 channels underfill (models/dqn_cnn_wide.py)
    cnn_wide_width: int = 128
    # MoE (dtqn-moe) routing: expert count, choices per token, per-row
    # slot headroom, and the Switch load-balancing loss weight
    # (models/moe.py)
    moe_experts: int = 8
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # Apply orthogonal init for the CNN.  The reference *defines* orthogonal
    # init but never applies it (dqn_cnn_model.py:33 commented out) — here it
    # is applied and this flag documents the deliberate divergence.
    orthogonal_init: bool = True
    # Compute dtype for the forward/backward pass on TPU (params stay fp32).
    compute_dtype: str = "bfloat16"
    # dtqn-hybrid: which frozen preset of models/hybrid.py PRESETS holds the
    # trunk's layer pattern, widths and mixer options ("nemotron-h-9": row
    # 20's published ones; "qwen3-next-4" / "kimi-linear-5" / "lfm2-moe-5":
    # row 21's, 22's and 23's, set by ROW_DEFAULTS; "tiny" / "tiny-qwen" /
    # "tiny-kimi" / "tiny-lfm2": CPU tests of each).  They live there and
    # nowhere else.
    hybrid_preset: str = "nemotron-h-9"


@dataclass
class AgentParams:
    """Algorithm + process-cadence hyperparameters.

    DQN values mirror reference utils/options.py:112-141; DDPG values mirror
    :142-168.  ``build_agent_params`` below selects per-family defaults.
    """

    agent_type: str = "dqn"
    # --- generic (reference :117-127 / :146-156) ---
    steps: int = 500000                # max learner steps
    # Wall-clock budget for the run, seconds; 0 = unlimited.  When it
    # expires the learner ends the run exactly as if ``steps`` was reached
    # (final checkpoint, clean join).  Used by time-boxed benches/drives;
    # no reference equivalent (runs there end on steps only).
    max_seconds: float = 0.0
    gamma: float = 0.99
    clip_grad: float = float("inf")    # dqn: inf; ddpg: 40.0
    lr: float = 1e-4
    lr_decay: bool = False
    weight_decay: float = 0.0
    actor_sync_freq: int = 100         # dqn: 100; ddpg: 400
    # --- logger cadences (reference :128-133 / :157-162) ---
    logger_freq: int = 15              # secs
    actor_freq: int = 250              # actor steps; ddpg: 2500
    learner_freq: int = 100            # learner steps; ddpg: 1000
    evaluator_freq: int = 30           # secs; ddpg: 60
    evaluator_nepisodes: int = 2
    tester_nepisodes: int = 50
    # Unix niceness applied to the evaluator process (0 = none).  Its
    # bursty batch-1 greedy episodes starved the learner on an
    # oversubscribed host (runtime._child_main).  On a 1-core host a
    # nice'd evaluator runs its episodes more slowly, which thins how
    # many curve points land per wall-clock hour — but each point still
    # carries cadence-true capture attribution (step + wall of the
    # weight snapshot, agents/evaluator.py), so crossings stay exact;
    # lower this only when eval DENSITY (not accuracy) matters more
    # than learner throughput (--set evaluator_nice=0).
    evaluator_nice: int = 5
    # --- TPU-native publication/checkpoint cadence (no reference
    # equivalent: there weight visibility is implicit shared-CUDA and only
    # the evaluator checkpoints) ---
    param_publish_freq: int = 10       # learner steps between ParamStore publishes
    # Checkpoint-epoch cadence: learner steps between coordinated epoch
    # saves (train state + replay when checkpoint_replay + clocks/RNG,
    # committed atomically — utils/checkpoint.py save_epoch).  0 = final
    # epoch only.  With checkpoint_replay on, EVERY epoch carries the
    # replay contents (the crash-consistency point of the subsystem), so
    # size the cadence to what the replay serialization costs.
    checkpoint_freq: int = 0
    # Committed epochs kept on disk; older ones are garbage-collected
    # after each successful commit (the newest complete epoch is never
    # collected).
    checkpoint_retain: int = 3
    # --- off-policy core (reference :134-137 / :163-166) ---
    learn_start: int = 5000            # ddpg: 250
    batch_size: int = 128              # ddpg: 64
    # Cap on samples-drawn-per-transition-collected (replay ratio): the
    # learner throttles when learner_step * batch_size exceeds
    # max_replay_ratio * global actor steps.  0 disables.  No reference
    # equivalent — there the GPU learner can't outrun 8 CPU actors; a TPU
    # learner can outrun any actor fleet, collapsing replay diversity, so
    # the pacing knob is first-class here (standard in Ape-X-family
    # systems).
    max_replay_ratio: float = 0.0
    # Device-replay learners fuse this many update steps into ONE
    # dispatched XLA program (lax.scan over sample+train): program-launch
    # latency is paid once per K updates.  0 = auto (32 on TPU, 1
    # elsewhere — factory.resolve_steps_per_dispatch; the 32 has not
    # been measured on a directly attached chip).  Cadences
    # (publish/checkpoint/stats) are quantized to the dispatch size, and
    # the ``steps`` budget itself may overshoot by up to K-1 updates
    # (the final dispatch is whole).
    steps_per_dispatch: int = 0
    target_model_update: float = 250   # >=1: hard every N steps; <1: soft tau
    nstep: int = 5
    # --- dqn specifics (reference :138-141) ---
    enable_double: bool = False
    eps: float = 0.4                   # Ape-X per-actor epsilon base
    eps_alpha: float = 7.0
    eps_eval: float = 0.0              # greedy at eval
    # --- r2d2 specifics (no reference equivalent; Kapturowski et al. 2019
    # defaults — the sequence family extends the reference's capability
    # set, SURVEY.md §5 "long-context" note) ---
    seq_len: int = 80                  # replay segment length
    seq_overlap: int = 40              # segment overlap (adjacent windows)
    burn_in: int = 40                  # stored-state refresh prefix
    value_rescale: bool = True         # h(x) target transform
    priority_eta: float = 0.9          # max/mean blend for seq priorities
    # --- ddpg specifics (reference :167-168 + random_process.py) ---
    critic_lr: float = 1e-3
    ou_theta: float = 0.15
    ou_sigma: float = 0.3
    ou_mu: float = 0.0
    # Keep the reference's single-optimizer gradient coupling between the
    # DDPG policy loss and critic params?  The reference couples them
    # (ddpg_learner.py:62-91: one zero_grad, both backwards, one Adam over
    # all params).  Default False = decoupled per-net optimizers (the
    # textbook DDPG), True reproduces reference behaviour bit-for-bit.
    ddpg_coupled_update: bool = False


def build_agent_params(agent_type: str, **overrides: Any) -> AgentParams:
    """Per-family defaults, mirroring the if/elif in reference
    utils/options.py:111-168."""
    if agent_type == "dqn":
        p = AgentParams(agent_type="dqn")
    elif agent_type == "r2d2":
        # R2D2 paper cadences; learn_start/batch count SEGMENTS here
        p = AgentParams(
            agent_type="r2d2",
            enable_double=True,
            nstep=5,
            batch_size=64,
            learn_start=64,
            target_model_update=2500,
        )
    elif agent_type == "ddpg":
        p = AgentParams(
            agent_type="ddpg",
            clip_grad=40.0,
            actor_sync_freq=400,
            actor_freq=2500,
            learner_freq=1000,
            evaluator_freq=60,
            learn_start=250,
            batch_size=64,
            target_model_update=1e-3,
        )
    else:
        raise ValueError(f"unknown agent_type: {agent_type}")
    return dataclasses.replace(p, **overrides)


@dataclass
class HealthParams:
    """Training health sentinel knobs (utils/health.py; no reference
    equivalent — the reference has no numeric/liveness protection at
    all).  Every field is env-overridable as
    ``TPU_APEX_HEALTH_<FIELD>`` (``health.resolve``), the same
    spawn-inheritance contract the fault planes use, so drills flip
    knobs without plumbing."""

    # In-jit finite check on loss/grad-norm/TD: a non-finite step is
    # skipped in-graph (params/opt-state pass through unchanged, PER
    # write-back suppressed) and counted as ``learner/skipped``.
    numeric_guards: bool = True
    # Host-side rolling anomaly detector, evaluated on the learner's
    # stats cadence: loss EWMA z-score bound, grad-norm/|TD| spike
    # ratio vs their own EWMAs, and the consecutive-anomalous-window
    # streak that triggers a rollback.
    anomaly_zmax: float = 8.0
    grad_spike: float = 100.0
    anomaly_threshold: int = 3
    # Priority-distribution floor (ISSUE 8): the detector's
    # ``priority_collapse`` signal fires when the PER leaves' normalized
    # effective sample size (ESS / rows, from the priority X-ray) drops
    # under this — sampling has concentrated onto ~ess_floor * rows
    # rows even though total mass still looks healthy.
    ess_floor: float = 0.02
    # Automatic in-process rollback to the last good checkpoint epoch on
    # sustained divergence (needs committed epochs: checkpoint_freq > 0
    # or a preemption save).  ``max_rollbacks`` bounds the budget before
    # the learner escalates to a fatal exit; each successive rollback
    # targets one epoch OLDER than the previous one's restore point
    # (the newest epoch may itself hold already-diverged params).
    rollback: bool = True
    max_rollbacks: int = 2
    # Ingest quarantine: validate chunks at the single-owner ingest
    # boundaries and write offenders to {log_dir}/quarantine/ instead of
    # replay (also gated process-wide by TPU_APEX_QUARANTINE).
    quarantine: bool = True
    quarantine_max_files: int = 64
    # Hang watchdog: seconds a worker may go without a progress mark
    # before the supervisor SIGKILLs and respawns it (EXIT_HUNG, paid
    # from the slot's RestartBudget).  0 disables the watchdog (the
    # default: a safe deadline depends on the host's compile times —
    # production fleets should set it to a few multiples of their
    # longest legitimate stall, e.g. 180).  ``hang_grace`` extends the
    # deadline before a worker's FIRST mark, covering jit compiles.
    hang_deadline: float = 0.0
    hang_grace: float = 120.0


@dataclass
class PerfParams:
    """Performance observability plane knobs (utils/perf.py; no
    reference equivalent — the reference publishes no throughput
    numbers at all, BASELINE.md).  Every field is env-overridable as
    ``TPU_APEX_PERF_<FIELD>`` via ``perf.resolve`` (the bare
    ``TPU_APEX_PERF=1`` shorthand maps to ``enabled``), the same
    spawn-inheritance contract the health/fault planes use."""

    # Master switch: continuously export learner MFU / updates-per-s,
    # actor env-frames-per-s, replay-ratio and per-role memory
    # watermarks as metrics rows on the normal cadences.  Off by
    # default: the per-step cost is one counter add, but the one-time
    # cost is an extra AOT compile of the fused step (for its
    # cost_analysis FLOPs) at learner startup.
    enabled: bool = False
    # Peak dense FLOP/s per chip for the MFU ratio.  0 = auto from the
    # device kind (utils/perf.PEAK_FLOPS): the CPU exports achieved
    # FLOP/s but no MFU row, a TPU kind missing from the table is an
    # error — unless this is set explicitly
    # (``TPU_APEX_PERF_PEAK_FLOPS=...``).
    peak_flops: float = 0.0
    # Per-role memory watermarks on the drain cadence: device
    # live/peak bytes from ``device.memory_stats()`` where the backend
    # reports them (TPU), host RSS current/peak everywhere.
    memory_watermarks: bool = True
    # Retrace detector: track the jit cache size of registered hot-path
    # programs and flag any growth after the warmup window — a recompile
    # after warmup means a shape/dtype leak is silently paying compile
    # latency on the hot path.
    retrace_detector: bool = True
    # Opt-in transfer audit (``jax.transfer_guard``-based): run the
    # fused learner dispatch under a disallow guard, attribute any
    # IMPLICIT host<->device transfer to its python call site, then
    # retry the dispatch with transfers allowed.  The fused hot path is
    # transfer-free by construction, so any hit is a regression.
    # Explicit ``device_put``s never trip it (they are intended by
    # definition).
    transfer_audit: bool = False
    # Upper bound, seconds, on one on-demand T_PROFILE trace window
    # (parallel/dcn.py): the verb is sessionless and unauthenticated
    # inside the cluster, so a typo'd duration must not pin the
    # profiler for an hour.
    profile_window_max: float = 30.0


@dataclass
class MetricsParams:
    """Mission-control metrics-plane knobs (utils/telemetry.py; no
    reference equivalent — the reference has no fleet-level telemetry
    at all).  Every field is env-overridable as
    ``TPU_APEX_METRICS_<FIELD>`` via ``telemetry.resolve_metrics``
    (bare ``TPU_APEX_METRICS=1`` maps to ``enabled``), the same
    spawn-inheritance contract the health/perf planes use."""

    # Master switch: aggregate every role's scalar stream into bounded
    # fleet time series, evaluate the alert rules on the poll cadence,
    # and serve ``alerts``/``series`` blocks on the gateway STATUS
    # verb.  Off by default: the plane is one tail-read + rule pass
    # per cadence, but it is an operator surface, not a training one.
    enabled: bool = False
    # Local tail-ingest + alert-evaluation cadence, seconds.
    poll_s: float = 2.0
    # Remote-host T_METRICS push cadence, seconds (the fleet actor
    # hosts' MetricsPusher).
    push_s: float = 5.0
    # Retention tiers: raw points cover ``raw_span_s`` seconds (capped
    # at ``raw_points`` per series); coarser 10 s / 60 s bucket tiers
    # extend history without unbounded memory (SeriesRing docstring).
    raw_span_s: float = 300.0
    raw_points: int = 1024
    # Distinct (tag, role) series bound — overflow is counted
    # (``series_dropped``), never silent.
    max_series: int = 512
    # Points per series in the STATUS ``series`` block (fleet_top's
    # sparklines; the block rides every STATUS reply, so keep it small).
    series_points: int = 32
    # Extra tags for the STATUS series block, comma-separated (the
    # vital-sign defaults + rule tags are always included).
    series_tags: str = ""
    # Opt-in OpenMetrics/Prometheus text endpoint (stdlib HTTP, GET
    # /metrics) on the aggregator host.
    openmetrics: bool = False
    openmetrics_port: int = 9108


@dataclass
class AlertParams:
    """Declarative SLO/alert rules over the aggregated fleet series
    (utils/telemetry.py AlertEngine).  Env-overridable as
    ``TPU_APEX_ALERT_<FIELD>``; ``TPU_APEX_ALERT_RULES`` replaces the
    whole rule set (``;``-separated DSL lines)."""

    # Evaluate rules at all (the metrics plane can aggregate without
    # alerting, e.g. for a pure-dashboard deployment).
    enabled: bool = True
    # The rule set, one DSL line per rule, ``;``-separated::
    #
    #   name: tag absent 120s            (absence/staleness)
    #   name: tag > 100 for 60s          (threshold with dwell)
    #   name: tag < 0.02 frac 0.5 over 300s   (windowed burn-rate)
    #
    # "" = telemetry.DEFAULT_RULES (learner-stall absence, staleness
    # burn-rate, priority-ESS collapse).
    rules: str = ""
    # Seconds a firing rule must observe clean before it resolves
    # (hysteresis against flapping series).  0 = resolve on the first
    # clean evaluation.
    resolve_s: float = 0.0


@dataclass
class FlowParams:
    """End-to-end flow-control / graceful-degradation knobs (ISSUE 11;
    utils/flow.py — no reference equivalent: the reference blocks on a
    full shared ring and has no overload story at all).  Every field is
    env-overridable as ``TPU_APEX_FLOW_<FIELD>`` via
    ``flow.resolve_flow`` (bare ``TPU_APEX_FLOW=0`` maps to
    ``enabled``), the same spawn-inheritance contract the
    health/perf/metrics planes use.

    The plane is ON by default but INERT until the gateway's pressure
    signal crosses ``throttle_at``: in the healthy state no credits
    ride the wire, no chunk is ever shed, and the per-chunk cost is a
    few dict/float ops."""

    # Master switch.  Off = the pre-ISSUE-11 behaviour everywhere: no
    # credits, no admission control, blocking local feeders.
    enabled: bool = True
    # Client-side bounded buffer (CHUNKS) a creditless DcnClient parks
    # experience in; overflow drops the OLDEST chunk (newest experience
    # wins, Ape-X priority-on-arrival), counted + provenance-stamped.
    client_ring: int = 256
    # Local transports (spawn-queue feeder, device-replay ingest
    # pending): "block" = the pre-ISSUE-11 backpressure stall (default);
    # "shed" = bounded drop-oldest with counted drops, the same
    # degradation contract the DCN client ring gives remote actors.
    local_policy: str = "block"
    # Feeder-side ring bound (CHUNKS) and device-ingest pending bound
    # (ROWS) under local_policy="shed".
    feeder_ring: int = 64
    max_pending_rows: int = 65536
    # Per-slot admission token bucket (CHUNKS/s + burst) metering the
    # throttled state's credit grants — and, at brownout tier 3, the
    # gateway-side shed of non-credit-aware peers.
    bucket_rate: float = 200.0
    bucket_burst: float = 400.0
    # Credit grant cap per ack while throttled (the healthy state
    # grants no credit field at all = unlimited; shedding grants 0).
    credits_throttled: int = 4
    # Overload state machine thresholds on the gateway pressure signal
    # (0..1, e.g. ingest-queue utilization): sustained >= throttle_at
    # escalates one state per ``dwell_s``; sustained < recover_at for
    # ``recover_s`` de-escalates one state (hysteresis — the band
    # between the two never flaps).
    throttle_at: float = 0.75
    shed_at: float = 0.92
    recover_at: float = 0.50
    dwell_s: float = 1.0
    recover_s: float = 3.0
    # Brownout ladder: seconds of SUSTAINED shedding before the tier
    # climbs one rung (1 = shed telemetry pushes, 2 = + trace
    # sampling, 3 = + oldest experience).  De-escalation rides the
    # same ``recover_s`` hysteresis as the states.
    brownout_dwell_s: float = 5.0


@dataclass
class BandwidthParams:
    """Byte-exact bandwidth-accounting knobs (ISSUE 18;
    utils/bandwidth.py — no reference equivalent: the reference counts
    neither bytes nor frames anywhere).  Every field is
    env-overridable as ``TPU_APEX_WIRE_<FIELD>`` via
    ``bandwidth.resolve_bandwidth`` (bare ``TPU_APEX_WIRE=0`` maps to
    ``enabled``), the same spawn-inheritance contract the
    flow/perf/metrics planes use.

    ON by default, counter-only hot path: one dict lookup + two
    integer adds per frame."""

    # Master switch.  Off = no counters, no wire/* series, no byte
    # legs in the flow conservation ledger.
    enabled: bool = True
    # Account spawn-queue mint/drain boundaries (QueueFeeder flush,
    # QueueOwner / DeviceReplayIngest drain) — linear in chunk rows at
    # flush cadence, not per-frame; off leaves only the wire planes.
    spawn: bool = True
    # Minimum seconds between emit_scalars snapshots for a
    # ``wire/<link>/bytes_per_s`` rate to be computed (guards the
    # delta against a ~0 denominator on back-to-back emits).
    rate_floor_s: float = 0.05


@dataclass
class AnakinParams:
    """Co-located Anakin-loop knobs (ISSUE 12; agents/anakin.py — no
    reference equivalent: the reference always runs actors as separate
    processes).  Every field is env-overridable as
    ``TPU_APEX_ANAKIN_<FIELD>`` via ``anakin.resolve_anakin``, the same
    spawn-inheritance contract the health/perf/flow planes use.  Active
    only under ``env_params.actor_backend="anakin"``."""

    # Duty-cycle setpoint: target env frames collected per learner
    # update.  The scheduler dispatches rollouts while
    # ``frames < updates * rollout_ratio`` (after the min-fill warmup)
    # and learner steps otherwise.  0 = strict alternation: one rollout
    # dispatch, one learner dispatch, repeat.
    rollout_ratio: float = 0.0
    # Ring rows required before the FIRST learner dispatch (per half in
    # double-buffer mode).  0 = derive from agent_params.learn_start
    # (clamped to the ring/half capacity like the learner's warmup
    # gate).
    min_fill: int = 0
    # Double-buffered replay halves: the ring is split into two
    # half-capacity rings — learner dispatches sample the STABLE half
    # while rollouts scatter into the other; the halves swap once the
    # write half holds ``min_fill`` fresh rows.  Sampling never reads a
    # row the current rollout cycle is writing, and the PER priority
    # write-back lands in the sample half only — write races are
    # excluded by construction, not by ordering.  Costs replay
    # diversity (each dispatch samples from half the history), so the
    # default is the strict alternation of ONE ring, where dispatch
    # ordering already serializes writers and readers.
    double_buffer: bool = False
    # Drain the cross-process ingest queue between dispatches (chunks
    # from remote DCN actor hosts landing at the gateway).  The
    # co-located fleet itself never touches the queue; this keeps a
    # hybrid topology (anakin learner + remote device actors) live.
    drain_ingest: bool = True


@dataclass
class ReplicaParams:
    """Elastic multi-learner replica plane knobs (ISSUE 15;
    parallel/dcn.py ReplicaRegistry / agents/learner.py replica driver —
    no reference equivalent: the reference's ``num_learners > 1`` hook
    races unsynchronized Adam steps on one shared CUDA model).  Every
    field is env-overridable as ``TPU_APEX_REPLICA_<FIELD>`` via
    ``parallel.dcn.resolve_replica``, the same spawn-inheritance
    contract the health/perf/flow planes use.

    N data-parallel learner replicas train one logical model over DCN:
    replicas hold renewable LEASES with monotonic generation numbers on
    the lead gateway; a missed lease expires the replica and FENCES its
    stragglers (a stale-generation gradient or priority write-back is a
    counted reject, never applied — the slot-fencing contract of PR 1,
    lifted to the learner plane).  The gradient exchange is a
    generation-stamped allreduce round that reconfigures on membership
    change: when a replica dies mid-round, survivors complete the round
    over the surviving set within one lease window; at N=1 the survivor
    is bit-identical to the solo learner (tests/test_replicas.py
    oracle).  The dp-mesh ``psum`` path (parallel/learner.py) stays the
    in-host fast path — this plane composes ACROSS hosts."""

    # Configured replica count (1 = plane off: the solo learner runs
    # exactly as before, no registry, no stamps).  The plane is elastic
    # below this: fewer live members is a DEGRADED (alerted) state, not
    # an error.
    replicas: int = 1
    # Lease window, seconds: a replica that neither renews nor submits
    # within it is expired and fenced.  Also the round-stall window —
    # once any member has contributed to a round, members that stay
    # silent past one lease window are expelled and the round completes
    # over the surviving set.
    lease_s: float = 5.0
    # Background renew cadence, seconds (0 = lease_s / 3).
    renew_s: float = 0.0
    # Hard cap, seconds, on one blocking round exchange before the
    # submitting replica gives up (0 = 3 lease windows — strictly after
    # the stall expulsion above, so it only fires on a wedged registry).
    round_timeout_s: float = 0.0
    # Seconds a pending rejoiner may take to load the barrier epoch and
    # activate before its join is cancelled and survivors proceed.
    join_timeout_s: float = 30.0
    # Lead gateway ``host:port`` a remote replica host dials
    # (fleet.py --role learner-replica --coordinator).
    coordinator: str = ""


@dataclass
class GatewayParams:
    """Gateway high-availability plane knobs (ISSUE 16;
    parallel/dcn.py DcnGateway HA role / GatewayJournal — no reference
    equivalent: the reference's single mp.Queue hub dies with the
    learner process).  Every field is env-overridable as
    ``TPU_APEX_GATEWAY_<FIELD>`` via ``parallel.dcn.resolve_gateway``,
    the same spawn-inheritance contract the health/perf/flow/replica
    planes use.

    The primary gateway journals its mutable control state (slot
    incarnations, tick dedup high-waters, cumulative flow ledgers,
    clock counters) to an append-only fsynced WAL under
    ``{log_dir}/gateway/`` and serves it to a warm standby over the
    sessionless ``T_SYNC`` verb.  Primary and standby carry a
    monotonic *term* (the PR-14 replica-generation pattern lifted one
    level up) persisted in ``TERM.json`` on the SHARED log_dir — the
    same shared-storage requirement checkpoint resume already has.
    The standby promotes when the primary goes silent for one lease
    window; a resurrected stale-term primary fences itself against the
    on-disk term and its writes are counted rejects
    (``gateway_term_fenced``), never applied.  With ``enabled`` False
    (the default) no journal is written, STATUS carries no ``gateway``
    block and the wire is byte-identical to the pre-HA protocol."""

    # Master switch.  Off = the single-gateway topology of PRs 1-15,
    # bit-for-bit: no term, no WAL, no sync verb traffic.
    enabled: bool = False
    # Primary lease window, seconds: the standby promotes once it has
    # failed to sync for this long.  Also bounds how long a fenced
    # primary can run before noticing the on-disk term moved.
    lease_s: float = 2.0
    # Standby sync cadence, seconds (journal records are pulled with
    # sessionless T_SYNC requests at this rate; sync lag on STATUS is
    # quantized by it).
    sync_s: float = 0.25
    # Standby bind ``host:port`` for fleet.py --role gateway-standby
    # ("" = 0.0.0.0 on an ephemeral port).
    standby: str = ""
    # Ordered client dial list ``host:port,host:port`` (primary first).
    # Exported to spawned actors so DcnClient redials the next endpoint
    # on terminal disconnect.  "" = single-endpoint (pre-HA) dialing.
    endpoints: str = ""


@dataclass
class ShardParams:
    """Sharded prioritized-replay plane knobs (ISSUE 20;
    memory/shard_plane.py ShardRegistry / ShardedReplayPlane — no
    reference equivalent: the reference's replay is one host's shared
    pages, full stop).  Every field is env-overridable as
    ``TPU_APEX_SHARD_<FIELD>`` via ``memory.shard_plane.resolve_shard``,
    the same spawn-inheritance contract the health/perf/flow/replica
    planes use.

    The INES topology (PAPERS.md): each gateway host owns a replay ring
    SHARD with its own local sum/min trees, and the learner samples
    through a two-level tree — a global priority-mass vector over
    shards routes stratified sample values to the shard that owns the
    mass stratum, which answers locally (sample where experience lands,
    never ship raw transitions twice).  Shard membership is lease-fenced
    with monotonic generations (the PR-14 replica contract): a shard
    that misses its lease window is expired, its priority mass leaves
    the global vector, its transitions are counted into the
    ``shard_lost`` ledger bucket (conservation stays EXACT through the
    loss), and any |TD| write-back stamped with its dead generation is
    a counted reject — never applied.  At ``shards <= 1`` the plane is
    off: the single-host PER path runs bit-identically, no registry,
    no verbs, no STATUS block."""

    # Configured shard count (<= 1 = plane off: build_memory constructs
    # the plain single-host PrioritizedReplay exactly as before).  The
    # plane is elastic below this: fewer live shards is a DEGRADED
    # (alerted) state, not an error.
    shards: int = 0
    # Lease window, seconds: a shard host that neither renews (renews
    # carry its mass/fill/ingest report) nor serves within it is
    # expired and fenced.
    lease_s: float = 5.0
    # Background renew cadence, seconds (0 = lease_s / 3).
    renew_s: float = 0.0
    # Global mass-vector refresh cadence on the sample path, seconds
    # (0 = refresh at EVERY sample — exact priority proportions, the
    # loopback/tier-1 default; wire fleets trade a bounded staleness
    # window for fewer T_SMASS round-trips by raising this).
    mass_refresh_s: float = 0.0
    # Seconds a rejoining shard may take to re-lease, warm its ring,
    # and activate at the rejoin barrier before the join is cancelled.
    join_timeout_s: float = 30.0
    # Coordinator gateway ``host:port`` a remote shard host dials
    # (fleet.py --role replay-shard --coordinator).
    coordinator: str = ""


@dataclass
class LearnerPerfParams:
    """MFU-campaign knobs (ISSUE 13; no reference equivalent — the
    reference never measures device utilization at all).  Every field
    is env-overridable as ``TPU_APEX_MXU_<FIELD>`` via
    ``utils/perf.resolve_mxu``, the same spawn-inheritance contract the
    health/perf/flow planes use.  All three levers are OPT-IN: the
    defaults reproduce the pre-campaign learner bit-for-bit."""

    # Megabatch factor M for the fused device-replay learner step (dqn
    # and ddpg flat families): each scan group samples M minibatches in
    # ONE widened gather (consuming the SAME M keys the sequential
    # schedule would) and computes all M per-minibatch gradients in one
    # lane-filling (M*B, ...) batched forward/backward at the
    # group-entry params, then applies the M optimizer updates
    # SEQUENTIALLY in-graph (Adam moments, step counter, target-update
    # cadence and PER |TD| write-backs chain exactly as M separate
    # steps).  The one semantic divergence from M sequential steps is
    # within-group gradient freshness — gradients see the group-entry
    # params instead of the per-step params — the large-effective-batch
    # trade Stooke & Abbeel (2018) validate for the DQN family; the
    # tier-1 oracle (tests/test_megabatch.py) pins the program
    # bit-exactly against an unfused reference of the same semantics.
    # 1 = off (the pre-campaign program); must divide
    # ``steps_per_dispatch``.
    megabatch: int = 1
    # Pallas fused conv-stack/Q-head torso for dqn-cnn
    # (ops/pallas_torso.py): the learner's train apply runs the torso
    # as hand-tiled 128-lane MXU matmul kernels (im2col) instead of
    # XLA's conv lowering and its re-tiling between conv ops (what that
    # buys on the chip is not measured: no benchmark cell turns it on).
    # Loud downgrade to the
    # XLA apply when Pallas/TPU is unavailable (unless
    # ``pallas_interpret``).  Actors/evaluators keep the standard
    # apply — the param tree is identical.
    pallas_torso: bool = False
    # Run the Pallas torso kernels in interpreter mode (CPU hosts):
    # the tier-1 parity tests use this; production CPU runs should
    # leave it off (interpret mode is slower than XLA's native conv).
    pallas_interpret: bool = False


@dataclass
class ParallelParams:
    """TPU topology knobs — no reference equivalent (the reference is a
    single-node torch.multiprocessing program, SURVEY.md §2); this is where
    the mesh/sharding design lives."""

    # Logical mesh axes over jax.devices().  data parallel ("dp") carries the
    # batch + gradient psum over ICI; model parallel ("mp") is available for
    # tensor-sharded heads on wide models.
    dp_size: int = -1                  # -1: all devices on dp
    mp_size: int = 1
    # sequence/context parallel: shards the time axis of long windows;
    # ring attention moves K/V around this axis over ICI
    # (ops/ring_attention.py)
    sp_size: int = 1
    # sp strategy: "ring" (K/V rotation, any head count) or "ulysses"
    # (head/time all-to-all, needs heads % sp == 0;
    # ops/ulysses_attention.py docstring has the trade-off)
    sp_attention: str = "ring"
    # expert parallel: MoE expert kernels shard over the ep axis
    # (dtqn-moe only; parallel/expert_parallel.py)
    ep_size: int = 1
    # pipeline parallel: stacked DTQN blocks stage over the pp axis with
    # a GPipe microbatch schedule (dtqn-pipe only; parallel/pipeline.py)
    pp_size: int = 1
    pp_microbatches: int = 4
    # Donate learner buffers (params/opt_state) to the jit step.
    donate: bool = True
    # Multi-host: call jax.distributed.initialize (DCN) before device init.
    multihost: bool = False
    coordinator_address: Optional[str] = None
    num_processes: int = 1
    process_id: int = 0


@dataclass
class Options:
    """Aggregate of everything a run needs — equivalent of reference
    ``Options`` (utils/options.py:171-175) but an explicit instance."""

    # --- run identity (reference Params, utils/options.py:17-51) ---
    mode: int = 1                      # 1 = train, 2 = test model_file
    config: int = 1
    seed: int = 100
    refs: str = field(default_factory=_default_refs)
    root_dir: str = field(default_factory=os.getcwd)
    num_actors: int = 8
    num_learners: int = 1
    model_file: Optional[str] = None   # finetune/test source checkpoint
    # Resume mode for the checkpoint-epoch tier (utils/checkpoint.py):
    #   "auto"  — resume from the newest complete epoch under
    #             ``{model_name}_ckpt`` if one exists (falling back to the
    #             legacy ``_state`` snapshot), else start fresh;
    #   "must"  — refuse to start without a resumable checkpoint (what
    #             ``--resume REFS`` sets: a preempted run restarted by an
    #             orchestrator must never silently train from scratch);
    #   "never" — ignore existing checkpoints (fresh run even if the refs
    #             collide with an old one's).
    resume: str = "auto"
    visualize: bool = True

    agent_type: str = "dqn"
    env_type: str = "fake"
    game: str = "chain"
    memory_type: str = "shared"
    model_type: str = "dqn-mlp"

    env_params: EnvParams = field(default_factory=EnvParams)
    memory_params: MemoryParams = field(default_factory=MemoryParams)
    model_params: ModelParams = field(default_factory=ModelParams)
    agent_params: AgentParams = field(default_factory=AgentParams)
    parallel_params: ParallelParams = field(default_factory=ParallelParams)
    health_params: HealthParams = field(default_factory=HealthParams)
    perf_params: PerfParams = field(default_factory=PerfParams)
    metrics_params: MetricsParams = field(default_factory=MetricsParams)
    alert_params: AlertParams = field(default_factory=AlertParams)
    flow_params: FlowParams = field(default_factory=FlowParams)
    anakin_params: AnakinParams = field(default_factory=AnakinParams)
    learner_perf_params: LearnerPerfParams = field(
        default_factory=LearnerPerfParams)
    replica_params: ReplicaParams = field(default_factory=ReplicaParams)
    gateway_params: GatewayParams = field(default_factory=GatewayParams)
    shard_params: ShardParams = field(default_factory=ShardParams)

    @property
    def model_dir(self) -> str:
        return os.path.join(self.root_dir, "models")

    @property
    def model_name(self) -> str:
        # reference utils/options.py:42
        return os.path.join(self.model_dir, f"{self.refs}")

    @property
    def log_dir(self) -> str:
        # reference utils/options.py:51
        return os.path.join(self.root_dir, "logs", self.refs)


def parse_set_overrides(pairs) -> dict:
    """Parse repeatable CLI ``--set key=value`` pairs into an overrides
    dict (int/float auto-typed, else string) — shared by main.py and the
    fleet launcher."""
    out = {}
    for kv in pairs:
        k, _, v = kv.partition("=")
        if v.lower() in ("true", "false"):
            v = v.lower() == "true"
        else:
            for cast in (int, float):
                try:
                    v = cast(v)
                    break
                except ValueError:
                    continue
        out[k] = v
    return out


def build_options(config: int = 1, **overrides: Any) -> Options:
    """Construct an Options from a CONFIGS row index + keyword overrides.

    Mirrors what reference Params.__init__ does at utils/options.py:26
    (unpacking the CONFIGS row) plus the shape bookkeeping EnvParams does at
    :54-69, then applies overrides (our CLI affordance).
    """
    agent_type, env_type, game, memory_type, model_type = CONFIGS[config]
    overrides = {**ROW_DEFAULTS.get(config, {}), **overrides}

    # Selector overrides must land before sub-param construction so the
    # per-family defaults they derive (hyperparams, shapes, dtypes, PER flag)
    # stay consistent.
    selectors = ("agent_type", "env_type", "game", "memory_type", "model_type")
    agent_type = overrides.pop("agent_type", agent_type)
    env_type = overrides.pop("env_type", env_type)
    game = overrides.pop("game", game)
    memory_type = overrides.pop("memory_type", memory_type)
    model_type = overrides.pop("model_type", model_type)

    if "cnn" in model_type or model_type == "dtqn-hybrid":
        env_shape = dict(state_cha=4, state_hei=84, state_wid=84)
        state_dtype = "uint8"
    else:
        # Low-dim envs report their own width at probe time; 0 = fill in
        # from the env probe in main (reference main.py:23-31 does the same
        # dummy-env probe).
        env_shape = dict(state_cha=1, state_hei=1, state_wid=0)
        state_dtype = "float32"

    opt = Options(
        config=config,
        agent_type=agent_type,
        env_type=env_type,
        game=game,
        memory_type=memory_type,
        model_type=model_type,
        env_params=EnvParams(env_type=env_type, game=game, **env_shape),
        memory_params=MemoryParams(
            memory_type=memory_type,
            state_dtype=state_dtype,
            enable_per=(memory_type == "prioritized"),
            # sequence replay is prioritized by default with the R2D2
            # constants (alpha 0.9 / beta0 0.6); --set overrides still land
            **({"priority_exponent": 0.9, "priority_weight": 0.6}
               if memory_type in ("sequence", "device-sequence") else {}),
        ),
        model_params=ModelParams(model_type=model_type),
        agent_params=build_agent_params(agent_type),
    )

    # Route simple top-level overrides to the right sub-dataclass.
    for key, val in overrides.items():
        assert key not in selectors  # popped above
        hits = []
        for sub in ("env_params", "memory_params", "model_params",
                    "agent_params", "parallel_params", "health_params",
                    "perf_params", "metrics_params", "alert_params",
                    "flow_params", "anakin_params",
                    "learner_perf_params", "replica_params",
                    "gateway_params", "shard_params"):
            subobj = getattr(opt, sub)
            if hasattr(subobj, key):
                hits.append((sub, subobj))
        if len(hits) > 1:
            # a field living on several sub-params ("enabled" is on the
            # perf/metrics/alert planes): a bare override would silently
            # flip every plane at once — refuse, name the candidates
            raise ValueError(
                f"ambiguous option {key!r}: lives on "
                f"{', '.join(s for s, _ in hits)} — set the field "
                f"directly (opt.<sub>.{key}) or use the plane's env "
                f"knob (TPU_APEX_*)")
        routed = False
        for _sub, subobj in hits:
            setattr(subobj, key, val)
            routed = True
        if hasattr(opt, key):
            setattr(opt, key, val)
            routed = True
        if not routed:
            raise ValueError(f"unknown option: {key}")

    # Keep seed coherent across sub-params.
    opt.env_params.seed = opt.seed
    if opt.mode == 2 and opt.model_file is None:
        # reference utils/options.py:45-48: test mode defaults to the
        # current run's checkpoint path.
        opt.model_file = opt.model_name
    return opt
