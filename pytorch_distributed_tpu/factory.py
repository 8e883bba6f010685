"""Registry / factory — the plugin surface.

Equivalent of reference ``utils/factory.py``: type-string keyed dicts for
every pluggable component family (envs :34, memories :37, models :42,
actor/learner/evaluator/tester/logger process functions :22-31), plus the
builder helpers that ``main``/runtime use to turn an ``Options`` into live
objects (the dummy-env shape probe of reference main.py:23-31 lives here as
``probe_env``).  Divergences on purpose: ``dqn-mlp`` is registered (the
reference leaves it out, reference utils/factory.py:42-43), and the builders
return *functional* pieces — Flax modules, apply fns, optax transforms, pure
train-step closures — not stateful torch modules.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

from pytorch_distributed_tpu.config import Options
from pytorch_distributed_tpu.envs import (
    FakeChainEnv, PongSimEnv, make_classic_env,
)
from pytorch_distributed_tpu.envs.atari import AtariEnv
from pytorch_distributed_tpu.memory import (
    PrioritizedReplay, SharedReplay,
)
from pytorch_distributed_tpu.memory.feeder import QueueOwner

# ---------------------------------------------------------------------------
# Component dicts (reference utils/factory.py:22-43)
# ---------------------------------------------------------------------------

def _gym_env(env_params, process_ind: int = 0):
    from pytorch_distributed_tpu.envs.gym_adapter import GymEnv

    return GymEnv(env_params, process_ind)


EnvsDict: Dict[str, Callable] = {
    "atari": AtariEnv,            # reference factory.py:34 "atari"
    "fake": FakeChainEnv,         # test/smoke env (no reference equivalent)
    "classic": make_classic_env,  # cartpole / pendulum
    "pong-sim": PongSimEnv,       # ALE-free Pong clone
    "gym": _gym_env,              # gym/gymnasium adapter (gated on install)
}

MemoriesDict: Dict[str, Optional[Callable]] = {
    "shared": SharedReplay,           # reference factory.py:37 "shared"
    "native": None,                    # C++ lock-free ring (native_ring.py)
    "prioritized": PrioritizedReplay,  # finishes the reference's PER TODO
    "device": None,                    # HBM-resident ring (device_replay.py)
    "device-per": None,                # HBM prioritized ring (device_per.py)
    "sequence": None,                  # episode segments (sequence_replay.py)
    "device-sequence": None,           # HBM segment ring (device_sequence.py)
    "none": None,                      # reference factory.py:38
}

# model ctors bound in build_model below (they need probed shapes)
ModelTypes = ("dqn-cnn", "dqn-cnn-wide", "dqn-mlp", "ddpg-mlp",
              "drqn-mlp", "drqn-cnn", "dtqn-mlp", "dtqn-moe",
              "dtqn-pipe", "dtqn-hybrid")


def _worker_dicts():
    # Imported lazily: agents modules import jax-heavy pieces and, under
    # spawn, child processes must be able to import this module before
    # choosing their jax platform.
    from pytorch_distributed_tpu.agents import actor as _actor
    from pytorch_distributed_tpu.agents import evaluator as _evaluator
    from pytorch_distributed_tpu.agents import learner as _learner
    from pytorch_distributed_tpu.agents import logger as _logger
    from pytorch_distributed_tpu.agents import recurrent_actor as _ractor
    from pytorch_distributed_tpu.agents import tester as _tester

    return {
        # reference utils/factory.py:22-31 (+ the r2d2 family extension)
        "actors": {"dqn": _actor.run_dqn_actor,
                   "ddpg": _actor.run_ddpg_actor,
                   "r2d2": _ractor.run_r2d2_actor},
        "learners": {"dqn": _learner.run_learner,
                     "ddpg": _learner.run_learner,
                     "r2d2": _learner.run_learner},
        "evaluators": {"dqn": _evaluator.run_evaluator,
                       "ddpg": _evaluator.run_evaluator,
                       "r2d2": _evaluator.run_evaluator},
        "testers": {"dqn": _tester.run_tester,
                    "ddpg": _tester.run_tester,
                    "r2d2": _tester.run_tester},
        "loggers": {"dqn": _logger.run_logger,
                    "ddpg": _logger.run_logger,
                    "r2d2": _logger.run_logger},
    }


def get_worker(role: str, agent_type: str) -> Callable:
    return _worker_dicts()[role + "s"][agent_type]


# ---------------------------------------------------------------------------
# Actor backend routing (ISSUE 4)
# ---------------------------------------------------------------------------

ACTOR_BACKENDS = ("inline", "pipelined", "batched", "device", "anakin")


def anakin_eligible(opt: Options) -> Tuple[bool, str]:
    """Whether this Options can run the co-located Anakin loop (ISSUE
    12): the dqn family, a pure-JAX env implementation and a device replay
    ring for the in-graph scatter.  Returns ``(ok, reason)`` so callers
    can warn with the actual blocker."""
    from pytorch_distributed_tpu.envs.device_env import (
        device_env_supported,
    )

    if opt.agent_type != "dqn":
        return False, f"agent_type={opt.agent_type} (dqn only)"
    if not device_env_supported(opt.env_params):
        return False, (f"env_type={opt.env_params.env_type!r} has no "
                       f"device env implementation")
    if opt.memory_type not in ("device", "device-per"):
        return False, (f"memory_type={opt.memory_type!r} (the fused "
                       f"rollout scatters into a device ring: use "
                       f"'device' or 'device-per')")
    return True, ""


def anakin_active(opt: Options) -> bool:
    """Whether the topology runs the co-located Anakin loop — the env
    fleet lives in the learner process, NO actor workers spawn, and the
    learner delegates to agents/anakin.run_anakin_learner.  One
    predicate shared by the topology (worker table), the learner (loop
    dispatch) and the fleet CLI so the pieces can never disagree."""
    return (getattr(opt.env_params, "actor_backend", "") == "anakin"
            and anakin_eligible(opt)[0])


def resolve_actor_backend(opt: Options, inference=None) -> str:
    """The actor hot-loop schedule actually run, from the
    ``env_params.actor_backend`` knob plus eligibility.

    Decided HERE — one gate shared by the runners (agents/actor.py,
    agents/recurrent_actor.py), the topology (runtime.py decides whether
    to build an InferenceServer from the same predicate via
    ``needs_inference_server``) and the fleet CLI — so the pieces can
    never disagree.  ``batched`` needs a co-located server handle
    (``inference``) and a flat family; ``device`` needs a dqn family
    whose env has a pure-JAX implementation (envs/device_env.py);
    anything else downgrades to ``pipelined`` with a loud warning
    rather than failing a whole fleet over a placement detail (remote
    DCN actor hosts have no server to reach)."""
    backend = getattr(opt.env_params, "actor_backend", "pipelined") \
        or "pipelined"
    if backend not in ACTOR_BACKENDS:
        raise ValueError(
            f"unknown actor_backend: {backend!r} (one of "
            f"{ACTOR_BACKENDS})")
    if backend == "batched":
        import warnings

        if opt.agent_type not in ("dqn", "ddpg"):
            warnings.warn(
                f"actor_backend=batched does not serve agent_type="
                f"{opt.agent_type} (per-env recurrent state stays "
                f"actor-side); falling back to pipelined", stacklevel=2)
            return "pipelined"
        if inference is None:
            warnings.warn(
                "actor_backend=batched but no InferenceClient was wired "
                "in (remote actor host, or a topology without the "
                "server); falling back to pipelined", stacklevel=2)
            return "pipelined"
    if backend == "anakin":
        import warnings

        ok, why = anakin_eligible(opt)
        if ok:
            return "anakin"
        # ineligible: fall through the device backend's own gates (the
        # config.py EnvParams contract: anakin downgrades to "device",
        # which itself may downgrade further to "pipelined")
        warnings.warn(
            f"actor_backend=anakin is not runnable here ({why}); "
            f"falling back to the split-process device backend",
            stacklevel=2)
        backend = "device"
    if backend == "device":
        import warnings

        from pytorch_distributed_tpu.envs.device_env import (
            device_env_supported,
        )

        if opt.agent_type != "dqn":
            warnings.warn(
                f"actor_backend=device serves the flat dqn family only "
                f"(got agent_type={opt.agent_type}); falling back to "
                f"pipelined", stacklevel=2)
            return "pipelined"
        if not device_env_supported(opt.env_params):
            warnings.warn(
                f"actor_backend=device but env_type="
                f"{opt.env_params.env_type!r} has no device env "
                f"implementation (envs/device_env.DEVICE_ENV_FAMILIES); "
                f"falling back to pipelined", stacklevel=2)
            return "pipelined"
    return backend


def build_device_env(opt: Options, process_ind: int, num_envs: int):
    """The pure-JAX env fleet for one device-backend actor slot
    (envs/device_env.py), seeded on the SAME slot contract as
    ``build_env_vector`` (env j of actor i takes slot ``seed + i*N +
    j``) so backend choice never changes the seed stream."""
    from pytorch_distributed_tpu.envs.device_env import (
        build_device_env as _build,
    )

    return _build(opt.env_params, process_ind, num_envs)


def needs_inference_server(opt: Options) -> bool:
    """Whether a topology should stand up the shared InferenceServer for
    its co-located actors (runtime.Topology)."""
    return (getattr(opt.env_params, "actor_backend", "") == "batched"
            and opt.agent_type in ("dqn", "ddpg"))


# ---------------------------------------------------------------------------
# Env probe + builders
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class EnvSpec:
    """What the models/replay need to know about an env — the product of the
    dummy-env probe (reference main.py:23-31 mutates Options with
    state_shape/action_dim/norm_val; here it is an explicit value)."""

    state_shape: Tuple[int, ...]
    discrete: bool
    num_actions: int        # discrete action count (0 if continuous)
    action_dim: int         # continuous action dim (0 if discrete)
    norm_val: float

    @property
    def action_shape(self) -> Tuple[int, ...]:
        return () if self.discrete else (self.action_dim,)

    @property
    def action_dtype(self):
        return np.int32 if self.discrete else np.float32


def build_env(opt: Options, process_ind: int = 0):
    ctor = EnvsDict[opt.env_type]
    return ctor(opt.env_params, process_ind)


def device_backend_active(opt: Options) -> bool:
    """Whether actor slots will run the device env fleet — the
    eligibility part of ``resolve_actor_backend``'s device gate,
    callable without triggering its downgrade warnings (the parent's
    prebuild must not warn about an inference server that is wired
    later)."""
    from pytorch_distributed_tpu.envs.device_env import (
        device_env_supported,
    )

    return (getattr(opt.env_params, "actor_backend", "") == "device"
            and opt.agent_type == "dqn"
            and device_env_supported(opt.env_params))


def _wants_native_pong(opt: Options) -> bool:
    """One gate for the native pong stepper, shared by the construction
    path (build_env_vector) and the parent-side prebuild (prebuild_native)
    so the two can't drift.  Device-backend runs skip it: no actor will
    dlopen the library (the env fleet is a pure-JAX program; the
    evaluator's single env never routes through the batched stepper)."""
    return (opt.env_type == "pong-sim"
            and getattr(opt.env_params, "native_env", True)
            and not device_backend_active(opt))


def build_env_vector(opt: Options, process_ind: int, num_envs: int):
    """N env instances as one batched VectorEnv; env j of actor i gets the
    distinct seed slot i*N + j (the reference's per-process scheme,
    reference atari_env.py:16, extended over the env axis).  For the
    Pong simulator the whole batch steps in one native C++ call
    (native/pong_batch.cpp) when the toolchain is available."""
    from pytorch_distributed_tpu.envs.vector import VectorEnv

    if _wants_native_pong(opt):
        try:
            from native.build import NativeBuildError
        except ImportError:  # native/ not shipped alongside the package
            NativeBuildError = OSError
        try:
            from pytorch_distributed_tpu.envs.native_pong import (
                NativePongVectorEnv,
            )

            return NativePongVectorEnv(opt.env_params, process_ind, num_envs)
        except (ImportError, OSError, NativeBuildError) as e:
            # no native package / toolchain / loadable .so: fall back.
            # Genuine wrapper bugs raise through — silently degrading a
            # fleet onto the ~6x-slower Python path is worse than failing.
            import warnings

            warnings.warn(f"native pong env unavailable ({e}); "
                          "falling back to Python VectorEnv", stacklevel=2)
    ctor = EnvsDict[opt.env_type]
    return VectorEnv([ctor(opt.env_params, process_ind * num_envs + j)
                      for j in range(num_envs)])


def prebuild_native(opt: Options) -> None:
    """Compile the native .so artifacts ONCE in the supervising parent
    before workers spawn — N actors racing identical `g++ -O3` builds of
    the same source is wasted work, and on a congested host some would hit
    the build timeout and silently drop onto the slower Python fallback.
    Children then just dlopen the cached library (native/build.py mtime
    check).  The parent build gets a generous timeout (it is the one that
    matters) and failures are reported loudly — the run still proceeds,
    each worker falling back with its own warning through the same
    gates."""
    import warnings

    def _prebuild(name: str, fallback: str) -> None:
        try:
            from native.build import build_library

            build_library(name, timeout=600.0)
        except Exception as e:  # noqa: BLE001 - degrade with a loud flag
            warnings.warn(f"parent-side native {name} build FAILED ({e}); "
                          f"{fallback}", stacklevel=3)

    if _wants_native_pong(opt):
        _prebuild("pong_batch",
                  "all workers will run the slower Python env")
    if opt.memory_type == "native":
        _prebuild("ring_buffer",
                  "workers fall back to the Python shared replay")
    if opt.env_type == "atari":
        _prebuild("image_ops",
                  "frame preprocessing falls back to numpy")


def probe_env(opt: Options) -> EnvSpec:
    """Instantiate a throwaway env to read shapes (reference main.py:23-31)."""
    env = build_env(opt, process_ind=0)
    space = env.action_space
    discrete = hasattr(space, "n")
    return EnvSpec(
        state_shape=tuple(env.state_shape),
        discrete=discrete,
        num_actions=space.n if discrete else 0,
        action_dim=0 if discrete else space.dim,
        norm_val=float(env.norm_val),
    )


# ---------------------------------------------------------------------------
# Model builders
# ---------------------------------------------------------------------------

def sequence_pack_frames(opt: Options) -> int:
    """Frame-pack factor C for sequence replay (0 = unpacked).

    C-stacked uint8 image segments ship every pixel C times; packing
    stores the de-duplicated frame sequence and the learner rebuilds
    stacks on device (memory/sequence_replay.py SegmentBuilder /
    ops/sequence_losses.py unpack_frame_stacks).  Decided HERE so the
    three parties — actor-side builders, the replay allocation, and the
    learner step — can never disagree on the wire format.  The two pixel
    sequence models qualify: drqn-cnn rebuilds the stacks, dtqn-hybrid
    reads the newest frame of each (models/hybrid.py window_applies);
    the other dtqn rows are low-dim."""
    if (opt.memory_type in ("sequence", "device-sequence")
            and opt.model_type in ("drqn-cnn", "dtqn-hybrid")
            and opt.memory_params.state_dtype == "uint8"):
        return opt.env_params.state_cha
    return 0


def lstm_dim_of(opt: Options) -> int:
    """Stored-recurrent-state width for the configured model (the CNN
    variant floors at 512, matching its torso output; every dtqn* model,
    the hybrid trunk with its state-space layers included, stores a 1-dim
    placeholder: a segment starts from zero state and its burn-in prefix
    is context)."""
    if opt.model_type.startswith("dtqn"):
        return 1
    d = opt.model_params.lstm_dim
    return max(d, 512) if opt.model_type == "drqn-cnn" else d


def build_model(opt: Options, spec: EnvSpec):
    """Flax module for the configured model_type (reference factory.py:42-43
    + model ctor calls in main.py:44)."""
    import jax.numpy as jnp

    from pytorch_distributed_tpu.models import (
        DdpgMlpModel, DqnCnnModel, DqnMlpModel,
    )

    mp_ = opt.model_params
    if opt.model_type == "dqn-cnn":
        return DqnCnnModel(
            action_space=spec.num_actions,
            norm_val=spec.norm_val,
            orthogonal_init=mp_.orthogonal_init,
            compute_dtype=jnp.dtype(mp_.compute_dtype),
        )
    if opt.model_type == "dqn-cnn-wide":
        # the MXU-filling torso family (ISSUE 13): IMPALA-deep residual
        # stack with 128-multiple channel widths (models/dqn_cnn_wide.py)
        from pytorch_distributed_tpu.models.dqn_cnn_wide import (
            DqnCnnWideModel,
        )

        return DqnCnnWideModel(
            action_space=spec.num_actions,
            norm_val=spec.norm_val,
            width=mp_.cnn_wide_width,
            compute_dtype=jnp.dtype(mp_.compute_dtype),
        )
    if opt.model_type == "dqn-mlp":
        return DqnMlpModel(
            action_space=spec.num_actions,
            hidden_dim=mp_.hidden_dim,
            norm_val=spec.norm_val,
        )
    if opt.model_type == "ddpg-mlp":
        assert not spec.discrete, "ddpg-mlp needs a continuous action space"
        return DdpgMlpModel(action_dim=spec.action_dim,
                            norm_val=spec.norm_val)
    if opt.model_type == "drqn-mlp":
        from pytorch_distributed_tpu.models.drqn import DrqnMlpModel

        return DrqnMlpModel(action_space=spec.num_actions,
                            hidden_dim=mp_.hidden_dim,
                            lstm_dim=mp_.lstm_dim,
                            norm_val=spec.norm_val)
    if opt.model_type in ("dtqn-mlp", "dtqn-moe", "dtqn-pipe"):
        from pytorch_distributed_tpu.models.dtqn import DtqnMlpModel

        kw = dict(
            action_space=spec.num_actions,
            state_shape=spec.state_shape,
            # the acting window and the learner's T+1-long segments share
            # one positional table (acting uses leading-aligned windows so
            # positions match the training distribution exactly)
            window=opt.agent_params.seq_len + 1,
            dim=mp_.tf_dim,
            heads=mp_.tf_heads,
            depth=mp_.tf_depth,
            norm_val=spec.norm_val)
        if opt.model_type == "dtqn-moe":
            from pytorch_distributed_tpu.models.moe import DtqnMoeModel

            return DtqnMoeModel(
                num_experts=mp_.moe_experts,
                top_k=mp_.moe_top_k,
                capacity_factor=mp_.moe_capacity_factor,
                **kw)
        if opt.model_type == "dtqn-pipe":
            from pytorch_distributed_tpu.models.dtqn_pipeline import (
                DtqnPipelineModel,
            )

            return DtqnPipelineModel(**kw)
        return DtqnMlpModel(**kw)
    if opt.model_type == "dtqn-hybrid":
        from pytorch_distributed_tpu.models.hybrid import (
            PRESETS, HybridQModel,
        )

        return HybridQModel(
            action_space=spec.num_actions,
            state_shape=spec.state_shape,
            window=opt.agent_params.seq_len + 1,
            preset=PRESETS[mp_.hybrid_preset],
            norm_val=spec.norm_val,
            compute_dtype=jnp.dtype(mp_.compute_dtype))
    if opt.model_type == "drqn-cnn":
        from pytorch_distributed_tpu.models.drqn import DrqnCnnModel

        return DrqnCnnModel(action_space=spec.num_actions,
                            lstm_dim=lstm_dim_of(opt),
                            norm_val=spec.norm_val,
                            compute_dtype=jnp.dtype(mp_.compute_dtype))
    raise ValueError(f"unknown model_type: {opt.model_type}")


def example_obs(opt: Options, spec: EnvSpec, batch: int = 1):
    import jax.numpy as jnp

    dtype = jnp.uint8 if opt.memory_params.state_dtype == "uint8" \
        else jnp.float32
    return jnp.zeros((batch, *spec.state_shape), dtype=dtype)


def init_params(opt: Options, spec: EnvSpec, model, seed: int):
    import jax

    variables = model.init(jax.random.PRNGKey(seed), example_obs(opt, spec))
    # keep ONLY the param collection: flax init also captures any sown
    # collections (the MoE aux losses, models/moe.py AUX_COLLECTION), and
    # letting those scalars ride inside TrainState.params would make them
    # trainable free parameters seeding every later sow reduce
    return {"params": variables["params"]} if "params" in variables \
        else variables


def ddpg_applies(model) -> Tuple[Callable, Callable]:
    actor_apply = lambda p, o: model.apply(p, o, method=model.forward_actor)
    critic_apply = lambda p, o, a: model.apply(p, o, a,
                                               method=model.forward_critic)
    return actor_apply, critic_apply


# ---------------------------------------------------------------------------
# Train-step builder (the learner's pure XLA program)
# ---------------------------------------------------------------------------

def build_train_state_and_step(opt: Options, spec: EnvSpec, model, params,
                               mesh=None):
    """Returns (TrainState, step_fn) for the configured agent family, wiring
    optimizers/targets exactly as ops/losses.py documents.  ``mesh`` (the
    learner's device mesh) activates sequence-parallel paths: a DTQN model
    on a mesh with sp > 1 swaps its attention for ring attention."""
    from pytorch_distributed_tpu.ops.losses import (
        build_ddpg_train_step, build_ddpg_train_step_coupled,
        build_dqn_train_step, init_ddpg_train_state, init_train_state,
        make_optimizer,
    )
    from pytorch_distributed_tpu.utils import health

    ap = opt.agent_params
    decay = ap.steps if ap.lr_decay else 0
    # in-jit numeric guards (utils/health.py finite_guard): on by
    # default, killable via HealthParams.numeric_guards / the
    # TPU_APEX_HEALTH_NUMERIC_GUARDS env override
    guard = health.resolve(opt.health_params).numeric_guards
    if opt.agent_type == "r2d2":
        from pytorch_distributed_tpu.ops.sequence_losses import (
            build_drqn_train_step,
        )

        # transformers force burn_in 0 below, so only the LSTM family
        # needs a train window left after the burn-in prefix
        assert opt.model_type.startswith("dtqn") \
            or ap.burn_in < ap.seq_len, (
                f"burn_in={ap.burn_in} must leave a train window inside "
                f"seq_len={ap.seq_len} (did a --set seq_len override "
                f"forget burn_in?)")
        tx = make_optimizer(ap.lr, ap.clip_grad, ap.weight_decay,
                            lr_decay_steps=decay)
        state = init_train_state(params, tx)
        kw = dict(
            burn_in=ap.burn_in,
            nstep=ap.nstep,
            gamma=ap.gamma,
            enable_double=ap.enable_double,
            target_model_update=ap.target_model_update,
            rescale_values=ap.value_rescale,
            priority_eta=ap.priority_eta,
            guard=guard,
        )
        if hasattr(model, "train_parts"):
            from pytorch_distributed_tpu.ops.sequence_losses import (
                build_dtqn_train_step,
            )

            # a model that brings its own window passes (models/hybrid.py):
            # the burn-in prefix stays, as context for its state-space and
            # attention layers, excluded from the loss; how the target
            # network is kept (there: bfloat16 where it is only read
            # through bfloat16 matmuls, 14 bytes a parameter of train state
            # and not 16) is the model's to say
            state = state._replace(target_params=model.target_copy(params))
            online, kw["target_window_apply"], kw["after_update"] = \
                model.train_parts(sequence_pack_frames(opt))
            step = build_dtqn_train_step(online, tx, **kw)
        elif opt.model_type.startswith("dtqn"):
            from pytorch_distributed_tpu.ops.sequence_losses import (
                build_dtqn_train_step,
            )

            # burn-in exists to refresh stale recurrent state; a
            # transformer has none, so every window position trains
            # (DTQN trains all timesteps) and acting never lands on a
            # positional slot without a training signal
            kw["burn_in"] = 0
            train_model = model
            sp = mesh.shape.get("sp", 1) if mesh is not None else 1
            pp = mesh.shape.get("pp", 1) if mesh is not None else 1
            if pp > 1:
                # pipeline parallelism: stage the stacked block family
                # over pp with the GPipe microbatch schedule
                # (parallel/pipeline.py); exclusive with sp — they split
                # the same transformer along different dims
                assert opt.model_type == "dtqn-pipe", (
                    f"pp_size>1 needs model_type dtqn-pipe "
                    f"(got {opt.model_type})")
                assert sp == 1, "pp and sp splits don't compose"
                from pytorch_distributed_tpu.parallel.pipeline import (
                    pipelined_window_apply,
                )

                window_apply = pipelined_window_apply(
                    model, mesh, opt.parallel_params.pp_microbatches)
                step = build_dtqn_train_step(window_apply, tx, **kw)
                return state, step
            if sp > 1:
                # long windows: shard the time axis over sp; attention
                # rides the ring or the Ulysses all-to-all (same params,
                # same math either way)
                from pytorch_distributed_tpu.models.dtqn import (
                    with_ring_attention, with_ulysses_attention,
                )

                assert (ap.seq_len + 1) % sp == 0, (
                    f"sequence-parallel DTQN needs window seq_len+1="
                    f"{ap.seq_len + 1} divisible by mesh sp={sp}")
                strategy = opt.parallel_params.sp_attention
                if strategy == "ulysses":
                    assert opt.model_params.tf_heads % sp == 0, (
                        f"sp_attention=ulysses needs tf_heads="
                        f"{opt.model_params.tf_heads} divisible by mesh "
                        f"sp={sp} (use sp_attention=ring otherwise)")
                    train_model = with_ulysses_attention(model, mesh)
                else:
                    assert strategy == "ring", (
                        f"unknown sp_attention: {strategy}")
                    train_model = with_ring_attention(model, mesh)
            if opt.model_type == "dtqn-moe":
                # MoE: the apply surfaces the sown load-balancing losses
                # as a (q, aux) tuple; the step adds aux_weight * aux
                from pytorch_distributed_tpu.models.moe import (
                    window_q_with_aux,
                )

                window_apply = window_q_with_aux(train_model)
                kw["aux_weight"] = opt.model_params.moe_aux_weight
                # target pass: q only — no mutable sow collection; the
                # frozen network's aux value is never used
                kw["target_window_apply"] = lambda p, obs: \
                    train_model.apply(p, obs, method=train_model.window_q)
            else:
                window_apply = lambda p, obs: train_model.apply(
                    p, obs, method=train_model.window_q)
            step = build_dtqn_train_step(window_apply, tx, **kw)
        else:
            from pytorch_distributed_tpu.models.drqn import halves

            step = build_drqn_train_step(
                *halves(model), tx,
                packed_frames=sequence_pack_frames(opt), **kw)
        return state, step

    if opt.agent_type == "dqn":
        tx = make_optimizer(ap.lr, ap.clip_grad, ap.weight_decay,
                            lr_decay_steps=decay)
        state = init_train_state(params, tx)
        train_apply = _dqn_train_apply(opt, model)
        step = build_dqn_train_step(
            train_apply, tx,
            enable_double=ap.enable_double,
            target_model_update=ap.target_model_update,
            guard=guard,
        )
        return state, step

    if opt.agent_type == "ddpg":
        actor_apply, critic_apply = ddpg_applies(model)
        if ap.ddpg_coupled_update:
            tx = make_optimizer(ap.lr, ap.clip_grad, lr_decay_steps=decay)
            state = init_train_state(params, tx)
            step = build_ddpg_train_step_coupled(
                actor_apply, critic_apply, tx,
                target_model_update=ap.target_model_update,
                guard=guard,
            )
        else:
            atx = make_optimizer(ap.lr, ap.clip_grad, lr_decay_steps=decay)
            ctx_ = make_optimizer(ap.critic_lr, ap.clip_grad,
                                  lr_decay_steps=decay)
            state = init_ddpg_train_state(params, atx, ctx_)
            step = build_ddpg_train_step(
                actor_apply, critic_apply, atx, ctx_,
                target_model_update=ap.target_model_update,
                guard=guard,
            )
        return state, step

    raise ValueError(f"unknown agent_type: {opt.agent_type}")


def select_torso(opt: Options) -> str:
    """Which torso the dqn learner's train program runs — ``"xla"``,
    ``"pallas"`` or ``"pallas-interpret"`` — decided from the
    ``pallas_torso`` knob, the model family and the backend.  The ONE
    gate: ``_dqn_train_apply`` builds what this names and the learner's
    start-up line prints it, so a request the host cannot honour is
    never a silent downgrade."""
    from pytorch_distributed_tpu.utils.perf import resolve_mxu

    lp = resolve_mxu(opt.learner_perf_params)
    if not lp.pallas_torso:
        return "xla"
    import warnings

    if opt.model_type != "dqn-cnn":
        warnings.warn(
            f"pallas_torso=true serves the dqn-cnn torso only (got "
            f"model_type={opt.model_type}); keeping the XLA apply",
            stacklevel=3)
        return "xla"
    if lp.pallas_interpret:
        return "pallas-interpret"
    import jax

    if jax.devices()[0].platform != "tpu":
        warnings.warn(
            "pallas_torso=true but no TPU backend is present "
            "(set pallas_interpret=true for the interpreter-mode CPU "
            "fallback — tier-1 parity tests only; it is slower than "
            "XLA's native conv); keeping the XLA apply", stacklevel=3)
        return "xla"
    return "pallas"


def _dqn_train_apply(opt: Options, model):
    """The learner-side apply for the dqn family: the model's own apply,
    swapped for the Pallas fused torso (ops/pallas_torso.py) when
    ``select_torso`` says so.  Decided HERE — one gate shared by the
    sequential step and the megabatch step — so the two programs can
    never train through different torsos.  Actors and evaluators never
    route through this: the param tree is identical, so they keep the
    standard apply."""
    torso = select_torso(opt)
    if torso == "xla":
        return model.apply
    from pytorch_distributed_tpu.ops.pallas_torso import (
        build_pallas_torso_apply,
    )
    import jax.numpy as jnp

    return build_pallas_torso_apply(
        norm_val=model.norm_val,
        compute_dtype=jnp.dtype(opt.model_params.compute_dtype),
        interpret=torso == "pallas-interpret")


def build_megabatch_train_step(opt: Options, model):
    """The ISSUE-13 megabatch twin of ``build_train_state_and_step``'s
    step: a ``(TrainState, batches(M, B)) -> (TrainState, metrics,
    td_abs(M, B), ok(M,))`` group step computing all M minibatch
    gradients in one lane-filling batched backward with sequential
    in-graph optimizer applies (ops/losses.py megabatch builders).

    The optimizer chain is constructed EXACTLY as the sequential
    builder constructs it, so the TrainState the sequential path
    initialised (and checkpointed) is directly consumable.  Returns
    None for families without megabatch support (the sequence/
    transformer families and coupled DDPG) — callers downgrade loudly.
    No mesh parameter on purpose: the supported families' data
    parallelism is SPMD through jit sharding (the sequential builder
    only consumes its mesh for the sequence-parallel DTQN paths, which
    megabatch does not serve).
    """
    from pytorch_distributed_tpu.ops.losses import (
        build_ddpg_megabatch_step, build_dqn_megabatch_step,
        make_optimizer,
    )
    from pytorch_distributed_tpu.utils import health

    ap = opt.agent_params
    decay = ap.steps if ap.lr_decay else 0
    guard = health.resolve(opt.health_params).numeric_guards
    if opt.agent_type == "dqn":
        tx = make_optimizer(ap.lr, ap.clip_grad, ap.weight_decay,
                            lr_decay_steps=decay)
        return build_dqn_megabatch_step(
            _dqn_train_apply(opt, model), tx,
            enable_double=ap.enable_double,
            target_model_update=ap.target_model_update,
            guard=guard,
        )
    if opt.agent_type == "ddpg" and not ap.ddpg_coupled_update:
        actor_apply, critic_apply = ddpg_applies(model)
        atx = make_optimizer(ap.lr, ap.clip_grad, lr_decay_steps=decay)
        ctx_ = make_optimizer(ap.critic_lr, ap.clip_grad,
                              lr_decay_steps=decay)
        return build_ddpg_megabatch_step(
            actor_apply, critic_apply, atx, ctx_,
            target_model_update=ap.target_model_update,
            guard=guard,
        )
    return None


def resolve_steps_per_dispatch(opt: Options) -> int:
    """Update steps fused into one dispatched program on the device-
    replay paths: ``agent_params.steps_per_dispatch`` when set, else
    auto — 32 on a TPU (amortise the launch) for the models whose update
    takes milliseconds, 1 for dtqn-hybrid (an update is half a second:
    nothing to amortise, and 32 would be a 17-second dispatch), 1
    elsewhere (on the CPU backend the dispatch IS
    the compute).  One rule for the split learner and the Anakin loop;
    the learner's start-up line prints the result.  The 32 was chosen
    over a link that no longer exists; on the directly attached chip it
    is what the benchmark's apex and r2d2 cells run (PERF.md)."""
    K = opt.agent_params.steps_per_dispatch
    if K > 0:
        return K
    if opt.model_type == "dtqn-hybrid":
        return 1
    import jax

    return 32 if jax.devices()[0].platform == "tpu" else 1


def resolve_megabatch(opt: Options, steps_per_call: int
                      ) -> Tuple[int, int]:
    """Resolve the ISSUE-13 megabatch knob against a dispatch's
    ``steps_per_call``: returns ``(M, K)`` with M clamped to >= 1 and K
    rounded UP to the next multiple of M (the ``steps`` budget already
    tolerates whole-dispatch overshoot; silently truncating updates
    would be worse).  One resolution point shared by the learner and
    its Anakin twin so the two can never disagree on grouping."""
    from pytorch_distributed_tpu.utils.perf import resolve_mxu

    M = max(1, int(resolve_mxu(opt.learner_perf_params).megabatch))
    K = max(1, int(steps_per_call))
    if M > 1 and K % M:
        K = ((K + M - 1) // M) * M
        print(f"[learner] steps_per_dispatch rounded up to {K} "
              f"(multiple of megabatch {M})", flush=True)
    return M, K


@dataclass(frozen=True)
class LearnerProgram:
    """The learner's device program for one ``Options``: what
    ``build_learner_core`` assembles, and, once a ring is attached, the
    ONE fused dispatch ``build_learner_dispatch`` adds.  The split
    learner (agents/learner.py) and the Anakin loop (agents/anakin.py)
    both hold this and nothing of their own, so a config can not train
    through two different programs.  The TrainState is not kept here:
    callers donate it on every dispatch and replace it on resume."""

    mesh: Any                   # None on one device
    model: Any
    step_fn: Callable           # (TrainState, Batch) -> (TrainState, m, td)
    learner: Any                # ShardedLearner(step_fn, mesh): place, step
    # -- set by build_learner_dispatch --
    K: int = 1                  # updates one call of ``fused`` applies
    fused: Optional[Callable] = None  # jitted; keys (K, ..) or one key at K=1
    takes_beta: bool = False    # fused(state, ring, keys[, beta])
    returns: Tuple[str, ...] = ()  # fused's outputs in order, of
    #                                "state", "ring", "metrics", "td"


def build_learner_core(opt: Options, spec: EnvSpec):
    """``(LearnerProgram, placed TrainState)`` for ``opt``: the mesh (any
    time more than one device is visible), the model, seeded params with
    ``opt.model_file`` loaded over them (finetune-from-file, reference
    main.py:45) BEFORE the optimizer state and target copy are made of
    them, the train step, and the one model split the mesh asks for
    (mp: Megatron FFN, ep: MoE experts, pp: stacked blocks), each refused
    for any family but its own."""
    import jax

    from pytorch_distributed_tpu.parallel.learner import ShardedLearner
    from pytorch_distributed_tpu.parallel.mesh import make_mesh
    from pytorch_distributed_tpu.utils import checkpoint as ckpt

    pp = opt.parallel_params
    # mesh first: sequence-parallel train steps (DTQN ring attention over
    # the sp axis) are built against it
    mesh = None
    if len(jax.devices()) > 1:
        mesh = make_mesh(pp.dp_size, pp.mp_size, pp.sp_size, pp.ep_size,
                         pp.pp_size)
    model = build_model(opt, spec)
    params = init_params(opt, spec, model, seed=opt.seed)
    if opt.model_file:
        path = opt.model_file if opt.model_file.endswith(".msgpack") \
            else ckpt.params_path(opt.model_file)
        params = ckpt.load_params(path, params)
    state, step_fn = build_train_state_and_step(opt, spec, model, params,
                                                mesh=mesh)
    state_shardings = None
    if mesh is not None and pp.mp_size > 1:
        # exact match: the moe/pipe families have no _Block_ param paths,
        # so dtqn_state_shardings would silently no-op on them (their
        # splits are ep and pp respectively)
        assert opt.model_type == "dtqn-mlp", (
            f"mp_size>1 is only supported for dtqn-mlp "
            f"(got {opt.model_type})")
        from pytorch_distributed_tpu.parallel.tensor_parallel import (
            dtqn_state_shardings,
        )

        state_shardings = dtqn_state_shardings(state, mesh)
    if mesh is not None and pp.ep_size > 1:
        # the DTQN families are either dense (mp) or MoE (ep)
        assert opt.model_type == "dtqn-moe", (
            f"ep_size>1 is only supported for dtqn-moe "
            f"(got {opt.model_type})")
        assert pp.mp_size == 1, "ep and mp splits don't compose"
        from pytorch_distributed_tpu.parallel.expert_parallel import (
            moe_state_shardings,
        )

        state_shardings = moe_state_shardings(state, mesh)
    if mesh is not None and pp.pp_size > 1:
        assert opt.model_type == "dtqn-pipe", (
            f"pp_size>1 is only supported for dtqn-pipe "
            f"(got {opt.model_type})")
        assert pp.mp_size == 1 and pp.ep_size == 1, (
            "pp does not compose with mp/ep splits")
        from pytorch_distributed_tpu.parallel.pipeline import (
            pipeline_state_shardings,
        )

        state_shardings = pipeline_state_shardings(state, mesh)
    learner = ShardedLearner(step_fn, mesh, donate=pp.donate,
                             state_shardings=state_shardings)
    return (LearnerProgram(mesh=mesh, model=model, step_fn=step_fn,
                           learner=learner),
            learner.place(state))


def build_learner_dispatch(core: LearnerProgram, replay, opt: Options,
                           role: str = "learner") -> LearnerProgram:
    """``core`` with the ONE fused program for the attached HBM ring
    ``replay``: sampling (and on the prioritized rings the |TD|
    write-back) fused into the train step, ``K`` scanned updates a call
    (``resolve_steps_per_dispatch``), regrouped by ``megabatch`` where the
    family has a group step and said LOUDLY (``[role] ...``) where it has
    none.  The record says how to call what it built: the PER and segment
    rings take ``beta`` and hand their ring state back, the uniform ring
    is read-only in the program, and its K = 1 form also returns |TD|."""
    import jax

    ap, pp = opt.agent_params, opt.parallel_params
    K = resolve_steps_per_dispatch(opt)
    M, K_mb = resolve_megabatch(opt, K)
    mb_kw = {}
    if M > 1:
        mega_step = build_megabatch_train_step(opt, core.model)
        if mega_step is None:
            print(f"[{role}] megabatch={M} is not supported for "
                  f"agent_type={opt.agent_type} (dqn/decoupled-ddpg "
                  f"only); running the sequential fused step at "
                  f"steps_per_dispatch={K}", flush=True)
        else:
            # only an ENGAGED megabatch inflates the dispatch quantum: a
            # downgrade keeps the configured K
            K = K_mb
            mb_kw = dict(megabatch=M, megabatch_step=mega_step)
    if hasattr(replay, "build_fused_step"):
        fused = replay.build_fused_step(core.step_fn, ap.batch_size,
                                        donate=pp.donate, steps_per_call=K,
                                        **mb_kw)
        takes_beta, returns = True, ("state", "ring", "metrics")
    elif K > 1:
        from pytorch_distributed_tpu.memory.device_replay import (
            build_uniform_fused_step,
        )

        fused = build_uniform_fused_step(core.step_fn, ap.batch_size,
                                         steps_per_call=K, donate=pp.donate,
                                         **mb_kw)
        takes_beta, returns = False, ("state", "metrics")
    else:
        from pytorch_distributed_tpu.memory.device_replay import sample_rows

        step_fn, B = core.step_fn, ap.batch_size
        fused = jax.jit(
            lambda ts, rs, key: step_fn(ts, sample_rows(rs, key, B)),
            donate_argnums=(0,) if pp.donate else ())
        takes_beta, returns = False, ("state", "metrics", "td")
    return replace(core, K=K, fused=fused, takes_beta=takes_beta,
                   returns=returns)


def build_replica_grad_apply(opt: Options, model):
    """The ISSUE-15 replica-plane twin of ``build_train_state_and_step``:
    the dqn update factored at the gradient boundary
    (ops/losses.build_dqn_grad_and_apply) so the replica driver can
    allreduce gradients over DCN between the halves.  The optimizer and
    train apply are constructed EXACTLY as the sequential builder
    constructs them (one ``_dqn_train_apply`` gate, one
    ``make_optimizer`` call), so a TrainState initialised — or
    checkpointed — by the solo learner is directly consumable by a
    replica, and vice versa.  Returns ``(grad_fn, apply_grads)`` or
    None for families without replica support (callers downgrade
    loudly)."""
    from pytorch_distributed_tpu.ops.losses import (
        build_dqn_grad_and_apply, make_optimizer,
    )

    if opt.agent_type != "dqn":
        return None
    ap = opt.agent_params
    tx = make_optimizer(ap.lr, ap.clip_grad, ap.weight_decay,
                        lr_decay_steps=(ap.steps if ap.lr_decay else 0))
    return build_dqn_grad_and_apply(
        _dqn_train_apply(opt, model), tx,
        enable_double=ap.enable_double,
        target_model_update=ap.target_model_update,
    )


def replica_active(opt: Options) -> bool:
    """Is the elastic multi-learner plane engaged (ISSUE 15)?  One
    resolution point (parallel.dcn.resolve_replica applies the
    TPU_APEX_REPLICA_* env contract) shared by the runtime wiring, the
    learner delegation and the fleet CLI."""
    from pytorch_distributed_tpu.parallel.dcn import resolve_replica

    return resolve_replica(opt.replica_params).replicas > 1


def published_params(opt: Options, state) -> Any:
    """The param tree the learner publishes to actors: the full model tree
    (merged back for decoupled DDPG, whose TrainState splits it)."""
    if opt.agent_type == "ddpg" and not opt.agent_params.ddpg_coupled_update:
        from pytorch_distributed_tpu.ops.losses import merge_ddpg_params

        return merge_ddpg_params(state.params["actor"],
                                 state.params["critic"])
    return state.params


# ---------------------------------------------------------------------------
# Memory routing
# ---------------------------------------------------------------------------

@dataclass
class MemoryHandles:
    """How the topology plugs a memory_type in:

    - ``actor_side``: what actor processes call ``feed`` on;
    - ``learner_side``: what the learner samples from (and updates
      priorities on);
    - for the shared ring both are the same object (reference
      shared_memory.py's one global buffer); for PER the actor side is a
      queue feeder and the learner side the single-owner tree buffer
      (memory/prioritized.py docstring).
    """

    actor_side: Any
    learner_side: Any


def build_memory(opt: Options, spec: EnvSpec) -> MemoryHandles:
    mp_ = opt.memory_params
    state_dtype = np.uint8 if mp_.state_dtype == "uint8" else np.float32
    if opt.memory_type in ("shared", "native"):
        ctor = SharedReplay
        if opt.memory_type == "native":
            try:
                from pytorch_distributed_tpu.memory.native_ring import (
                    NativeRingReplay, get_lib,
                )

                get_lib()
                ctor = NativeRingReplay
            except Exception as e:  # noqa: BLE001 - no toolchain: fall back
                import warnings

                warnings.warn(f"native ring unavailable ({e}); "
                              "falling back to Python shared replay",
                              stacklevel=2)
        mem = ctor(
            capacity=mp_.memory_size,
            state_shape=spec.state_shape,
            action_shape=spec.action_shape,
            state_dtype=state_dtype,
            action_dtype=spec.action_dtype,
        )
        return MemoryHandles(actor_side=mem, learner_side=mem)
    if opt.memory_type == "prioritized":
        from pytorch_distributed_tpu.memory import shard_plane

        if shard_plane.sharding_active(opt.shard_params):
            # ISSUE 20: the sharded priority plane — N loopback shards
            # behind the SAME QueueOwner boundary, so the learner loop,
            # feeder, and quarantine path never learn sharding exists.
            # At shards <= 1 this branch is never taken and the plain
            # PER below is constructed bit-identically to every prior
            # release.
            plane, _shards, _reg = shard_plane.build_loopback_plane(
                opt.shard_params,
                capacity=mp_.memory_size,
                state_shape=spec.state_shape,
                action_shape=spec.action_shape,
                state_dtype=state_dtype,
                action_dtype=spec.action_dtype,
                priority_exponent=mp_.priority_exponent,
                importance_weight=mp_.priority_weight,
                importance_anneal_steps=opt.agent_params.steps,
            )
            owner = QueueOwner(plane)
            return MemoryHandles(actor_side=owner.make_feeder(),
                                 learner_side=owner)
        per = PrioritizedReplay(
            capacity=mp_.memory_size,
            state_shape=spec.state_shape,
            action_shape=spec.action_shape,
            state_dtype=state_dtype,
            action_dtype=spec.action_dtype,
            priority_exponent=mp_.priority_exponent,
            importance_weight=mp_.priority_weight,
            importance_anneal_steps=opt.agent_params.steps,
        )
        owner = QueueOwner(per)
        return MemoryHandles(actor_side=owner.make_feeder(),
                             learner_side=owner)
    if opt.memory_type == "sequence":
        from pytorch_distributed_tpu.memory.sequence_replay import (
            SequenceReplay,
        )

        ap = opt.agent_params
        seq = SequenceReplay(
            # memory_size counts transitions everywhere else; overlapping
            # windows mean ~seq_len/overlap rows per transition, so divide
            # by the overlap stride to hold the same history span
            capacity=max(mp_.memory_size
                         // max(ap.seq_len - ap.seq_overlap, 1), 16),
            seq_len=ap.seq_len,
            state_shape=spec.state_shape,
            lstm_dim=lstm_dim_of(opt),
            state_dtype=state_dtype,
            priority_exponent=mp_.priority_exponent,
            importance_weight=mp_.priority_weight,
            importance_anneal_steps=ap.steps * ap.batch_size,
            pack_frames=sequence_pack_frames(opt),
        )
        owner = QueueOwner(seq)
        return MemoryHandles(actor_side=owner.make_feeder(),
                             learner_side=owner)
    if opt.memory_type == "device-sequence":
        from pytorch_distributed_tpu.memory.device_sequence import (
            DeviceSequenceIngest,
        )

        ap = opt.agent_params
        ingest = DeviceSequenceIngest(
            # same segments-per-history-span arithmetic as the host plane
            capacity=max(mp_.memory_size
                         // max(ap.seq_len - ap.seq_overlap, 1), 16),
            seq_len=ap.seq_len,
            state_shape=spec.state_shape,
            lstm_dim=lstm_dim_of(opt),
            state_dtype=state_dtype,
            priority_exponent=mp_.priority_exponent,
            importance_weight=mp_.priority_weight,
            importance_anneal_steps=ap.steps,
            pack_frames=sequence_pack_frames(opt),
        )
        return MemoryHandles(actor_side=ingest.make_feeder(),
                             learner_side=ingest)
    if opt.memory_type in ("device", "device-per"):
        from pytorch_distributed_tpu.memory.device_replay import (
            DevicePerIngest, DeviceReplayIngest,
        )

        geom = dict(
            capacity=mp_.memory_size,
            state_shape=spec.state_shape,
            action_shape=spec.action_shape,
            state_dtype=state_dtype,
            action_dtype=spec.action_dtype,
        )
        if opt.memory_type == "device-per":
            ingest = DevicePerIngest(
                priority_exponent=mp_.priority_exponent,
                importance_weight=mp_.priority_weight,
                importance_anneal_steps=opt.agent_params.steps,
                **geom)
        else:
            ingest = DeviceReplayIngest(**geom)
        return MemoryHandles(actor_side=ingest.make_feeder(),
                             learner_side=ingest)
    raise ValueError(f"unknown memory_type: {opt.memory_type}")
