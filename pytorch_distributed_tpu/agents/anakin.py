"""The closed Anakin loop (ISSUE 12 tentpole): env fleet + learner in
ONE accelerator-owning process, zero host round-trips on the experience
path.

PR 7 put the env fleet on the device (envs/device_env.py) and fused
policy + env physics + n-step assembly into one donated scan
(models/policies.build_fused_rollout), but the device actor still ran
as a separate CPU-pinned process shipping finished chunks through the
spawn queue — ~56 KB per transition of pickle/pipe/H2D work (two
uint8 84x84x4 stacks a row) while the chip waits (in the closed loop
92 % of the chip's idle time lay under ``learner/drain``: my chip run,
PR 24, PERF.md section 5).  Podracer's Anakin topology (Hessel et al.
2021) and Ape-X's own act→store→sample→learn cycle (Horgan et al.
2018) both say the whole loop belongs in one program on one chip.
This module is that loop:

- the env fleet lives IN the learner process (``num_actors x
  num_envs_per_actor`` envs as one batched pure-JAX program on the
  fleet seed/epsilon slot contract, so backend choice never changes
  the exploration schedule);
- one driver alternates the donated fused-rollout dispatch
  (``emit="replay"``: transitions scatter straight into the
  device-resident replay ring, PER rows stamped at the running max
  priority via memory/device_per.per_write_masked) and the fused
  learner-step dispatch against the SAME ``ReplayState`` /
  ``PerReplayState`` — no actor processes, no spawn queue, no D2H on
  the experience path at all;
- the acting params ARE the train state's params (one shared
  reference): the published version is the acting version by
  construction, with zero staleness;
- a duty-cycle scheduler (``AnakinParams.rollout_ratio``) balances
  frames collected against updates applied — 0 = strict alternation,
  the bit-reproducible schedule the parity oracle pins;
- ``AnakinParams.double_buffer`` splits the ring into two
  half-capacity halves: learner dispatches sample the stable half
  while rollouts scatter into the other, halves swapping once the
  write half holds ``min_fill`` fresh rows — priority write-back races
  excluded by construction, not by ordering.

Parity contract (tests/test_anakin.py): under a fixed seed and the
strict-alternation schedule, a co-located run is bit-identical to the
split-process ``actor_backend="device"`` path — actions (via ring
contents), emitted transitions, PER priorities, and learner params
after N steps — because every XLA program involved is the SAME program
the split path dispatches (the fused rollout's replay-emit leg and the
learner's fused step), only the host plumbing between them vanishes.

Knobs live in ``config.AnakinParams``, env-overridable as
``TPU_APEX_ANAKIN_<FIELD>`` via ``resolve_anakin`` — the same
spawn-inheritance contract the health/perf/flow planes use.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Any

import numpy as np

from pytorch_distributed_tpu.config import Options
from pytorch_distributed_tpu.utils.profiling import report_setup

_ENV_PREFIX = "TPU_APEX_ANAKIN_"


def resolve_anakin(ap=None):
    """AnakinParams + ``TPU_APEX_ANAKIN_<FIELD>`` env overrides — the
    override-by-env contract the health/perf/flow planes use.  Returns
    a NEW instance; the input is never mutated (Options rides spawn
    pickles)."""
    from pytorch_distributed_tpu.config import AnakinParams

    if ap is None:
        ap = AnakinParams()
    changes: dict = {}
    for f in dataclasses.fields(ap):
        raw = os.environ.get(_ENV_PREFIX + f.name.upper())
        if raw is None:
            continue
        cur = getattr(ap, f.name)
        if isinstance(cur, bool):
            changes[f.name] = raw.strip().lower() not in (
                "0", "false", "off", "no", "")
        elif isinstance(cur, int) and not isinstance(cur, bool):
            changes[f.name] = int(float(raw))
        elif isinstance(cur, float):
            changes[f.name] = float(raw)
        else:
            changes[f.name] = raw.strip()
    return dataclasses.replace(ap, **changes) if changes else ap


class AnakinDriver:
    """The co-located act→store→sample→learn driver.

    Owns the train state, the device env fleet, the fused rollout and
    fused learner programs, and the (single or double-buffered) HBM
    ring(s).  ``dispatch_rollout`` / ``dispatch_learn`` are exposed
    individually so the parity tests can drive bounded
    deterministic schedules; ``run`` is the production duty-cycle loop
    with the learner's usual cadences (publish / checkpoint / stats).
    """

    def __init__(self, opt: Options, spec, memory: Any, param_store,
                 clock, learner_stats, actor_stats=None,
                 process_ind: int = 0):
        import jax
        import jax.numpy as jnp

        from pytorch_distributed_tpu.agents.clocks import ActorStats
        from pytorch_distributed_tpu.factory import (
            anakin_eligible, build_device_env, build_learner_core,
            build_learner_dispatch,
        )
        from pytorch_distributed_tpu.memory.device_per import (
            per_write_masked,
        )
        from pytorch_distributed_tpu.models.policies import (
            apex_epsilons, build_fused_rollout, init_rollout_carry,
        )
        from pytorch_distributed_tpu.parallel.mesh import replicated
        from pytorch_distributed_tpu.utils import checkpoint as ckpt
        from pytorch_distributed_tpu.utils import perf
        from pytorch_distributed_tpu.utils.metrics import MetricsWriter
        from pytorch_distributed_tpu.utils.profiling import StepTimer
        from pytorch_distributed_tpu.utils.rngs import (
            np_rng, process_key, process_seed,
        )

        ok, why = anakin_eligible(opt)
        if not ok:
            raise RuntimeError(f"anakin driver on an ineligible config: "
                               f"{why}")
        self._jax = jax
        self._version = 0
        self.opt = opt
        self.ap = opt.agent_params
        self.an = resolve_anakin(opt.anakin_params)
        self.memory = memory
        self.param_store = param_store
        self.clock = clock
        self.learner_stats = learner_stats
        self.actor_stats = (actor_stats if actor_stats is not None
                            else ActorStats())
        self.process_ind = process_ind
        ap = self.ap

        # ---- mesh, model, train state (the learner half: the factory's
        # one assembly, as run_learner; a model split asked of the dqn
        # family is refused there) ----
        core, self.state = build_learner_core(opt, spec)
        self.mesh = mesh = core.mesh
        self.model = core.model
        self._learner = core.learner
        # every small device-resident operand (keys, eps, tick, prov,
        # beta, carry) is placed EXPLICITLY in the mesh's replicated
        # layout at creation — the compiled programs' input shardings —
        # so dispatches stage zero implicit reshards and the transfer
        # audit stays clean under a mesh exactly as on one device
        self._sharding = replicated(mesh) if mesh is not None else None

        # ---- resume: newest complete epoch's train state + counters.
        # The anakin driver keeps resume SIMPLE — state, clocks and the
        # device sampling key, no rollback ladder (the health sentinel's
        # rollback machinery stays a split-topology feature for now).
        assert opt.resume in ("auto", "must", "never"), (
            f"unknown resume mode {opt.resume!r}")
        epoch = None
        if opt.resume != "never":
            epoch = ckpt.resolve_epoch(opt.model_name)
            if epoch is not None:
                self.state = self._learner.place(
                    ckpt.load_epoch_state(epoch,
                                          jax.device_get(self.state)))
                clock.seed_actor_steps(
                    int(epoch.extras.get("actor_step", 0)))
                clock.best_eval_reward.value = max(
                    float(epoch.extras.get("best_eval_reward",
                                           float("-inf"))),
                    ckpt.load_best_score(opt.model_name))
                print(f"[anakin] resumed epoch {epoch.epoch} "
                      f"(step {epoch.learner_step})")
            elif opt.resume == "must":
                raise RuntimeError(
                    f"resume='must' but no complete checkpoint epoch "
                    f"under {ckpt.ckpt_root(opt.model_name)}")
        self._epoch = epoch

        # ---- ring(s): single, or double-buffered halves ----
        if self.an.double_buffer:
            self.rings = list(memory.attach_halves(mesh=mesh))
        else:
            self.rings = [memory.attach(mesh=mesh)]
        # a prioritized ring: rollouts stamp fresh rows at the running max
        self.is_per = hasattr(self.rings[0].state, "priority")
        self.sample_ix = 0
        self.write_ix = 0
        self._fresh = 0  # rows into the write half since the last swap
        half_cap = self.rings[0].capacity
        mf = self.an.min_fill or min(ap.learn_start, half_cap - 1)
        self.min_fill = max(1, min(int(mf), half_cap))
        # host-side fill accounting per ring — no device sync on the
        # scheduler's hot path (the in-graph scatter's row count is a
        # pure function of the tick window, fetched with the stats)
        self._fill = [0 for _ in self.rings]
        if epoch is not None and opt.memory_params.checkpoint_replay:
            rows = ckpt.load_epoch_replay(epoch, memory)
            if rows:
                self._fill[0] = min(rows, half_cap)
                print(f"[anakin] replay restored from epoch "
                      f"{epoch.epoch}: {rows} rows")

        # ---- the co-located env fleet + fused rollout ----
        # the WHOLE fleet as one batched program: num_actors x
        # num_envs_per_actor envs on the fleet slot contract (env j of
        # virtual actor i takes seed slot i*N + j and epsilon slot
        # i*N + j of A*N — the same streams the split fleet draws)
        A = max(1, opt.num_actors)
        N = max(1, opt.env_params.num_envs_per_actor)
        self.fleet_envs = A * N
        self.env = build_device_env(opt, 0, self.fleet_envs)
        self.K_roll = max(1, int(opt.env_params.device_rollout_ticks))
        self.rollout = build_fused_rollout(
            self.model.apply, self.env, nstep=ap.nstep, gamma=ap.gamma,
            rollout_ticks=self.K_roll, emit="replay",
            ring_write_fn=per_write_masked if self.is_per else None)
        self.carry = self._place(init_rollout_carry(self.env, ap.nstep))
        self.eps_dev = self._place(jnp.asarray(
            apex_epsilons(0, 1, self.fleet_envs, ap.eps, ap.eps_alpha),
            jnp.float32))
        self.base_key = self._place(
            jnp.asarray(process_key(opt.seed, "actor", 0)))
        self.tick0 = self._place(jnp.int32(0))

        # ---- the fused learner program: the SAME factory assembly the
        # split-process learner dispatches (the parity oracle's ground);
        # built from ring 0, run against either half's state ----
        self._prog = prog = build_learner_dispatch(core, self.rings[0], opt,
                                                   role="anakin")
        self.K_learn = K = prog.K
        self._beta = None

        from pytorch_distributed_tpu.agents.learner import announce_startup

        announce_startup(opt, mesh=mesh, steps_per_dispatch=K,
                         replay=self.rings[0])

        # learner-side sampling key stream (run_learner's scheme: one
        # split amortised over 64 dispatches, beta refreshed with it)
        self._device_key = jax.random.PRNGKey(
            np_rng(opt.seed, "learner", process_ind).integers(2 ** 31))
        saved = (epoch.extras.get("rng", {}).get("learner_device")
                 if epoch is not None else None)
        if saved:
            self._device_key = ckpt.deserialize_prng_key(saved,
                                                         self._device_key)
        self._key_buf: list = []

        # ---- perf plane: ONE monitor carries both counters; live MFU
        # sums the learner program's per-update FLOPs and the rollout's
        # per-frame FLOPs (utils/perf.py drain combines them) ----
        self.perf = perf.get_monitor("learner", opt.perf_params)
        if self.perf.enabled:
            # fp32 models score MFU against the fp32 peak (ISSUE 13)
            _cd = getattr(self.model, "compute_dtype", None)
            if _cd is not None:
                self.perf.set_compute_dtype(jnp.dtype(_cd).name)
            self.perf.register_jit("fused_step", prog.fused)
            self.perf.register_jit("anakin_rollout", self.rollout)
            # seed-derived even though these keys only feed .lower()
            # for the FLOP capture (apexlint rng-key-reuse contract)
            _pkeys = jax.random.split(
                jax.random.PRNGKey(process_seed(opt.seed, "learner",
                                                process_ind)),
                K + 1)[1:]
            _pkeys = (_pkeys.reshape(K, *_pkeys.shape[1:]) if K > 1
                      else _pkeys[0])
            rs0 = self.rings[0].state
            _pbeta = ((jax.device_put(np.float32(self.rings[0].beta(0))),)
                      if prog.takes_beta else ())
            self.perf.capture_flops(
                lambda: prog.fused.lower(self.state, rs0, _pkeys, *_pbeta))
            self.perf.capture_frame_flops(
                lambda: self.rollout.lower(
                    self.state.params, self.carry, rs0, self.base_key,
                    self.tick0, self.eps_dev, self._make_prov(0)),
                frames_per_call=self.fleet_envs)
        self.audit = self.perf.audit

        # episode accounting (the actor harness's accumulators, fleet-
        # wide) + stat-flush cadence state
        self.episode_reward = np.zeros(self.fleet_envs, dtype=np.float64)
        self.episode_steps = np.zeros(self.fleet_envs, dtype=np.int64)
        self._acc = dict.fromkeys(ActorStats.FIELDS, 0.0)
        self.env_steps = 0
        self._next_flush = ap.actor_freq

        # duty-cycle input: CUMULATIVE frames vs cumulative updates
        # (lstep - lstep0).  Resume seeds it from the same epoch extras
        # the clock rides — a restart that restored lstep but started
        # frames at 0 would read as a huge frames deficit and flood
        # rollout-only (zero updates, zero stats cadences) until the
        # counter caught back up.
        self.frames = (int(epoch.extras.get("actor_step", 0))
                       if epoch is not None else 0)
        self.lstep = int(jax.device_get(self.state.step))
        self.lstep0 = self.lstep
        if epoch is not None:
            self.lstep0 = int(epoch.extras.get("lstep0", self.lstep0))
        clock.set_learner_step(self.lstep)
        self._last_was_rollout = False
        self._last_metrics = None
        # duty-cycle window accumulators (drained on the stats cadence)
        self._roll_s = 0.0
        self._learn_s = 0.0
        self._roll_frames = 0
        self.timer = StepTimer("learner")
        self.writer = MetricsWriter(opt.log_dir, enable_tensorboard=False,
                                    role="learner", run_id=opt.refs)
        # CPU backends block per dispatch (free — the dispatch IS the
        # compute there), which also makes the duty-cycle host timers
        # exact; on TPU timers attribute async-dispatch waits to the
        # NEXT fetch point, a documented approximation
        self._block = jax.devices()[0].platform == "cpu"

    # -- helpers -----------------------------------------------------------

    def _place(self, x):
        """Explicit device placement in the compiled programs' input
        layout (replicated over the mesh when one exists)."""
        if self._sharding is not None:
            return self._jax.device_put(x, self._sharding)
        return self._jax.device_put(x)

    def _make_prov(self, birth_step: int):
        """(actor_id, param_version, birth_step) for the in-graph
        provenance scatter — an EXPLICIT 12-byte device_put per rollout
        dispatch (control plane, not experience; never trips the
        transfer audit)."""
        return self._place(np.asarray([0, self._version, birth_step],
                                      np.int32))

    def _publish(self) -> None:
        from jax.flatten_util import ravel_pytree

        from pytorch_distributed_tpu.factory import published_params

        flat, _ = ravel_pytree(self._jax.device_get(
            published_params(self.opt, self.state)))
        self.param_store.publish(np.asarray(flat, dtype=np.float32))
        self._version = int(getattr(self.param_store, "version", 0) or 0)

    def _save_epoch(self) -> None:
        from pytorch_distributed_tpu.utils import checkpoint as ckpt

        extras = dict(
            learner_step=self.lstep,
            lstep0=self.lstep0,
            actor_step=int(self.clock.actor_step.value),
            best_eval_reward=float(self.clock.best_eval_reward.value),
            replay_size=int(getattr(self.memory, "size", 0)),
            rollbacks=int(self.clock.rollbacks.value),
            skipped_steps=int(self.clock.skipped_steps.value),
            rng=dict(
                learner_device=ckpt.serialize_prng_key(self._device_key)),
        )
        ckpt.save_epoch(
            self.opt.model_name, state=self.state,
            memory=(self.memory
                    if self.opt.memory_params.checkpoint_replay else None),
            extras=extras, retain=self.ap.checkpoint_retain)

    def replay_fill(self) -> float:
        """Fraction of total ring capacity holding valid rows (host
        accounting; both halves count in double-buffer mode)."""
        cap = sum(r.capacity for r in self.rings)
        return min(1.0, sum(self._fill) / max(cap, 1))

    def _maybe_swap(self) -> None:
        """Double-buffer swap schedule: the cold-start split (write
        half detaches from the sample half once it holds ``min_fill``
        rows), then a swap whenever the write half has accumulated
        ``min_fill`` FRESH rows.  Runs only between dispatches, so the
        learner never samples a half a rollout is writing."""
        if not self.an.double_buffer:
            return
        if self.write_ix == self.sample_ix:
            if self._fill[self.write_ix] >= self.min_fill:
                self.write_ix = 1 - self.write_ix
                self._fresh = 0
        elif self._fresh >= self.min_fill:
            self.sample_ix, self.write_ix = self.write_ix, self.sample_ix
            self._fresh = 0

    def want_rollout(self) -> bool:
        """The duty-cycle scheduler: warmup until the sample ring holds
        ``min_fill`` rows, then either the ``rollout_ratio`` frames-
        per-update setpoint or (ratio 0) strict alternation."""
        self._maybe_swap()
        if self._fill[self.sample_ix] < self.min_fill:
            return True
        ratio = self.an.rollout_ratio
        if ratio > 0:
            return self.frames < (self.lstep - self.lstep0) * ratio
        return not self._last_was_rollout

    # -- the two dispatches ------------------------------------------------

    def dispatch_rollout(self):
        """One fused rollout dispatch into the write ring: K_roll ticks
        of the whole fleet, transitions scattered in-graph.  Returns
        the dispatch's RolloutStats (host copies of the per-tick env
        stats — the control-plane D2H; experience never crosses)."""
        jax = self._jax
        ring = self.rings[self.write_ix]
        prov = self._make_prov(self.lstep)
        t0 = time.perf_counter()
        args = (self.state.params, self.carry, ring.state, self.base_key,
                self.tick0, self.eps_dev, prov)
        if self.audit is not None:
            self.carry, ring.state, stats = self.audit.run(self.rollout,
                                                           *args)
        else:
            self.carry, ring.state, stats = self.rollout(*args)
        self.tick0 = self.tick0 + self.K_roll
        stats = jax.device_get(stats)
        dt = time.perf_counter() - t0
        self.timer.add("rollout", dt)
        self._roll_s += dt
        fed = int(stats.fed)
        frames = self.K_roll * self.fleet_envs
        self.frames += frames
        self._roll_frames += frames
        self.env_steps += frames
        self.perf.note_frames(frames)
        self.clock.add_actor_steps(frames)
        self._fill[self.write_ix] = min(self._fill[self.write_ix] + fed,
                                        ring.capacity)
        self._fresh += fed
        # surface the scatter in the ingest's host accounting so the
        # fleet STATUS replay_size/fill and checkpoint extras see the
        # zero-copy rows too (queue drains count themselves)
        if hasattr(self.memory, "note_scatter"):
            self.memory.note_scatter(fed)
        self._last_was_rollout = True
        # episode + stat accounting shared with the device actor loop
        from pytorch_distributed_tpu.agents.actor import (
            fold_rollout_episode_stats,
        )

        self._acc["total_nframes"] += frames
        fold_rollout_episode_stats(stats.step_reward, stats.step_terminal,
                                   self.episode_reward, self.episode_steps,
                                   self._acc)
        if self.env_steps >= self._next_flush:
            self._next_flush += self.ap.actor_freq
            if any(self._acc.values()):
                self.actor_stats.add(**self._acc)
                self._acc = dict.fromkeys(self._acc, 0.0)
        return stats

    def dispatch_learn(self):
        """One fused learner dispatch (K_learn scanned updates) sampling
        the stable ring; PER priorities write back in-graph."""
        jax = self._jax
        ring = self.rings[self.sample_ix]
        if not self._key_buf:
            K = self.K_learn
            keys = jax.random.split(self._device_key, 64 * K + 1)
            self._device_key = keys[0]
            rest = self._place(keys[1:])  # one bulk placement / 64
            self._key_buf = (list(rest.reshape(64, K, *rest.shape[1:]))
                             if K > 1 else list(rest))
            if self._prog.takes_beta:
                self._beta = self._place(
                    np.float32(self.rings[0].beta(self.lstep)))
        key = self._key_buf.pop()
        t0 = time.perf_counter()
        prog = self._prog
        args = (self.state, ring.state, key,
                *((self._beta,) if prog.takes_beta else ()))
        out = dict(zip(prog.returns,
                       self.audit.run(prog.fused, *args)
                       if self.audit is not None else prog.fused(*args)))
        self.state, m = out["state"], out["metrics"]
        if "ring" in out:
            ring.state = out["ring"]
        if self._block:
            jax.block_until_ready(self.state.params)
        dt = time.perf_counter() - t0
        self.timer.add("learn", dt)
        self._learn_s += dt
        self.lstep += self.K_learn
        self.clock.set_learner_step(self.lstep)
        self.perf.note_updates(self.K_learn)
        self._last_was_rollout = False
        self._last_metrics = m
        return m

    # -- the production loop -----------------------------------------------

    def run(self) -> None:
        jax = self._jax
        ap = self.ap
        clock = self.clock
        deadline = (time.monotonic() + ap.max_seconds) \
            if ap.max_seconds > 0 else float("inf")
        self._publish()
        if self.perf.enabled:
            self.writer.scalars(self.perf.drain(step=self.lstep),
                                step=self.lstep)
        t_cadence = time.monotonic()
        last_stats_lstep = self.lstep
        setup_reported = False
        while self.lstep < ap.steps and not clock.stop.is_set() \
                and time.monotonic() < deadline:
            clock.bump_progress("learner")
            if self.an.drain_ingest and hasattr(self.memory, "drain"):
                # hybrid topologies: remote DCN actors' chunks land in
                # ring 0 between dispatches (zero rows on the pure
                # co-located path — the fleet never touches the queue)
                with self.timer.phase("drain"):
                    fed = self.memory.drain()
                if fed:
                    self._fill[0] = min(self._fill[0] + fed,
                                        self.rings[0].capacity)
            prev = self.lstep
            if self.want_rollout():
                self.dispatch_rollout()
            else:
                self.dispatch_learn()
            crossed = lambda freq: (freq and
                                    self.lstep // freq != prev // freq)
            if crossed(ap.param_publish_freq):
                with self.timer.phase("publish"):
                    self._publish()
            if crossed(ap.checkpoint_freq):
                self._save_epoch()
            if crossed(ap.learner_freq):
                now = time.monotonic()
                vals = {}
                if self._last_metrics is not None:
                    vals = {k: float(v) for k, v in jax.device_get(
                        self._last_metrics).items()}
                self.learner_stats.add(
                    counter=1,
                    critic_loss=vals.get("learner/critic_loss", 0.0),
                    actor_loss=vals.get("learner/actor_loss", 0.0),
                    q_mean=vals.get("learner/q_mean", 0.0),
                    grad_norm=vals.get("learner/grad_norm", 0.0),
                    exchange_rounds=vals.get("learner/exchange_rounds",
                                             0.0),
                    steps_per_sec=(self.lstep - last_stats_lstep)
                    / max(now - t_cadence, 1e-9),
                )
                busy = self._roll_s + self._learn_s
                duty = self._roll_s / busy if busy > 0 else 0.0
                window = max(now - t_cadence, 1e-9)
                rows = {
                    "anakin/duty_cycle": duty,
                    "anakin/rollout_frames_per_s":
                        self._roll_frames / window,
                    "anakin/replay_fill": self.replay_fill(),
                }
                self.writer.scalars(rows, step=self.lstep)
                if self.perf.enabled:
                    for tag, v in rows.items():
                        self.perf.set_gauge(tag, v)
                    self.writer.scalars(self.perf.drain(step=self.lstep),
                                        step=self.lstep)
                self.writer.scalars(self.timer.drain(), step=self.lstep)
                if not setup_reported:
                    # the learner's metrics were fetched above, so both
                    # programs have run: set-up as the compile record saw it
                    setup_reported = True
                    report_setup(self.writer, self.lstep)
                self._roll_s = self._learn_s = 0.0
                self._roll_frames = 0
                t_cadence = now
                last_stats_lstep = self.lstep
        # final publication + epoch (also the SIGTERM preemption path:
        # runtime trips clock.stop, the loop drains out, state commits)
        self._publish()
        self._save_epoch()
        if any(self._acc.values()):
            self.actor_stats.add(**self._acc)
        if self.perf.enabled:
            self.writer.scalars(self.perf.drain(step=self.lstep),
                                step=self.lstep)
        self.writer.close()


def run_anakin_learner(opt: Options, spec, process_ind: int, memory: Any,
                       param_store, clock, stats,
                       actor_stats=None) -> None:
    """Learner-process entry for the co-located Anakin topology — the
    ``run_learner`` drop-in the runtime dispatches to when
    ``factory.anakin_active(opt)`` (no actor workers spawn; this loop
    IS the actor fleet and the learner)."""
    driver = AnakinDriver(opt, spec, memory, param_store, clock, stats,
                          actor_stats=actor_stats,
                          process_ind=process_ind)
    driver.run()
