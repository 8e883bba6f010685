"""Evaluator process: periodic greedy evaluation + checkpointing.

Re-design of reference core/single_processes/evaluators.py (shared by both
agent families, reference utils/factory.py:28-29): every
``evaluator_freq`` seconds pull the freshest published weights, run
``evaluator_nepisodes`` greedy episodes in ``env.eval()`` mode, hand the
stats to the logger through the EvaluatorStats flag handshake (reference
:90-95), and write the params-only checkpoint — the reference's only
checkpoint writer (reference :97-100).

CAPTURE is decoupled from EVALUATION (no reference equivalent; the
reference's single loop is also its cadence).  A background thread
snapshots (weights, learner_step, wall) on the ``evaluator_freq`` cadence
— a cheap shared-memory copy that holds its schedule even when this
process is starved of CPU (``evaluator_nice`` on a 1-core host stretched
the old eval-inline cadence from ~60 s to ~10 min and made a north-star
run's +18 crossing timestamp a sampling artifact) —
while the expensive greedy episodes drain the snapshot backlog in order
and publish each result against its CAPTURE step and wall time.  Under
sustained starvation the backlog drops its oldest pending snapshots
(bounded lag), but every published point still carries the step/time the
policy actually existed, so learning-curve crossings are exact regardless
of how slowly the episodes themselves got scheduled.
"""

from __future__ import annotations

import time
from typing import Any, Tuple

import numpy as np

from pytorch_distributed_tpu.config import Options
from pytorch_distributed_tpu.factory import (
    EnvSpec, build_env, build_model, init_params,
)
from pytorch_distributed_tpu.agents.clocks import EvaluatorStats, GlobalClock
from pytorch_distributed_tpu.agents.param_store import (
    ParamStore, make_flattener,
)
from pytorch_distributed_tpu.utils import checkpoint as ckpt
from pytorch_distributed_tpu.utils.helpers import unravel_on_cpu
from pytorch_distributed_tpu.utils.rngs import process_seed


def greedy_episodes(opt: Options, spec: EnvSpec, model, params, env,
                    nepisodes: int) -> Tuple[float, float, int]:
    """Run n greedy episodes; returns (avg_steps, avg_reward, solved).
    Greedy = eps 0 for DQN (reference evaluators.py:56-86), noiseless policy
    forward for DDPG, zero-carry recurrent greedy for R2D2."""
    from pytorch_distributed_tpu.utils.helpers import pin_to_cpu

    # greedy eval is host-side inference: pin params (and any carry) to the
    # CPU device so batch-1 forwards never round-trip the learner's chip
    params = pin_to_cpu(params)
    on_reset = lambda: None  # recurrent policies re-bind this per episode
    if opt.agent_type == "dqn":
        from pytorch_distributed_tpu.models.policies import build_greedy_act

        act = build_greedy_act(model.apply)

        def pick(obs):
            a, _ = act(params, obs[None])
            return int(a[0])
    elif opt.agent_type == "r2d2":
        from pytorch_distributed_tpu.models.policies import (
            build_recurrent_greedy_act,
        )

        ract = build_recurrent_greedy_act(model.apply)
        carry_box = [pin_to_cpu(model.zero_carry(1))]

        def pick(obs):
            a, carry_box[0] = ract(params, obs[None], carry_box[0])
            return int(a[0])

        def _reset_carry():
            carry_box[0] = pin_to_cpu(model.zero_carry(1))
        on_reset = _reset_carry
    else:
        from pytorch_distributed_tpu.models.policies import build_ddpg_act

        dact = build_ddpg_act(
            lambda p, o: model.apply(p, o, method=model.forward_actor))

        def pick(obs):
            return np.asarray(dact(params, obs[None]))[0]

    total_steps, total_reward, solved = 0, 0.0, 0
    for _ in range(nepisodes):
        on_reset()
        obs = env.reset()
        env.render()  # no-op unless a FrameDumper is attached
        ep_reward, ep_steps, terminal, info = 0.0, 0, False, {}
        while not terminal:
            obs, r, terminal, info = env.step(pick(obs))
            env.render()
            ep_reward += float(r)
            ep_steps += 1
        total_steps += ep_steps
        total_reward += ep_reward
        solved += int(bool(info.get("solved", ep_reward > 0)))
    return total_steps / nepisodes, total_reward / nepisodes, solved


def run_evaluator(opt: Options, spec: EnvSpec, process_ind: int, memory: Any,
                  param_store: ParamStore, clock: GlobalClock,
                  stats: EvaluatorStats) -> None:
    ap = opt.agent_params
    # seed slot past the whole actor fleet (actors hold slots
    # 0 .. num_actors*num_envs_per_actor - 1)
    fleet = opt.num_actors * max(1, opt.env_params.num_envs_per_actor)
    env = build_env(opt, process_ind=fleet + 1)
    env.eval()  # standard episode boundaries (reference evaluators.py:19)
    if opt.env_params.render:
        from pytorch_distributed_tpu.utils.render import attach_frame_dumper

        attach_frame_dumper(env, opt.log_dir, "evaluator")
    model = build_model(opt, spec)
    params0 = init_params(opt, spec, model, seed=process_seed(
        opt.seed, "evaluator"))
    _, unravel = make_flattener(params0)

    # best-so-far lives on the shared clock, not a process-local: the
    # learner binds it into every checkpoint epoch and restores it before
    # its first publication (agents/learner.py), so a resumed run's dips
    # can never overwrite <refs>_best.msgpack with a worse policy than
    # the pre-crash best (the reference has no best tier at all)
    if clock.best_eval_reward.value > float("-inf"):
        print(f"[evaluator] best-so-far restored: "
              f"{clock.best_eval_reward.value:g}")

    # ---- capture thread: cadence-true weight snapshots -------------------
    # (flat, learner_step, wall) tuples, oldest first.  MAX_BACKLOG bounds
    # both memory and staleness: under sustained CPU starvation the oldest
    # pending snapshots drop, so evaluated points thin to what the host
    # affords while each keeps its true capture attribution.
    import threading
    from collections import deque

    MAX_BACKLOG = 8
    snapshots: deque = deque()
    snap_lock = threading.Lock()

    def capture_loop() -> None:
        version = 0
        flat = None
        last_cap = float("-inf")  # capture immediately once weights exist
        while not clock.done(ap.steps):
            time.sleep(0.25)
            if time.monotonic() - last_cap < ap.evaluator_freq:
                continue
            got = param_store.fetch(version)
            if got is not None:
                flat, version = got
            if flat is None:
                continue  # learner hasn't published yet
            last_cap = time.monotonic()
            with snap_lock:
                if len(snapshots) >= MAX_BACKLOG:
                    snapshots.popleft()
                # re-capturing an unchanged flat at a new step is still a
                # new curve point (the policy existed unchanged there)
                snapshots.append((flat, clock.learner_step.value,
                                  time.time()))

    cap_thread = threading.Thread(target=capture_loop, name="eval-capture",
                                  daemon=True)
    cap_thread.start()

    def evaluate(flat: np.ndarray, at_step: int, at_wall: float) -> None:
        # host-side inference: unravel straight onto the CPU device
        # (actors do the same; see utils/helpers.py pin_to_cpu)
        params = unravel_on_cpu(unravel, flat)
        avg_steps, avg_reward, solved = greedy_episodes(
            opt, spec, model, params, env, ap.evaluator_nepisodes)
        # the logger's handshake slot holds ONE result; when a drained
        # backlog produces evals faster than its 0.2 s poll, wait for the
        # slot instead of overwriting an unconsumed point
        waited = time.monotonic() + 10.0
        while stats.flag.value and time.monotonic() < waited \
                and not clock.stop.is_set():
            time.sleep(0.05)
        stats.publish(
            at_step,
            wall=at_wall,
            avg_steps=avg_steps,
            avg_reward=avg_reward,
            nepisodes=float(ap.evaluator_nepisodes),
            nepisodes_solved=float(solved),
        )
        # the params-only checkpoint (reference evaluators.py:97-100);
        # snapshots evaluate oldest-first, so the last write is newest
        ckpt.save_params(ckpt.params_path(opt.model_name), params)
        # best-so-far tier (no reference equivalent): value curves dip —
        # DQN evals can transiently collapse right after a peak — and the
        # latest-params tier alone would let a run that ends mid-dip
        # overwrite its own best policy.  <refs>_best.msgpack always
        # holds the weights of the highest eval so far — ACROSS resumes,
        # via the clock-shared score the checkpoint epochs persist.
        with clock.best_eval_reward.get_lock():
            is_best = avg_reward > clock.best_eval_reward.value
            if is_best:
                clock.best_eval_reward.value = avg_reward
        if is_best:
            # sidecar BEFORE the weights: a crash between the two writes
            # then leaves the score ahead of the file — a conservative
            # threshold that can only delay the next best-write, never
            # let a worse policy overwrite a better one (the reverse
            # order would; checkpoint.py save_best_score docstring)
            ckpt.save_best_score(opt.model_name, avg_reward, step=at_step)
            ckpt.save_params(
                ckpt.params_path(opt.model_name + "_best"), params)

    def pop_snapshot():
        with snap_lock:
            return snapshots.popleft() if snapshots else None

    # hang-watchdog liveness mark (utils/supervision.ProgressBoard):
    # bumped on every poll and after every eval, so a stuck episode —
    # not a merely starved evaluator — is what goes stale
    bump = getattr(clock, "bump_progress", lambda label: None)
    try:
        while not clock.done(ap.steps):
            bump("evaluator-0")
            snap = pop_snapshot()
            if snap is None:
                time.sleep(0.1)
                continue
            evaluate(*snap)
            bump("evaluator-0")
        # final eval of the FINISHED weights (short runs may never have hit
        # the cadence; the run's acceptance signal must still be written):
        # always fetch fresh — a pending backlog snapshot can be up to
        # evaluator_freq stale, and the final <refs>.msgpack is what
        # mode-2/resume loads.  Backlog only as a fallback when the fetch
        # has nothing (learner died before its final publication).
        cap_thread.join(timeout=2.0)
        got = param_store.fetch(0)
        if got is not None:
            snap = (got[0], clock.learner_step.value, time.time())
        else:
            with snap_lock:
                snap = snapshots.pop() if snapshots else None
        if snap is not None:
            evaluate(*snap)
    finally:
        stats.done.value = 1
