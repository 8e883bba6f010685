"""Logger process: metrics aggregation.

Re-design of reference core/single_processes/dqn_logger.py /
ddpg_logger.py (near-identical files; unified here).  Same push model: the
workers accumulate into shared counter structs and this process drains on a
cadence — evaluator scalars whenever the flag handshake is raised (reference
dqn_logger.py:23-33), actor/learner accumulators every ``logger_freq``
seconds (reference :34-55) — writing every scalar against the global
learner step as x-axis, with the reference's exact tag names
(utils/metrics.py docstring).
"""

from __future__ import annotations

import time

from pytorch_distributed_tpu.config import Options
from pytorch_distributed_tpu.agents.clocks import (
    ActorStats, EvaluatorStats, GlobalClock, LearnerStats,
)
from pytorch_distributed_tpu.utils.metrics import MetricsWriter


def run_logger(opt: Options, clock: GlobalClock, actor_stats: ActorStats,
               learner_stats: LearnerStats,
               evaluator_stats: EvaluatorStats) -> None:
    ap = opt.agent_params
    writer = MetricsWriter(opt.log_dir, enable_tensorboard=opt.visualize,
                           role="logger", run_id=opt.refs)
    last_drain = time.monotonic()
    finished_at = None
    closing_at = None
    quiescent = 0
    final_a: dict = {}
    final_le: dict = {}
    try:
        while True:
            finished = clock.done(ap.steps)
            if finished and finished_at is None:
                finished_at = time.monotonic()
            # after the run ends, keep draining until the evaluator's final
            # eval lands (grace-capped) so its scalars are not dropped.
            # Grace sits just under runtime._join_all's 240 s deadline —
            # a batch-1 pixel eval on a starved 1-core host takes minutes,
            # and a 60 s grace silently dropped the config-14 run's final
            # point (round 4) — while leaving headroom for the quiescence
            # drains + final write below before the join terminates us.
            closing = finished and (
                evaluator_stats.done.value
                or time.monotonic() - finished_at > 230.0)
            if closing and closing_at is None:
                closing_at = time.monotonic()
            time.sleep(0.2)

            got = evaluator_stats.consume()
            if got is not None:
                # reference dqn_logger.py:23-33; rows carry the CAPTURE
                # wall time so curve crossings date the policy, not the
                # (possibly starved) eval episodes
                at_step, at_wall, ev = got
                writer.scalars({
                    "evaluator/avg_steps": ev["avg_steps"],
                    "evaluator/avg_reward": ev["avg_reward"],
                    "evaluator/nepisodes": ev["nepisodes"],
                    "evaluator/nepisodes_solved": ev["nepisodes_solved"],
                }, step=at_step, wall=at_wall or None)

            def write_group(a: dict, le: dict) -> None:
                step = clock.learner_step.value
                if a["nepisodes"] > 0:  # reference dqn_logger.py:34-47
                    writer.scalars({
                        "actor/avg_steps": a["total_steps"] / a["nepisodes"],
                        "actor/avg_reward": a["total_reward"] / a["nepisodes"],
                        "actor/nepisodes_solved": a["nepisodes_solved"],
                    }, step=step)
                if a["total_nframes"] > 0:
                    writer.scalar("actor/total_nframes", a["total_nframes"],
                                  step=step)
                if le["counter"] > 0:  # reference dqn_logger.py:48-55
                    writer.scalars({
                        "learner/critic_loss": le["critic_loss"] / le["counter"],
                        "learner/actor_loss": le["actor_loss"] / le["counter"],
                        "learner/q_mean": le["q_mean"] / le["counter"],
                        "learner/grad_norm": le["grad_norm"] / le["counter"],
                        "learner/steps_per_sec":
                            le["steps_per_sec"] / le["counter"],
                        # nonzero only for MoE models (models/moe.py);
                        # rides along like actor_loss does for non-DDPG
                        "learner/moe_aux": le["moe_aux"] / le["counter"],
                        **{f"learner/{k}": le[k] / le["counter"]
                           for k in learner_stats.MOE_FIELDS},
                        # a mesh learner's row exchange only (>= 1 there)
                        **({"learner/exchange_rounds":
                            le["exchange_rounds"] / le["counter"]}
                           if le["exchange_rounds"] else {}),
                    }, step=step)
                writer.flush()

            if closing:
                # shutdown race guard: workers flush their accumulators in
                # their own shutdown paths, which can land AFTER the run
                # end is observed here — keep draining until quiescent
                # (nothing arrived for 2 consecutive drains and a settle
                # window passed), MERGING the late fragments so the final
                # datapoint is one aggregate, not several per-fragment
                # averages at the same step
                a, le = actor_stats.drain(), learner_stats.drain()
                arrived = (got is not None or a["nepisodes"] > 0
                           or a["total_nframes"] > 0 or le["counter"] > 0)
                for k, v in a.items():
                    final_a[k] = final_a.get(k, 0.0) + v
                for k, v in le.items():
                    final_le[k] = final_le.get(k, 0.0) + v
                quiescent = 0 if arrived else quiescent + 1
                if quiescent >= 2 \
                        and time.monotonic() - closing_at >= 2.0:
                    write_group(final_a, final_le)
                    break
            elif time.monotonic() - last_drain >= ap.logger_freq:
                last_drain = time.monotonic()
                write_group(actor_stats.drain(), learner_stats.drain())
    finally:
        writer.close()
