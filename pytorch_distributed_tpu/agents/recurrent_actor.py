"""Recurrent (R2D2) rollout workers.

Same Ape-X topology as agents/actor.py — vectorized envs, per-slot epsilon
schedule, versioned weight pulls, stat cadences — but the policy carries an
LSTM state across steps and experience leaves as overlapping episode
SEGMENTS (memory/sequence_replay.py SegmentBuilder), not n-step
transitions.  The carry recorded with each step is the state BEFORE acting,
which is what the stored-state burn-in strategy replays from
(ops/sequence_losses.py docstring).

The hot loop rides the shared scheduler (agents/actor._drive_actor_loop),
so the recurrent family gets the same inline/pipelined split as the flat
ones (ISSUE 4).  Pipelining a recurrent policy adds one wrinkle: the
carry.  It stays DEVICE-RESIDENT across ticks inside the engine — no
host->device upload per tick — and episode resets ride into the NEXT
tick's fused act as a per-row boolean mask
(models/policies.build_recurrent_packed_act), which zeroes exactly the
rows the serial loop used to zero host-side between ticks.  The host
keeps a copy of each tick's post-act carry for segment storage; its
terminal rows are zeroed by ``advance`` (as before), so the host copy and
the device carry agree on every episode boundary.  ``actor_backend=
batched`` is NOT served for this family — per-env recurrent state on a
shared server is a different design — and downgrades to ``pipelined``
(factory.resolve_actor_backend).
"""

from __future__ import annotations

from typing import Any

import numpy as np

from pytorch_distributed_tpu.config import Options
from pytorch_distributed_tpu.factory import (
    EnvSpec, resolve_actor_backend, sequence_pack_frames,
)
from pytorch_distributed_tpu.agents.actor import (
    _ActorHarness, _drive_actor_loop,
)
from pytorch_distributed_tpu.agents.clocks import ActorStats, GlobalClock
from pytorch_distributed_tpu.agents.param_store import ParamStore
from pytorch_distributed_tpu.memory.sequence_replay import SegmentBuilder
from pytorch_distributed_tpu.utils.experience import make_prov
from pytorch_distributed_tpu.utils.helpers import pin_to_cpu
from pytorch_distributed_tpu.utils.rngs import process_key


class _RecurrentHarness(_ActorHarness):
    """Actor harness with the n-step assemblers swapped for per-env
    SegmentBuilders."""

    def __init__(self, opt: Options, spec: EnvSpec, process_ind: int,
                 memory: Any, param_store: ParamStore, clock: GlobalClock,
                 stats: ActorStats, backend: str = "pipelined"):
        super().__init__(opt, spec, process_ind, memory, param_store, clock,
                         stats, backend=backend)
        ap = self.ap
        state_dtype = (np.uint8 if opt.memory_params.state_dtype == "uint8"
                       else np.float32)
        self.builders = [
            SegmentBuilder(ap.seq_len, ap.seq_overlap,
                           state_dtype=state_dtype,
                           pack_frames=sequence_pack_frames(opt))
            for _ in range(self.num_envs)]
        # initial-carry rows precomputed host-side once so per-episode
        # resets never allocate on the accelerator
        self._init_carry = tuple(np.asarray(c)
                                 for c in self.model.zero_carry(1))

    # segments replace transitions: override the per-env feed
    def advance(self, actions, next_obs, rewards, terminals, infos,
                carry_before=None, carry_after=None) -> None:
        state_for_segment = getattr(self.model, "state_for_segment", None)
        for j in range(self.num_envs):
            true_next = infos[j].get("final_obs", next_obs[j])
            truncated = bool(infos[j].get("truncated", False))
            # stored state for the segment: the LSTM carry row, unless the
            # model substitutes its own (transformers store a placeholder)
            per_env_carry = (state_for_segment(carry_before, j)
                             if state_for_segment is not None
                             else (carry_before[0][j], carry_before[1][j]))
            for seg in self.builders[j].push(
                    self._obs[j], int(actions[j]), float(rewards[j]),
                    # time-limit truncation ends the segment but must
                    # bootstrap through (not a death) — same distinction
                    # the n-step assembler draws for feed()
                    bool(terminals[j]) and not truncated, true_next,
                    per_env_carry, episode_end=bool(terminals[j]),
                    prov=make_prov(self.process_ind, j,
                                   self._feed_version, self._birth_step)):
                self.memory.feed(seg, None)
            self.episode_steps[j] += 1
            self.episode_reward[j] += float(rewards[j])
            if terminals[j]:
                self._record_episode(j, infos[j])
                # fresh episode: zero the HOST copy's rows (the engine's
                # carry_before for the next tick); the DEVICE carry rows
                # are zeroed by the reset mask inside the next fused act
                for c_row, c_init in zip(carry_after, self._init_carry):
                    c_row[j] = c_init[0]
                self.builders[j].reset()
        self._obs = next_obs
        self._flush_cadence()

    # shutdown: the base _ActorHarness.shutdown is used as-is (its
    # pending-holds loop is a no-op here — segments carry no deferred
    # priorities) — a copied override once missed the QueueFeeder.close
    # fix and hung the config-14 probe's join for 240 s.


class _RecurrentEngine:
    """Fused recurrent act with a device-resident carry.

    ``submit`` advances the device carry (resetting masked rows
    on-device) and returns (action, carry') handles without blocking;
    ``collect`` syncs the action plus a mutable host copy of the
    post-act carry — ``carry_after`` for segment storage — and rotates
    it into ``carry_before`` for the next tick.  ``advance`` zeroes the
    host copy's terminal rows in place, mirroring the device-side mask
    reset, so the two stay equal at every episode boundary."""

    def __init__(self, h: _RecurrentHarness, base_key, eps):
        import jax.numpy as jnp

        from pytorch_distributed_tpu.models.policies import (
            build_recurrent_packed_act,
        )

        self._h = h
        self._act = build_recurrent_packed_act(h.model.apply,
                                               h.model.zero_carry(1))
        self._key = pin_to_cpu(base_key)
        self._eps = pin_to_cpu(jnp.asarray(eps, jnp.float32))
        # distinct leaf buffers, explicitly: zero_carry may alias its
        # leaves (DrqnMlpModel returns (z, z)), and the fused act DONATES
        # the carry — the same buffer donated twice is an XLA error
        self._dev_carry = pin_to_cpu(tuple(
            jnp.array(c, copy=True) for c in h.model.zero_carry(h.num_envs)))
        self._host_carry = tuple(np.asarray(c)
                                 for c in h.model.zero_carry(h.num_envs))

    def submit(self, obs, tick, reset_mask):
        action, carry = self._act(self._h.params, obs, self._dev_carry,
                                  np.ascontiguousarray(reset_mask),
                                  self._key, tick, self._eps)
        self._dev_carry = carry
        action.copy_to_host_async()
        return action, carry

    def collect(self, pending):
        action, carry = pending
        # np.array (copy): zero-copy views of jax buffers are read-only,
        # and advance() writes per-env reset rows in place
        carry_after = tuple(np.array(c) for c in carry)
        extras = dict(carry_before=self._host_carry,
                      carry_after=carry_after)
        self._host_carry = carry_after
        return np.asarray(action).astype(np.int64), extras

    def close(self) -> None:
        pass


def run_r2d2_actor(opt: Options, spec: EnvSpec, process_ind: int,
                   memory: Any, param_store: ParamStore, clock: GlobalClock,
                   stats: ActorStats, inference: Any = None):
    """eps-greedy recurrent rollout worker, batched over the env vector."""
    from pytorch_distributed_tpu.models.policies import apex_epsilons

    backend = resolve_actor_backend(opt, inference)
    h = _RecurrentHarness(opt, spec, process_ind, memory, param_store,
                          clock, stats, backend=backend)
    eps = apex_epsilons(process_ind, opt.num_actors, h.num_envs,
                        h.ap.eps, h.ap.eps_alpha)
    engine = _RecurrentEngine(
        h, process_key(opt.seed, "actor", process_ind), eps)
    return _drive_actor_loop(h, engine, clock,
                             pipelined=(backend != "inline"))
