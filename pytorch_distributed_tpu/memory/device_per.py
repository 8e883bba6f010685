"""Prioritized replay resident in HBM, fused into the learner step.

The TPU-native completion of the reference's PER TODO beyond the host
sum-tree (memory/prioritized.py): the host tree exists because CPUs need
O(log N) sampling — a TPU doesn't.  Proportional sampling over a 50k-row
ring is a cumulative sum + inverse-CDF search (``cumsum`` +
``searchsorted``), microseconds of vectorized work that XLA fuses INTO the
training program, along with the importance weights and the |TD| priority
write-back.  One XLA program per learner step does: sample → forward →
backward → Adam → target update → priority scatter — the learner hot loop
never touches the host.

Priorities are stored pre-exponentiated (p_i = (|td|+eps)^alpha) so the
sampling pass needs no pow; new rows enter at the running max priority so
everything is replayed at least once (Ape-X standard).  Importance weights
are normalised by the max weight over valid rows (min-probability row),
annealed by beta supplied per call.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pytorch_distributed_tpu.memory.device_replay import (
    DeviceReplay, RowCodec, gather_rows, group_step_on, jit_feed,
    ring_write, ring_write_masked, round_capacity, with_exchange_rounds,
)
from pytorch_distributed_tpu.utils.experience import (
    REPLAY_FIELDS, Batch, Transition,
)
from pytorch_distributed_tpu.utils.profiling import (
    PHASE_DRAW, PHASE_FEED, PHASE_GATHER, PHASE_WRITEBACK,
)

# single-owner declaration (apexlint): the masked PER scatter may only
# be composed into programs by the replay planes themselves and the
# fused rollout that receives it as ``ring_write_fn``
# (models/policies.build_fused_rollout, wired by agents/anakin.py)
__apex_fn_owners__ = {
    "per_write_masked": ("memory.", "models.policies", "agents.anakin"),
}


class PerReplayState(NamedTuple):
    state0: jax.Array        # stored rows (device_replay.py RowCodec)
    action: jax.Array
    reward: jax.Array
    gamma_n: jax.Array
    state1: jax.Array
    terminal1: jax.Array
    prov: jax.Array          # (N, 4) int32 provenance columns; -1 = unknown
    priority: jax.Array      # (N,) f32, pre-exponentiated p^alpha; 0 = empty
    max_priority: jax.Array  # () f32, running max of p^alpha
    pos: jax.Array           # int32 write cursor
    fill: jax.Array          # int32 valid rows
    codec: RowCodec          # static: how state0/state1 are stored


def per_feed(state: PerReplayState, chunk: Transition,
             capacity: int) -> PerReplayState:
    """Ingest a chunk at the cursor (shared ring write, device_replay.py
    ring_write); new rows take the running max priority."""
    new, idx = ring_write(state, chunk, capacity)
    with jax.named_scope(PHASE_FEED):
        return new._replace(
            priority=new.priority.at[idx].set(new.max_priority))


def per_write_masked(state: PerReplayState, chunk: Transition, valid,
                     capacity: int):
    """Masked-scatter twin of ``per_feed`` for in-graph ingest
    (device_replay.ring_write_masked semantics): only the ``valid``
    rows take slots, and every written slot enters at the RUNNING MAX
    priority — the same everything-replayed-at-least-once contract the
    queue ingest path applies, so the co-located Anakin scatter and
    the split-process drain produce bit-identical PER rings.  Returns
    ``(state', n_written)``."""
    new, total = ring_write_masked(state, chunk, valid, capacity)
    with jax.named_scope(PHASE_FEED):
        # same drop-indexing as the field scatter: invalid rows point at
        # ``capacity`` (out of bounds) and are dropped branch-free
        offs = jnp.cumsum(valid.astype(jnp.int32)) - 1
        idx = jnp.where(valid, (state.pos + offs) % capacity, capacity)
        return new._replace(
            priority=new.priority.at[idx].set(new.max_priority,
                                              mode="drop")), total


def per_draw(state: PerReplayState, key: jax.Array, batch_size: int,
             beta: jax.Array, sample_fn=None):
    """Proportional draw + IS weights, all on device: ``(idx, weights)``,
    replicated on a mesh (the draw is over the whole ring).

    ``sample_fn(priority, key, batch_size) -> (idx, probs)`` overrides the
    index draw — the hook the Pallas hierarchical sampler
    (ops/pallas_sampling.py) plugs into on unsharded TPU rings; None keeps
    the flat cumsum+searchsorted XLA scheme."""
    with jax.named_scope(PHASE_DRAW):
        p = state.priority  # empty rows hold 0 and can never be drawn
        if sample_fn is not None:
            idx, probs = sample_fn(p, key, batch_size)
            total = jnp.sum(p)
        else:
            cdf = jnp.cumsum(p)
            total = cdf[-1]  # one O(N) pass serves u-scaling and probs
            u = jax.random.uniform(key, (batch_size,)) * total
            idx = jnp.clip(jnp.searchsorted(cdf, u, side="right"),
                           0, state.priority.shape[0] - 1
                           ).astype(jnp.int32)
            probs = p[idx] / jnp.maximum(total, 1e-12)
        fill = jnp.maximum(state.fill.astype(jnp.float32), 1.0)
        weights = (fill * jnp.maximum(probs, 1e-12)) ** (-beta)
        # max weight = weight of the min-probability VALID row
        min_p = (jnp.min(jnp.where(p > 0, p, jnp.inf))
                 / jnp.maximum(total, 1e-12))
        max_w = (fill * jnp.maximum(min_p, 1e-12)) ** (-beta)
        weights = (weights / jnp.maximum(max_w, 1e-12)).astype(jnp.float32)
    return idx, weights


def per_sample(state: PerReplayState, key: jax.Array, batch_size: int,
               beta: jax.Array, sample_fn=None) -> Batch:
    """The rows of a ``per_draw`` as a ``Batch``."""
    return gather_rows(state, *per_draw(state, key, batch_size, beta,
                                        sample_fn))


PRIORITY_XRAY_LOG10_LO = -6.0   # log10 bucket floor (p^alpha units)
PRIORITY_XRAY_LOG10_HI = 3.0    # log10 bucket ceiling


def priority_xray_device(state: PerReplayState, bins: int = 16):
    """In-jit priority X-ray over the HBM PER leaves (ISSUE 8): a
    log10-bucketed histogram of the non-empty leaves plus the
    effective sample size ``(sum p)^2 / sum p^2`` — the distribution
    shape the AnomalyDetector needs instead of a bare mass ratio, at
    the cost of ONE small D2H (bins + 3 scalars) per stats cadence.
    Bucket edges are the fixed [10^-6, 10^3) decade grid shared with
    the host X-ray (utils/health.priority_xray), so ``fleet_top``
    renders either identically.  Jit with ``static_argnames='bins'``.

    Returns ``(counts[bins] int32, ess, rows, mass)``."""
    p = state.priority
    valid = p > 0
    rows = jnp.sum(valid.astype(jnp.int32))
    s1 = jnp.sum(jnp.where(valid, p, 0.0))
    s2 = jnp.sum(jnp.where(valid, p * p, 0.0))
    ess = jnp.where(s2 > 0, s1 * s1 / jnp.maximum(s2, 1e-30), 0.0)
    logp = jnp.log10(jnp.maximum(p, 10.0 ** PRIORITY_XRAY_LOG10_LO))
    t = (logp - PRIORITY_XRAY_LOG10_LO) / (
        PRIORITY_XRAY_LOG10_HI - PRIORITY_XRAY_LOG10_LO)
    b = jnp.clip((t * bins).astype(jnp.int32), 0, bins - 1)
    counts = jnp.zeros((bins,), jnp.int32).at[
        jnp.where(valid, b, bins)].add(1, mode="drop")
    return counts, ess, rows, s1


def per_update_priorities(state: PerReplayState, idx: jax.Array,
                          td_abs: jax.Array, alpha: float,
                          epsilon: float = 1e-6) -> PerReplayState:
    """|TD| write-back (pre-exponentiated) + running-max maintenance."""
    pr = (jnp.abs(td_abs) + epsilon) ** alpha
    return state._replace(
        priority=state.priority.at[idx].set(pr.astype(jnp.float32)),
        max_priority=jnp.maximum(state.max_priority, jnp.max(pr)),
    )


# one jitted write-back program shared by every caller of the grouped
# apply below (alpha is static: one value per run, one compile)
_writeback_jit = jax.jit(per_update_priorities,
                         static_argnames=("alpha", "epsilon"))


def per_apply_writeback_groups(state: PerReplayState, groups,
                               alpha: float) -> PerReplayState:
    """Apply an ORDERED list of ``(idx, td_abs)`` write-back groups
    sequentially — the ISSUE-15 merged-priority application.  The
    replica plane's round reply carries every surviving contributor's
    |TD| write-back (ascending replica order, then out-of-round
    arrivals), and every replica applies the SAME groups in the SAME
    order through this function, so the N local rings remain one
    logical priority plane bit-for-bit.

    Sequential jitted scatters on purpose, not one fused scatter:
    XLA's duplicate-index ``.set`` order within a single scatter is
    unspecified, and cross-group index collisions must resolve exactly
    last-group-wins for the solo-parity oracle to hold."""
    for idx, td in groups:
        state = _writeback_jit(state,
                               jnp.asarray(idx, jnp.int32),
                               jnp.asarray(td, jnp.float32),
                               alpha=alpha)
    return state


class DevicePerReplay(DeviceReplay):
    """Stateful wrapper owning the HBM PER ring (learner process only):
    the uniform ring (device_replay.py DeviceReplay) extended with the
    priority vector and the running max.

    ``build_fused_step`` wraps a ``(TrainState, Batch) -> (TrainState,
    metrics, td_abs)`` train step into ``(TrainState, PerReplayState, key,
    beta) -> (TrainState, PerReplayState, metrics)`` — sampling and priority
    write-back fused in.
    """

    def __init__(self, capacity: int, state_shape: Tuple[int, ...],
                 action_shape: Tuple[int, ...] = (),
                 state_dtype=np.uint8, action_dtype=np.int32,
                 priority_exponent: float = 0.6,
                 importance_weight: float = 0.4,
                 importance_anneal_steps: int = 500000,
                 mesh: Optional[jax.sharding.Mesh] = None):
        self.alpha = priority_exponent
        self.beta0 = importance_weight
        self.beta_steps = importance_anneal_steps
        super().__init__(round_capacity(capacity, mesh, label="device PER"),
                         state_shape, action_shape, state_dtype,
                         action_dtype, mesh=mesh)

        # Pallas hierarchical sampler on unsharded TPU rings; the flat XLA
        # scheme everywhere else (dp-sharded rings address rows through
        # collectives the kernel can't, and CPU interpret mode is slower
        # than XLA's cumsum).  ``sampler`` names the choice for the
        # learner's start-up line.  NOTE the gate is "a mesh exists", not
        # "dp > 1": the learner builds a mesh whenever more than one chip
        # is visible, so on a multi-chip host this is always "xla".
        self._draw_fn = None
        self.sampler = "xla"
        if (self._row_sharding is None
                and jax.devices()[0].platform == "tpu"):
            from pytorch_distributed_tpu.ops.pallas_sampling import (
                hierarchical_sample,
            )

            self._draw_fn = hierarchical_sample
            self.sampler = "pallas"

        self._feed_fn = jit_feed(
            functools.partial(per_feed, capacity=self.capacity))
        self._sample_fn = jax.jit(
            functools.partial(per_sample, sample_fn=self._draw_fn),
            static_argnames="batch_size")

    def _init_state(self) -> PerReplayState:
        base = super()._init_state()
        return PerReplayState(
            *base[:6],
            prov=base.prov,
            priority=self._alloc((self.capacity,), jnp.float32),
            max_priority=self._alloc((), jnp.float32, sharded=False) + 1.0,
            pos=base.pos,
            fill=base.fill,
            codec=base.codec,
        )

    def beta(self, step: int) -> float:
        frac = min(1.0, step / max(1, self.beta_steps))
        return self.beta0 + (1.0 - self.beta0) * frac

    def build_fused_step(self, train_step, batch_size: int,
                         donate: bool = True, steps_per_call: int = 1,
                         megabatch: int = 1, megabatch_step=None):
        """Fused sample -> train -> priority write-back; ``steps_per_call``
        sub-steps scan inside one XLA program (keys then shaped (K, 2)),
        amortising dispatch latency like
        device_replay.build_uniform_fused_step — with the priority state
        chained through the scan so each sub-step samples from the
        previous one's updated priorities.

        ``megabatch`` M > 1 (ISSUE 13, with ``megabatch_step`` from
        factory.build_megabatch_train_step) regroups the K sub-steps
        into K/M groups: one WIDENED PER gather draws all M minibatches
        of a group from the GROUP-ENTRY priorities (consuming the same
        M keys the sequential schedule would — within-group priority
        freshness is the documented megabatch trade; groups still chain
        through each other's write-backs), one lane-filling batched
        forward/backward computes the M gradients, and the M |TD|
        write-backs land sequentially in minibatch order so index
        collisions resolve exactly as M sequential steps — skipped
        (guarded) minibatches suppressed per row."""
        alpha = self.alpha
        draw_fn = self._draw_fn

        from pytorch_distributed_tpu.utils.health import (
            SKIPPED_KEY, reduce_scan_metrics, suppress_writeback,
        )

        # Only the priority leaves change inside a dispatch.  They alone
        # are carried through the scans; the row columns are closed over
        # and handed back as they came in, so donation aliases them
        # straight through and no loop ever holds a second ring.
        def leaves(rs: PerReplayState):
            return rs.priority, rs.max_priority

        def with_leaves(rs: PerReplayState, pri) -> PerReplayState:
            return rs._replace(priority=pri[0], max_priority=pri[1])

        def sample(rs: PerReplayState, key, beta):
            """``(Batch, idx)``: the batch, and the draw as it was before
            the batch took it (replicated on a mesh: what
            ``learner/exchange_rounds`` is counted from at no cost)."""
            idx, weight = per_draw(rs, key, batch_size, beta,
                                   sample_fn=draw_fn)
            return gather_rows(rs, idx, weight), idx

        def writeback(rs: PerReplayState, idx, td_abs, skipped):
            """The leaves after one minibatch's |TD| write-back; a
            skipped (non-finite, guarded) minibatch must not scatter its
            zeroed TD over real priorities."""
            new = leaves(per_update_priorities(rs, idx, td_abs, alpha))
            if skipped is None:
                return new
            return suppress_writeback(skipped, new, leaves(rs))

        if megabatch > 1:
            assert megabatch_step is not None, \
                "megabatch > 1 needs the factory's megabatch step"
            assert steps_per_call % megabatch == 0, (
                f"megabatch {megabatch} must divide steps_per_call "
                f"{steps_per_call}")
            groups = steps_per_call // megabatch

            def one_group(ts, rs: PerReplayState, kset, beta):
                # the batching of the M draws moves gathered rows
                # (vmap's own transposes): gather's, where no inner
                # name says draw
                with jax.named_scope(PHASE_GATHER):
                    batches, idx = jax.vmap(
                        lambda k: sample(rs, k, beta))(kset)
                ts, metrics, td_abs, ok = group_step_on(
                    rs, megabatch_step)(ts, batches)

                def land(pri, x):
                    idx, td, ok_i = x
                    # suppress_writeback takes the SKIPPED flag (1.0 =
                    # skipped); ok is the validity mask
                    return writeback(with_leaves(rs, pri), idx, td,
                                     1.0 - ok_i), None

                with jax.named_scope(PHASE_WRITEBACK):
                    pri, _ = jax.lax.scan(land, leaves(rs),
                                          (batches.index, td_abs, ok))
                return ts, pri, with_exchange_rounds(metrics, rs, idx)

            def multi_mega(ts, rs, keys, beta):
                with jax.named_scope(PHASE_DRAW):
                    gkeys = keys.reshape(groups, megabatch,
                                         *keys.shape[1:])

                def body(carry, kset):
                    ts, pri = carry
                    ts, pri, metrics = one_group(
                        ts, with_leaves(rs, pri), kset, beta)
                    return (ts, pri), metrics

                (ts, pri), metrics = jax.lax.scan(
                    body, (ts, leaves(rs)), gkeys)
                return ts, with_leaves(rs, pri), reduce_scan_metrics(metrics)

            return jax.jit(multi_mega,
                           donate_argnums=(0, 1) if donate else ())

        def substep(ts, rs: PerReplayState, key, beta):
            batch, idx = sample(rs, key, beta)
            ts, metrics, td_abs = train_step(ts, batch)
            with jax.named_scope(PHASE_WRITEBACK):
                pri = writeback(rs, batch.index, td_abs,
                                metrics.get(SKIPPED_KEY)
                                if isinstance(metrics, dict) else None)
            return ts, pri, with_exchange_rounds(metrics, rs, idx)

        if steps_per_call <= 1:
            def one(ts, rs, key, beta):
                ts, pri, metrics = substep(ts, rs, key, beta)
                return ts, with_leaves(rs, pri), metrics

            return jax.jit(one, donate_argnums=(0, 1) if donate else ())

        def multi(ts, rs, keys, beta):
            def body(carry, key):
                ts, pri = carry
                ts, pri, metrics = substep(ts, with_leaves(rs, pri), key,
                                           beta)
                return (ts, pri), metrics

            (ts, pri), metrics = jax.lax.scan(body, (ts, leaves(rs)), keys)
            return ts, with_leaves(rs, pri), reduce_scan_metrics(metrics)

        return jax.jit(multi, donate_argnums=(0, 1) if donate else ())

    # -- checkpoint: uniform-ring snapshot + the priority leaves -----------

    def snapshot(self) -> dict:
        st = jax.device_get(self.state)
        out = self._aged_columns(st, REPLAY_FIELDS + ("prov", "priority"))
        out["leaf_priority"] = out.pop("priority")
        # stored p^alpha on device; snapshot in the shared UNexponentiated
        # unit so host<->device PER resumes agree
        mx = float(np.asarray(st.max_priority))
        out["max_priority_base"] = np.float64(
            mx ** (1.0 / self.alpha) if self.alpha else mx)
        return out

    def restore(self, data: dict) -> int:
        n = super().restore(data)  # rows land at max priority...
        if n and "leaf_priority" in data:
            # ...then the saved (pre-exponentiated) leaves overwrite the
            # fresh slots [pos-n, pos) so sampling resumes where it left off
            st = self.state
            pos = int(jax.device_get(st.pos))
            idx = jnp.asarray(
                (np.arange(pos - n, pos) % self.capacity).astype(np.int32))
            pr = jnp.asarray(
                np.asarray(data["leaf_priority"], np.float32)[-n:])
            base = float(data.get("max_priority_base", 1.0))
            self.state = st._replace(
                priority=st.priority.at[idx].set(pr),
                max_priority=jnp.float32(
                    base ** self.alpha if self.alpha else base))
        return n

    def sample(self, batch_size: int, key: jax.Array,
               beta: float = 1.0) -> Batch:
        """As ``DeviceReplay.sample``, drawn in proportion to priority:
        on a mesh ``batch_size`` must be a multiple of the data axis."""
        return self._sample_fn(self.state, key, batch_size=batch_size,
                               beta=jnp.asarray(beta))
