"""Sequence (R2D2 segment) replay resident in HBM, fused into the learner.

The TPU-native completion of the sequence plane: the host SequenceReplay
(memory/sequence_replay.py) keeps segments in a queue-owned numpy ring and
pays one host->device transfer per sampled batch: every update re-ships
(B, T+C, 84, 84) pixels through the host (what that costs on a directly
attached chip is not measured).  Here the segment arrays live in device HBM as jax
Arrays (optionally dp-sharded over the learner mesh, rows split across
devices like memory/device_replay.py), actors stream FRAME-PACKED segments
through a spawn queue once, and one XLA program per dispatch runs

    proportional sample -> burn-in unroll -> train-window unroll
    -> n-step targets -> Adam -> target update -> |TD| priority scatter

for ``steps_per_call`` scanned sub-steps — the sequence counterpart of
memory/device_per.py build_fused_step, with the same pre-exponentiated
priority scheme (p_i = (|td|+eps)^alpha stored, new rows at the running
max so every segment trains at least once).

Sampling uses the flat cumsum+searchsorted XLA scheme only: segment rings
are small (capacity counts SEGMENTS — the pixel config holds ~1k rows, vs
50k transitions for the flat rings), so the O(N) pass is noise and the
Pallas hierarchical sampler's block padding (ops/pallas_sampling.py,
>=1024-wide superblocks) would exceed the whole ring.

Reference relationship: the reference stores single transitions only
(core/memories/shared_memory.py:59-67); SURVEY.md §5 requires the replay
layout not preclude "contiguous episode segments" — this module is that
layout's TPU-native home.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pytorch_distributed_tpu.memory.device_replay import (
    jit_feed, round_capacity,
)
from pytorch_distributed_tpu.memory.sequence_replay import SegmentBatch
from pytorch_distributed_tpu.utils.profiling import (
    PHASE_DRAW, PHASE_FEED, PHASE_GATHER, PHASE_WRITEBACK,
)


class SegmentChunk(NamedTuple):
    """Host->device ingest payload: a stack of segments (leading chunk
    dim), field-for-field the Segment schema."""

    obs: np.ndarray        # (n, T+C, H, W) packed / (n, T+1, *S) unpacked
    action: np.ndarray     # (n, T) int32
    reward: np.ndarray     # (n, T) float32
    terminal: np.ndarray   # (n, T) float32
    mask: np.ndarray       # (n, T) float32
    c0: np.ndarray         # (n, lstm_dim) float32
    h0: np.ndarray         # (n, lstm_dim) float32


class SeqReplayState(NamedTuple):
    obs: jax.Array
    action: jax.Array
    reward: jax.Array
    terminal: jax.Array
    mask: jax.Array
    c0: jax.Array
    h0: jax.Array
    priority: jax.Array      # (N,) f32 pre-exponentiated p^alpha; 0 = empty
    max_priority: jax.Array  # () f32 running max of p^alpha
    pos: jax.Array           # int32 write cursor
    fill: jax.Array          # int32 valid rows


def seq_feed(state: SeqReplayState, chunk: SegmentChunk,
             capacity: int) -> SeqReplayState:
    """Ring-write a chunk of segments at the cursor; new rows enter at the
    running max priority (Ape-X/R2D2 standard — replayed at least once)."""
    n = chunk.reward.shape[0]
    with jax.named_scope(PHASE_FEED):
        idx = (state.pos + jnp.arange(n, dtype=jnp.int32)) % capacity
        return state._replace(
            obs=state.obs.at[idx].set(chunk.obs),
            action=state.action.at[idx].set(chunk.action),
            reward=state.reward.at[idx].set(chunk.reward),
            terminal=state.terminal.at[idx].set(chunk.terminal),
            mask=state.mask.at[idx].set(chunk.mask),
            c0=state.c0.at[idx].set(chunk.c0),
            h0=state.h0.at[idx].set(chunk.h0),
            priority=state.priority.at[idx].set(state.max_priority),
            pos=(state.pos + n) % capacity,
            fill=jnp.minimum(state.fill + n, capacity),
        )


def seq_sample(state: SeqReplayState, key: jax.Array, batch_size: int,
               beta: jax.Array) -> SegmentBatch:
    """Proportional segment sample + IS weights, all on device — the
    sequence twin of device_per.per_sample (same inverse-CDF scheme, same
    max-weight normalisation over valid rows)."""
    with jax.named_scope(PHASE_DRAW):
        p = state.priority  # empty rows hold 0 and can never be drawn
        cdf = jnp.cumsum(p)
        total = cdf[-1]
        u = jax.random.uniform(key, (batch_size,)) * total
        idx = jnp.clip(jnp.searchsorted(cdf, u, side="right"),
                       0, p.shape[0] - 1).astype(jnp.int32)
        probs = p[idx] / jnp.maximum(total, 1e-12)
        fill = jnp.maximum(state.fill.astype(jnp.float32), 1.0)
        weights = (fill * jnp.maximum(probs, 1e-12)) ** (-beta)
        min_p = (jnp.min(jnp.where(p > 0, p, jnp.inf))
                 / jnp.maximum(total, 1e-12))
        max_w = (fill * jnp.maximum(min_p, 1e-12)) ** (-beta)
        weights = weights / jnp.maximum(max_w, 1e-12)
    with jax.named_scope(PHASE_GATHER):
        return SegmentBatch(
            obs=state.obs[idx],
            action=state.action[idx],
            reward=state.reward[idx],
            terminal=state.terminal[idx],
            mask=state.mask[idx],
            c0=state.c0[idx],
            h0=state.h0[idx],
            weight=weights.astype(jnp.float32),
            index=idx,
        )


def seq_update_priorities(state: SeqReplayState, idx: jax.Array,
                          td_abs: jax.Array, alpha: float,
                          epsilon: float = 1e-6) -> SeqReplayState:
    """Eta-blended per-sequence |TD| write-back (the learner's seq_pr,
    ops/sequence_losses.py _masked_loss_and_priority), pre-exponentiated."""
    pr = (jnp.abs(td_abs) + epsilon) ** alpha
    return state._replace(
        priority=state.priority.at[idx].set(pr.astype(jnp.float32)),
        max_priority=jnp.maximum(state.max_priority, jnp.max(pr)),
    )


class DeviceSequenceReplay:
    """Stateful wrapper owning the HBM segment ring (learner process only).

    ``build_fused_step`` wraps a sequence train step ``(TrainState,
    SegmentBatch) -> (TrainState, metrics, seq_pr)`` (ops/sequence_losses.py
    build_drqn_train_step / build_dtqn_train_step) into ``(TrainState,
    SeqReplayState, keys, beta) -> (TrainState, SeqReplayState, metrics)``
    with sampling and priority write-back fused in — the same contract
    DevicePerReplay.build_fused_step gives the learner, so the learner's
    device-PER hot loop drives this ring unchanged.
    """

    sampler = "xla"  # module docstring says why; start-up line reads it

    def __init__(self, capacity: int, seq_len: int,
                 state_shape: Tuple[int, ...], lstm_dim: int,
                 state_dtype=np.uint8,
                 priority_exponent: float = 0.9,
                 importance_weight: float = 0.6,
                 importance_anneal_steps: int = 500000,
                 pack_frames: int = 0,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 axis: str = "dp"):
        self.capacity = round_capacity(capacity, mesh, axis=axis,
                                       label="device sequence replay")
        self.T = seq_len
        self.lstm_dim = lstm_dim
        self.alpha = priority_exponent
        self.beta0 = importance_weight
        self.beta_steps = importance_anneal_steps
        self.pack_frames = int(pack_frames)
        self.state_dtype = jnp.dtype(state_dtype)
        S = tuple(state_shape)
        if self.pack_frames:
            # frame-packed rows (T+C, H, W): stacks rebuilt on device by the
            # train step (ops/sequence_losses.py unpack_frame_stacks) — the
            # C-fold pixel de-dup holds on the wire, in host RAM, AND here
            # in HBM, where the ring would otherwise be C times larger
            assert S[0] == self.pack_frames, (S, pack_frames)
            self.obs_shape = (seq_len + self.pack_frames, *S[1:])
        else:
            self.obs_shape = (seq_len + 1, *S)

        if mesh is not None:
            P = jax.sharding.PartitionSpec
            self._row_sharding = jax.sharding.NamedSharding(mesh, P(axis))
            self._scalar_sharding = jax.sharding.NamedSharding(mesh, P())
        else:
            self._row_sharding = None
            self._scalar_sharding = None

        self.state = self._init_state()
        self._feed_fn = jit_feed(
            functools.partial(seq_feed, capacity=self.capacity))
        self._sample_fn = jax.jit(seq_sample, static_argnames="batch_size")

    def _alloc(self, shape, dtype, sharded: bool = True):
        arr = jnp.zeros(shape, dtype=dtype)
        if self._row_sharding is not None:
            arr = jax.device_put(
                arr,
                self._row_sharding if sharded else self._scalar_sharding)
        return arr

    def _init_state(self) -> SeqReplayState:
        N, T = self.capacity, self.T
        alloc = self._alloc
        return SeqReplayState(
            obs=alloc((N, *self.obs_shape), self.state_dtype),
            action=alloc((N, T), jnp.int32),
            reward=alloc((N, T), jnp.float32),
            terminal=alloc((N, T), jnp.float32),
            mask=alloc((N, T), jnp.float32),
            c0=alloc((N, self.lstm_dim), jnp.float32),
            h0=alloc((N, self.lstm_dim), jnp.float32),
            priority=alloc((N,), jnp.float32),
            max_priority=alloc((), jnp.float32, sharded=False) + 1.0,
            pos=alloc((), jnp.int32, sharded=False),
            fill=alloc((), jnp.int32, sharded=False),
        )

    @property
    def size(self) -> int:
        return int(jax.device_get(self.state.fill))

    def beta(self, step: int) -> float:
        frac = min(1.0, step / max(1, self.beta_steps))
        return self.beta0 + (1.0 - self.beta0) * frac

    def feed_chunk(self, chunk: SegmentChunk) -> None:
        """One host->device transfer per fixed-size chunk (fixed so the
        jitted feed never retraces)."""
        self.state = self._feed_fn(self.state, chunk)

    def sample(self, batch_size: int, key: jax.Array,
               beta: float = 1.0) -> SegmentBatch:
        return self._sample_fn(self.state, key, batch_size=batch_size,
                               beta=jnp.asarray(beta, jnp.float32))

    def update_priorities(self, idx, td_abs) -> None:
        self.state = seq_update_priorities(self.state, jnp.asarray(idx),
                                           jnp.asarray(td_abs), self.alpha)

    def build_fused_step(self, train_step, batch_size: int,
                         donate: bool = True, steps_per_call: int = 1):
        """Fused sample -> burn-in/train -> priority write-back;
        ``steps_per_call`` sub-steps scan inside one XLA program with the
        priority state chained through, so each sub-step samples from the
        previous one's refreshed priorities — dispatch latency amortised
        K-fold exactly like the transition planes."""
        alpha = self.alpha

        from pytorch_distributed_tpu.utils.health import (
            SKIPPED_KEY, reduce_scan_metrics, suppress_writeback,
        )

        def one(ts, rs: SeqReplayState, key, beta):
            batch = seq_sample(rs, key, batch_size, beta)
            ts, metrics, seq_pr = train_step(ts, batch)
            with jax.named_scope(PHASE_WRITEBACK):
                rs_new = seq_update_priorities(rs, batch.index, seq_pr,
                                               alpha)
                skipped = (metrics.get(SKIPPED_KEY)
                           if isinstance(metrics, dict) else None)
                if skipped is not None:
                    # a guard-skipped substep's zeroed priorities must not
                    # overwrite the ring's real ones (utils/health.py)
                    rs_new = suppress_writeback(skipped, rs_new, rs)
            return ts, rs_new, metrics

        if steps_per_call <= 1:
            return jax.jit(one, donate_argnums=(0, 1) if donate else ())

        def multi(ts, rs, keys, beta):
            def body(carry, key):
                ts, rs = carry
                ts, rs, metrics = one(ts, rs, key, beta)
                return (ts, rs), metrics

            (ts, rs), metrics = jax.lax.scan(body, (ts, rs), keys)
            return ts, rs, reduce_scan_metrics(metrics)

        return jax.jit(multi, donate_argnums=(0, 1) if donate else ())

    # -- checkpoint: the replay-contents tier (utils/checkpoint.py) --------

    _FIELDS = ("obs", "action", "reward", "terminal", "mask", "c0", "h0")

    def snapshot(self) -> dict:
        """Valid rows to host in age order, plus the priority leaves in the
        shared UNexponentiated unit (same convention as device_per.py)."""
        st = jax.device_get(self.state)
        fill, pos = int(st.fill), int(st.pos)
        shift = -pos if fill == self.capacity else 0
        out = {k: np.roll(np.asarray(getattr(st, k)), shift,
                          axis=0)[:fill].copy()
               for k in self._FIELDS}
        out["leaf_priority"] = np.roll(
            np.asarray(st.priority), shift)[:fill].copy()
        mx = float(np.asarray(st.max_priority))
        out["max_priority_base"] = np.float64(
            mx ** (1.0 / self.alpha) if self.alpha else mx)
        return out

    def restore(self, data: dict) -> int:
        """Refill through the normal chunked write path (newest rows that
        fit), then overwrite the fresh max-priority slots with the saved
        leaves so sampling resumes where it left off."""
        if self.size:
            self.state = self._init_state()
        rows = np.asarray(data["reward"])
        n = min(len(rows), self.capacity)
        if n:
            self.feed_chunk(SegmentChunk(*(
                np.asarray(data[k])[-n:] for k in self._FIELDS)))
            if "leaf_priority" in data:
                st = self.state
                pos = int(jax.device_get(st.pos))
                idx = jnp.asarray((np.arange(pos - n, pos)
                                   % self.capacity).astype(np.int32))
                pr = jnp.asarray(
                    np.asarray(data["leaf_priority"], np.float32)[-n:])
                base = float(data.get("max_priority_base", 1.0))
                self.state = st._replace(
                    priority=st.priority.at[idx].set(pr),
                    max_priority=jnp.float32(
                        base ** self.alpha if self.alpha else base))
        return n


class DeviceSequenceIngest:
    """Cross-process front end for the HBM segment ring.

    Actors cannot address HBM, so the ring is single-owner (the Ape-X
    topology proper): recurrent actors stream Segments over a spawn queue
    via ``make_feeder()`` and the learner calls ``attach`` (after it owns
    the mesh) then ``drain()`` between dispatches — stacking fixed-size
    SegmentChunks host-side and ingesting each with one transfer.  Same
    duck-typed learner surface as DevicePerIngest (attach / drain / size /
    capacity / replay.build_fused_step / replay.beta), so the learner's
    fused-priority hot loop needs no sequence-specific branch.
    """

    # single-owner declaration (apexlint): learner-only ingest pump
    __apex_mutators__ = ("drain",)
    __apex_owner__ = ("agents.learner", "memory.")

    def __init__(self, capacity: int, seq_len: int,
                 state_shape: Tuple[int, ...], lstm_dim: int,
                 state_dtype=np.uint8,
                 priority_exponent: float = 0.9,
                 importance_weight: float = 0.6,
                 importance_anneal_steps: int = 500000,
                 pack_frames: int = 0,
                 chunk_size: int = 16, max_queue_chunks: int = 4096):
        import multiprocessing as mp

        self.capacity = capacity
        self.seq_len = seq_len
        self.state_shape = tuple(state_shape)
        self.lstm_dim = lstm_dim
        self.state_dtype = np.dtype(state_dtype)
        self.priority_exponent = priority_exponent
        self.importance_weight = importance_weight
        self.importance_anneal_steps = importance_anneal_steps
        self.pack_frames = int(pack_frames)
        self.chunk_size = chunk_size
        # largest-first ingest sizes: a deep backlog moves in few large
        # transfers (one jit trace each) — same rationale as
        # DeviceReplayIngest.chunk_sizes, smaller multipliers because one
        # segment is ~T times a transition's bytes
        self.chunk_sizes = tuple(sorted(
            {min(s, capacity) for s in (chunk_size, chunk_size * 8)},
            reverse=True))
        self.max_queue_chunks = max_queue_chunks
        self._q = mp.get_context("spawn").Queue(max_queue_chunks)
        self.replay: Optional[DeviceSequenceReplay] = None
        self._pending: list = []
        self._fed_total = 0

    def make_feeder(self, chunk: int = 8):
        from pytorch_distributed_tpu.memory.feeder import QueueFeeder

        return QueueFeeder(self._q, chunk)

    def attach(self, mesh: Optional[jax.sharding.Mesh] = None
               ) -> DeviceSequenceReplay:
        self.replay = DeviceSequenceReplay(
            self.capacity, self.seq_len, self.state_shape, self.lstm_dim,
            state_dtype=self.state_dtype,
            priority_exponent=self.priority_exponent,
            importance_weight=self.importance_weight,
            importance_anneal_steps=self.importance_anneal_steps,
            pack_frames=self.pack_frames, mesh=mesh)
        self.capacity = self.replay.capacity  # mesh rounding
        return self.replay

    @property
    def size(self) -> int:
        # host-side accounting — no device sync in the hot loop
        assert self.replay is not None, "attach() first"
        return min(self._fed_total, self.capacity)

    def drain(self, max_chunks: int = 1024, max_rows: int = 512) -> int:
        """Move queued segments into HBM; bounded per call so a deep
        backlog cannot stall the learner's dispatch cadence."""
        from pytorch_distributed_tpu.memory.feeder import pop_chunks

        assert self.replay is not None, "attach() first"
        self._pending.extend(
            seg for seg, _priority in pop_chunks(self._q, max_chunks))
        fed = 0
        while fed < max_rows:
            C = next((s for s in self.chunk_sizes
                      if s <= len(self._pending)), None)
            if C is None:
                break
            rows, self._pending = self._pending[:C], self._pending[C:]
            self.replay.feed_chunk(self._stack(rows))
            fed += C
        self._fed_total += fed
        return fed

    def _stack(self, rows) -> SegmentChunk:
        dt = {"obs": self.state_dtype, "action": np.int32}
        return SegmentChunk(*(
            np.stack([getattr(r, f) for r in rows]).astype(
                dt.get(f, np.float32))
            for f in SegmentChunk._fields))

    # -- checkpoint: drain then delegate to the HBM ring -------------------

    def snapshot(self) -> dict:
        assert self.replay is not None, "attach() first"
        while self.drain():
            pass
        if self._pending:  # sub-chunk remainder: one odd-sized trace
            rows, self._pending = self._pending, []
            self.replay.feed_chunk(self._stack(rows))
            self._fed_total += len(rows)
        return self.replay.snapshot()

    def restore(self, data: dict) -> None:
        assert self.replay is not None, "attach() first"
        self._fed_total += self.replay.restore(data)

    def close(self) -> None:
        """See QueueOwner.close: discard, never join a dead pipe."""
        if hasattr(self._q, "cancel_join_thread"):
            self._q.cancel_join_thread()
        if hasattr(self._q, "close"):
            self._q.close()
