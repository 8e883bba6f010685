"""HBM-resident replay buffer as a jitted functional ring buffer.

TPU-native upgrade over the host shared-memory plane (SURVEY.md §7 step 4):
the six transition arrays live in device HBM as jax Arrays, optionally
sharded over the learner mesh's data axis, so sampling a minibatch never
crosses the host-device boundary — the learner consumes batches straight
from HBM and actors only pay one host->device transfer per *feed chunk*
(amortised), not per sampled batch.

Functional design: the buffer is a ``ReplayState`` pytree; ``feed`` and
``sample`` are jit-compiled pure functions with donated state so XLA updates
the rings in place.  Capacity is statically padded; the write cursor wraps
with modular index arithmetic (the jit-safe equivalent of the reference's
circular cursor, reference core/memories/shared_memory.py:45-57).

Stored format of the observation columns (``RowCodec``): a row of
uint8 frames is kept as ONE line of 32-bit words, ``uint32[N, lanes]`` with
``lanes`` a multiple of 128.  The TPU's default layout of an array is the
one with least padding, so ``u8[N, 4, 84, 84]`` (and ``u32[N, 7056]``
alike) lands with the ROW index minor-most, and every program that gathers
or scatters rows first copies the whole ring into row-major scratch and
back (nine tenths of the flagship learner's chip and 9 GB of scratch,
PERF.md PR 22/24).  A minor dimension that fills whole 128-lane tiles
keeps the array row-major at rest, so a row gather is a gather and a row
write a scatter, in place; the feed packs its chunk and the sampler unpacks
its batch (a few MB), and nothing else sees the words.

No reference equivalent — the reference buffer is host memory only.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pytorch_distributed_tpu.utils import bandwidth, experience
from pytorch_distributed_tpu.utils.experience import (
    REPLAY_FIELDS, Batch, Transition,
)
from pytorch_distributed_tpu.utils.profiling import (
    PHASE_DRAW, PHASE_FEED, PHASE_GATHER,
)


LANES = 128  # 32-bit lanes of one TPU tile row
RESTORE_ROWS = 4096  # rows per feed of a checkpoint restore


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class RowCodec:
    """Storage format of a ring's observation columns (``state0``,
    ``state1``), decided from what a row is: a row of one-byte integers
    with more than one dimension whose byte count divides by 4 is stored
    as ``lanes`` uint32 words (module docstring); already-flat or wider
    rows (the control tasks' float32 vectors) are stored as they are.

    Word ``j`` holds byte ``j`` of each quarter of the flattened row (for
    (4, H, W) frames: pixel ``j`` of the four frames), so ``pack`` and
    ``unpack`` are shifts over whole ``(n, words)`` planes: exact in both
    directions, independent of byte order, and with no 4-wide minor
    dimension for the compiler to tile.
    A static pytree node: it rides in the ring state, costs no leaf, and
    tells every jitted reader and writer the format it was built with.

    ``rows`` is where the ring's rows live: the ``NamedSharding`` that
    splits every per-row column over the mesh's data axis, None on one
    device.  ``gather_rows`` reads it and hands out the batch split the
    same way, so whatever trains on the batch is data-parallel."""
    row_shape: Tuple[int, ...]   # one row in store order: (C,H,W)/(H,W,C)
    dtype: np.dtype
    rows: Optional[jax.sharding.NamedSharding] = None

    @property
    def words(self) -> int:
        """uint32 words a packed row fills; 0 = stored as it is."""
        nbytes = int(np.prod(self.row_shape))
        packs = (self.dtype.itemsize == 1 and self.dtype.kind in "iu"
                 and len(self.row_shape) > 1 and nbytes % 4 == 0)
        return nbytes // 4 if packs else 0

    @property
    def stored_row(self) -> Tuple[int, ...]:
        return ((-(-self.words // LANES) * LANES,) if self.words
                else tuple(self.row_shape))

    @property
    def stored_dtype(self) -> np.dtype:
        return np.dtype(np.uint32) if self.words else self.dtype

    def pack(self, rows):
        """Public ``(n, *row_shape)`` -> stored ``(n, *stored_row)``, inside
        the feed program."""
        if not self.words:
            return rows
        q = _as(jnp.reshape(rows, (rows.shape[0], 4, self.words)), np.uint8)
        # one quarter at a time, widened after it is sliced: the whole
        # chunk in 32 bits would be four chunks of working set
        w = q[:, 0].astype(jnp.uint32)
        for k in (1, 2, 3):
            w = w | (q[:, k].astype(jnp.uint32) << (8 * k))
        return jnp.pad(w, ((0, 0), (0, self.stored_row[0] - self.words)))

    def unpack(self, stored):
        """Stored ``(..., *stored_row)`` -> public ``(..., *row_shape)``,
        bit for bit what ``pack`` was given; on a fetched (numpy) column
        it runs on the host (snapshot).  One broadcast shift over the
        gathered words: the compiler turns it into a single pass that
        writes the batch in the layout the first convolution reads
        (PERF.md PR 25: stacking four shifted planes cost 0.09 ms more
        per update)."""
        if not self.words:
            return stored
        xp = np if isinstance(stored, np.ndarray) else jnp
        w = stored[..., None, :self.words]
        shifts = (xp.arange(4, dtype=xp.uint32) * 8)[:, None]
        q = ((w >> shifts) & 0xFF).astype(xp.uint8)
        return _as(q, self.dtype).reshape(*stored.shape[:-1],
                                          *self.row_shape)


def _as(x, dtype):
    """Reinterpret one-byte integers (int8 <-> uint8), bits untouched."""
    if x.dtype == dtype:
        return x
    return (x.view(dtype) if isinstance(x, np.ndarray)
            else jax.lax.bitcast_convert_type(x, dtype))


class ReplayState(NamedTuple):
    state0: jax.Array     # (N, *codec.stored_row): read through the codec
    action: jax.Array
    reward: jax.Array
    gamma_n: jax.Array
    state1: jax.Array     # stored like state0
    terminal1: jax.Array
    # data-plane provenance columns (ISSUE 8): (actor_id, env_slot,
    # param_version, birth_step) per row as int32 (-1 = unknown) — kept
    # AFTER the six replay columns so ``state[:6]`` keeps meaning the
    # replay schema for the PER subclass's constructor
    prov: jax.Array       # (N, 4) int32
    pos: jax.Array        # int32 write cursor
    fill: jax.Array       # int32 number of valid rows
    codec: RowCodec       # static: how state0/state1 are stored


# single-owner declaration for the module-level ring mutators
# (apexlint single-owner rule): the functional ring writes may only be
# composed into programs by the replay backends themselves and the
# fused rollout (models/policies emit="replay") — any other caller is
# a second writer racing the cursor
__apex_fn_owners__ = {
    "ring_write": ("memory.",),
    "ring_write_masked": ("memory.", "models.policies"),
}


def ring_write(state, chunk: Transition, capacity: int):
    """Write a chunk at the cursor of ANY ring state carrying the six-array
    schema plus pos/fill/codec (ReplayState, and device_per.py's
    PerReplayState); the chunk's states arrive in store order and are
    packed here, inside the feed program (RowCodec).  Returns
    (state', idx) so extended schemas can set their extra per-row fields
    at the same slots."""
    n = chunk.reward.shape[0]
    pack = state.codec.pack
    with jax.named_scope(PHASE_FEED):
        idx = (state.pos + jnp.arange(n, dtype=jnp.int32)) % capacity
        repl = dict(
            state0=state.state0.at[idx].set(pack(chunk.state0)),
            action=state.action.at[idx].set(chunk.action),
            reward=state.reward.at[idx].set(chunk.reward),
            gamma_n=state.gamma_n.at[idx].set(chunk.gamma_n),
            state1=state.state1.at[idx].set(pack(chunk.state1)),
            terminal1=state.terminal1.at[idx].set(chunk.terminal1),
            pos=(state.pos + n) % capacity,
            fill=jnp.minimum(state.fill + n, capacity),
        )
        prov_col = getattr(state, "prov", None)
        if prov_col is not None:
            # rows without provenance overwrite with the -1 sentinel (a
            # recycled slot must never keep its previous row's provenance)
            repl["prov"] = prov_col.at[idx].set(
                jnp.full((n, prov_col.shape[1]), -1, prov_col.dtype)
                if chunk.prov is None
                else chunk.prov.astype(prov_col.dtype))
    return state._replace(**repl), idx


def _feed(state: ReplayState, chunk: Transition, capacity: int) -> ReplayState:
    return ring_write(state, chunk, capacity)[0]


def ring_write_masked(state, chunk: Transition, valid,
                      capacity: int):
    """Write only the ``valid`` rows of a chunk at the cursor, in chunk
    order, inside jit — the device actor plane's ingest primitive
    (models/policies.build_fused_rollout emit="replay"): the fused
    rollout's per-tick emissions carry a validity column (warmup ticks
    have no closed n-step window yet), and invalid rows must neither
    consume ring slots nor corrupt neighbours.

    Valid rows take positions ``pos + rank`` (rank = prefix count of
    valid rows); invalid rows are pointed at index ``capacity`` —
    out of bounds — and dropped by the scatter (``mode="drop"``), which
    XLA resolves with no branch.  Returns ``(state', n_written)``."""
    with jax.named_scope(PHASE_FEED):
        offs = jnp.cumsum(valid.astype(jnp.int32)) - 1
        idx = jnp.where(valid, (state.pos + offs) % capacity, capacity)
        total = jnp.sum(valid.astype(jnp.int32))
        wr = lambda buf, x: buf.at[idx].set(x, mode="drop")
        pack = state.codec.pack
        repl = dict(
            state0=wr(state.state0, pack(chunk.state0)),
            action=wr(state.action, chunk.action),
            reward=wr(state.reward, chunk.reward),
            gamma_n=wr(state.gamma_n, chunk.gamma_n),
            state1=wr(state.state1, pack(chunk.state1)),
            terminal1=wr(state.terminal1, chunk.terminal1),
            pos=(state.pos + total) % capacity,
            fill=jnp.minimum(state.fill + total, capacity),
        )
        prov_col = getattr(state, "prov", None)
        if prov_col is not None:
            n = chunk.reward.shape[0]
            repl["prov"] = wr(prov_col, (
                jnp.full((n, prov_col.shape[1]), -1, prov_col.dtype)
                if chunk.prov is None
                else chunk.prov.astype(prov_col.dtype)))
    return state._replace(**repl), total


def jit_feed(feed_fn):
    """The jitted, donating feed program of a ring, under a name a trace
    can show (``jit_feed_chunk``; a bare ``functools.partial`` compiles to
    ``jit__unknown``)."""
    def feed_chunk(state, chunk):
        return feed_fn(state, chunk)

    return jax.jit(feed_chunk, donate_argnums=0)


def round_capacity(capacity: int, mesh: Optional[jax.sharding.Mesh],
                   axis: str = "dp", label: str = "device replay") -> int:
    """Round capacity up to a multiple of the mesh axis so ring rows split
    evenly across devices (e.g. the default 50000 on a 32-wide mesh ->
    50016)."""
    if mesh is None:
        return capacity
    ndev = mesh.shape[axis]
    if capacity % ndev:
        rounded = capacity + ndev - capacity % ndev
        import warnings

        warnings.warn(
            f"{label} capacity {capacity} rounded up to {rounded} "
            f"(multiple of mesh {axis}={ndev})", stacklevel=3)
        return rounded
    return capacity


def draw_rows(state: ReplayState, key: jax.Array, batch_size: int):
    """Uniform draw over the ring's valid rows: ``(idx, weight)``,
    replicated on a mesh."""
    with jax.named_scope(PHASE_DRAW):
        idx = jax.random.randint(key, (batch_size,), 0,
                                 jnp.maximum(state.fill, 1), jnp.int32)
        weight = jnp.ones((batch_size,), dtype=jnp.float32)
    return idx, weight


def sample_rows(state: ReplayState, key: jax.Array,
                batch_size: int) -> Batch:
    """Uniform on-device sampling from the ring — public so the learner and
    the driver dryrun can fuse it into their train-step programs."""
    return gather_rows(state, *draw_rows(state, key, batch_size))


def gather_rows(state, idx: jax.Array, weight: jax.Array) -> Batch:
    """Rows ``idx`` of any ring state as a ``Batch`` in the public (store
    order) shapes and dtypes: the one reader of the stored observation
    columns inside a program — the gathered words are unpacked here, on
    the batch only (RowCodec).

    On a row-sharded ring (``codec.rows``) the batch comes out sharded the
    same way, ``index`` and ``weight`` included, in the draw's order: chip
    ``c`` holds rows ``[c*B/dp, (c+1)*B/dp)`` of every leaf
    (``_exchange_rows`` brings them), unpacks and trains those, and
    the compiler partitions the train step behind it (batch-parallel
    forward and backward, an all-reduce of the gradients).  The DRAW is
    not touched: ``idx`` arrives replicated, drawn over the whole ring."""
    codec, unpack = state.codec, state.codec.unpack
    with jax.named_scope(PHASE_GATHER):
        cols = {k: getattr(state, k) for k in REPLAY_FIELDS}
        take = lambda col: col[idx]
        if codec.rows is not None:
            cols, idx, weight = _exchange_rows(cols, idx, weight, codec.rows)
            take = lambda col: col          # the batch's rows already
        return Batch(
            state0=unpack(take(cols["state0"])),
            action=take(cols["action"]),
            reward=take(cols["reward"]),
            gamma_n=take(cols["gamma_n"]),
            state1=unpack(take(cols["state1"])),
            terminal1=take(cols["terminal1"]),
            weight=weight,
            index=idx,
        )


def exchange_bound(share: int, ndev: int) -> int:
    """Rows a chip sends one other chip in one round of ``_exchange_rows``:
    a static function of the batch's split, never an option.  Of the
    ``share`` slots of a chip's block a level draw finds Binomial(share,
    1/ndev) in any one shard; the bound is 6.5 deviations over that mean,
    up to a multiple of 8 (whole sublanes), at most ``share`` (where one
    round carries any draw).  (128, 4) -> 64: a second round is a 1e-10
    event under a level draw, and half the words of a whole block move."""
    p = 1.0 / ndev
    need = share * p + 6.5 * math.sqrt(share * p * (1.0 - p))
    return min(share, -(-math.ceil(need) // 8) * 8)


def _exchange_plan(idx: jax.Array, n: int, ndev: int):
    """What every chip works out alike from a replicated draw, with no
    communication: by destination block ``(ndev, share)``, the chip that
    owns each slot's row (shards of ``n`` rows) and the slot's rank among
    the EARLIER slots of its block with that owner (its place in what the
    owner sends the block); and the rounds of ``exchange_bound`` rows the
    fullest (owner, block) pair needs."""
    share = idx.shape[-1] // ndev
    owner = (idx // n).reshape(*idx.shape[:-1], ndev, share)
    earlier = jnp.tril(jnp.ones((share, share), bool), -1)
    rank = jnp.sum((owner[..., :, None] == owner[..., None, :]) & earlier,
                   axis=-1, dtype=jnp.int32)
    bound = exchange_bound(share, ndev)
    rounds = (jnp.max(rank, axis=(-2, -1)) + bound) // bound
    return owner, rank, rounds


def exchange_rounds(state, idx: jax.Array):
    """The step metric ``learner/exchange_rounds`` of a draw ``idx`` (as
    ``gather_rows`` was handed it; a leading group axis counts as one
    draw, as the vmapped loop runs to its largest): the rounds
    ``_exchange_rows`` ran to bring it out of ring ``state``.  1 = the
    draw fit one bounded exchange; under PER new rows enter at the cursor,
    in ONE shard, at the running max priority, and a draw that leans on
    them takes more.  None on a ring that is not row-sharded: there is no
    exchange."""
    rows = state.codec.rows
    if rows is None:
        return None
    ndev = rows.mesh.shape[rows.spec[0]]
    with jax.named_scope(PHASE_GATHER):
        rounds = _exchange_plan(idx, state.reward.shape[0] // ndev, ndev)[2]
        return jnp.max(rounds).astype(jnp.float32)


def with_exchange_rounds(metrics, state, idx: jax.Array):
    """A train step's ``metrics`` with ``learner/exchange_rounds`` of the
    batch it trained on, as the fused builders report it; unchanged on a
    ring with no exchange."""
    from pytorch_distributed_tpu.utils.health import EXCHANGE_ROUNDS_KEY

    rounds = exchange_rounds(state, idx)
    if rounds is None or not isinstance(metrics, dict):
        return metrics
    return {**metrics, EXCHANGE_ROUNDS_KEY: rounds}


def _exchange_rows(cols, idx: jax.Array, weight: jax.Array, rows):
    """Rows ``idx`` (replicated, global) of columns sharded by ``rows``,
    with ``idx`` and ``weight`` themselves, as a batch sharded the same
    way: ``(cols, idx, weight)``.  Written out and not left to the
    partitioner, which answers a gather from a sharded operand with a
    masked local gather and an ALL-REDUCE that leaves the whole batch on
    every chip (PERF.md PR 30).

    Only rows a chip owns travel, compacted (PR 32).  In round ``r`` chip
    ``c`` gathers, for each block ``d``, its rows of ranks ``[r*bound,
    (r+1)*bound)`` (``_exchange_plan``) from its shard, ``(ndev, bound)``
    rows in all, and an ``all_to_all`` hands chip ``d`` its part; slot
    ``j`` of a chip's block then takes row ``rank[j] - r*bound`` of what
    ``owner[j]`` sent.  Positions no slot asked for carry some row of the
    sender and are never read.  The rounds a draw needs follow from
    ``idx`` alone, so every chip runs the same number: one for any level
    draw (``exchange_bound``), ``share / bound`` when a block's rows all
    sit in one shard.  The first stands straight-line and the others, the
    same code, in a loop: a level draw then pays for no select and no
    zero-filled buffer, and a bound that clamps to ``share`` for no loop
    (with every round in the loop the chip ran the same 0.76 ms an update,
    PERF.md PR 32)."""
    axis = rows.spec[0]
    ndev = rows.mesh.shape[axis]
    if idx.shape[0] % ndev:
        raise ValueError(
            f"a batch of {idx.shape[0]} rows does not split over mesh axis "
            f"{axis}={ndev}: on a row-sharded ring every chip takes an "
            f"equal share of the batch")
    share = idx.shape[0] // ndev
    bound = exchange_bound(share, ndev)

    def exchange(cols, idx, weight):
        chip = jax.lax.axis_index(axis)
        n = cols["reward"].shape[0]             # rows this chip holds
        owner, rank, rounds = _exchange_plan(idx, n, ndev)
        local = (idx % n).reshape(ndev, share)
        place = jnp.arange(bound, dtype=jnp.int32)[None, :, None]

        def one_round(r, out):
            at = rank - r * bound               # place in this round's part
            # what this chip sends block d at place p: its row that the
            # block's slot of rank r*bound + p drew (at most one slot)
            send = jnp.sum(jnp.where(
                (owner == chip)[:, None, :] & (at[:, None, :] == place),
                local[:, None, :], 0), axis=-1).reshape(-1)
            here = (at[chip] >= 0) & (at[chip] < bound)
            take = owner[chip] * bound + jnp.clip(at[chip], 0, bound - 1)

            def one(col, had):
                got = jax.lax.all_to_all(col[send], axis, split_axis=0,
                                         concat_axis=0, tiled=True)[take]
                if had is None:     # later rounds rewrite what is not here
                    return got
                return jnp.where(
                    here.reshape(-1, *(1,) * (got.ndim - 1)), got, had)

            return {k: one(col, None if out is None else out[k])
                    for k, col in cols.items()}

        out = one_round(0, None)
        if bound < share:           # else one round carries any draw
            _, out = jax.lax.while_loop(
                lambda c: c[0] < rounds,
                lambda c: (c[0] + 1, one_round(c[0], c[1])),
                (jnp.int32(1), out))
        mine = lambda x: jax.lax.dynamic_slice_in_dim(x, chip * share, share)
        return out, mine(idx), mine(weight)

    P = jax.sharding.PartitionSpec
    return jax.shard_map(
        exchange, mesh=rows.mesh, in_specs=(rows.spec, P(), P()),
        out_specs=rows.spec)(cols, idx, weight)


def group_step_on(state, megabatch_step):
    """``megabatch_step`` as the fused programs call it on a group drawn
    from ring ``state``.  On one device: itself.  On a row-sharded ring the
    group's ``(M, B)`` batch is split over the chips along ``B``, and a
    megabatch step merges ``M`` into the batch (``vmap`` of the per-row
    work; the filter gradients become batch-GROUPED convolutions), which
    the partitioner answers by training the whole group on every chip.  So
    the step runs per chip on its share of every minibatch
    (``shard_map``), and is told the axis to reduce its gradients, losses
    and guard flags over (the ``axis_name`` argument of the steps that
    ops/losses.py's megabatch builders return; this is its one caller):
    train state in and out replicated, |TD| sharded like the batch.

    Where it stands: held on the virtual CPU mesh by
    tests/test_device_per.py (compiled HLO; every metric and the
    parameters against one device) and tests/test_megabatch.py, and
    compiled at dp4's real size for a described v5e:2x2; no benchmark cell
    runs a megabatch on a mesh: it has run on the four chips once, by a
    builder's hand (PERF.md section 6, PR 32)."""
    rows = state.codec.rows
    if rows is None:
        return megabatch_step
    P, axis = jax.sharding.PartitionSpec, rows.spec[0]
    # check_vma off: the step reduces by hand (pmean / pmin over
    # ``axis_name``); with the check on, differentiating through the
    # replicated parameters would sum the chips' gradients a second time.
    # The price: ``out_specs=P()`` then hands out chip 0's value of
    # anything the step forgot to reduce, which is why the tier-1 test
    # holds EVERY metric to the one-device program's
    return jax.shard_map(
        functools.partial(megabatch_step, axis_name=axis), mesh=rows.mesh,
        in_specs=(P(), P(None, axis)),
        out_specs=(P(), P(), P(None, axis), P()), check_vma=False)


def provenance_sample(state: ReplayState, key: jax.Array,
                      n: int):
    """Gather ``n`` uniformly-drawn rows' provenance columns — the
    learner's ONE small D2H per stats cadence on the device replay
    paths (n * 4 int32s; the telemetry is a distribution read, so a
    bounded sample is the whole point).  Returns ``(prov[n, 4],
    fill)``; jit with ``static_argnames='n'``."""
    idx = jax.random.randint(key, (n,), 0, jnp.maximum(state.fill, 1))
    return state.prov[idx], state.fill


def build_uniform_fused_step(step_fn, batch_size: int,
                             steps_per_call: int = 1, donate: bool = True,
                             megabatch: int = 1, megabatch_step=None):
    """One XLA program running ``steps_per_call`` sample+train steps over
    the HBM ring: ``(train_state, ring_state, keys (K, 2)) ->
    (train_state', metrics_of_last_substep)``.

    Multi-step fusion amortises program-launch latency: K updates per
    dispatch pay the launch once, 1/K per update (how much that buys on
    a directly attached chip is not measured).  The ring is read-only
    inside —
    ingest stays on the host drain cadence between dispatches.

    ``megabatch`` M > 1 (ISSUE 13, with ``megabatch_step`` from
    factory.build_megabatch_train_step) regroups the K scanned steps
    into K/M groups: each group samples its M minibatches in one
    WIDENED gather — consuming exactly the keys the sequential schedule
    would (key g*M+i draws minibatch i of group g, bit-identical index
    streams) — and runs them as one lane-filling (M*B, ...) batched
    forward/backward with sequential in-graph optimizer applies
    (ops/losses.build_dqn_megabatch_step).  Dispatch count is
    unchanged; per-update op count drops ~M-fold, which is the whole
    win on dispatch-bound families.
    """
    from pytorch_distributed_tpu.utils.health import reduce_scan_metrics

    def sample(ring_state, key):
        """``(Batch, idx)``: the batch and the draw as it was before the
        batch took it (replicated on a mesh)."""
        idx, weight = draw_rows(ring_state, key, batch_size)
        return gather_rows(ring_state, idx, weight), idx

    if megabatch > 1:
        assert megabatch_step is not None, \
            "megabatch > 1 needs the factory's megabatch step"
        assert steps_per_call % megabatch == 0, (
            f"megabatch {megabatch} must divide steps_per_call "
            f"{steps_per_call}")
        groups = steps_per_call // megabatch

        def multi_mega(ts, ring_state, keys):
            with jax.named_scope(PHASE_DRAW):
                gkeys = keys.reshape(groups, megabatch, *keys.shape[1:])

            def one_group(ts, kset):
                with jax.named_scope(PHASE_GATHER):  # vmap's transposes
                    batches, idx = jax.vmap(
                        lambda k: sample(ring_state, k))(kset)
                ts, metrics, _td, _ok = group_step_on(
                    ring_state, megabatch_step)(ts, batches)
                return ts, with_exchange_rounds(metrics, ring_state, idx)

            ts, metrics = jax.lax.scan(one_group, ts, gkeys)
            return ts, reduce_scan_metrics(metrics)

        return jax.jit(multi_mega, donate_argnums=(0,) if donate else ())

    def multi(ts, ring_state, keys):
        def one(ts, key):
            batch, idx = sample(ring_state, key)
            ts, metrics, _td = step_fn(ts, batch)
            return ts, with_exchange_rounds(metrics, ring_state, idx)

        ts, metrics = jax.lax.scan(one, ts, keys)
        # last substep's metrics stand in for the dispatch, EXCEPT the
        # guard's skip counter, which sums over the scan, and the
        # exchange's rounds, their mean (utils/health.py
        # reduce_scan_metrics)
        return ts, reduce_scan_metrics(metrics)

    return jax.jit(multi, donate_argnums=(0,) if donate else ())


class DeviceReplay:
    """Convenience stateful wrapper around the functional ring.

    ``mesh``/``axis`` shard every buffer row-wise across the data axis so
    each device holds capacity/n_dev rows of the ring and gathers ride
    ICI; a sampled batch then leaves the ring sharded over the same axis
    (``gather_rows``, told by ``codec.rows``), which is what makes the
    fused step behind it data-parallel.
    """

    def __init__(self, capacity: int, state_shape: Tuple[int, ...],
                 action_shape: Tuple[int, ...] = (),
                 state_dtype=np.uint8, action_dtype=np.int32,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 axis: str = "dp"):
        self.capacity = capacity
        self.state_shape = tuple(state_shape)
        self.action_shape = tuple(action_shape)
        self.state_dtype = jnp.dtype(state_dtype)
        self.action_dtype = jnp.dtype(action_dtype)
        self.mesh = mesh
        self.axis = axis

        if mesh is not None:
            ndev = mesh.shape[axis]
            assert capacity % ndev == 0, (
                f"capacity {capacity} must be divisible by mesh axis "
                f"{axis}={ndev} (round it via DeviceReplayIngest.attach)")
            P = jax.sharding.PartitionSpec
            self._row_sharding = jax.sharding.NamedSharding(mesh, P(axis))
            self._scalar_sharding = jax.sharding.NamedSharding(mesh, P())
        else:
            self._row_sharding = None
            self._scalar_sharding = None
        self.codec = RowCodec(tuple(state_shape), self.state_dtype,
                              rows=self._row_sharding)

        self.state = self._init_state()
        self._feed_fn = jit_feed(functools.partial(_feed, capacity=capacity))
        self._sample_fn = jax.jit(
            sample_rows, static_argnames="batch_size", donate_argnums=())

    def _alloc(self, shape, dtype, sharded: bool = True):
        """Zeros, made in their sharding: a column built whole on one
        device and then spread holds that device to two whole columns
        while the next is built (12.7 GB of dp4's device 0, PERF.md)."""
        return jnp.zeros(shape, dtype=dtype, device=(
            self._row_sharding if sharded else self._scalar_sharding))

    def _init_state(self) -> ReplayState:
        N = self.capacity
        alloc = self._alloc
        stored = (N, *self.codec.stored_row), self.codec.stored_dtype
        return ReplayState(
            state0=alloc(*stored),
            action=alloc((N, *self.action_shape), self.action_dtype),
            reward=alloc((N,), jnp.float32),
            gamma_n=alloc((N,), jnp.float32),
            state1=alloc(*stored),
            terminal1=alloc((N,), jnp.float32),
            # -1 = unknown provenance (the zeros alloc carries the row
            # sharding; the elementwise subtract preserves it)
            prov=alloc((N, 4), jnp.int32) - 1,
            pos=alloc((), jnp.int32, sharded=False),
            fill=alloc((), jnp.int32, sharded=False),
            codec=self.codec,
        )

    @property
    def size(self) -> int:
        return int(self.state.fill)

    @property
    def stored_rows(self) -> str:
        """An observation column at rest, for the learner's start-up
        line: ``uint32[100000,7168]`` is the packed format."""
        col = self.state.state0
        return f"{col.dtype}[{','.join(map(str, col.shape))}]"

    def batch_rows(self, batch_size: int) -> str:
        """How a sampled batch of ``batch_size`` rows is ASKED to lie on
        the chips, for the learner's start-up line: ``128x4dp`` = each of
        four chips gets 128 rows (what ``gather_rows`` is told by
        ``codec.rows``), ``512x1`` = one device holds them all.  Read off
        the ring's sharding, not off a compiled program: that the train
        step behind the batch is partitioned the same way is held by
        tests/test_device_per.py
        ``test_fused_step_of_a_sharded_ring_is_partitioned_over_dp``
        (the compiled HLO of ``one`` / ``multi`` / ``multi_mega``)."""
        if self._row_sharding is None:
            return f"{batch_size}x1"
        ndev = self.mesh.shape[self.axis]
        return f"{batch_size // ndev}x{ndev}{self.axis}"

    # -- checkpoint (utils/checkpoint.py save_replay/load_replay) -----------

    def snapshot(self) -> dict:
        """Pull the valid HBM rows to host in AGE order (when full, the
        cursor points at the oldest row; before that, [0, fill) is already
        oldest-first).  The stored words are unpacked, so checkpoints are
        independent of the ring's format."""
        return self._aged_columns(jax.device_get(self.state),
                                  REPLAY_FIELDS + ("prov",))

    def _aged_columns(self, st, names) -> dict:
        """Host copies of the per-row columns ``names`` of a fetched ring
        state, oldest row first, in the public schema."""
        fill, pos = int(st.fill), int(st.pos)
        shift = -pos if fill == self.capacity else 0
        out = {k: np.roll(np.asarray(getattr(st, k)), shift,
                          axis=0)[:fill].copy() for k in names}
        for k in ("state0", "state1"):
            out[k] = self.codec.unpack(out[k])
        out["prov"] = out["prov"].astype(np.int64)
        return out

    def restore(self, data: dict) -> int:
        """Refill via the normal chunked write path (works across capacity
        changes, keeps the newest rows that fit).  Returns rows restored.

        Replaces any existing contents — the ring is re-initialised first so
        restore has the same overwrite-[:n] semantics as the host-side
        replays (SharedReplay/PrioritizedReplay) rather than appending at
        the current cursor."""
        if self.size:
            self.state = self._init_state()
        rows = np.asarray(data["reward"])
        n = min(len(rows), self.capacity)
        cols = [np.asarray(data[k])[-n:] for k in REPLAY_FIELDS]
        prov = (np.asarray(data["prov"], np.int32)[-n:]
                if "prov" in data else None)
        # in slices: one chunk of a whole ring would sit on the device
        # beside the ring, with the feed's working set on top
        for lo in range(0, n, RESTORE_ROWS):
            hi = lo + RESTORE_ROWS
            self.feed_chunk(Transition(
                *(c[lo:hi] for c in cols),
                prov=None if prov is None else prov[lo:hi]))
        return n

    def feed_chunk(self, chunk: Transition) -> None:
        """Host->device ingest of a chunk of transitions (leading dim = chunk
        size).  Chunk sizes should be fixed (e.g. the actor flush size) to
        avoid retracing."""
        self.state = self._feed_fn(self.state, chunk)

    def sample(self, batch_size: int, key: jax.Array) -> Batch:
        """``batch_size`` uniformly drawn rows.  On a mesh the batch comes
        back sharded over the data axis (``gather_rows``), so
        ``batch_size`` must be a multiple of the axis size there: another
        size is refused (``ValueError``), where before PR 30 it returned a
        replicated batch."""
        return self._sample_fn(self.state, key, batch_size=batch_size)


class DeviceReplayIngest:
    """Cross-process front end for a device-resident ring.

    Actors cannot address HBM, so (like PER) the device ring is
    single-owner: actors stream transitions over a spawn queue via
    ``make_feeder()`` and the learner process calls ``attach`` (after it
    owns the mesh) then ``drain()`` per step — which assembles **fixed-size
    chunks** host-side (fixed so ``feed_chunk`` never retraces) and ingests
    them with one host->device transfer each; partial chunks stay pending
    until filled.
    """

    # single-owner declaration (apexlint): the learner process owns the
    # HBM ring's ingest; actors can only reach it through make_feeder()
    __apex_mutators__ = ("drain",)
    __apex_owner__ = ("agents.learner", "memory.")

    def __init__(self, capacity: int, state_shape: Tuple[int, ...],
                 action_shape: Tuple[int, ...] = (),
                 state_dtype=np.uint8, action_dtype=np.int32,
                 chunk_size: int = 64, max_queue_chunks: int = 4096):
        import multiprocessing as mp

        self.capacity = capacity
        self.state_shape = tuple(state_shape)
        self.action_shape = tuple(action_shape)
        self.state_dtype = np.dtype(state_dtype)
        self.action_dtype = np.dtype(action_dtype)
        self.chunk_size = chunk_size
        # Ingest sizes, largest-first: a deep backlog moves in few large
        # transfers (one jit trace per size) instead of many chunk_size
        # ones — host->device transfer count, not bytes, is what stalls a
        # learner step when actors outpace it.  Capped at capacity: a chunk
        # larger than the ring would scatter duplicate indices, whose
        # winner XLA leaves unspecified.
        self.chunk_sizes = tuple(sorted(
            {min(s, capacity)
             for s in (chunk_size, chunk_size * 8, chunk_size * 64)},
            reverse=True))
        self.max_queue_chunks = max_queue_chunks  # backpressure bound
        self._q = mp.get_context("spawn").Queue(max_queue_chunks)
        self.replay: Optional[DeviceReplay] = None
        # second half-capacity ring under the Anakin double-buffer mode
        # (attach_halves); None on every other path
        self.replay_b: Optional[DeviceReplay] = None
        self._pending: list = []
        self._fed_total = 0
        self._validator = None  # ingest quarantine, built on first drain
        # ISSUE-11 shed policy (utils/flow.py): under
        # ``local_policy="shed"`` the host-side pending list is bounded
        # at ``max_pending_rows`` — oldest rows beyond it are dropped
        # (counted + prov-stamped into flow_counters) instead of
        # growing without bound when actors outrun the drain cadence.
        # Default "block" keeps the pre-flow behaviour: the bounded mp
        # queue is the backpressure point, pending stays unbounded.
        self._flow_params = None  # resolved lazily on first drain
        self.flow_counters: dict = {}

    def make_feeder(self, chunk: int = 16):
        from pytorch_distributed_tpu.memory.feeder import QueueFeeder

        return QueueFeeder(self._q, chunk)

    def configure_flow(self, params=None) -> None:
        """Pin the ISSUE-11 shed-vs-block policy for this ingest
        (otherwise resolved from the environment on first drain)."""
        from pytorch_distributed_tpu.utils import flow

        self._flow_params = flow.resolve_flow(params)

    def _make_replay(self, capacity: int,
                     mesh: Optional[jax.sharding.Mesh]) -> DeviceReplay:
        """One construction point for the HBM ring so ``attach`` and the
        Anakin ``attach_halves`` (and the PER subclass's overrides) can
        never diverge on geometry."""
        return DeviceReplay(
            capacity, self.state_shape, self.action_shape,
            self.state_dtype, self.action_dtype, mesh=mesh)

    def attach(self, mesh: Optional[jax.sharding.Mesh] = None
               ) -> DeviceReplay:
        """Allocate the HBM ring on the learner's mesh (geometry was fixed
        at construction by the memory factory)."""
        self.replay = self._make_replay(round_capacity(self.capacity, mesh),
                                        mesh)
        bandwidth.note_device_replay(self.replay.state)
        return self.replay

    def attach_halves(self, mesh: Optional[jax.sharding.Mesh] = None
                      ) -> Tuple[DeviceReplay, DeviceReplay]:
        """Double-buffer allocation for the co-located Anakin loop
        (agents/anakin.py, AnakinParams.double_buffer): TWO
        half-capacity rings instead of one — learner dispatches sample
        one half while rollouts scatter into the other; the driver owns
        the swap schedule.  Returns ``(half_a, half_b)``; ``half_a`` is
        also ``self.replay``, so the cross-process ingest drain (remote
        DCN rows in a hybrid topology) and the checkpoint snapshot keep
        working against half A — a documented asymmetry, not a race
        (the driver treats half A as a normal half)."""
        cap = round_capacity(max(self.capacity // 2, 1), mesh,
                             label="anakin half ring")
        self.replay = self._make_replay(cap, mesh)
        self.replay_b = self._make_replay(cap, mesh)
        bandwidth.note_device_replay(self.replay.state,
                                     self.replay_b.state)
        return self.replay, self.replay_b

    def note_scatter(self, rows: int) -> None:
        """Account rows written into the attached ring(s) by an
        in-graph scatter (the co-located Anakin rollout's replay-emit
        leg) — the zero-copy path never crosses ``drain``, so without
        this the host-side ``size``/fill reporting (fleet STATUS,
        checkpoint extras) would read a full ring as empty."""
        self._fed_total += int(rows)

    @property
    def size(self) -> int:
        # host-side accounting — no device sync in the hot loop
        assert self.replay is not None, "attach() first"
        cap = self.replay.capacity * (2 if self.replay_b is not None
                                      else 1)
        return min(self._fed_total, cap)

    # -- checkpoint: delegate to the attached HBM ring ---------------------

    def snapshot(self) -> dict:
        assert self.replay is not None, "attach() first"
        while self.drain():  # a deep backlog needs multiple capped drains
            pass
        if self._pending:
            # sub-chunk remainder: the drain cadence leaves rows below the
            # smallest preset chunk size pending; a checkpoint must not
            # lose them, so flush the remainder as one odd-sized chunk
            # (costs a single extra jit trace).
            from pytorch_distributed_tpu.utils.experience import (
                transition_dtypes,
            )

            dt = transition_dtypes(self.replay.state_dtype,
                                   self.replay.action_dtype)
            rows, self._pending = self._pending, []
            self.replay.feed_chunk(Transition(*(
                np.stack([getattr(r, f) for r in rows]).astype(dt[f])
                for f in REPLAY_FIELDS),
                prov=experience.stack_prov(rows).astype(np.int32)))
            self._fed_total += len(rows)
        return self.replay.snapshot()

    def restore(self, data: dict) -> None:
        assert self.replay is not None, "attach() first"
        self._fed_total += self.replay.restore(data)

    def close(self) -> None:
        """See QueueOwner.close: reap the queue feeder thread."""
        # discard rather than flush: leftover experience is garbage at
        # shutdown, and join_thread would block forever on a full pipe
        # nobody drains anymore
        if hasattr(self._q, "cancel_join_thread"):  # mp queue only
            self._q.cancel_join_thread()
        if hasattr(self._q, "close"):  # queue.Queue has no close
            self._q.close()

    def drain(self, max_chunks: int = 1024,
              max_rows: int = 32768) -> int:
        """Move queued transitions into HBM; bounded by ``max_rows`` per
        call so a deep backlog cannot stall the learner's update cadence —
        leftover rows carry to the next step's drain.

        Also the single-owner ingest boundary for the HBM rings, so the
        health sentinel's quarantine runs here (utils/health.py): a
        non-finite or schema-drifted row diverted to
        ``{log_dir}/quarantine/`` instead of being scattered into a ring
        every future minibatch samples from — and instead of crashing
        the learner's np.stack below on a shape drift."""
        from pytorch_distributed_tpu.memory.feeder import pop_chunks
        from pytorch_distributed_tpu.utils import flow, health, tracing
        from pytorch_distributed_tpu.utils.experience import (
            transition_dtypes,
        )

        assert self.replay is not None, "attach() first"
        items = pop_chunks(self._q, max_chunks)
        if items and health.quarantine_active():
            if self._validator is None:
                self._validator = health.ChunkValidator(
                    state_shape=self.state_shape,
                    state_dtype=self.state_dtype)
            items, bad = self._validator.filter(items)
            if bad:
                health.get_quarantine("feeder-device").put(
                    bad, trace_id=tracing.current_trace())
        self._pending.extend(t for t, _priority in items)
        if self._flow_params is None:
            self._flow_params = flow.resolve_flow()
        fp = self._flow_params
        if (fp.enabled and fp.local_policy == "shed"
                and len(self._pending) > fp.max_pending_rows):
            # the device-ingest shed point (ISSUE 11): oldest pending
            # rows beyond the bound are dropped, counted and
            # prov-stamped — newest experience wins, memory stays
            # bounded even when the drain cadence loses the race
            self._pending = flow.shed_overflow(
                self._pending, fp.max_pending_rows, self.flow_counters)
        fed = 0
        dt = transition_dtypes(self.replay.state_dtype,
                               self.replay.action_dtype)
        while fed < max_rows:
            C = next((s for s in self.chunk_sizes
                      if s <= len(self._pending)), None)
            if C is None:
                break
            rows, self._pending = self._pending[:C], self._pending[C:]
            chunk = Transition(*(
                np.stack([getattr(r, f) for r in rows]).astype(dt[f])
                for f in REPLAY_FIELDS),
                prov=experience.stack_prov(rows).astype(np.int32))
            self.replay.feed_chunk(chunk)
            fed += C
        self._fed_total += fed
        return fed


class DevicePerIngest(DeviceReplayIngest):
    """Queue front end for the HBM prioritized ring (device_per.py): same
    chunked ingestion; new rows enter at max priority, so the actor-side
    initial-priority plumbing is intentionally bypassed on this path —
    priorities live and update entirely on device."""

    def __init__(self, *args, priority_exponent: float = 0.6,
                 importance_weight: float = 0.4,
                 importance_anneal_steps: int = 500000, **kw):
        super().__init__(*args, **kw)
        self.priority_exponent = priority_exponent
        self.importance_weight = importance_weight
        self.importance_anneal_steps = importance_anneal_steps

    def _make_replay(self, capacity: int,
                     mesh: Optional[jax.sharding.Mesh]):
        from pytorch_distributed_tpu.memory.device_per import DevicePerReplay

        return DevicePerReplay(
            capacity, self.state_shape, self.action_shape,
            self.state_dtype, self.action_dtype,
            priority_exponent=self.priority_exponent,
            importance_weight=self.importance_weight,
            importance_anneal_steps=self.importance_anneal_steps,
            mesh=mesh)
