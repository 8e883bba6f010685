"""Shared inference batcher: SEED-style centralized actor forwards.

The inline/pipelined actor loops run rollout inference on each actor
process's OWN host CPU (utils/helpers.pin_to_cpu — the learner alone owns
the accelerator).  That is the right call when the accelerator is remote
or contended, but it leaves the chip idle between learner dispatches and
burns the actor host's cores on convnet forwards: an actor's tick is
mostly batched CNN inference on a CPU while a TPU idles (ISSUE 4
motivation; the split of a tick on the directly attached chip's host is
not measured: no benchmark cell runs the actor plane, PERF.md section 7).

``actor_backend=batched`` flips the topology to the SEED architecture
(Espeholt et al. 2019; PAPERS.md): actor processes stop holding model
replicas entirely — no param fetches, no unravels, no local jit — and
submit observation batches to an ``InferenceServer`` THREAD living in the
process that owns the accelerator (the learner parent, runtime.py).  The
server coalesces whatever requests are pending, runs ONE wide forward on
the device, and scatters packed results back over per-client queues.  The
actor's software pipeline (agents/actor.py) is unchanged: submit is the
dispatch, collect is the sync, and the device forward + transfers overlap
the host's env stepping and feed work.

Determinism: per-row PRNG keys are ``fold_in(fold_in(fold_in(root, tick?
no — actor base key), tick), row)`` (models/policies.tick_keys), a pure
function of (actor, tick, row) — so action streams are independent of how
rows get batched together, and on a same-device server they are
bit-identical to the local loops.  What batched mode does NOT preserve is
the actors' weight-staleness schedule: the server refreshes from the
ParamStore on its own throttle (``sync_secs``), not per-actor cadences.

Wire format is deliberately dumb — numpy arrays over spawn-context
queues; clients are picklable and carry no jax state, so a batched actor
process never needs a model, flattener, or prefetcher.
"""

from __future__ import annotations

import multiprocessing as mp
import queue as _queue
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from pytorch_distributed_tpu.config import Options

_CTX = mp.get_context("spawn")

# response payload marker for a server-side failure: clients re-raise
# instead of hanging on a queue nobody will ever fill again
_ERROR = "__inference_error__"


class InferenceClient:
    """Actor-side handle: submit/collect one in-flight request.

    Picklable (rides the actor spec tuple through spawn); holds only the
    shared request queue, this client's response queue, and its row
    geometry.  ``begin_session`` must be called in the actor process
    before the first submit — it stamps a fresh nonce so responses to a
    dead incarnation of this slot (actor restarts are routine, runtime
    supervision) can never be mistaken for this one's.
    """

    def __init__(self, client_id: int, family: str, req_q, resp_q):
        self.client_id = client_id
        self.family = family
        self._req_q = req_q
        self._resp_q = resp_q
        self._nonce = 0
        self._key: Optional[np.ndarray] = None
        self._eps: Optional[np.ndarray] = None
        self._prev_obs: Optional[np.ndarray] = None

    def begin_session(self, base_key=None, eps=None) -> None:
        """Fresh incarnation: drain stale responses, stamp a nonce, bind
        this actor's PRNG base key + per-env epsilon ladder (sent with
        every request — a few dozen bytes — so the server stays
        stateless about clients)."""
        self._nonce = int(time.monotonic_ns() & 0x7FFFFFFF) or 1
        if base_key is not None:
            self._key = np.asarray(base_key)
        if eps is not None:
            self._eps = np.asarray(eps, np.float32)
        self._prev_obs = None  # first request re-seeds the server stack
        while True:
            try:
                self._resp_q.get_nowait()
            except _queue.Empty:
                break

    def submit(self, obs: np.ndarray, tick: int) -> int:
        """Ship this tick's obs.  Frame-stacked uint8 image batches whose
        rows all satisfy the roll property (``obs[:, :-1] == prev[:,
        1:]`` — no env reset this tick) go FRAME-PACKED: only the newest
        frame per env crosses to the server, which rolls its
        device-resident stack (models/policies.build_packed_roll_act);
        anything else — first tick, any reset, low-dim obs — ships full
        and re-seeds the server's stack.  The check is a cheap host
        memcmp against the previous tick, so packing is automatic and
        env-agnostic: it can never desync the device stack from what the
        env actually emitted."""
        obs = np.ascontiguousarray(obs)
        mode = "full"
        if (self.family == "dqn" and obs.dtype == np.uint8
                and obs.ndim >= 3 and obs.shape[1] > 1
                and self._prev_obs is not None
                and np.array_equal(obs[:, :-1], self._prev_obs[:, 1:])):
            mode = "packed"
            payload = np.ascontiguousarray(obs[:, -1])
        else:
            payload = obs
        self._prev_obs = obs
        self._req_q.put((self.client_id, self._nonce, int(tick), mode,
                         payload, self._eps, self._key))
        return int(tick)

    def collect(self, handle: int, timeout: float = 300.0) -> np.ndarray:
        """Block for the response to ``handle`` (the submitted tick).
        Responses from an older incarnation are dropped; a server error
        sentinel re-raises here so the actor dies loudly instead of
        spinning against a dead server."""
        deadline = time.monotonic() + timeout
        while True:
            remain = deadline - time.monotonic()
            if remain <= 0:
                raise TimeoutError(
                    f"inference client {self.client_id}: no response for "
                    f"tick {handle} within {timeout}s (server dead?)")
            try:
                nonce, tick, payload = self._resp_q.get(timeout=remain)
            except _queue.Empty:
                continue
            if isinstance(payload, tuple) and payload[:1] == (_ERROR,):
                raise RuntimeError(
                    f"inference server failed: {payload[1]}")
            if nonce != self._nonce:
                continue  # a dead incarnation's leftover
            if tick != handle:
                raise RuntimeError(
                    f"inference client {self.client_id}: got tick {tick}, "
                    f"expected {handle} (protocol violated)")
            return payload


class InferenceServer:
    """Batching forward server; one thread in the accelerator-owning
    process (runtime.Topology starts/stops it when
    ``actor_backend=batched``).

    Scheduling is greedy coalescing: block for the first pending request,
    then sweep whatever else is already queued (no artificial batching
    window — with pipelined clients there is always a tick of host work
    in flight to hide the forward under, and a wait would add straggler
    latency for nothing).  The single-client case — the production 1x16
    topology — skips concat/pad entirely and dispatches the same fused
    ``build_packed_act`` program the local pipelined loop runs, with the
    obs buffer device_put once and handed to the jit.
    Multi-client sweeps concatenate rows, pad to a power-of-two bucket
    (bounded compile count), and scatter the packed columns back.
    """

    def __init__(self, opt: Options, spec, param_store,
                 max_batch: int = 1024, sync_secs: float = 1.0):
        assert opt.agent_type in ("dqn", "ddpg"), (
            f"batched inference serves the flat families, not "
            f"{opt.agent_type} (recurrent actors keep per-env carry "
            f"state; resolve_actor_backend downgrades them)")
        self.opt = opt
        self.spec = spec
        self.param_store = param_store
        self.max_batch = max_batch
        self.sync_secs = sync_secs
        self._req_q = _CTX.Queue()
        self._clients: Dict[int, Any] = {}
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._params = None
        self._version = 0
        self._last_sync = 0.0
        # per-client device-resident frame stacks for the packed path:
        # client_id -> device array, or ("host", rows) parked seed
        self._stacks: Dict[int, Any] = {}
        # observability: swept into the learner-side metrics by whoever
        # owns the server (tests read them off the object directly)
        self.stats = {"requests": 0, "batches": 0, "rows": 0,
                      "widest_batch": 0, "param_refreshes": 0}
        # perf plane (utils/perf.py): served-rows counter + retrace
        # watch on the server's jits; lands in the T_STATUS ``perf``
        # block via the process registry (the server lives in the
        # gateway's process, so no extra plumbing)
        from pytorch_distributed_tpu.utils import perf

        self.perf = perf.get_monitor("inference", opt.perf_params)

    # -- wiring (parent process, before spawn) ------------------------------

    def make_client(self, client_id: int) -> InferenceClient:
        resp_q = _CTX.Queue()
        self._clients[client_id] = resp_q
        return InferenceClient(client_id, self.opt.agent_type,
                               self._req_q, resp_q)

    def start(self) -> None:
        self._thread = threading.Thread(target=self._serve,
                                        name="inference-server",
                                        daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._req_q.put(None)  # wake the blocking get
        if self._thread is not None:
            self._thread.join(timeout=10.0)

    def healthy(self) -> bool:
        """False once the serve thread has died abnormally.  Watched by
        the runtime monitor: without it, a dead server turns every
        supervised actor restart into a full collect() timeout — the
        crashed thread broadcasts ONE error sentinel per live client,
        but a freshly respawned actor drains its queue in begin_session
        and then blocks on a server that will never answer, burning the
        restart budget at 300 s per attempt instead of failing fast."""
        return (self._thread is None or self._thread.is_alive()
                or self._stop.is_set())

    # -- device programs ----------------------------------------------------

    def _build(self) -> None:
        """Model + jitted programs, built lazily INSIDE the serve thread:
        the constructor runs in the parent before workers spawn, and
        paying the device compile there would serialize it against the
        learner's own startup compiles."""
        import jax

        from pytorch_distributed_tpu.factory import (
            build_model, init_params,
        )
        from pytorch_distributed_tpu.models.policies import (
            build_packed_act, build_packed_act_rowkeys, tick_keys,
        )
        from pytorch_distributed_tpu.agents.param_store import (
            make_flattener,
        )

        model = build_model(self.opt, self.spec)
        params0 = init_params(self.opt, self.spec, model,
                              seed=self.opt.seed)
        _, self._unravel = make_flattener(params0)
        if self.opt.agent_type == "dqn":
            # no donate_obs: a feedforward act has no output that can
            # alias the obs buffer, so donation would only warn (the
            # buffers XLA genuinely reuses in place are the RECURRENT
            # carry and the frame-packed roll stack below)
            self._act_single = build_packed_act(model.apply)
            self._act_rows = build_packed_act_rowkeys(model.apply)
            from pytorch_distributed_tpu.models.policies import (
                build_packed_roll_act,
            )

            self._roll_act = build_packed_roll_act(model.apply)
        else:  # ddpg: deterministic forward, noise stays actor-side
            def act(p, o):
                return model.apply(p, o, method=model.forward_actor)

            self._act_single = jax.jit(act)
            self._act_rows = self._act_single
        # per-row key expanders, cached per row count (row counts are
        # per-client env widths — a handful of static shapes)
        self._expanders: Dict[int, Any] = {}

        def expander(n: int):
            fn = self._expanders.get(n)
            if fn is None:
                fn = jax.jit(lambda bk, t: tick_keys(bk, t, n))
                self._expanders[n] = fn
            return fn

        self._expander = expander
        # this thread's programs are told apart by name (act / act_rows /
        # roll_act): the retrace detector sees a compile by name only
        self.perf.register_jit("act_single", self._act_single)
        self.perf.register_jit("act_rows", self._act_rows)

    def _refresh_params(self, block: bool) -> None:
        """Pull the newest published weights onto the device.  Blocking
        only for the very first request (nobody can act on unseeded
        weights); afterwards refreshes ride a ``sync_secs`` throttle so
        a fast-publishing learner can't turn the weight plane into a
        device-transfer firehose."""
        now = time.monotonic()
        if self._params is not None:
            if (now - self._last_sync < self.sync_secs
                    or self.param_store.version <= self._version):
                return
            got = self.param_store.fetch(self._version)
        else:
            got = self.param_store.wait(0, timeout=300.0,
                                        stop=self._stop) if block else None
        if got is None:
            return
        flat, version = got
        self._params = self._unravel(flat)  # lands on the server device
        self._version = version
        self._last_sync = now
        self.stats["param_refreshes"] += 1

    # -- serve loop ---------------------------------------------------------

    def _serve(self) -> None:
        perf_writer = None
        last_perf = time.monotonic()
        try:
            self._build()
            if self.perf.enabled:
                # the server owns no stats cadence of its own, so the
                # serve loop drains its monitor every ~15 s — without
                # this the registered retrace watch never runs and the
                # served-frames rate never reaches the metrics stream
                from pytorch_distributed_tpu.utils.metrics import (
                    MetricsWriter,
                )

                perf_writer = MetricsWriter(
                    self.opt.log_dir, enable_tensorboard=False,
                    role="inference", run_id=self.opt.refs)
                self.perf.drain()  # anchor past the build compiles
            while not self._stop.is_set():
                if perf_writer is not None \
                        and time.monotonic() - last_perf >= 15.0:
                    last_perf = time.monotonic()
                    perf_writer.scalars(self.perf.drain(), step=0)
                try:
                    first = self._req_q.get(timeout=0.2)
                except _queue.Empty:
                    continue
                if first is None:
                    continue
                batch = [first]
                rows = len(first[4])
                while rows < self.max_batch:
                    try:
                        nxt = self._req_q.get_nowait()
                    except _queue.Empty:
                        break
                    if nxt is None:
                        continue
                    batch.append(nxt)
                    rows += len(nxt[4])
                self._refresh_params(block=True)
                self.stats["requests"] += len(batch)
                self.stats["batches"] += 1
                self.stats["rows"] += rows
                self.stats["widest_batch"] = max(
                    self.stats["widest_batch"], rows)
                self.perf.note_frames(rows)
                # Frame-packed requests carry per-client device state
                # (the roll stack), so they dispatch as one small fused
                # program per client — ALL issued asynchronously first,
                # then synced, so N packed clients cost N dispatches but
                # only one device round-trip of latency, not N blocking
                # syncs.  Full requests coalesce into one wide forward.
                # The trade is deliberate: packing buys a C-factor
                # upload cut per client at the price of the cross-client
                # wide batch; the topology this serves is a few actors
                # with WIDE env vectors (the wide batch is already
                # inside each request), not a large fleet of narrow
                # ones — those should run unpacked low-dim obs, which
                # coalesce below.
                inflight = [self._begin_packed(req) for req in batch
                            if req[3] == "packed"]
                full = [r for r in batch if r[3] == "full"]
                if full:
                    self._dispatch(full)
                for (cid, nonce, tick), out in inflight:
                    self._clients[cid].put((nonce, tick,
                                            np.asarray(out)))
        except BaseException as e:  # noqa: BLE001 - broadcast, then die
            if self._stop.is_set():
                return  # shutdown race (e.g. interrupted param wait)
            from pytorch_distributed_tpu.utils import flight_recorder

            flight_recorder.get_recorder("inference").record(
                "server-crash", error=repr(e))
            err = (0, 0, (_ERROR, repr(e)))
            for resp_q in self._clients.values():
                try:
                    resp_q.put(err)
                except Exception:  # noqa: BLE001
                    pass
            if not self._stop.is_set():
                raise
        finally:
            if perf_writer is not None:
                perf_writer.scalars(self.perf.drain(), step=0)
                perf_writer.close()

    def _begin_packed(self, req: Tuple):
        """Dispatch one frame-packed request WITHOUT syncing: roll the
        client's device-resident stack by its new frames and act, fused
        in one program — only the newest frame crossed the host-to-device
        link.  Returns ``((cid, nonce, tick), out_handle)``
        for the caller to sync after every pending dispatch is issued.
        The stack seed always exists: a client's first
        post-``begin_session`` submit is a full upload by
        construction."""
        import jax

        cid, nonce, tick, _mode, new, eps, key = req
        stack = self._stacks[cid]
        if isinstance(stack, tuple):  # host-parked seed (multi-path full)
            stack = jax.device_put(stack[1])
        stack, out = self._roll_act(self._params, stack,
                                    jax.device_put(new), np.asarray(key),
                                    tick, np.asarray(eps, np.float32))
        self._stacks[cid] = stack
        if hasattr(out, "copy_to_host_async"):
            out.copy_to_host_async()
        return (cid, nonce, tick), out

    def _dispatch(self, batch: List[Tuple]) -> None:
        import jax

        if len(batch) == 1:
            cid, nonce, tick, _mode, obs, eps, key = batch[0]
            obs_dev = jax.device_put(obs)
            if self.family == "dqn":
                # the full upload doubles as the roll-stack seed for any
                # frame-packed follow-ups (obs_dev is NOT donated here)
                self._stacks[cid] = obs_dev
                out = self._act_single(self._params, obs_dev,
                                       np.asarray(key), tick,
                                       np.asarray(eps, np.float32))
            else:
                out = self._act_single(self._params, obs_dev)
            self._clients[cid].put((nonce, tick, np.asarray(out)))
            return
        # multi-client sweep: one wide forward over concatenated rows,
        # padded to a power-of-two bucket so compile count stays bounded
        sizes = [len(req[4]) for req in batch]
        total = sum(sizes)
        padded = 1
        while padded < total:
            padded *= 2
        obs = np.concatenate([req[4] for req in batch])
        if self.family == "dqn":
            for req in batch:  # park roll-stack seeds host-side (lazy
                self._stacks[req[0]] = ("host", req[4])  # upload on use)
        if padded > total:
            obs = np.concatenate(
                [obs, np.zeros((padded - total, *obs.shape[1:]),
                               obs.dtype)])
        obs_dev = jax.device_put(obs)
        if self.family == "dqn":
            keys = [np.asarray(self._expander(n)(np.asarray(req[6]),
                                                 req[2]))
                    for n, req in zip(sizes, batch)]
            keys.append(np.zeros((padded - total, 2),
                                 keys[0].dtype))
            eps = np.concatenate(
                [np.asarray(req[5], np.float32) for req in batch]
                + [np.zeros(padded - total, np.float32)])
            out = np.asarray(self._act_rows(self._params, obs_dev,
                                            np.concatenate(keys), eps))
            cuts = np.cumsum(sizes)[:-1]
            parts = np.split(out[:, :total], cuts, axis=1)
        else:
            out = np.asarray(self._act_rows(self._params, obs_dev))
            parts = np.split(out[:total], np.cumsum(sizes)[:-1])
        for (cid, nonce, tick, _m, _o, _e, _k), part in zip(batch, parts):
            self._clients[cid].put((nonce, tick, part))

    @property
    def family(self) -> str:
        return self.opt.agent_type
