"""Timing inside a run: the compile counter, completion times of whole
dispatches, and the rate they give.

A rate here is never a count divided by the nominal ``--seconds``: it is
work completed between two completion times, over the time between them,
and the median of that over many such pairs, because a completion time is
read on the host's clock by a thread that the host may wake late.
"""

from __future__ import annotations

import collections
import queue
import statistics
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

# fires once per executable that is compiled OR loaded from the persistent
# cache (jax/_src/interpreters/pxla.py wraps compile_or_get_cached in it)
_PROGRAM_EVENT = "/jax/core/compile/backend_compile_duration"
# fires once per executable the persistent cache supplied
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """Counts, in this process, the programs JAX made ready (``programs``),
    how many of them came from the persistent cache (``loads``) and the
    seconds both took.  ``since(snapshot())`` counts a section, so set-up and
    the measured window are counted apart: the window must show no compile
    (programs - loads = 0)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._programs = 0
        self._loads = 0
        self._seconds = 0.0

    def install(self) -> "CompileCounter":
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def _on_time(self, event: str, duration: float, **_kw) -> None:
        if event == _PROGRAM_EVENT:
            with self._lock:
                self._programs += 1
                self._seconds += duration

    def _on_event(self, event: str, **_kw) -> None:
        if event == _CACHE_HIT_EVENT:
            with self._lock:
                self._loads += 1

    def snapshot(self) -> Tuple[int, int, float]:
        with self._lock:
            return self._programs, self._loads, self._seconds

    def since(self, snap: Tuple[int, int, float]) -> Tuple[int, int, float]:
        """(compiled, loaded from the cache, seconds) since ``snap``."""
        programs, loads, seconds = self.snapshot()
        programs, loads = programs - snap[0], loads - snap[1]
        return programs - loads, loads, seconds - snap[2]


class PhaseClock:
    """Host-clock seconds per named set-up phase."""

    def __init__(self, t_start: float) -> None:
        self.t_start = t_start
        self.seconds = collections.OrderedDict()
        self._t = t_start

    def lap(self, name: str) -> None:
        now = time.perf_counter()
        self.seconds[name] = self.seconds.get(name, 0.0) + (now - self._t)
        self._t = now


class CompletionWatcher:
    """Blocks on each dispatch's result IN ORDER on its own thread and
    records when it completed, so the enqueue loop stays pipelined as in
    ``run_learner``.  ``slots`` bounds the dispatches in flight: the loop
    takes a slot before it enqueues and the watcher gives it back, so that
    when the window closes only ``slots`` dispatches are left to wait for
    (``run_learner`` itself lets the runtime bound them).  The clock is read
    between ``block`` and ``read``, so fetching a result's counters is not
    part of its completion time."""

    def __init__(self, block: Callable[[object], None],
                 read: Callable[[object], float], slots: int) -> None:
        self._block = block               # returns when the dispatch is done
        self._read = read                 # its skipped count, fetched after
        self._q: "queue.Queue" = queue.Queue()
        self._slots = threading.Semaphore(slots)
        self.done_at: List[float] = []    # perf_counter per completed dispatch
        self.skipped: List[float] = []
        self.error: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._run, name="bench-watch",
                                        daemon=True)
        self._thread.start()

    def _run(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            try:
                self._block(item)
                done_at = time.perf_counter()
                skipped = self._read(item)
            except BaseException as e:  # noqa: BLE001 - re-raised by close()
                self.error = e
                self._slots.release()
                return
            self.done_at.append(done_at)
            self.skipped.append(skipped)
            self._slots.release()

    def take_slot(self) -> None:
        while not self._slots.acquire(timeout=1.0):
            if self.error is not None:
                raise self.error

    def submit(self, result: object) -> None:
        self._q.put(result)

    def completed(self) -> int:
        return len(self.done_at)

    def close(self, timeout: float = 300.0) -> None:
        """Wait for every submitted dispatch, then stop the thread."""
        self._q.put(None)
        self._thread.join(timeout)
        if self._thread.is_alive():
            raise TimeoutError("dispatches still in flight after "
                               f"{timeout:.0f} s")
        if self.error is not None:
            raise self.error


_PAIR_ENDS = 64    # completions taken from each end of the window, at most


def completion_rate(done_at: Sequence[float], t_open: float, t_close: float,
                    work_per_item: int) -> Tuple[Optional[float], int]:
    """Work per second from the ``n`` completion times inside ``[t_open,
    t_close]``: the median, over every pair of one completion ``i`` in the
    first quarter of them and one ``j`` in the last, of ``work * (j - i) /
    (t[j] - t[i])``.  Every pair spans at least half the window, so a stall
    of the device that recurs, or falls in the middle half, is in all of
    them and counts in full; a completion that the host reported late (the
    watcher woken late on a shared host: 1 % of a 20 s window is 0.2 s) is
    in a minority of the pairs and moves nothing.  With fewer than eight
    completions this is ``work * (n - 1) / (t[n] - t[1])``.  Returns
    ``(rate, n)``; the rate is None with fewer than two completions."""
    inside = [t for t in done_at if t_open <= t <= t_close]
    n = len(inside)
    if n < 2:
        return None, n
    ends = min(_PAIR_ENDS, max(1, n // 4))
    rates = [work_per_item * (j - i) / (inside[j] - inside[i])
             for i in range(ends) for j in range(n - ends, n)]
    return statistics.median(rates), n


def completion_jitter(done_at: Sequence[float], t_open: float, t_close: float,
                      work_per_item: int) -> Dict[str, float]:
    """For whoever reads a noisy run's log: the first-to-last rate that
    ``completion_rate`` would have been without its median, and how far
    the times between consecutive completions lie from their median."""
    inside = [t for t in done_at if t_open <= t <= t_close]
    if len(inside) < 3:
        return {}
    gaps = [b - a for a, b in zip(inside, inside[1:])]
    mid = statistics.median(gaps)
    return {"first_to_last_rate": work_per_item * len(gaps)
            / (inside[-1] - inside[0]),
            "interval_median_ms": 1e3 * mid,
            "interval_min_ms": 1e3 * min(gaps),
            "interval_max_ms": 1e3 * max(gaps),
            "intervals_off_by_1ms": sum(abs(g - mid) > 1e-3 for g in gaps)}
