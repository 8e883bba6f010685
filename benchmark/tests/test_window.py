"""Window timing: rates from completion times, the compile counter, the
completion watcher."""

import threading
import time

import pytest

from benchmark.harness import window


def test_rate_is_work_between_completions_over_the_time_between_them():
    done = [0.5, 1.0, 2.0, 3.0, 4.0, 9.0]
    # window [0.9, 4.5] holds completions at 1, 2, 3, 4: three intervals
    rate, n = window.completion_rate(done, 0.9, 4.5, work_per_item=32)
    assert n == 4 and rate == pytest.approx(32 * 3 / 3.0)


def test_rate_needs_two_completions():
    assert window.completion_rate([1.0], 0.0, 2.0, 32) == (None, 1)
    assert window.completion_rate([], 0.0, 2.0, 32) == (None, 0)


def _steady(n=40, every=0.5):
    return [10.0 + every * i for i in range(n)]


@pytest.mark.parametrize("late", [
    {0: 0.11}, {39: 0.11}, {0: 0.11, 39: 0.08}, {1: 0.05, 2: 0.2, 38: 0.11},
    {20: 0.3}], ids=["first", "last", "both-ends", "three", "middle"])
def test_rate_ignores_completions_the_host_reported_late(late):
    """What the driver's check met in PR 22: the device kept its pace and
    the watcher thread was woken late on a few completions.  First-to-last
    would read 0.6 % low or high here."""
    done = _steady()
    for i, by in late.items():
        done[i] += by
    rate, n = window.completion_rate(done, 0.0, 100.0, work_per_item=32)
    assert n == 40 and rate == pytest.approx(32 / 0.5, rel=1e-9)
    jitter = window.completion_jitter(done, 0.0, 100.0, 32)
    assert jitter["intervals_off_by_1ms"] >= len(late)
    assert jitter["interval_median_ms"] == pytest.approx(500.0)


@pytest.mark.parametrize("stall_every", [1000, 8, 3],
                         ids=["once", "every-8th", "every-3rd"])
def test_rate_counts_a_stall_of_the_device_in_full(stall_every):
    """A real stall moves every later completion, so it is in every pair:
    the rate is the work over the time it really took."""
    done, t = [], 10.0
    for i in range(40):
        t += 0.5 + (0.2 if i % stall_every == 20 % stall_every else 0.0)
        done.append(t)
    rate, _ = window.completion_rate(done, 0.0, 100.0, work_per_item=32)
    true_rate = 32 * 39 / (done[-1] - done[0])
    assert true_rate < 0.991 * 32 / 0.5
    assert rate == pytest.approx(true_rate, rel=0.004)


def test_rate_of_a_long_window_stays_cheap():
    done = _steady(n=20000, every=0.001)
    t0 = time.perf_counter()
    rate, n = window.completion_rate(done, 0.0, 100.0, work_per_item=1)
    assert time.perf_counter() - t0 < 0.5
    assert n == 20000 and rate == pytest.approx(1000.0, rel=1e-6)


def test_compile_counter_counts_programs_and_sections():
    import jax
    import jax.numpy as jnp

    counter = window.CompileCounter().install()
    f = jax.jit(lambda x: x * 3 + 1)
    seven, nine = jnp.ones(7), jnp.ones(9)
    f(seven).block_until_ready()
    programs, loads, seconds = counter.snapshot()
    assert programs >= 1 and loads == 0 and seconds > 0
    before = counter.snapshot()
    f(seven).block_until_ready()                # cached: nothing compiles
    assert counter.since(before)[:2] == (0, 0)
    f(nine).block_until_ready()                 # a new shape compiles
    assert counter.since(before)[:2] == (1, 0)


def test_watcher_bounds_in_flight_and_records_in_order():
    gate = threading.Event()
    seen = []

    def wait(item):
        gate.wait(5.0)
        seen.append(item)

    w = window.CompletionWatcher(wait, lambda item: 0.0, slots=2)
    w.take_slot(); w.submit("a")
    w.take_slot(); w.submit("b")
    blocked = threading.Thread(target=w.take_slot)
    blocked.start()
    blocked.join(0.2)
    assert blocked.is_alive()                   # third slot not free yet
    gate.set()
    blocked.join(5.0)
    assert not blocked.is_alive()
    w.submit("c")
    w.close(timeout=5.0)
    assert seen == ["a", "b", "c"] and w.completed() == 3
    assert w.done_at == sorted(w.done_at)


def test_watcher_reads_the_clock_before_it_fetches_counters():
    read_at = []

    def read(item):
        read_at.append(time.perf_counter())
        time.sleep(0.05)
        return 2.0

    w = window.CompletionWatcher(lambda item: None, read, slots=1)
    w.take_slot(); w.submit("a")
    w.close(timeout=5.0)
    assert w.done_at[0] <= read_at[0] and w.skipped == [2.0]


def test_watcher_hands_a_failure_to_the_loop():
    def wait(item):
        raise RuntimeError("device lost")

    w = window.CompletionWatcher(wait, lambda item: 0.0, slots=1)
    w.take_slot(); w.submit("a")
    with pytest.raises(RuntimeError, match="device lost"):
        deadline = time.time() + 5.0
        while time.time() < deadline:
            w.take_slot()
    with pytest.raises(RuntimeError, match="device lost"):
        w.close(timeout=5.0)
