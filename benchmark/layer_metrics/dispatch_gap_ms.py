"""Median time on the chip between the end of one step program and the
start of the next.  In ``learner_only`` nothing else runs there, so this is
what the dispatch loop costs.  (No 95th percentile beside it: a tail wants
some two hundred gaps, and a traced stretch of a second or two holds 4 to
12 dispatches.)"""

from ..harness.trace import median

METRIC = {"layer": "dispatch_loop", "unit": "ms", "better": "lower",
          "source": "device_trace", "moves": "updates_per_s"}


def read(ctx):
    if ctx.trace is None or not ctx.trace.step_gaps_ms:
        return None
    return median(ctx.trace.step_gaps_ms)
