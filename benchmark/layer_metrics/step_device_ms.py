"""Device time of one learner update: median duration of the fused-step
program's module events in the trace, over the updates one dispatch fuses."""

from ..harness.trace import median

METRIC = {"layer": "fused_step", "unit": "ms", "better": "lower",
          "source": "device_trace", "moves": "updates_per_s"}


def read(ctx):
    if ctx.trace is None or not ctx.trace.step_ms:
        return None
    return median(ctx.trace.step_ms) / ctx.result.updates_per_dispatch
