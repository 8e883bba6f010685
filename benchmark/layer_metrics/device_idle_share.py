"""1 - (union of the intervals in which an op ran on the chip) / window,
averaged over the chips, over the traced steady stretch."""

METRIC = {"layer": "device", "unit": "%", "better": "lower",
          "source": "device_trace", "moves": "updates_per_s"}


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.idle_share
