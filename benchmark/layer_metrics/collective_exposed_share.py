"""Share of the traced window in which a chip sits in a collective op
(all-reduce, all-gather, collective-permute, reduce-scatter, all-to-all)
with nothing else running on it: the op's self time on the chip's one
in-order core, averaged over the chips."""

METRIC = {"layer": "mesh_collectives", "unit": "%", "better": "lower",
          "source": "device_trace", "moves": "updates_per_s"}


def read(ctx):
    if ctx.trace is None or ctx.device_count < 2:
        return None
    return 100.0 * ctx.trace.collective_exposed_s / ctx.trace.window_s
