"""Model FLOP/s utilisation of the whole job over the traced steady stretch:
updates/s there (updates per dispatch x step programs started, over the time
between the first and the last start) x FLOPs one update needs (counted from
the published architecture by the configuration's family file,
``families/<family>.py``) over chips x the chip's bf16 peak.  A utilisation,
not a kernel's roofline share, and it says nothing about idle time.  Read
from the trace and not from the run's own window: stopping the profiler
stalls the loop for seconds."""

METRIC = {"layer": "fused_step", "unit": "%", "better": "higher",
          "source": "device_trace", "moves": "updates_per_s"}


def read(ctx):
    starts = ctx.trace.step_starts_s if ctx.trace is not None else []
    if len(starts) < 2 or ctx.peaks is None:
        return None
    rate = ctx.result.updates_per_dispatch * (len(starts) - 1) / (
        starts[-1] - starts[0])
    return 100.0 * rate * ctx.flops_per_update() / (
        ctx.device_count * ctx.peaks.flops_bf16)
