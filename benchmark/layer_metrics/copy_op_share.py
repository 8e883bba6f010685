"""Share of the traced window the chip spends in HLO ``copy`` ops: self time
of EVERY op whose name starts with ``copy`` (``copy.N``, ``copy-start``,
``copy-done``), averaged over the chips.  These are layout changes and
whole-buffer copies: the ring's arrays live in a layout the fused step's
gathers and the feed's scatters do not take, so XLA re-tiles them per
program (PERF.md, findings of PR 22).

Reads HLO op names, which is all the trace offers until the program names
its scopes: a re-tiling that XLA fuses into a ``fusion.N`` leaves this
metric and not the chip.  Read it beside ``step_device_ms``, which cannot be
fooled that way."""

METRIC = {"layer": "replay_ring", "unit": "%", "better": "lower",
          "source": "device_trace", "moves": "updates_per_s"}


def read(ctx):
    if ctx.trace is None:
        return None
    seconds = sum(s for name, s in ctx.trace.op_self_s.items()
                  if name.startswith("copy"))
    return 100.0 * seconds / ctx.trace.window_s
