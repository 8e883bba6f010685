"""Set-up phase, host clock: the warm-up dispatches of the step program, with
its compile or its load from the cache."""

METRIC = {"layer": "entry", "unit": "s", "better": "lower",
          "source": "host_clock", "moves": "setup_s"}


def read(ctx):
    return ctx.phases.get("warm")
