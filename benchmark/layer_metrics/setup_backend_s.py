"""Set-up phase, host clock: process start -> JAX has its devices (interpreter, imports, TPU backend start-up)."""

METRIC = {"layer": "entry", "unit": "s", "better": "lower",
          "source": "host_clock", "moves": "setup_s"}


def read(ctx):
    return ctx.phases.get("backend")
