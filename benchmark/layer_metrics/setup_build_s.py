"""Set-up phase, host clock: the program's objects built from the factory
(options, env probe, model, seeded weights, train state, empty ring)."""

METRIC = {"layer": "entry", "unit": "s", "better": "lower",
          "source": "host_clock", "moves": "setup_s"}


def read(ctx):
    return ctx.phases.get("build")
