"""Set-up phase, host clock: the ring filled to capacity on the device through the program's feed path."""

METRIC = {"layer": "entry", "unit": "s", "better": "lower",
          "source": "host_clock", "moves": "setup_s"}


def read(ctx):
    return ctx.phases.get("fill")
