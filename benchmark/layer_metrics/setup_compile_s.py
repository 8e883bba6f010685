"""Seconds JAX spent compiling programs or loading them from the persistent
cache during set-up, in this process (summed
``/jax/core/compile/backend_compile_duration`` events).  Lies inside the
other set-up phases, not beside them."""

METRIC = {"layer": "entry", "unit": "s", "better": "lower",
          "source": "host_clock", "moves": "setup_s"}


def read(ctx):
    return ctx.result.notes.get("setup_compile_s")
