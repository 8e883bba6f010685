"""Trace spans in set-up, a nested one counted each time (every ``jnp`` call
inside a jitted function is a trace of its own): JAX's
``/jax/core/compile/jaxpr_trace_duration`` spans up to the moment the step
program was ready, from the program's compile-path record
(harness/compile_spans.py).  The same in every run of one tree."""

from ..harness import compile_spans

METRIC = {"layer": "entry", "unit": "traces", "better": "lower",
          "source": "program_counter", "moves": "setup_s"}


def read(ctx):
    return compile_spans.setup_total(ctx, "traces")
