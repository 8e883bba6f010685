"""Seconds of set-up spent tracing programs, a nested trace counted once: the
union of JAX's ``/jax/core/compile/jaxpr_trace_duration`` spans up to the
moment the step program was ready, from the program's compile-path record
(harness/compile_spans.py).  A trace made while a program lowers (a Pallas
kernel's body) is in ``setup_lower_s`` alone.  Lies inside the set-up
phases, not beside them."""

from ..harness import compile_spans

METRIC = {"layer": "entry", "unit": "s", "better": "lower",
          "source": "program_span", "moves": "setup_s"}


def read(ctx):
    return compile_spans.setup_total(ctx, "trace_s")
