"""Seconds of set-up spent lowering programs to MLIR: JAX's
``/jax/core/compile/jaxpr_to_mlir_module_duration`` spans up to the moment
the step program was ready, from the program's compile-path record
(harness/compile_spans.py).  Lies inside the set-up phases, not beside them."""

from ..harness import compile_spans

METRIC = {"layer": "entry", "unit": "s", "better": "lower",
          "source": "program_span", "moves": "setup_s"}


def read(ctx):
    return compile_spans.setup_total(ctx, "lower_s")
