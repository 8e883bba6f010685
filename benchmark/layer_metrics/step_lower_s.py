"""Seconds of the step program's first lowering to MLIR
(``jaxpr_to_mlir_module_duration`` of ``jit(multi)`` / ``jit(one)`` /
``jit(multi_mega)``), from the program's compile-path record
(harness/compile_spans.py)."""

from ..harness import compile_spans

METRIC = {"layer": "entry", "unit": "s", "better": "lower",
          "source": "program_span", "moves": "setup_s"}


def read(ctx):
    return compile_spans.step_span_s(ctx, "first_lower")
