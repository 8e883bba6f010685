"""Seconds of the step program's first outermost trace span
(``jaxpr_trace_duration``, trace name ``multi`` / ``one`` /
``multi_mega``), from the program's compile-path record
(harness/compile_spans.py)."""

from ..harness import compile_spans

METRIC = {"layer": "entry", "unit": "s", "better": "lower",
          "source": "program_span", "moves": "setup_s"}


def read(ctx):
    return compile_spans.step_span_s(ctx, "first_trace")
