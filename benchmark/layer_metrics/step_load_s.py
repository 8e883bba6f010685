"""Seconds of the step program's first backend span: its load from the
persistent compile cache (a cache hit on the same thread just before it),
or its compile where the cache had no entry (the driver's first run).
From the program's compile-path record (harness/compile_spans.py)."""

from ..harness import compile_spans

METRIC = {"layer": "entry", "unit": "s", "better": "lower",
          "source": "program_span", "moves": "setup_s"}


def read(ctx):
    return compile_spans.step_span_s(ctx, "first_ready")
