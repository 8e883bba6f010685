"""Device ms per update in every other op of the step program that stands under
no scope of the program: what its names do not explain.  With the seven
other ``phase_*_ms`` it adds up to the self time of all ops in the step's
events (harness/phases.py)."""

from ..harness import phases

METRIC = {"layer": "fused_step", "unit": "ms", "better": "lower",
          "source": "program_span", "moves": "updates_per_s"}


def read(ctx):
    return phases.read(ctx, phases.UNNAMED)
