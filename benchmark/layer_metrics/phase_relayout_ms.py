"""Device ms per update in ops of the step program that stand under NO scope of
the program and that the compiler files under ``hlo_category`` "data
formatting": layout changes and copies it added on its own account, such as
the re-tiling of the whole ring at the top of every dispatch.  The successor
of ``copy_op_share`` that goes by the compiler's category and not by an op's
name (``copy.33``): a recompile cannot rename it (harness/phases.py)."""

from ..harness import phases

METRIC = {"layer": "replay_ring", "unit": "ms", "better": "lower",
          "source": "program_span", "moves": "updates_per_s"}


def read(ctx):
    return phases.read(ctx, phases.RELAYOUT)
