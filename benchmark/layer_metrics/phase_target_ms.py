"""Device ms per update inside the program's scope
``train.target``: every pass through ``target_params`` (DQN ``q_next``; R2D2
target burn-in + unroll).
Self time of the step program's ops whose ``tf_op`` path holds that scope
innermost, over the updates of the whole step events in the traced window
(harness/phases.py).  None where the program names no such scope."""

from ..harness import phases

METRIC = {"layer": "fused_step", "unit": "ms", "better": "lower",
          "source": "program_span", "moves": "updates_per_s"}


def read(ctx):
    return phases.read(ctx, "target")
