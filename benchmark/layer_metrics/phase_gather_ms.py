"""Device ms per update inside the program's scope
``replay.gather``: the row / segment gathers out of the ring, frame unpacking
included; a layout copy the compiler files under the gather it serves is here too.
Self time of the step program's ops whose ``tf_op`` path holds that scope
innermost, over the updates of the whole step events in the traced window
(harness/phases.py).  None where the program names no such scope."""

from ..harness import phases

METRIC = {"layer": "replay_ring", "unit": "ms", "better": "lower",
          "source": "program_span", "moves": "updates_per_s"}


def read(ctx):
    return phases.read(ctx, "gather")
