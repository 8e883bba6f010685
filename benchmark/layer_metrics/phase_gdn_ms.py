"""Device ms per update inside the model's scope ``model.gdn``: the
gated-delta-rule mixers: the fused in-projections, conv, q / k norms, the
chunked recurrence, the gated norm, the out-projection.  Target pass, online
pass, its backward and what ``jax.checkpoint`` computes again, together:
self time of the step program's ops whose ``tf_op`` path holds that scope
innermost among the model's (harness/gdn_scopes.py).  Cuts the time of
``phase_target_ms`` + ``phase_online_ms`` another way; does not add to the
eight phases.  None where the program names no such scope."""

from ..harness import gdn_scopes

METRIC = {"layer": "trunk_gdn", "unit": "ms", "better": "lower",
          "source": "program_span", "moves": "updates_per_s"}


def read(ctx):
    return gdn_scopes.read(ctx, "gdn")
