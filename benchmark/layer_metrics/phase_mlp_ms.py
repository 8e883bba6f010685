"""Device ms per update inside the model's scope ``model.mlp``: the dense
SwiGLU feed-forward block of the trunk's leading layer.  All passes
together, self time of the step program's ops whose ``tf_op`` path holds
that scope innermost among the model's (harness/kda_scopes.py); cuts
``phase_target_ms`` + ``phase_online_ms`` another way.  None where the
program names no such scope."""

from ..harness import kda_scopes

METRIC = {"layer": "trunk_mlp", "unit": "ms", "better": "lower",
          "source": "program_span", "moves": "updates_per_s"}


def read(ctx):
    return kda_scopes.read(ctx, "mlp")
