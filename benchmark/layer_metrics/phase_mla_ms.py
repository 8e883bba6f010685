"""Device ms per update inside the model's scope ``model.mla``: the latent
attention mixer: the query and latent projections, the latent's norm, keys
and values expanded from it, the blocked causal softmax, the
out-projection.  All passes together, self time of the step program's ops
whose ``tf_op`` path holds that scope innermost among the model's
(harness/kda_scopes.py); cuts ``phase_target_ms`` + ``phase_online_ms``
another way.  None where the program names no such scope."""

from ..harness import kda_scopes

METRIC = {"layer": "trunk_mla", "unit": "ms", "better": "lower",
          "source": "program_span", "moves": "updates_per_s"}


def read(ctx):
    return kda_scopes.read(ctx, "mla")
