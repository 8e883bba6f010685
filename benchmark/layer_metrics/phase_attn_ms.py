"""Device ms per update inside the model's scope ``model.attn``:
the grouped-query attention layer: projections and the blocked causal softmax.
Target pass, online pass, its backward and what ``jax.checkpoint`` computes
again, together: self time of the step program's ops whose ``tf_op`` path
holds that scope innermost among the model's (harness/model_scopes.py).  Cuts
the time of ``phase_target_ms`` + ``phase_online_ms`` another way; does not
add to the eight phases.  None where the program names no such scope."""

from ..harness import model_scopes

METRIC = {"layer": "trunk_attn", "unit": "ms", "better": "lower",
          "source": "program_span", "moves": "updates_per_s"}


def read(ctx):
    return model_scopes.read(ctx, "attn")
