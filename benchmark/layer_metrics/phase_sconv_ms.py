"""Device ms per update inside the model's scope ``model.sconv``: the gated
short-convolution mixers: the in-projection to ``[B | C | x]``, the gates
and the causal depth-wise conv, the out-projection.  Target pass, online
pass, its backward and what ``jax.checkpoint`` computes again, together:
self time of the step program's ops whose ``tf_op`` path holds that scope
innermost among the model's (harness/sconv_scopes.py).  Cuts the time of
``phase_target_ms`` + ``phase_online_ms`` another way; does not add to the
eight phases.  None where the program names no such scope."""

from ..harness import sconv_scopes

METRIC = {"layer": "trunk_sconv", "unit": "ms", "better": "lower",
          "source": "program_span", "moves": "updates_per_s"}


def read(ctx):
    return sconv_scopes.read(ctx, "sconv")
