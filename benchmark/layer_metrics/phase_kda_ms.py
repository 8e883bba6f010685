"""Device ms per update inside the model's scope ``model.kda``: the
channel-gated delta-rule mixers (Kimi Delta Attention): the q / k / v
projections and their convs, the low-rank decay and output gates, q / k
norms, the chunked recurrence, the gated norm, the out-projection.  Target
pass, online pass, its backward and what ``jax.checkpoint`` computes again,
together: self time of the step program's ops whose ``tf_op`` path holds
that scope innermost among the model's (harness/kda_scopes.py).  Cuts the
time of ``phase_target_ms`` + ``phase_online_ms`` another way; does not add
to the eight phases.  None where the program names no such scope."""

from ..harness import kda_scopes

METRIC = {"layer": "trunk_kda", "unit": "ms", "better": "lower",
          "source": "program_span", "moves": "updates_per_s"}


def read(ctx):
    return kda_scopes.read(ctx, "kda")
