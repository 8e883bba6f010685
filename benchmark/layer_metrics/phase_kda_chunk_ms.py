"""Device ms per update inside ``kda.chunk``, the scope ``model.kda`` holds
around the channel-gated delta rule's recurrence proper: the cumulative
per-channel decay, the sub-blocked intra-chunk products, the triangular
inverse of each chunk, the chunk products and the scan over chunk states
(projections, convs, gates and norms stay outside it).  All passes together,
as ``phase_kda_ms``, of which it is a part (harness/kda_scopes.py).  None
where the program names no such scope."""

from ..harness import kda_scopes

METRIC = {"layer": "trunk_kda", "unit": "ms", "better": "lower",
          "source": "program_span", "moves": "updates_per_s"}


def read(ctx):
    return kda_scopes.read(ctx, "kda_chunk")
