"""Device ms per update inside ``gdn.chunk``, the scope ``model.gdn`` holds
around the delta rule's recurrence proper: the cumulative decay, the
triangular inverse of each chunk, the chunk products and the scan over chunk
states (projections, conv, norms and gate stay outside it).  All passes
together, as ``phase_gdn_ms``, of which it is a part (harness/gdn_scopes.py).
None where the program names no such scope."""

from ..harness import gdn_scopes

METRIC = {"layer": "trunk_gdn", "unit": "ms", "better": "lower",
          "source": "program_span", "moves": "updates_per_s"}


def read(ctx):
    return gdn_scopes.read(ctx, "gdn_chunk")
