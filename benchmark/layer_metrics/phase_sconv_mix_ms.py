"""Device ms per update inside ``sconv.mix``, the scope ``model.sconv``
holds around its gate-conv-gate middle (``B * x``, the three taps, ``C *``):
the memory-bound part of the mixer; the projections are the rest of
``phase_sconv_ms``, of which this is a part (harness/sconv_scopes.py).  All
passes together.  None where the program names no such scope."""

from ..harness import sconv_scopes

METRIC = {"layer": "trunk_sconv", "unit": "ms", "better": "lower",
          "source": "program_span", "moves": "updates_per_s"}


def read(ctx):
    return sconv_scopes.read(ctx, "sconv_mix")
