"""Rows the expert layers' grouped matmuls were handed over the rows routed
to the experts held, of the check's one update: the program's own counters
``learner/moe_rows_computed`` ÷ ``learner/moe_rows_here`` (models/hybrid.py
``moe_stats``; means over the expert layers).  1 = no padding; the layer
computes every run of sorted rows that holds a routed row WHOLE, the first
run twice the balanced load, so 2 is a level router's reading.  None where
the family counts neither (harness/gdn_scopes.py)."""

from ..harness import gdn_scopes

METRIC = {"layer": "trunk_moe", "unit": "ratio", "better": "lower",
          "source": "program_counter", "moves": "updates_per_s"}


def read(ctx):
    return gdn_scopes.rows_computed_over_routed(ctx)
