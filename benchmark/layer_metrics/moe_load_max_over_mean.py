"""Rows the busiest held expert received over the mean of the held experts,
in the worst expert layer, of the check's one update: the program's own
counter ``learner/moe_load_max_over_mean`` (models/hybrid.py ``moe_stats``),
handed over in the family's ``agrees`` result.  1 = balanced; the grouped
matmuls' work follows the rows, not the maximum, so imbalance costs tiles
that are part empty, not padding.  None where the family counts no routing."""

METRIC = {"layer": "trunk_moe", "unit": "ratio", "better": "lower",
          "source": "program_counter", "moves": "updates_per_s"}


def read(ctx):
    return ctx.result.check.get("moe", {}).get("load_max_over_mean")
