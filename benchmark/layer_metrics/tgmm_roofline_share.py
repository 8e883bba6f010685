"""Share of its roofline of the Pallas kernel ``tgmm``
(``jax.experimental.pallas.ops.tpu.megablox``, called by
``models/hybrid.py grouped_dot``): the gradients of the routed experts' weights: each held expert's rows, transposed, times their cotangents.
100 x the least time the chip could take for the kernel's calls of one update
(the larger of operations over 197 TFLOP/s and bytes over 819 GB/s, counted
from shapes at the EXPECTED rows routed to the experts held:
harness/kernel_counts.py) over the self time of the ops named ``tgmm[.n]`` in
the traced steps (the device trace read through the program's kernel names,
as the ``phase_*_ms`` read it through its scopes).  The expert layers compute a run of sorted rows whole, twice
the expected rows, so the share cannot pass about half.  None where the step
program holds no such kernel."""

from ..harness import kernel_counts

METRIC = {"layer": "trunk_moe", "unit": "%", "better": "higher",
          "source": "program_span", "moves": "updates_per_s"}


def read(ctx):
    return kernel_counts.roofline_share(ctx, "tgmm")
