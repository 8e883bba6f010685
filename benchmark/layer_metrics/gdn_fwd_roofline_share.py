"""Share of its roofline of the Pallas kernel ``gdn_chunk_fwd``
(``pytorch_distributed_tpu/ops/pallas_gated_delta.py``, called by
``models/gated_delta.py gated_delta_chunked`` on a TPU): the gated delta
rule over a whole window, a chunk of a key head's value heads a grid step,
every intermediate of the chunk in fast memory; three calls a delta-rule
block an update (target, online, recomputed).  100 x the least time the chip
could take for the kernel's calls of one update (the larger of operations
over 197 TFLOP/s and bytes over 819 GB/s, counted from shapes as the
algorithm needs them: harness/gdn_kernel_counts.py; at this cell's shapes
the bytes bind) over the self time of the ops named ``gdn_chunk_fwd[.n]`` in
the traced steps.  The kernel also writes each chunk's entry states and
inverse for the backward, which the count leaves out.  None where the step
program holds no such kernel."""

from ..harness import gdn_kernel_counts

METRIC = {"layer": "trunk_gdn", "unit": "%", "better": "higher",
          "source": "program_span", "moves": "updates_per_s"}


def read(ctx):
    return gdn_kernel_counts.roofline_share(ctx, "gdn_chunk_fwd")
