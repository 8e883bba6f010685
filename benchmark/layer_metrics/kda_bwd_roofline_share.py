"""Share of its roofline of the Pallas kernel ``kda_chunk_bwd``
(``pytorch_distributed_tpu/ops/pallas_kda.py``): the backward of the
channel-gated delta rule over a whole window, the chunks in reverse with the
states' cotangents carried in fast memory; one call a K block an update.
100 x the least time the chip could take for the kernel's calls of one update
(the larger of operations over 197 TFLOP/s and bytes over 819 GB/s: twice the
forward's operations, and the inputs, the output's cotangent and the five
cotangents it writes, counted from shapes: harness/kda_kernel_counts.py)
over the self time of the ops named ``kda_chunk_bwd[.n]`` in the traced
steps.  The kernel computes the chunk's forward products and pairwise decays
again and reads the kept states and inverse, which the count leaves out.
None where the step program holds no such kernel."""

from ..harness import kda_kernel_counts

METRIC = {"layer": "trunk_kda", "unit": "%", "better": "higher",
          "source": "program_span", "moves": "updates_per_s"}


def read(ctx):
    return kda_kernel_counts.roofline_share(ctx, "kda_chunk_bwd")
