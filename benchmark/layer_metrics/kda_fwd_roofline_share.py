"""Share of its roofline of the Pallas kernel ``kda_chunk_fwd``
(``pytorch_distributed_tpu/ops/pallas_kda.py``, called by
``models/gated_delta.py kda_chunked`` on one TPU chip): the channel-gated
delta rule over a whole window, a chunk of four heads (two 128-row tiles of
two) a grid step, every intermediate of the chunk in fast memory; three calls
a K block an update (target, online, recomputed).  100 x the least time the
chip could take for the kernel's calls of one update (the larger of
operations over 197 TFLOP/s and bytes over 819 GB/s, counted from shapes as
the algorithm needs them: harness/kda_kernel_counts.py; at this cell's shapes
the bytes bind) over the self time of the ops named ``kda_chunk_fwd[.n]`` in
the traced steps.  The kernel also computes the sub-blocks' pairwise decays
and writes each chunk's entry states and inverse for the backward, which the
count leaves out.  None where the step program holds no such kernel."""

from ..harness import kda_kernel_counts

METRIC = {"layer": "trunk_kda", "unit": "%", "better": "higher",
          "source": "program_span", "moves": "updates_per_s"}


def read(ctx):
    return kda_kernel_counts.roofline_share(ctx, "kda_chunk_fwd")
