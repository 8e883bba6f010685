"""Scratch memory one call of the learner's step program needs on a chip, as
the TPU compiler reports it (``memory_analysis().temp_size_in_bytes``).
Today it is mostly the re-tiled copies of the ring.  The allocator's
statistics do not show it, so ``hbm_peak_gb`` adds it to what is allocated
while the step runs (harness/cell.py ``hbm_peak_bytes``): this is the part of
that metric a change to the step program moves."""

METRIC = {"layer": "fused_step", "unit": "GB", "better": "lower",
          "source": "program_counter", "moves": "hbm_peak_gb"}


def read(ctx):
    scratch = ctx.result.notes.get("step_memory", {}).get("scratch_bytes")
    return None if scratch is None else scratch / 1e9
