"""Operations and bytes the gated delta rule's window NEEDS, from shapes
alone, for the roofline shares of the two Pallas kernels that run it
(``pytorch_distributed_tpu/ops/pallas_gated_delta.py``: ``gdn_chunk_fwd``,
``gdn_chunk_bwd``, named in a trace by their ``name=``).  As
``kernel_counts.py``: the counts follow the ALGORITHM, whatever implements
it, so what a kernel computes beside them (the inverse formed by products
where a triangular solve is counted, a chunk recomputed in the backward),
and what it writes for its own later use (the chunk states and the inverse
kept for the backward), lower its share.

One call = one delta-rule block over the update's whole batch of windows.
Forward (three calls a block an update: target, online, recomputed): the
chunked form's products as ``families/qwen3_next.py forward_flops`` counts
them; bytes = q and k once in the compute dtype, v, g and beta once and o
once in float32.  Backward (one call a block): every product of the forward
transposed twice (2 x its operations; the forward's products it computes
again are not counted); bytes = q, k, v, g, beta and o's cotangent read,
the five cotangents written, float32 but q and k as read.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple


def window_calls(shapes: dict) -> Dict[str, Tuple[float, float]]:
    """``{kernel: (FLOPs, bytes) of one call}`` at the cell's shapes."""
    positions = shapes["batch_size"] * (shapes["seq_len"] + 1)
    hk, hv = shapes["linear_num_key_heads"], shapes["linear_num_value_heads"]
    dk, dv, L = (shapes["linear_key_head_dim"],
                 shapes["linear_value_head_dim"], shapes["gdn_chunk"])
    flops = positions * (
        hk * 2 * 2 * (L / 2) * dk                  # K K^T; Q K^T, causal half
        + hv * (2 * (L / 2) * (dk + dv)            # (I + A) \ [K | V]
                + 3 * 2 * dk * dv                  # W S; Q S; K^T V'
                + 2 * (L / 2) * dv))               # (Q K^T) V'
    qk, vo, gates = 2 * hk * dk, hv * dv, 2 * hv
    forward = positions * (2 * qk + 4 * (2 * vo + gates))
    backward = positions * ((2 + 4) * qk + 4 * (3 * vo + 2 * gates))
    return {"gdn_chunk_fwd": (flops, float(forward)),
            "gdn_chunk_bwd": (2 * flops, float(backward))}


def roofline_share(ctx, kernel: str) -> Optional[float]:
    """100 x the least time of the kernel's calls of one update (the larger
    of operations over the peak FLOP/s and bytes over the peak bytes/s, a
    call) over their self time in the traced steps; None where the step
    program holds no such kernel or the configuration no delta rule."""
    from . import model_scopes

    shapes = ctx.cell.config.get("shapes", {})
    if "gdn_chunk" not in shapes or ctx.peaks is None:
        return None
    got = model_scopes.read_kernel(ctx, kernel)
    if got is None:
        return None
    ms, calls = got
    flops, nbytes = window_calls(shapes)[kernel]
    bound_s = max(flops / ctx.peaks.flops_bf16,
                  nbytes / ctx.peaks.hbm_bytes_per_s)
    return 100.0 * calls * bound_s * 1e3 / ms
