"""Operations and bytes the channel-gated delta rule's window NEEDS, from
shapes alone, for the roofline shares of the two Pallas kernels that run it
(``pytorch_distributed_tpu/ops/pallas_kda.py``: ``kda_chunk_fwd``,
``kda_chunk_bwd``, named in a trace by their ``name=``).  As
``gdn_kernel_counts.py``: the counts follow the ALGORITHM, whatever
implements it, as a lower bound, so what a kernel computes beside them (the
pairwise decays, the inverse formed by products, a chunk recomputed in the
backward) and what it writes for its own later use (the chunk states and
the inverse kept for the backward) lower its share.

One call = one K block over the update's whole batch of windows.  Forward
(three calls a block an update: target, online, recomputed), a position and
a head: ``K K^T`` and ``Q K^T`` over the causal half of a chunk (2 x 2 x L/2
x d_k), the solve ``(I + A) \\ [K | V]`` (2 x L/2 x (d_k + d_v)), ``W S``,
``Q S`` and ``K^T V'`` (3 x 2 x d_k x d_v), ``P V'`` (2 x L/2 x d_v); bytes
= q and k once in the compute dtype, v, g (a key channel), beta and o once
in float32.  Backward (one call a block): every product of the forward
transposed twice (2 x its operations); bytes = q, k, v, g, beta and o's
cotangent read, the five cotangents written, float32 but q and k as read.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple


def window_calls(shapes: dict) -> Dict[str, Tuple[float, float]]:
    """``{kernel: (FLOPs, bytes) of one call}`` at the cell's shapes."""
    positions = shapes["batch_size"] * (shapes["seq_len"] + 1)
    h, d, L = (shapes["kda_num_heads"], shapes["kda_head_dim"],
               shapes["kda_chunk"])
    dk = dv = d
    flops = positions * h * (
        2 * 2 * (L / 2) * dk                       # K K^T; Q K^T, causal half
        + 2 * (L / 2) * (dk + dv)                  # (I + A) \ [K | V]
        + 3 * 2 * dk * dv                          # W S; Q S; K^T V'
        + 2 * (L / 2) * dv)                        # P V'
    qk, vo, gates = 2 * h * dk, h * dv, h * dk + h
    forward = positions * (2 * qk + 4 * (2 * vo + gates))
    backward = positions * ((2 + 4) * qk + 4 * (3 * vo + 2 * gates))
    return {"kda_chunk_fwd": (flops, float(forward)),
            "kda_chunk_bwd": (2 * flops, float(backward))}


def roofline_share(ctx, kernel: str) -> Optional[float]:
    """100 x the least time of the kernel's calls of one update (the larger
    of operations over the peak FLOP/s and bytes over the peak bytes/s, a
    call) over their self time in the traced steps; None where the step
    program holds no such kernel or the configuration no channel-gated
    rule."""
    from . import model_scopes

    shapes = ctx.cell.config.get("shapes", {})
    if "kda_chunk" not in shapes or ctx.peaks is None:
        return None
    got = model_scopes.read_kernel(ctx, kernel)
    if got is None:
        return None
    ms, calls = got
    flops, nbytes = window_calls(shapes)[kernel]
    bound_s = max(flops / ctx.peaks.flops_bf16,
                  nbytes / ctx.peaks.hbm_bytes_per_s)
    return 100.0 * calls * bound_s * 1e3 / ms
