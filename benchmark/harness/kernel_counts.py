"""Operations and bytes a Pallas kernel's call NEEDS, from shapes alone, for
its share of the roofline: the least time the chip could take, the larger of
operations over peak FLOP/s and bytes over peak bytes/s, over the time the
kernel took in the trace.  Independent of the implementation: the counts
follow the algorithm (a grouped matmul multiplies the rows that are IN its
groups, reads each operand once and writes its result once), so tiles the
kernel computes beside them, or operands it reads twice, lower its share.

The hybrid trunk's expert layers (``models/hybrid.py``) call two kernels of
``jax.experimental.pallas.ops.tpu.megablox``, named in a trace by their
function: ``gmm`` (rows of group g times w[g]: both projections, forward,
recomputed and the backward's products with the transposed weights) and
``tgmm`` (the weights' gradients: x[rows of g]^T dy[rows of g]).
"""

from __future__ import annotations

from typing import Dict, List, Tuple


def grouped_matmul(rows: float, k: int, n: int, groups: int,
                   out_itemsize: int) -> Tuple[float, float]:
    """``gmm``: (rows, k) bf16 times (groups, k, n) bf16 -> (rows, n)."""
    return (2.0 * rows * k * n,
            2.0 * rows * k + 2.0 * groups * k * n + out_itemsize * rows * n)


def grouped_matmul_transposed(rows: float, k: int, n: int, groups: int,
                              out_itemsize: int) -> Tuple[float, float]:
    """``tgmm``: (rows, k)^T bf16 times (rows, n) bf16 -> (groups, k, n)."""
    return (2.0 * rows * k * n,
            2.0 * rows * k + 2.0 * rows * n + out_itemsize * groups * k * n)


def expert_layer_calls(shapes: dict) -> Dict[str, List[Tuple[float, float]]]:
    """The (FLOPs, bytes) of each kind of call one expert layer makes, at
    the EXPECTED rows routed to the experts held: every token's
    ``num_experts_per_tok`` choices fall on the held experts with
    probability held / published.  The mix of one update: the forward
    three times (target, online, recomputed), the backward once."""
    rows = (shapes["batch_size"] * (shapes["seq_len"] + 1)
            * shapes["num_experts_per_tok"] * shapes["n_routed_experts"]
            / shapes["n_routed_experts_published"])
    d, w, H = (shapes["hidden_size"], shapes["moe_intermediate_size"],
               shapes["n_routed_experts"])
    up, down = (rows, d, w, H), (rows, w, d, H)
    return {
        "gmm": 3 * [grouped_matmul(*up, 4), grouped_matmul(*down, 4)] + [
            grouped_matmul(*down, 2),      # d hidden = dy w_down^T
            grouped_matmul(*up, 2)],       # d x      = d hidden w_up^T
        "tgmm": [grouped_matmul_transposed(*up, 2),
                 grouped_matmul_transposed(*down, 2)],
    }


def roofline_share(ctx, kernel: str):
    """100 x least time of the kernel's calls over their time in the
    trace, per update: the calls counted from the trace, each given the
    mean bound of the kernel's calls in one update's mix."""
    from . import model_scopes

    got = model_scopes.read_kernel(ctx, kernel)
    if got is None or ctx.peaks is None:
        return None
    ms, calls = got
    mix = expert_layer_calls(ctx.cell.config["shapes"])[kernel]
    bound_s = sum(max(flops / ctx.peaks.flops_bf16,
                      nbytes / ctx.peaks.hbm_bytes_per_s)
                  for flops, nbytes in mix) / len(mix)
    return 100.0 * calls * bound_s * 1e3 / ms
