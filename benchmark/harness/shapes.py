"""Operations the algorithm needs, computed from shapes: the primitive
counts.  What one learner update of a model family adds up to is that
family's own ``update_flops`` (``families/<family>.py``).

Kept with the benchmark and never read from the compiled program: XLA's
``cost_analysis`` counts a scan body once and follows the HLO, so it moves
when a PR changes the program; these follow the published architecture and
move only when the configuration does.  A multiply-add is 2 FLOPs.  The
backward pass costs twice the forward; recomputed operations do not count.
"""

from __future__ import annotations

from typing import Sequence

# Nature DQN torso (Mnih et al. 2015, Methods): (filters, kernel, stride)
NATURE_CONVS = ((32, 8, 4), (64, 4, 2), (64, 3, 1))
NATURE_FC = 512


def conv_out(size: int, kernel: int, stride: int) -> int:
    """VALID padding."""
    return (size - kernel) // stride + 1


def nature_cnn_forward_flops(state_shape: Sequence[int] = (4, 84, 84),
                             fc: int = NATURE_FC) -> int:
    """Conv stack + first fully connected layer, one frame stack."""
    cin, h, w = state_shape
    flops = 0
    for filters, k, s in NATURE_CONVS:
        h, w = conv_out(h, k, s), conv_out(w, k, s)
        flops += 2 * h * w * filters * (k * k * cin)
        cin = filters
    return flops + 2 * (h * w * cin) * fc


def dense_flops(n_in: int, n_out: int) -> int:
    return 2 * n_in * n_out


def lstm_step_flops(n_in: int, n_hidden: int) -> int:
    """Four gates, each an input and a hidden matmul."""
    return 4 * (dense_flops(n_in, n_hidden) + dense_flops(n_hidden, n_hidden))
