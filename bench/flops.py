"""Operations and bytes of the benchmark's work, counted from shapes.

Training FLOPs count the forward pass, the backward pass (twice the
forward's products) and the optimizer's update, and nothing recomputed.
"""
from __future__ import annotations

def mlp_params(dim: int, hidden: int, classes: int) -> int:
    """Parameters of the ``dim -> hidden -> classes`` MLP, biases included."""
    return dim * hidden + hidden + hidden * classes + classes


def mlp_train_flops_per_traj_round(*, dim: int, hidden: int, classes: int,
                                   clients: int, local_steps: int,
                                   batch: int) -> float:
    """One trajectory-round: every client's ``local_steps`` SGD steps."""
    fwd = 2 * (dim * hidden + hidden * classes)         # per sample
    per_step = 3 * fwd * batch + 2 * mlp_params(dim, hidden, classes)
    return float(clients * local_steps * per_step)
