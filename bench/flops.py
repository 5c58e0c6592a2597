"""Operations and bytes a training step requires, counted from shapes.

Counts use real nodes and edges only, never padding, and do not depend on
which kernel the plan picks.  A matmul of (m, k) by (k, n) is 2mkn
operations; one aggregation pass over E edges at width d is 2Ed.  Only
what the gradient needs is counted: the first layer's input gradient is
not.  Elementwise work (bias, activation, loss, Adam) is left out.

The least bytes of one aggregation pass at width d over n nodes and E edges
read the input once, write the output once, and read each edge's column
index and value once (8 bytes) plus one row pointer per node, in float32.
"""
from __future__ import annotations


def _dims(in_dim: int, hidden: int, n_layers: int, n_classes: int):
    d = [in_dim] + [hidden] * (n_layers - 1) + [n_classes]
    return list(zip(d[:-1], d[1:]))


def gcn_step(n: int, e: int, in_dim: int, hidden: int, n_layers: int,
             n_classes: int) -> dict:
    """GCN training step over n nodes and e edges (self-loops included in
    e), transform first: Y = A (X W) + b."""
    dense = agg = 0
    passes = []
    for i, (fi, fo) in enumerate(_dims(in_dim, hidden, n_layers, n_classes)):
        mm = 2 * n * fi * fo
        dense += mm * (2 if i == 0 else 3)       # X W, dW, and dX after 0
        agg += 2 * (2 * e * fo)                   # A (XW) and A^T dY
        passes += [fo, fo]
    return dict(flops=dense + agg, dense_flops=dense, agg_flops=agg,
                agg_passes=passes)


def sage_step(n: int, e: int, in_dim: int, hidden: int, n_layers: int,
              n_classes: int) -> dict:
    """GraphSAGE-mean training step: Y = X W_self + mean(X) W_neigh + b,
    the mean taken at the narrower of the layer's two widths."""
    dense = agg = 0
    passes = []
    for i, (fi, fo) in enumerate(_dims(in_dim, hidden, n_layers, n_classes)):
        mm = 2 * (2 * n * fi * fo)                # two weights
        dense += mm * (2 if i == 0 else 3)
        d = min(fi, fo)
        agg += 2 * (2 * e * d)
        passes += [d, d]
    return dict(flops=dense + agg, dense_flops=dense, agg_flops=agg,
                agg_passes=passes)


def agg_pass_bytes(n: int, e: int, d: int) -> int:
    return 4 * (2 * n * d) + 8 * e + 4 * (n + 1)


def agg_least_seconds(n: int, e: int, passes, peaks: dict) -> float:
    """The least time the chip could take for the aggregation passes:
    each pass bound by its operations or by its bytes, whichever is
    slower."""
    return sum(max(2 * e * d / peaks["flops_per_s"],
                   agg_pass_bytes(n, e, d) / peaks["hbm_bytes_per_s"])
               for d in passes)
