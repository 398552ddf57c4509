"""Kernel ``pairwise_l2`` (each round's divergences over the [N, P] plane
and the initial round's K-means distances, ``kernels/ops``): least time
from each call's shapes over its device time in the trace, in percent
(bytes over HBM bandwidth bound it). Moves ``updates_per_s``."""

from chipbench import flops

KERNEL = "pairwise_l2"


def cost(shapes):
    """Rows [n, f] against centroids [m, f]: the x.c contraction and both
    squared norms, 2nmf + 2(n + m)f FLOPs per lane."""
    _, x, c = shapes
    n, f = x[1][-2:]
    m = c[1][-2]
    return (flops.batch(x[1]) * (2 * n * m * f + 2 * (n + m) * f),
            flops.moved_bytes(shapes))


def read(ctx):
    return flops.roofline_pct(ctx, KERNEL, cost)
