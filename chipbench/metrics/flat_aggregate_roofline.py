"""Kernel ``flat_aggregate`` (eq.-(4) aggregation over the flat plane,
``kernels/ops.flat_aggregate``): least time from each call's shapes over
its device time in the trace, in percent (bytes over HBM bandwidth bound
it). Moves ``updates_per_s``."""

from chipbench import flops

KERNEL = "flat_aggregate"


def cost(shapes):
    """Weights [1, n] times the plane [n, p]: 2np FLOPs per lane."""
    _, _, x = shapes
    n, p = x[1][-2:]
    return 2 * flops.batch(x[1]) * n * p, flops.moved_bytes(shapes)


def read(ctx):
    return flops.roofline_pct(ctx, KERNEL, cost)
