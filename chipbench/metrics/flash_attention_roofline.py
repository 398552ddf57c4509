"""Kernel ``flash_attention`` (the causal attention of evaluation's
forward on a TPU, ``kernels/ops.attention``): least time from each call's
shapes over its device time in the trace, in percent. Moves
``updates_per_s``."""

from chipbench import flops

KERNEL = "flash_attention"


def cost(shapes):
    """The output [BH, Sq, D], then q [BH, Sq, D], k and v [BH, Sk, D]
    (key/value heads already repeated to the query heads). Causal: query
    i meets keys 0..i, a q.k and a p.v multiply-add per head dimension
    each, 4D(i + 1) FLOPs."""
    q = shapes[1]
    bh, sq, d = q[1][-3:]
    return (flops.batch(q[1], own=3) * bh * 4 * d * sq * (sq + 1) // 2,
            flops.moved_bytes(shapes))


def read(ctx):
    return flops.roofline_pct(ctx, KERNEL, cost)
