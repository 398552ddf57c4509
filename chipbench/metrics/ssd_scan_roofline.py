"""Kernel ``ssd_scan`` (the Mamba-2 SSD chunked scan that evaluation runs
on a TPU, ``kernels/ops.ssd``): least time from each call's shapes over
its device time in the trace, in percent. Moves ``updates_per_s``."""

from chipbench import flops

KERNEL = "ssd_scan"


def cost(shapes, chunk: int):
    """Results y [BH, S, P] and the final state [BH, P, N], then the
    operands x [BH, S, P], the chunk-local log-decay [BH, S, 1] and B, C
    [BH, S, N]. Per head and chunk of Q positions: the C.B scores (2Q^2N),
    their product with x (2Q^2P), the state's readout through C and the
    chunk's state update (4QNP)."""
    y, state = shapes[0], shapes[1]
    bh, s, p = y[1][-3:]
    n = state[1][-1]
    q = min(chunk, s)
    per_chunk = 2 * q * q * n + 2 * q * q * p + 4 * q * n * p
    return (flops.batch(y[1], own=3) * bh * (s // q) * per_chunk,
            flops.moved_bytes(shapes))


def read(ctx):
    chunk = ctx.model["mamba_chunk_size"]
    return flops.roofline_pct(ctx, KERNEL, lambda s: cost(s, chunk))
