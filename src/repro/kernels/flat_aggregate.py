"""Pallas TPU kernel: masked weighted row-reduction over the flat client
plane — FedAvg aggregation (eq. 4) as one GEMV.

The FL round's aggregation is ``g = Σ_n w_n · flat[n, :]`` over the
``[N, P]`` client-weight buffer (weights already masked + normalized by the
caller, ``repro.kernels.ops.flat_aggregate``). On TPU each (bn × bp) tile
of the plane is read into VMEM exactly once and contracted against its
weight slab on the MXU, accumulating fp32 partial sums in the output tile
across the N grid axis — the same single-read discipline as
``pairwise_l2`` (DESIGN.md §5). Block shapes default to MXU/VPU-aligned
(128, 512).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _flat_aggregate_kernel(w_ref, x_ref, out_ref):
    """Grid: (P/bp, N/bn); N is the minor (sequential) axis, so the output
    tile accumulates partial weighted sums across N blocks."""
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    w = w_ref[...].astype(jnp.float32)          # [1, bn]
    x = x_ref[...].astype(jnp.float32)          # [bn, bp]
    # HIGHEST: at Mosaic's default an f32 dot takes one bf16 MXU pass
    # (~4e-3 relative error on a v5e), far from the f32 jnp reference
    out_ref[...] += jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                                        precision=jax.lax.Precision.HIGHEST,
                                        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bn", "bp", "interpret"))
def flat_aggregate(flat: jnp.ndarray, weights: jnp.ndarray, *,
                   bn: int = 128, bp: int = 512,
                   interpret: bool = True) -> jnp.ndarray:
    """Weighted row sum. flat: [N, P]; weights: [N] -> [P] float32.

    Zero-padded lanes contribute zero weight, so padding N or P to block
    multiples never changes the sum. interpret=True executes the kernel
    body in Python on CPU (validation); on a real TPU pass interpret=False.
    """
    N, P = flat.shape
    bn = min(bn, max(8, N))
    bp = min(bp, max(128, P))
    pad_n = (-N) % bn
    pad_p = (-P) % bp
    if pad_n or pad_p:
        flat = jnp.pad(flat, ((0, pad_n), (0, pad_p)))
    if pad_n:
        weights = jnp.pad(weights, (0, pad_n))
    Np, Pp = flat.shape
    w2d = weights.astype(jnp.float32).reshape(1, Np)

    out = pl.pallas_call(
        _flat_aggregate_kernel,
        grid=(Pp // bp, Np // bn),
        in_specs=[
            pl.BlockSpec((1, bn), lambda j, k: (0, k)),
            pl.BlockSpec((bn, bp), lambda j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((1, bp), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, Pp), jnp.float32),
        interpret=interpret,
    )(w2d, flat)
    return out[0, :P]
