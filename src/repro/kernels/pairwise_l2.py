"""Pallas TPU kernel: blocked pairwise squared-L2 distance.

The HBM-bandwidth hot spot of the paper's selection layer: K-means
assignment (Alg. 2/3, eq. 13) and the Fig.-4 distance-matrix study both
reduce to ‖x_n − c_m‖² over clients × centroids with feature dims up to
millions (all-weights features).

TPU adaptation (DESIGN.md §5): each (bn × bf) X-tile and (bm × bf) C-tile is
read into VMEM exactly once; within the tile the squared distance is the
‖x‖²+‖c‖²−2x·c expansion (x·c on the MXU at f32 precision), accumulated
in an fp32 VMEM tile across the F grid axis. Its roundoff is relative to
‖x‖²+‖c‖², not to the distance: near-identical rows lose digits to
cancellation. Block shapes default to MXU/VPU-aligned (128, 512).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _pairwise_l2_kernel(x_ref, c_ref, out_ref):
    """Grid: (N/bn, M/bm, F/bf); F is the minor (sequential) axis, so the
    output tile accumulates partial sums across F blocks."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...].astype(jnp.float32)          # [bn, bf]
    c = c_ref[...].astype(jnp.float32)          # [bm, bf]
    # sum_f (x_nf - c_mf)^2 for this F-slab, via the MXU-friendly expansion
    # INSIDE one slab (single read per operand, fp32 accumulate).
    xx = jnp.sum(x * x, axis=1, keepdims=True)              # [bn, 1]
    cc = jnp.sum(c * c, axis=1, keepdims=True).T            # [1, bm]
    # HIGHEST: at Mosaic's default an f32 dot takes one bf16 MXU pass
    xc = jax.lax.dot_general(x, c, (((1,), (1,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
    out_ref[...] += xx + cc - 2.0 * xc


@functools.partial(jax.jit,
                   static_argnames=("bn", "bm", "bf", "interpret"))
def pairwise_l2(x: jnp.ndarray, c: jnp.ndarray, *, bn: int = 128,
                bm: int = 128, bf: int = 512,
                interpret: bool = True) -> jnp.ndarray:
    """Squared pairwise distances. x: [N, F]; c: [M, F] -> [N, M] float32.

    interpret=True executes the kernel body in Python on CPU (this
    container); on a real TPU pass interpret=False.
    """
    N, F = x.shape
    M = c.shape[0]
    bn = min(bn, max(8, N))
    bm = min(bm, max(8, M))
    bf = min(bf, max(128, F))
    pad_n = (-N) % bn
    pad_m = (-M) % bm
    pad_f = (-F) % bf
    if pad_n or pad_f:
        x = jnp.pad(x, ((0, pad_n), (0, pad_f)))
    if pad_m or pad_f:
        c = jnp.pad(c, ((0, pad_m), (0, pad_f)))
    Np, Fp = x.shape
    Mp = c.shape[0]

    out = pl.pallas_call(
        _pairwise_l2_kernel,
        grid=(Np // bn, Mp // bm, Fp // bf),
        in_specs=[
            pl.BlockSpec((bn, bf), lambda i, j, k: (i, k)),
            pl.BlockSpec((bm, bf), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bn, bm), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Np, Mp), jnp.float32),
        interpret=interpret,
    )(x, c)
    out = jnp.maximum(out, 0.0)   # clamp fp roundoff on the diagonal
    return out[:N, :M]
