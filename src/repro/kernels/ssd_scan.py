"""Pallas TPU kernel: Mamba2 SSD chunked scan.

The TPU-idiomatic form of the selective scan (DESIGN.md §5): instead of the
GPU per-timestep selective-scan kernel, SSD factorizes each chunk into dense
MXU matmuls (intra-chunk quadratic attention-like block + chunk-state
outer products) with a tiny sequential state recurrence across chunks.

Grid: (B·H, S/Q) with the chunk axis minor/sequential; the [P, N] SSM state
lives in VMEM scratch across chunk steps.

Layouts: X [BH, S, P]; A (log-decay, = dt·a < 0) [BH, S]; B, C [BH, S, N]
(already head-expanded for grouped SSMs). Outputs: Y [BH, S, P] and the
final state [BH, P, N].

The wrapper hands the kernel the CHUNK-LOCAL cumulative log-decay as a
``[BH, S, 1]`` column: Mosaic tiles the last two block dims by (8, 128)
unless they span the whole array dim, so a ``(1, Q)`` block of a
``[BH, S]`` array is refused (its second-to-last dim is 1, not BH), while
``(1, Q, 1)`` is legal for any Q divisible by 8 or equal to S.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, a_ref, b_ref, c_ref, y_ref, hout_ref, h_ref, *,
                Q: int):
    ci = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0].astype(jnp.float32)            # [Q, P]
    a_cum = a_ref[0].astype(jnp.float32)        # [Q, 1] chunk-local cumsum
    b = b_ref[0].astype(jnp.float32)            # [Q, N]
    c = c_ref[0].astype(jnp.float32)            # [Q, N]

    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    # the same cumsum as a [1, Q] row: read the diagonal of the column
    # broadcast (elementwise + reduce; no vector transpose of a [Q, 1])
    a_row = jnp.sum(jnp.where(ii == jj, a_cum, 0.0), axis=0, keepdims=True)
    # intra-chunk decay matrix L[i, j] = exp(sum_{j<k<=i} a_k), i >= j
    L = jnp.where(ii >= jj, jnp.exp(a_cum - a_row), 0.0)

    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # [Q, Q]
    y_diag = jax.lax.dot_general(scores * L, x, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # [Q, P]

    h = h_ref[...]                               # [P, N]
    # off-diagonal: carried state read out through C with in-chunk decay
    y_off = jax.lax.dot_general(c, h, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)   # [Q, P]
    y_off = y_off * jnp.exp(a_cum)
    y_ref[0] = (y_diag + y_off).astype(y_ref.dtype)

    # state update: h' = exp(A_chunk)·h + Σ_q exp(A_chunk − a_cum_q)·x_q⊗b_q
    a_last = a_cum[Q - 1:, :]                    # [1, 1] = A_chunk
    decay_states = jnp.exp(a_last - a_cum)       # [Q, 1]
    # exp(A_chunk) as a [1, N] row, read off the column like a_row:
    # Mosaic cannot broadcast a [1, 1] across sublanes and lanes at once
    n_state = h.shape[1]
    last = jax.lax.broadcasted_iota(jnp.int32, (Q, n_state), 0) == Q - 1
    chunk_decay = jnp.sum(jnp.where(last, jnp.exp(a_cum), 0.0), axis=0,
                          keepdims=True)                          # [1, N]
    h_new = h * chunk_decay + jax.lax.dot_general(
        x * decay_states, b, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)      # [P, N]
    h_ref[...] = h_new

    @pl.when(ci == nc - 1)
    def _finish():
        hout_ref[0] = h_new.astype(hout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, a, b, c, *, chunk: int = 256, interpret: bool = True):
    """x: [BH, S, P]; a: [BH, S]; b, c: [BH, S, N].

    Returns (y: [BH, S, P], final_state: [BH, P, N] fp32). S must not be
    ragged; the wrapper pads with a=0, x=0 (identity steps). The call is
    named ``ssd_scan`` in the compiled program and in a profiler trace.
    """
    BH, S, P = x.shape
    N = b.shape[-1]
    Q = min(chunk, S)
    pad = (-S) % Q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        a = jnp.pad(a, ((0, 0), (0, pad)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
    Sp = S + pad
    # chunk-local cumulative log-decay, as a [BH, Sp, 1] column (see the
    # module docstring for why the kernel never sees a [BH, Sp] block)
    a_cum = jnp.cumsum(a.astype(jnp.float32).reshape(BH, Sp // Q, Q),
                       axis=-1).reshape(BH, Sp, 1)

    kernel = functools.partial(_ssd_kernel, Q=Q)
    y, h = pl.pallas_call(
        kernel,
        grid=(BH, Sp // Q),
        in_specs=[
            pl.BlockSpec((1, Q, P), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, Q, 1), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, Q, N), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, Q, N), lambda bh, ci: (bh, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Q, P), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, P, N), lambda bh, ci: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sp, P), x.dtype),
            jax.ShapeDtypeStruct((BH, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
        name="ssd_scan",
    )(x, a_cum, b, c)
    return y[:, :S], h
