"""Pallas TPU kernel: flash attention (forward), causal + sliding window.

The compute hot-spot of every attention-bearing assigned architecture.
Online-softmax over KV tiles: the [Sq, Sk] score matrix never leaves VMEM,
and each KV tile is streamed through the MXU once. Block sizes default to
(128, 128) — MXU-aligned on both matmul dims.

Grid: (B·H, Sq/bq, Sk/bk) with the KV axis minor (sequential on TPU), so the
running max / sum / accumulator live in VMEM scratch across KV steps.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window, sq: int, sk: int,
                  bq: int, bk: int):
    i = pl.program_id(1)          # q block
    j = pl.program_id(2)          # kv block
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale          # [bq, D]
    k = k_ref[0].astype(jnp.float32)                  # [bk, D]
    v = v_ref[0].astype(jnp.float32)                  # [bk, D]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [bq, bk]

    # positions: queries right-aligned to keys (supports Sq < Sk decode)
    qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + (sk - sq)
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = kpos < sk                                   # padding
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= (qpos - kpos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.where(s > NEG_INF * 0.5, jnp.exp(s - m_new[:, None]), 0.0)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret", "scale"))
def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                    bq: int = 128, bk: int = 128,
                    interpret: bool = True,
                    scale: float | None = None) -> jnp.ndarray:
    """q: [B, H, Sq, D]; k, v: [B, H, Sk, D] -> [B, H, Sq, D].

    GQA callers repeat KV heads up to H before the call (the wrapper in
    ``repro.kernels.ops`` does this). ``scale`` multiplies the scores
    (None = 1/sqrt(D)). The call is named ``flash_attention`` in the
    compiled program and in a profiler trace.
    """
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    pad_q = (-Sq) % bq
    pad_k = (-Sk) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    Sqp, Skp = Sq + pad_q, Sk + pad_k
    qf = q.reshape(B * H, Sqp, D)
    kf = k.reshape(B * H, Skp, D)
    vf = v.reshape(B * H, Skp, D)

    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               window=window, sq=Sq, sk=Sk, bq=bq, bk=bk)
    out = pl.pallas_call(
        kernel,
        grid=(B * H, Sqp // bq, Skp // bk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sqp, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),          # running max m
            pltpu.VMEM((bq,), jnp.float32),          # running sum l
            pltpu.VMEM((bq, D), jnp.float32),        # output accumulator
        ],
        interpret=interpret,
        name="flash_attention",
    )(qf, kf, vf)
    return out.reshape(B, H, Sqp, D)[:, :, :Sq]
