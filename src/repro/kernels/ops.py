"""Jit'd public wrappers around the Pallas kernels.

Model code calls these; ``use_pallas`` switches between the kernel (TPU
target; interpret mode on CPU) and the pure-jnp reference path. The default
follows the backend: kernels on TPU, references on CPU — interpret mode is
for validation, not speed.

Dispatch policy (``_resolve_use_pallas``): an EXPLICIT ``use_pallas=True``
off-TPU lands the kernel in interpret mode, which on the round hot path is
orders of magnitude slower than the jnp reference (``flat_aggregate``:
3.3 s interpreted vs sub-ms jnp — see ROADMAP) — so it raises a
``RuntimeWarning``. Setting ``REPRO_FORCE_PALLAS=1`` is the escape hatch
for deliberate interpret-mode validation runs: it silences the warning and
also flips the ``use_pallas=None`` default to the kernel path everywhere.

Mesh policy (``_on_plane_mesh``): a Mosaic kernel inside a jit that
spans several devices must sit in a ``shard_map`` — GSPMD cannot partition
it and lowering fails. Under the ``p_shards`` plane mesh (a context mesh
with a ``model`` axis, set by ``FLExperiment._run_traced``) every kernel
call here is wrapped: the plane kernels run on their column shard (the
distance kernels ``psum`` their per-shard partial sums), and a call whose
column count does not divide the axis runs whole on every device.
"""
from __future__ import annotations

import functools
import os
import warnings

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.flat_aggregate import flat_aggregate as _flat_agg
from repro.kernels.pairwise_l2 import pairwise_l2 as _pairwise
from repro.kernels.ssd_scan import ssd_scan as _ssd
from repro.sharding.specs import MODEL_AXIS


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _force_pallas() -> bool:
    # read at call time (not import time) so tests/validation runs can
    # monkeypatch the environment per-case
    return os.environ.get("REPRO_FORCE_PALLAS", "").lower() not in (
        "", "0", "false", "no")


def _resolve_use_pallas(op: str, use_pallas: bool | None) -> bool:
    """Apply the dispatch policy for one op call (see module docstring)."""
    if use_pallas is None:
        return True if _force_pallas() else _on_tpu()
    if use_pallas and not _on_tpu() and not _force_pallas():
        warnings.warn(
            f"{op}: use_pallas=True off-TPU runs the Pallas kernel in "
            "interpret mode — a hot-path op becomes orders of magnitude "
            "slower than the jnp reference. Pass use_pallas=None to follow "
            "the backend, or set REPRO_FORCE_PALLAS=1 for a deliberate "
            "interpret-mode validation run.",
            RuntimeWarning, stacklevel=3)
    return use_pallas


_WHOLE = PartitionSpec()                  # every device holds all of it
_COLS = PartitionSpec(None, MODEL_AXIS)   # [rows, cols] split by column


def _on_plane_mesh(kernel, split_specs, cols=None, *, psum: bool = False):
    """``kernel`` made legal on the context mesh (see the module docstring).

    Without a multi-device ``model`` axis in context this is ``kernel``
    itself. ``split_specs`` gives each argument's ``PartitionSpec`` when it
    is split along the plane's ``cols`` columns; that split is taken when
    ``cols`` divides the axis. Split outputs are column shards, or, with
    ``psum``, per-shard partial sums added across the axis. Otherwise
    (``cols`` None or not divisible) every device runs the whole kernel.
    """
    mesh = jax.sharding.get_abstract_mesh()
    m = (mesh.shape[MODEL_AXIS]
         if not mesh.empty and MODEL_AXIS in mesh.axis_names else 1)
    if m == 1:
        return kernel
    if cols is None or cols % m:
        return jax.shard_map(kernel, in_specs=(_WHOLE,) * len(split_specs),
                             out_specs=_WHOLE, check_vma=False)
    if psum:
        return jax.shard_map(lambda *a: jax.lax.psum(kernel(*a), MODEL_AXIS),
                             in_specs=split_specs, out_specs=_WHOLE,
                             check_vma=False)
    return jax.shard_map(kernel, in_specs=split_specs,
                         out_specs=PartitionSpec(MODEL_AXIS), check_vma=False)


def kernel_dispatch(use_pallas: bool | None = None) -> bool:
    """Would this call take the kernel route? The policy of
    ``_resolve_use_pallas`` WITHOUT the off-TPU warning — for callers
    (``models.transformer`` / ``models.layers``) that branch between an op
    here and their own jnp path, then pass the raw ``use_pallas`` down so
    the op's resolver still owns the single warning."""
    if use_pallas is None:
        return _force_pallas() or _on_tpu()
    return use_pallas


def pairwise_sq_dists(x, c, *, use_pallas: bool | None = None):
    """[N, F] × [M, F] -> [N, M] squared L2 (K-means / Fig. 4 hot spot).

    THE pairwise-distance implementation — K-means assignment
    (``repro.core.clustering``) and the Fig.-4 divergence matrix
    (``repro.core.divergence``) both route here. Off-TPU it is the
    streaming ‖x‖²+‖c‖²−2x·c expansion; both paths clamp at zero so no
    call site can see a negative squared distance from fp roundoff.
    """
    use_pallas = _resolve_use_pallas("pairwise_sq_dists", use_pallas)
    if use_pallas:
        kernel = functools.partial(_pairwise, interpret=not _on_tpu())
        return _on_plane_mesh(kernel, (_COLS, _COLS), x.shape[1],
                              psum=True)(x, c)
    x = x.astype(jnp.float32)
    c = c.astype(jnp.float32)
    xn = jnp.sum(jnp.square(x), axis=1, keepdims=True)
    cn = jnp.sum(jnp.square(c), axis=1)[None, :]
    return jnp.maximum(xn + cn - 2.0 * x @ c.T, 0.0)


def flat_aggregate(flat, weights, *, mask=None, normalize: bool = True,
                   use_pallas: bool | None = None):
    """Masked weighted row-reduction over the flat client plane:
    ``[N, P] × [N] -> [P]`` — FedAvg aggregation (eq. 4) as one fused op.

    ``mask`` zeroes padding lanes' weights; ``normalize`` divides by the
    (masked) weight sum, giving the eq.-(4) weighted mean. On TPU this is
    the ``flat_aggregate`` Pallas GEMV kernel; elsewhere the jnp reference
    whose summation order matches the pytree ``tree_weighted_mean_stacked``
    bit for bit in fp32.
    """
    w = weights.astype(jnp.float32)
    if mask is not None:
        w = jnp.where(mask, w, 0.0)
    # Non-finite guard: a NaN/Inf row would poison the fold even at
    # weight 0 (0·NaN = NaN in the weighted reduction), so zero the
    # payload of every masked-out lane before either backend sees it.
    # Bitwise no-op for finite inputs: a 0-weight finite row contributed
    # exactly 0.0 to each partial sum already.
    flat = jnp.where((w > 0.0)[:, None], flat, jnp.zeros((), flat.dtype))
    if normalize:
        # the max() guard only bites when every lane is masked out (sum=0):
        # an empty round then aggregates to zeros instead of poisoning the
        # scan carry with 0/0 NaNs; real weight sums are untouched bitwise
        w = w / jnp.maximum(jnp.sum(w), 1e-12)
    use_pallas = _resolve_use_pallas("flat_aggregate", use_pallas)
    if use_pallas:
        kernel = functools.partial(_flat_agg, interpret=not _on_tpu())
        return _on_plane_mesh(kernel, (_COLS, _WHOLE), flat.shape[1])(flat, w)
    return ref.flat_aggregate_ref(flat, w)


def client_divergence(flat, gvec, *, use_pallas: bool | None = None):
    """[N] weight divergences ‖flat_n − g‖₂ of the flat client plane
    against the flat global row — §IV-C's selection signal as one fused
    row-norm reduction (the Pallas ``pairwise_l2`` kernel with the global
    model as a single centroid on TPU; a fused subtract-square-reduce
    elsewhere, numerically stronger than the expansion for near-identical
    rows)."""
    use_pallas = _resolve_use_pallas("client_divergence", use_pallas)
    if use_pallas:
        kernel = functools.partial(_pairwise, interpret=not _on_tpu())
        d2 = _on_plane_mesh(kernel, (_COLS, _COLS), flat.shape[1],
                            psum=True)(flat, gvec[None, :])
        return jnp.sqrt(d2[:, 0])
    diff = flat.astype(jnp.float32) - gvec.astype(jnp.float32)[None, :]
    return jnp.sqrt(jnp.sum(jnp.square(diff), axis=1))


def chunked_client_divergence(rows, gvec, *, chunk_size: int | None = None):
    """Streaming form of :func:`client_divergence` for the paged client
    store: pages ``rows`` (an array or an iterable of ``[c, P]`` blocks,
    e.g. ``PagedStore.iter_chunks()``) through the fused row-norm reduction
    one chunk at a time. Bitwise identical per row (the reduction is
    row-independent); peak device memory is O(chunk·P). Returns a host
    ``[N]`` fp32 array."""
    from repro.kernels.chunked import chunked_client_divergence as _impl
    return _impl(rows, gvec, chunk_size=chunk_size)


def chunked_pairwise(rows, centroids, *, chunk_size: int | None = None):
    """Streaming form of :func:`pairwise_sq_dists` over row chunks —
    K-means assignment against a cold store without materializing the
    ``[N, P]`` plane. Bitwise identical per row; returns a host ``[N, M]``
    fp32 array."""
    from repro.kernels.chunked import chunked_pairwise as _impl
    return _impl(rows, centroids, chunk_size=chunk_size)


def attention(q, k, v, *, causal: bool = True, window: int | None = None,
              use_pallas: bool | None = None, scale: float | None = None):
    """GQA-aware attention. q: [B, S, H, D]; k, v: [B, S, K, D]; ``scale``
    multiplies the scores (None = 1/sqrt(D))."""
    use_pallas = _resolve_use_pallas("attention", use_pallas)
    B, Sq, H, D = q.shape
    K = k.shape[2]
    if K != H:
        rep = H // K
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if use_pallas:
        kernel = functools.partial(_flash, causal=causal, window=window,
                                   interpret=not _on_tpu(), scale=scale)
        out = _on_plane_mesh(kernel, (_WHOLE,) * 3)(qt, kt, vt)
    else:
        out = ref.flash_attention_ref(qt, kt, vt, causal=causal, window=window,
                                      scale=scale)
    return out.transpose(0, 2, 1, 3)


def ssd(x, a, b, c, *, chunk: int = 256, n_groups: int = 1,
        use_pallas: bool | None = None):
    """Mamba2 SSD. x: [B, S, H, P]; a: [B, S, H]; b, c: [B, S, G, N].

    Returns (y: [B, S, H, P], state: [B, H, P, N]).
    """
    use_pallas = _resolve_use_pallas("ssd", use_pallas)
    B, S, H, P = x.shape
    N = b.shape[-1]
    repg = H // b.shape[2]
    bh = jnp.repeat(b, repg, axis=2)
    ch = jnp.repeat(c, repg, axis=2)
    if use_pallas:
        xf = x.transpose(0, 2, 1, 3).reshape(B * H, S, P)
        af = a.transpose(0, 2, 1).reshape(B * H, S)
        bf = bh.transpose(0, 2, 1, 3).reshape(B * H, S, N)
        cf = ch.transpose(0, 2, 1, 3).reshape(B * H, S, N)
        kernel = functools.partial(_ssd, chunk=chunk,
                                   interpret=not _on_tpu())
        y, h = _on_plane_mesh(kernel, (_WHOLE,) * 4)(xf, af, bf, cf)
        return (y.reshape(B, H, S, P).transpose(0, 2, 1, 3),
                h.reshape(B, H, P, N))
    return ref.ssd_ref(x, a, bh, ch)
