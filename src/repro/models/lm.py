"""Federated LM workload: per-client LoRA adapters over a frozen transformer.

The per-client trainable state is a LoRA adapter tree (stacked per-layer
low-rank ``A``/``B`` factors on the attention q/v projections for dense
families, the Mamba2 in/out projections for SSM families). The frozen base
weights are derived ONCE per :class:`LMConfig` from ``base_seed`` and live
OUTSIDE the flat parameter plane — the analogue of the paged store's
broadcast base row — so the ``[N, P]`` client plane holds only
``P = P_adapter`` columns and divergence / K-means / aggregation /
compression / upload pricing all operate on adapter rows unchanged.

``merge_lora`` materializes ``w_eff = w_base + (alpha/rank)·A@B`` on the
stacked block leaves and hands the merged tree to the untouched
``transformer.forward`` — every existing model feature (RoPE, GQA,
scan-stacked layers, the flash-attention/SSD kernel dispatch) applies to the
federated workload for free.

Data rides the engine's existing ``(images, labels)`` slots: ``"images"``
holds ``[B, seq_len+1]`` int32 token windows (``repro.data.lm_data``),
``"labels"`` the window's dialect id — the loss derives next-token targets
from the window shift and never reads the dialect.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.configs.base import ModelConfig
from repro.models.registry import (ModelDef, register_model_def,
                                   register_workload)
from repro.models.transformer import forward, init_model


@dataclasses.dataclass(frozen=True)
class LMConfig:
    """Frozen, hashable config of one LoRA LM workload — the engine cache
    key, exactly as ``CNNConfig`` is for the paper CNN."""
    model: ModelConfig              # the frozen-base transformer architecture
    seq_len: int = 32               # tokens per training window
    rank: int = 4                   # LoRA rank r
    alpha: float = 8.0              # LoRA scaling (applied as alpha/rank)
    base_seed: int = 0              # PRNG seed the frozen base derives from
    num_dialects: int = 10          # synthetic dialects = "classes" for
                                    # non-iid partitioning and per_class eval


def _check_supported(m: ModelConfig) -> None:
    if m.is_encoder_decoder or m.attn_period or m.moe is not None:
        raise ValueError(
            f"{m.name}: LoRA FL workloads support homogeneous dense/ssm "
            "stacks only (no enc-dec / hybrid / MoE)")
    if m.family not in ("dense", "ssm", "vlm"):
        raise ValueError(f"{m.name}: unsupported family {m.family!r}")


def adapter_targets(cfg: LMConfig):
    """``name -> (d_in, d_out)`` of the frozen-base leaves LoRA wraps."""
    m = cfg.model
    _check_supported(m)
    if m.family == "ssm":
        s = m.ssm
        d_inner = s.expand * m.d_model
        n_heads = d_inner // s.head_dim
        return {"in_proj": (m.d_model,
                            2 * d_inner + 2 * s.n_groups * s.d_state + n_heads),
                "out_proj": (d_inner, m.d_model)}
    hd = m.resolved_head_dim
    return {"wq": (m.d_model, m.num_heads * hd),
            "wv": (m.d_model, m.num_kv_heads * hd)}


def init_adapter(cfg: LMConfig, key, dtype=jnp.float32):
    """One client's trainable state: stacked ``[L, d_in, r]`` A factors
    (scaled normals) and ``[L, r, d_out]`` B factors (zeros — the standard
    LoRA init, so a fresh adapter is an exact no-op on the base model)."""
    m = cfg.model
    targets = adapter_targets(cfg)
    ks = jax.random.split(key, len(targets))
    group = "mamba" if m.family == "ssm" else "attn"
    leaves = {}
    for k, (name, (d_in, d_out)) in zip(ks, sorted(targets.items())):
        a = (jax.random.normal(k, (m.num_layers, d_in, cfg.rank), jnp.float32)
             * (1.0 / math.sqrt(d_in))).astype(dtype)
        leaves[f"{name}_a"] = a
        leaves[f"{name}_b"] = jnp.zeros((m.num_layers, cfg.rank, d_out), dtype)
    return {"blocks": {group: leaves}}


@functools.lru_cache(maxsize=8)
def base_params(cfg: LMConfig):
    """The frozen base weights for ``cfg`` — derived from ``base_seed``
    once per process and captured as jit constants by every closure that
    merges against them (the broadcast ``[P_base]`` row that never enters
    the client plane). The first call may land inside a trace (the engine's
    scanned program), where jnp ops stage instead of executing —
    ``ensure_compile_time_eval`` forces concrete arrays so the cache never
    holds tracers."""
    _check_supported(cfg.model)
    with jax.ensure_compile_time_eval():
        return init_model(cfg.model, jax.random.PRNGKey(cfg.base_seed))


def adapter_num_params(cfg: LMConfig) -> int:
    """P_adapter — the per-client upload size in parameters."""
    template = jax.eval_shape(functools.partial(init_adapter, cfg),
                              jax.ShapeDtypeStruct((2,), jnp.uint32))
    return int(sum(np.prod(l.shape)
                   for l in jax.tree_util.tree_leaves(template)))


def merge_lora(cfg: LMConfig, adapter):
    """``base + (alpha/rank)·A@B`` on the wrapped block leaves; every other
    leaf is the shared base object (no copy)."""
    base = base_params(cfg)
    scale = cfg.alpha / cfg.rank

    def low_rank(a, b):
        return scale * jnp.einsum("ldr,lrk->ldk", a.astype(jnp.float32),
                                  b.astype(jnp.float32))

    group = "mamba" if cfg.model.family == "ssm" else "attn"
    ad = adapter["blocks"][group]
    wrapped = dict(base["blocks"][group])
    for name in adapter_targets(cfg):
        wrapped[name] = wrapped[name] + low_rank(ad[f"{name}_a"],
                                                 ad[f"{name}_b"])
    blocks = dict(base["blocks"])
    blocks[group] = wrapped
    merged = dict(base)
    merged["blocks"] = blocks
    return merged


def lm_loss(adapter, batch, cfg: LMConfig):
    """Next-token cross-entropy over the window shift. ``batch["images"]``
    is ``[B, seq_len+1]`` int32; the dialect labels are partition metadata
    only.

    The training forward always takes the jnp attention / SSD path
    (``use_pallas=False``): the flash-attention and SSD Pallas kernels are
    forward-only (no VJP), and the local update differentiates this loss.
    Evaluation (:func:`lm_evaluate`) keeps the backend's kernel route."""
    merged = merge_lora(cfg, adapter)
    tokens = batch["images"]
    logits, _ = forward(cfg.model, merged, {"tokens": tokens[:, :-1]},
                        use_pallas=False)
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll)


def lm_evaluate(adapter, test_windows, test_dialects, *, cfg: LMConfig):
    """(next-token accuracy, per-dialect accuracy) — the LM analogue of the
    CNN's (accuracy, per_class) contract, so traced history bookkeeping is
    shape-compatible across workloads."""
    merged = merge_lora(cfg, adapter)
    tokens = test_windows
    logits, _ = forward(cfg.model, merged, {"tokens": tokens[:, :-1]})
    pred = jnp.argmax(logits, axis=-1)                       # [T, S]
    hit = (pred == tokens[:, 1:]).astype(jnp.float32)
    window_acc = jnp.mean(hit, axis=-1)                      # [T]
    acc = jnp.mean(window_acc)
    onehot = jax.nn.one_hot(test_dialects, cfg.num_dialects)
    per_class = (jnp.sum(onehot * window_acc[:, None], 0)
                 / jnp.maximum(jnp.sum(onehot, 0), 1.0))
    return acc, per_class


def lm_make_dataset(cfg: LMConfig, num_samples: int, seed: int = 0):
    from repro.data.lm_data import make_lm_dataset
    return make_lm_dataset(num_samples, cfg.seq_len, cfg.model.vocab_size,
                           num_dialects=cfg.num_dialects, seed=seed)


LORA_LM_DEF = ModelDef(name="lora-lm", init=init_adapter, loss=lm_loss,
                       evaluate=lm_evaluate, price_uploads=True,
                       make_dataset=lm_make_dataset)

register_model_def(LMConfig, LORA_LM_DEF)
register_workload("tinyllama",
                  lambda: LMConfig(model=get_smoke_config("tinyllama-1.1b")))
register_workload("mamba2-130m",
                  lambda: LMConfig(model=get_smoke_config("mamba2-130m")))
