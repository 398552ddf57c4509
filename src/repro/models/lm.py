"""Federated LM workload: per-client LoRA adapters over a frozen transformer.

The per-client trainable state is a LoRA adapter tree keyed by layer kind:
low-rank ``A``/``B`` factors on the attention q/v projections
(``blocks/attn/{wq,wv}_{a,b}``) and on the Mamba-2 in/out projections
(``blocks/mamba/{in_proj,out_proj}_{a,b}``), each stacked over the layers
of its kind in layer order; a hybrid stack has both. The frozen base
weights are derived ONCE per :class:`LMConfig` from ``base_seed``
(:func:`base_params`, the :class:`ModelDef`'s ``frozen`` hook) and live
OUTSIDE the flat parameter plane — the analogue of the paged store's
broadcast base row — so the ``[N, P]`` client plane holds only
``P = P_adapter`` columns and divergence / K-means / aggregation /
compression / upload pricing all operate on adapter rows unchanged. The
engine places the base on the device once and passes it to every program
as the argument ``frozen``; no program holds it as a constant.

The adapter is applied BESIDE each wrapped base weight,
``x·W + (alpha/rank)·(x·A)·B`` inside the layer
(``repro.models.layers.linear``), so no merged copy of a base weight is
ever made, in training or in evaluation; every other model feature (GQA,
NoPE, the granite multipliers, scan-stacked layers, the flash-attention /
SSD kernel dispatch) comes from the untouched ``transformer.forward``.

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
from repro.configs import granite_4_0_h_micro as granite_h_micro
from repro.configs.base import ModelConfig
from repro.models.registry import (ModelDef, register_model_def,
                                   register_workload)
from repro.models.transformer import _layer_plan, forward, init_model, unembed

@dataclasses.dataclass(frozen=True)
class LMConfig:
    """Frozen, hashable config of one LoRA LM workload — the engine cache
    key, exactly as ``CNNConfig`` is for the paper CNN.

    ``base_dtype`` is the frozen base's storage dtype (the adapters, their
    gradients and SGD stay float32, and so do the activations)."""
    model: ModelConfig              # the frozen-base transformer architecture
    seq_len: int = 32               # tokens per training window
    rank: int = 4                   # LoRA rank r
    alpha: float = 8.0              # LoRA scaling (applied as alpha/rank)
    base_seed: int = 0              # PRNG seed the frozen base derives from
    num_dialects: int = 10          # synthetic dialects = "classes" for
                                    # non-iid partitioning and per_class eval
    base_dtype: str = "float32"

    @property
    def name(self) -> str:
        return self.model.name


def _check_supported(m: ModelConfig) -> None:
    if m.is_encoder_decoder or m.moe is not None:
        raise ValueError(
            f"{m.name}: LoRA FL workloads support dense, SSM and hybrid "
            "attention/SSM stacks only (no enc-dec / MoE)")
    if m.family not in ("dense", "ssm", "vlm", "hybrid"):
        raise ValueError(f"{m.name}: unsupported family {m.family!r}")


def layer_kinds(m: ModelConfig) -> dict:
    """``kind -> [layer indices]`` of the stack, in layer order."""
    kinds = {}
    for i, (mixer, _) in enumerate(_layer_plan(m)):
        kinds.setdefault(mixer, []).append(i)
    return kinds


def adapter_targets(cfg: LMConfig):
    """``kind -> {name: (d_in, d_out)}`` of the frozen-base leaves LoRA
    wraps, for each layer kind the stack has."""
    m = cfg.model
    _check_supported(m)
    out = {}
    kinds = layer_kinds(m)
    if "mamba" in kinds:
        s = m.ssm
        d_inner = s.expand * m.d_model
        n_heads = d_inner // s.head_dim
        out["mamba"] = {
            "in_proj": (m.d_model,
                        2 * d_inner + 2 * s.n_groups * s.d_state + n_heads),
            "out_proj": (d_inner, m.d_model)}
    if "attn" in kinds:
        hd = m.resolved_head_dim
        out["attn"] = {"wq": (m.d_model, m.num_heads * hd),
                       "wv": (m.d_model, m.num_kv_heads * hd)}
    return out


def init_adapter(cfg: LMConfig, key, dtype=jnp.float32):
    """One client's trainable state: per layer kind, stacked
    ``[L_kind, d_in, r]`` A factors (scaled normals) and ``[L_kind, r,
    d_out]`` B factors (zeros — the standard LoRA init, so a fresh adapter
    is an exact no-op on the base model). One key per target, the targets
    in sorted (kind, name) order."""
    targets = adapter_targets(cfg)
    kinds = layer_kinds(cfg.model)
    order = [(kind, name) for kind in sorted(targets)
             for name in sorted(targets[kind])]
    ks = jax.random.split(key, len(order))
    blocks = {kind: {} for kind in targets}
    for k, (kind, name) in zip(ks, order):
        d_in, d_out = targets[kind][name]
        n = len(kinds[kind])
        a = (jax.random.normal(k, (n, d_in, cfg.rank), jnp.float32)
             * (1.0 / math.sqrt(d_in))).astype(dtype)
        blocks[kind][f"{name}_a"] = a
        blocks[kind][f"{name}_b"] = jnp.zeros((n, cfg.rank, d_out), dtype)
    return {"blocks": blocks}


def base_params(cfg: LMConfig):
    """The frozen base weights for ``cfg``, derived from ``base_seed`` and
    stored in ``cfg.base_dtype`` (its per-head SSM scalars, ``A_log``,
    ``D`` and ``dt_bias``, stay float32 as the init makes them). The
    engine places them once per process (``engine.frozen_params``)."""
    _check_supported(cfg.model)
    return init_model(cfg.model, jax.random.PRNGKey(cfg.base_seed),
                      dtype=jnp.dtype(cfg.base_dtype))


def base_values(cfg: LMConfig):
    """The values of the base the programs are given (``engine.
    frozen_params``) in float32, whatever its storage dtype: an exact
    widening, the form a float32 reference holds them in."""
    from repro.core.engine import frozen_params
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32),
                                  frozen_params(cfg))


def adapter_num_params(cfg: LMConfig) -> int:
    """P_adapter — the per-client upload size in parameters."""
    template = jax.eval_shape(functools.partial(init_adapter, cfg),
                              jax.ShapeDtypeStruct((2,), jnp.uint32))
    return int(sum(np.prod(l.shape)
                   for l in jax.tree_util.tree_leaves(template)))


def with_adapter(cfg: LMConfig, base, adapter):
    """The base tree with each wrapped weight's adapter beside it: layer
    dict entry ``<name>_lora = (A, (alpha/rank)·B)``, read by
    ``layers.linear``. Only dicts are rebuilt; no base leaf is copied."""
    scale = cfg.alpha / cfg.rank
    targets = adapter_targets(cfg)

    def loras(kind, pick):
        ad = adapter["blocks"][kind]
        return {f"{name}_lora": (pick(ad[f"{name}_a"]),
                                 scale * pick(ad[f"{name}_b"]))
                for name in targets[kind]}

    params = dict(base)
    if "blocks" in base:                       # homogeneous stack
        kind = _layer_plan(cfg.model)[0][0]
        blocks = dict(base["blocks"])
        blocks[kind] = {**blocks[kind], **loras(kind, lambda x: x)}
        params["blocks"] = blocks
        return params
    # hybrid: groups/pos<j> stacked over the periods; the kind's adapter
    # [periods * per_period, ...] is read period-major
    m = cfg.model
    period = m.attn_period
    n_groups = m.num_layers // period
    plan = _layer_plan(m)[:period]
    groups = dict(base["groups"])
    seen = {}
    for j, (kind, _) in enumerate(plan):
        per = sum(1 for k, _ in plan if k == kind)
        slot = seen.get(kind, 0)
        seen[kind] = slot + 1
        pick = (lambda x, per=per, slot=slot:
                x.reshape((n_groups, per) + x.shape[1:])[:, slot])
        pos = dict(groups[f"pos{j}"])
        pos[kind] = {**pos[kind], **loras(kind, pick)}
        groups[f"pos{j}"] = pos
    params["groups"] = groups
    return params


def _hidden(cfg: LMConfig, frozen, adapter, tokens, **kw):
    """Final-normed hidden states ``[B, S, D]`` of ``tokens``, float32."""
    params = with_adapter(cfg, frozen, adapter)
    h, _ = forward(cfg.model, params, {"tokens": tokens},
                   act_dtype=jnp.float32, return_hidden=True, **kw)
    return params, h


#: positions the tied head and its cross-entropy take at a time: no
#: ``[tokens, vocab]`` array lives for a whole long window
HEAD_CHUNK = 256


def _chunked(fn, *arrays):
    """``fn`` over blocks of ``HEAD_CHUNK`` positions of ``[B, S, ...]``
    arrays, one block alive at a time (recomputed in the backward pass),
    the results stacked ``[n_blocks, ...]``; one call where the window is
    no longer than a block."""
    S = arrays[0].shape[1]
    c = HEAD_CHUNK
    if c >= S:
        return fn(*arrays)[None]
    if S % c:
        raise ValueError(f"HEAD_CHUNK {c} does not divide the window's "
                         f"{S} positions")
    blocks = [a.reshape((a.shape[0], S // c, c) + a.shape[2:]).swapaxes(0, 1)
              for a in arrays]
    return jax.lax.map(jax.checkpoint(lambda xs: fn(*xs)), tuple(blocks))


def lm_loss(adapter, batch, cfg: LMConfig, *, frozen):
    """Next-token cross-entropy over the window shift. ``batch["images"]``
    is ``[B, seq_len+1]`` int32; the dialect labels are partition metadata
    only. ``frozen`` is the base (:func:`base_params`, on the device).

    The training forward always takes the jnp attention / SSD path
    (``use_pallas=False``): the flash-attention and SSD Pallas kernels are
    forward-only (no VJP), and the local update differentiates this loss.
    Evaluation (:func:`lm_evaluate`) keeps the backend's kernel route.
    Each layer is recomputed in the backward pass, so that only the
    layers' inputs stay alive across a long window."""
    tokens = batch["images"]
    params, h = _hidden(cfg, frozen, adapter, tokens[:, :-1],
                        use_pallas=False, remat=True)
    targets = tokens[:, 1:]

    def nll_sum(h, t):
        logits = unembed(cfg.model, params, h)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, t[..., None], axis=-1))

    with jax.named_scope("lm.loss"):
        return jnp.sum(_chunked(nll_sum, h, targets)) / targets.size


def lm_evaluate(adapter, test_windows, test_dialects, *, cfg: LMConfig,
                frozen):
    """(next-token accuracy, per-dialect accuracy) — the LM analogue of the
    CNN's (accuracy, per_class) contract, so traced history bookkeeping is
    shape-compatible across workloads."""
    tokens = test_windows
    params, h = _hidden(cfg, frozen, adapter, tokens[:, :-1])

    def hits(h, t):
        pred = jnp.argmax(unembed(cfg.model, params, h), axis=-1)
        return (pred == t).astype(jnp.float32)

    with jax.named_scope("lm.loss"):
        hit = _chunked(hits, h, tokens[:, 1:])              # [n, T, c]
        window_acc = jnp.mean(hit.swapaxes(0, 1).reshape(
            tokens.shape[0], -1), axis=-1)                   # [T]
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
                       make_dataset=lm_make_dataset, frozen=base_params)

register_model_def(LMConfig, LORA_LM_DEF)
register_workload("tinyllama",
                  lambda: LMConfig(model=get_smoke_config("tinyllama-1.1b")))
register_workload("mamba2-130m",
                  lambda: LMConfig(model=get_smoke_config("mamba2-130m")))
#: one period of granite-4.0-h-micro at its published widths (the depth
#: one chip holds as one of four pipeline stages): rank-16 adapters over a
#: bfloat16 base, 2k-token windows
register_workload("granite-4.0-h-micro", lambda: LMConfig(
    model=granite_h_micro.one_period(), seq_len=2048, rank=16, alpha=32.0,
    base_dtype="bfloat16"))
#: the same stack at CPU widths (granite_4_0_h_micro.smoke_config)
register_workload("granite-4.0-h-smoke", lambda: LMConfig(
    model=granite_h_micro.smoke_config(), seq_len=32, rank=4, alpha=8.0))
