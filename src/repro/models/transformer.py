"""Model zoo: all six assigned architecture families, one functional API.

Families
--------
dense   : pre-norm GQA transformer decoder (llama/qwen style)
moe     : dense + mixture-of-experts MLPs (mixtral, granite)
ssm     : Mamba2 / SSD stack (attention-free)
hybrid  : jamba-style 1:7 attention:mamba interleave with periodic MoE
encdec  : encoder-decoder with cross-attention (seamless backbone)
vlm     : dense decoder consuming stubbed image-patch embeddings (phi-3-v)

API
---
init_model(cfg, key, dtype)                          -> params
forward(cfg, params, batch, ...)                     -> (logits, aux)
init_cache(cfg, batch_size, cache_len, dtype, ...)   -> cache
decode_step(cfg, params, batch, cache, ...)          -> (logits, cache)

All layer stacks are ``lax.scan`` over layer-stacked params, so the HLO holds
ONE layer body regardless of depth — essential for the 72B/398B dry-run
compiles (DESIGN.md §7).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models import layers as L
from repro.sharding.ctx import constrain

# Fixed encoder-memory length used by decode shapes of encoder-decoder archs.
ENC_MEMORY_LEN = 1024


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _stacked_init(init_fn, key, n: int):
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys) if n > 0 else None


def _init_block(key, cfg: ModelConfig, dtype, *, mixer: str, mlp: str,
                cross: bool = False):
    """One transformer block: {ln1, mixer, ln2?, mlp?, cross?}."""
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {"ln1": jnp.ones((cfg.d_model,), dtype)}
    if mixer == "attn":
        p["attn"] = L.init_attention(ks[0], cfg, dtype)
    else:
        p["mamba"] = L.init_mamba2(ks[0], cfg, dtype)
    if cross:
        p["ln_cross"] = jnp.ones((cfg.d_model,), dtype)
        p["cross"] = L.init_attention(ks[2], cfg, dtype)
    if mlp == "dense":
        p["ln2"] = jnp.ones((cfg.d_model,), dtype)
        p["mlp"] = L.init_mlp(ks[1], cfg.d_model, cfg.d_ff, dtype)
    elif mlp == "moe":
        p["ln2"] = jnp.ones((cfg.d_model,), dtype)
        p["moe"] = L.init_moe(ks[1], cfg.d_model, cfg.moe, dtype)
    return p


def _layer_plan(cfg: ModelConfig):
    """Static per-layer (mixer, mlp) plan for one stack."""
    plan = []
    for i in range(cfg.num_layers):
        mixer = "attn" if cfg.is_attention_layer(i) else "mamba"
        if cfg.family == "ssm":
            mlp = "none"
        elif cfg.moe is not None and (
                cfg.moe_period == 0 or (i % cfg.moe_period) == cfg.moe_period - 1):
            mlp = "moe"
        elif cfg.d_ff:
            mlp = "dense"
        else:
            mlp = "none"
        plan.append((mixer, mlp))
    return plan


def _homogeneous(cfg: ModelConfig) -> bool:
    plan = _layer_plan(cfg)
    return all(p == plan[0] for p in plan)


def init_model(cfg: ModelConfig, key, dtype=jnp.float32):
    ks = jax.random.split(key, 8)
    params: Dict[str, Any] = {
        "embed": (jax.random.normal(ks[0], (cfg.vocab_size, cfg.d_model),
                                    jnp.float32) * 0.02).astype(dtype),
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.init_dense(ks[1], cfg.d_model, cfg.vocab_size, dtype)

    if cfg.is_encoder_decoder:
        mixer, mlp = "attn", ("moe" if cfg.moe else "dense")
        params["encoder"] = _stacked_init(
            lambda k: _init_block(k, cfg, dtype, mixer="attn", mlp=mlp),
            ks[2], cfg.num_layers)
        params["enc_final_norm"] = jnp.ones((cfg.d_model,), dtype)
        params["decoder"] = _stacked_init(
            lambda k: _init_block(k, cfg, dtype, mixer="attn", mlp=mlp, cross=True),
            ks[3], cfg.num_layers)
        if cfg.continuous_encoder_input:
            params["enc_in_proj"] = L.init_dense(ks[4], cfg.d_model, cfg.d_model, dtype)
        return params

    plan = _layer_plan(cfg)
    if _homogeneous(cfg):
        mixer, mlp = plan[0]
        params["blocks"] = _stacked_init(
            lambda k: _init_block(k, cfg, dtype, mixer=mixer, mlp=mlp),
            ks[2], cfg.num_layers)
    else:
        # hybrid: stack per (position-in-group) so a 2-level scan works.
        period = cfg.attn_period
        n_groups = cfg.num_layers // period
        group_keys = jax.random.split(ks[2], period)
        positions = {}
        for j in range(period):
            mixer, mlp = plan[j]
            positions[f"pos{j}"] = _stacked_init(
                lambda k, m=mixer, f=mlp: _init_block(k, cfg, dtype, mixer=m, mlp=f),
                group_keys[j], n_groups)
        params["groups"] = positions
    return params


# ---------------------------------------------------------------------------
# block apply
# ---------------------------------------------------------------------------


def _residual(cfg: ModelConfig, x, y):
    """``x + residual_multiplier · y`` (the plain sum where it is 1)."""
    if cfg.residual_multiplier != 1.0:
        y = y * cfg.residual_multiplier
    return x + y


def _block_apply(p, x, cfg: ModelConfig, *, mixer: str, mlp: str,
                 causal: bool = True, window=None, positions=None,
                 memory=None, moe_impl: str = "dense",
                 q_chunk: int = 512, kv_chunk: int = 1024,
                 use_pallas=None):
    """Full-sequence block application (train / prefill). Returns (x, aux).

    The mixer and the MLP run under the named scopes ``lm.attn`` /
    ``lm.mamba`` and ``lm.mlp`` (op metadata only)."""
    aux = jnp.zeros((), jnp.float32)
    h = L.rmsnorm(x, p["ln1"], cfg.norm_eps)
    if mixer == "attn":
        with jax.named_scope("lm.attn"):
            q, k, v = L.attention_qkv(p["attn"], h, cfg)
            if cfg.rope:
                q = L.apply_rope(q, positions, cfg.rope_theta)
                k = L.apply_rope(k, positions, cfg.rope_theta)
            scale = cfg.attention_multiplier
            if ops.kernel_dispatch(use_pallas):
                # flash-attention kernel under the dispatch policy (TPU /
                # REPRO_FORCE_PALLAS / explicit opt-in); ops.attention owns
                # the off-TPU interpret-mode warning
                attn_out = ops.attention(q, k, v, causal=causal,
                                         window=window,
                                         use_pallas=use_pallas, scale=scale)
            else:
                attn_out = L.blockwise_attention(
                    q, k, v, causal=causal, window=window,
                    q_positions=None, k_positions=None,
                    q_chunk=q_chunk, kv_chunk=kv_chunk, scale=scale)
            B, S = x.shape[:2]
            x = _residual(cfg, x, attn_out.reshape(B, S, -1) @ p["attn"]["wo"])
    else:
        with jax.named_scope("lm.mamba"):
            x = _residual(cfg, x, L.mamba2_apply(p["mamba"], h, cfg,
                                                 use_pallas=use_pallas))
    if memory is not None and "cross" in p:
        h = L.rmsnorm(x, p["ln_cross"], cfg.norm_eps)
        q, k, v = L.attention_qkv(p["cross"], h, cfg, kv_x=memory)
        out = L.blockwise_attention(q, k, v, causal=False,
                                    q_chunk=q_chunk, kv_chunk=kv_chunk)
        B, S = x.shape[:2]
        x = x + out.reshape(B, S, -1) @ p["cross"]["wo"]
    if mlp == "dense":
        with jax.named_scope("lm.mlp"):
            h = L.rmsnorm(x, p["ln2"], cfg.norm_eps)
            x = _residual(cfg, x, L.mlp_apply(p["mlp"], h))
    elif mlp == "moe":
        with jax.named_scope("lm.mlp"):
            h = L.rmsnorm(x, p["ln2"], cfg.norm_eps)
            out, a = L.MOE_IMPLS[moe_impl](p["moe"], h, cfg.moe)
            x = _residual(cfg, x, out)
        aux = aux + a
    return x, aux


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------


def _embed(cfg: ModelConfig, params, tokens, dtype=None):
    """Token embeddings, in ``dtype`` (None = the table's), scaled by
    ``embedding_multiplier``."""
    x = jnp.take(params["embed"], tokens, axis=0)
    if dtype is not None and x.dtype != dtype:
        x = x.astype(dtype)
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    return x


def unembed(cfg: ModelConfig, params, x):
    """Logits of final-normed hidden states ``x`` (the tied embedding or
    ``lm_head``), divided by ``logits_scaling``."""
    if cfg.tie_embeddings:
        logits = x @ params["embed"].T
    else:
        logits = x @ params["lm_head"]
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits


def _scan_stack(stacked, x, body, unroll: int = 1):
    """Scan ``body(layer_params, x) -> (x, aux)`` over a layer-stacked tree.

    ``unroll``: lax.scan unroll factor. The dry-run/roofline path uses full
    unroll because XLA's cost_analysis counts while-loop bodies ONCE, not
    × trip-count — scanned-layer FLOPs/bytes would under-report ~L×.
    """
    def f(carry, lp):
        x, aux = carry
        x, a = body(lp, x)
        return (x, aux + a), None
    n = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    unroll = n if unroll in (0, -1) or unroll >= n else unroll
    (x, aux), _ = lax.scan(f, (x, jnp.zeros((), jnp.float32)), stacked,
                           unroll=unroll)
    return x, aux


def forward(cfg: ModelConfig, params, batch: Dict[str, Any], *,
            moe_impl: str = "dense", q_chunk: int = 512, kv_chunk: int = 1024,
            remat: bool = False, unroll: int = 1, use_pallas=None,
            act_dtype=None, return_hidden: bool = False):
    """Returns (logits [B, S, V], aux_loss scalar).

    ``use_pallas`` selects the attention / SSD kernel route per the
    ``repro.kernels.ops`` dispatch policy (None = follow the backend).
    ``act_dtype`` holds the activations in that dtype whatever the
    weights' (None = the embedding table's). ``remat`` recomputes each
    layer in the backward pass, keeping only the layer's input.
    ``return_hidden`` returns the final-normed hidden states ``[B, S, D]``
    in place of the logits (for a loss that applies the head itself)."""
    window = cfg.sliding_window

    if cfg.is_encoder_decoder:
        return _forward_encdec(cfg, params, batch, moe_impl=moe_impl,
                               q_chunk=q_chunk, kv_chunk=kv_chunk, remat=remat,
                               unroll=unroll, use_pallas=use_pallas)

    tokens = batch["tokens"]
    B, S = tokens.shape
    x = constrain(_embed(cfg, params, tokens, act_dtype), "act")
    if cfg.family == "vlm" and "image_embeds" in batch:
        img = batch["image_embeds"].astype(x.dtype)
        n_img = img.shape[1]
        x = jnp.concatenate([img, x[:, n_img:]], axis=1)
    positions = jnp.arange(S)

    def make_body(mixer, mlp, prevent_cse=False):
        def body(lp, x):
            x, aux = _block_apply(lp, x, cfg, mixer=mixer, mlp=mlp,
                                  causal=True, window=window,
                                  positions=positions, moe_impl=moe_impl,
                                  q_chunk=q_chunk, kv_chunk=kv_chunk,
                                  use_pallas=use_pallas)
            return constrain(x, "act"), aux
        if remat:
            return jax.checkpoint(body, prevent_cse=prevent_cse)
        return body

    plan = _layer_plan(cfg)
    if "blocks" in params:
        mixer, mlp = plan[0]
        x, aux = _scan_stack(params["blocks"], x, make_body(mixer, mlp),
                             unroll=unroll)
    else:
        period = cfg.attn_period
        # a period's layers share one scan step, and a one-period stack's
        # scan is inlined: there the recomputed layers would be merged with
        # the forward pass (and every residual kept) unless CSE is barred
        bodies = [make_body(*plan[j], prevent_cse=True)
                  for j in range(period)]

        def group_body(gp, x):
            aux = jnp.zeros((), jnp.float32)
            for j in range(period):
                x, a = bodies[j](gp[f"pos{j}"], x)
                aux = aux + a
            return x, aux

        x, aux = _scan_stack(params["groups"], x, group_body, unroll=unroll)

    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    if return_hidden:
        return x, aux
    return constrain(unembed(cfg, params, x), "logits"), aux


def _forward_encdec(cfg: ModelConfig, params, batch, *, moe_impl, q_chunk,
                    kv_chunk, remat, unroll: int = 1, use_pallas=None):
    mlp = "moe" if cfg.moe else "dense"
    # --- encoder ---
    if cfg.continuous_encoder_input:
        src = batch["src_embeds"]                        # [B, Ss, D] (stub frontend)
        enc_x = src @ params["enc_in_proj"]
    else:
        enc_x = _embed(cfg, params, batch["src_tokens"])
    Ss = enc_x.shape[1]
    enc_pos = jnp.arange(Ss)

    def enc_body(lp, x):
        x, aux = _block_apply(lp, x, cfg, mixer="attn", mlp=mlp, causal=False,
                              positions=enc_pos, moe_impl=moe_impl,
                              q_chunk=q_chunk, kv_chunk=kv_chunk,
                              use_pallas=use_pallas)
        return constrain(x, "act"), aux

    body = jax.checkpoint(enc_body, prevent_cse=False) if remat else enc_body
    memory, aux_e = _scan_stack(params["encoder"], enc_x, body, unroll=unroll)
    memory = L.rmsnorm(memory, params["enc_final_norm"], cfg.norm_eps)

    # --- decoder ---
    tokens = batch["tokens"]
    St = tokens.shape[1]
    dec_x = _embed(cfg, params, tokens)
    dec_pos = jnp.arange(St)

    def dec_body(lp, x):
        x, aux = _block_apply(lp, x, cfg, mixer="attn", mlp=mlp, causal=True,
                              positions=dec_pos, memory=memory,
                              moe_impl=moe_impl, q_chunk=q_chunk,
                              kv_chunk=kv_chunk, use_pallas=use_pallas)
        return constrain(x, "act"), aux

    body = jax.checkpoint(dec_body, prevent_cse=False) if remat else dec_body
    x, aux_d = _scan_stack(params["decoder"], dec_x, body, unroll=unroll)
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return constrain(unembed(cfg, params, x), "logits"), aux_e + aux_d


# ---------------------------------------------------------------------------
# KV / state caches
# ---------------------------------------------------------------------------


def _attn_cache(n_layers, B, C, cfg, dtype):
    hd = cfg.resolved_head_dim
    return {
        "k": jnp.zeros((n_layers, B, C, cfg.num_kv_heads, hd), dtype),
        "v": jnp.zeros((n_layers, B, C, cfg.num_kv_heads, hd), dtype),
        "k_pos": jnp.full((n_layers, C), -1, jnp.int32),
    }


def _ssm_cache(n_layers, B, cfg, dtype):
    s = cfg.ssm
    d_inner, n_heads, conv_ch = L.mamba2_split_dims(cfg)
    return {
        "ssm_state": jnp.zeros((n_layers, B, n_heads, s.head_dim, s.d_state),
                               jnp.float32),
        "conv_state": jnp.zeros((n_layers, B, s.conv_width - 1, conv_ch), dtype),
    }


def init_cache(cfg: ModelConfig, batch_size: int, cache_len: int,
               dtype=jnp.float32, window: Optional[int] = None):
    """Create the decode cache.

    ``window`` (if set) makes attention caches ring buffers of that size —
    the sub-quadratic SWA variant used by ``long_500k`` for full-attention
    families (DESIGN.md §6).
    """
    C = min(cache_len, window) if window else cache_len
    cache: Dict[str, Any] = {"pos": jnp.zeros((), jnp.int32),
                             "cache_len": jnp.asarray(C, jnp.int32)}
    if cfg.is_encoder_decoder:
        cache["self"] = _attn_cache(cfg.num_layers, batch_size, C, cfg, dtype)
        hd = cfg.resolved_head_dim
        cache["cross_k"] = jnp.zeros(
            (cfg.num_layers, batch_size, ENC_MEMORY_LEN, cfg.num_kv_heads, hd), dtype)
        cache["cross_v"] = jnp.zeros_like(cache["cross_k"])
        return cache
    if cfg.family == "ssm":
        cache["ssm"] = _ssm_cache(cfg.num_layers, batch_size, cfg, dtype)
        return cache
    if cfg.attn_period:
        period = cfg.attn_period
        n_groups = cfg.num_layers // period
        cache["attn"] = _attn_cache(n_groups, batch_size, C, cfg, dtype)
        cache["ssm"] = {
            k: v.reshape((n_groups, period - 1) + v.shape[1:])
            for k, v in _ssm_cache(n_groups * (period - 1), batch_size, cfg,
                                   dtype).items()}
        return cache
    cache["attn"] = _attn_cache(cfg.num_layers, batch_size, C, cfg, dtype)
    return cache


def encode_memory(cfg: ModelConfig, params, batch, *, moe_impl: str = "dense",
                  q_chunk: int = 512, kv_chunk: int = 1024):
    """Run the encoder and precompute per-decoder-layer cross-attention K/V.

    Returns (cross_k, cross_v): [L, B, S_enc, K, hd] — plugged into the
    decode cache of encoder-decoder architectures.
    """
    assert cfg.is_encoder_decoder
    mlp = "moe" if cfg.moe else "dense"
    if cfg.continuous_encoder_input:
        enc_x = batch["src_embeds"] @ params["enc_in_proj"]
    else:
        enc_x = _embed(cfg, params, batch["src_tokens"])
    enc_pos = jnp.arange(enc_x.shape[1])

    def enc_body(lp, x):
        return _block_apply(lp, x, cfg, mixer="attn", mlp=mlp, causal=False,
                            positions=enc_pos, moe_impl=moe_impl,
                            q_chunk=q_chunk, kv_chunk=kv_chunk)

    memory, _ = _scan_stack(params["encoder"], enc_x, enc_body)
    memory = L.rmsnorm(memory, params["enc_final_norm"], cfg.norm_eps)

    hd = cfg.resolved_head_dim
    B, Ss = memory.shape[:2]

    def layer_kv(carry, lp):
        h = L.rmsnorm(memory, lp["ln_cross"], cfg.norm_eps)
        k = h @ lp["cross"]["wk"]
        v = h @ lp["cross"]["wv"]
        if "bk" in lp["cross"]:
            k = k + lp["cross"]["bk"]
            v = v + lp["cross"]["bv"]
        k = k.reshape(B, Ss, cfg.num_kv_heads, hd)
        v = v.reshape(B, Ss, cfg.num_kv_heads, hd)
        return carry, (k, v)

    _, (ck, cv) = lax.scan(layer_kv, 0, params["decoder"])
    return ck, cv


def _attn_decode(p, h, cfg, lc, pos, window):
    """One-token attention with ring-buffer cache update.

    h: [B, 1, D]; lc: per-layer cache {k, v, k_pos}. Returns (out, new_lc).
    """
    C = lc["k"].shape[1]
    q, k, v = L.attention_qkv(p, h, cfg)
    pos_b = jnp.full((h.shape[0],), pos, jnp.int32)
    if cfg.rope:
        q = L.apply_rope(q, pos_b[:, None], cfg.rope_theta)
        k = L.apply_rope(k, pos_b[:, None], cfg.rope_theta)
    slot = jnp.mod(pos, C)
    new_k = lax.dynamic_update_slice(lc["k"], k, (0, slot, 0, 0))
    new_v = lax.dynamic_update_slice(lc["v"], v, (0, slot, 0, 0))
    new_kpos = lax.dynamic_update_slice(lc["k_pos"], pos[None].astype(jnp.int32),
                                        (slot,))
    valid = new_kpos >= 0
    out = L.full_attention_1q(q, new_k, new_v,
                              jnp.broadcast_to(new_kpos, (h.shape[0], C)),
                              pos_b, window=window,
                              kv_valid=jnp.broadcast_to(valid, (h.shape[0], C)),
                              scale=cfg.attention_multiplier)
    out = out.reshape(h.shape[0], 1, -1) @ p["wo"]
    return out, {"k": new_k, "v": new_v, "k_pos": new_kpos}


def decode_step(cfg: ModelConfig, params, batch: Dict[str, Any], cache, *,
                moe_impl: str = "dense", unroll: int = 1):
    """One decode step. batch["tokens"]: [B, 1]. Returns (logits [B,1,V], cache)."""
    tokens = batch["tokens"]
    B = tokens.shape[0]
    pos = cache["pos"]
    window = cfg.sliding_window
    x = _embed(cfg, params, tokens)                      # [B, 1, D]

    aux_cache = dict(cache)

    def attn_block_decode(lp, x, lc):
        h = L.rmsnorm(x, lp["ln1"], cfg.norm_eps)
        out, new_lc = _attn_decode(lp["attn"], h, cfg, lc, pos, window)
        x = _residual(cfg, x, out)
        x = _mlp_decode(lp, x, cfg, moe_impl)
        return x, new_lc

    def mamba_block_decode(lp, x, lc):
        h = L.rmsnorm(x, lp["ln1"], cfg.norm_eps)
        y, ssm_state, conv_state = L.mamba2_decode(
            lp["mamba"], h[:, 0], cfg, lc["ssm_state"], lc["conv_state"])
        x = _residual(cfg, x, y[:, None])
        x = _mlp_decode(lp, x, cfg, moe_impl)
        return x, {"ssm_state": ssm_state, "conv_state": conv_state}

    if cfg.is_encoder_decoder:
        def body(x, inp):
            lp, lc, ck, cv = inp
            h = L.rmsnorm(x, lp["ln1"], cfg.norm_eps)
            out, new_lc = _attn_decode(lp["attn"], h, cfg, lc, pos, window)
            x = x + out
            # cross-attention against fixed encoder memory K/V
            h = L.rmsnorm(x, lp["ln_cross"], cfg.norm_eps)
            q = (h @ lp["cross"]["wq"])
            if "bq" in lp["cross"]:
                q = q + lp["cross"]["bq"]
            hd = cfg.resolved_head_dim
            q = q.reshape(B, 1, cfg.num_heads, hd)
            mem_pos = jnp.broadcast_to(jnp.arange(ck.shape[1]), (B, ck.shape[1]))
            big = jnp.full((B,), 2**30, jnp.int32)
            out = L.full_attention_1q(q, ck, cv, mem_pos, big)
            x = x + out.reshape(B, 1, -1) @ lp["cross"]["wo"]
            x = _mlp_decode(lp, x, cfg, moe_impl)
            return x, new_lc

        sc = cache["self"]
        n_l = cfg.num_layers
        x, new_sc = lax.scan(
            lambda x, inp: body(x, inp), x,
            (params["decoder"], sc, cache["cross_k"], cache["cross_v"]),
            unroll=n_l if unroll in (0, -1) or unroll >= n_l else unroll)
        aux_cache["self"] = new_sc
    elif cfg.family == "ssm":
        def body(x, inp):
            lp, lc = inp
            return mamba_block_decode(lp, x, lc)
        n_l = cfg.num_layers
        x, new_ssm = lax.scan(body, x, (params["blocks"], cache["ssm"]),
                              unroll=n_l if unroll in (0, -1) or unroll >= n_l
                              else unroll)
        aux_cache["ssm"] = new_ssm
    elif cfg.attn_period:
        period = cfg.attn_period
        plan = _layer_plan(cfg)

        def group_body(x, inp):
            gp, attn_lc, ssm_lc = inp
            new_ssm, mamba_i = {}, 0
            new_attn = attn_lc
            for j in range(period):
                mixer, _ = plan[j]
                lp = gp[f"pos{j}"]
                if mixer == "attn":
                    x, new_attn = attn_block_decode(lp, x, attn_lc)
                else:
                    lc_j = {k: v[mamba_i] for k, v in ssm_lc.items()}
                    x, upd = mamba_block_decode(lp, x, lc_j)
                    for k in upd:
                        new_ssm.setdefault(k, []).append(upd[k])
                    mamba_i += 1
            new_ssm = {k: jnp.stack(v) for k, v in new_ssm.items()}
            return x, (new_attn, new_ssm)

        n_g = cfg.num_layers // period
        x, (new_attn, new_ssm) = lax.scan(
            group_body, x, (params["groups"], cache["attn"], cache["ssm"]),
            unroll=n_g if unroll in (0, -1) or unroll >= n_g else unroll)
        aux_cache["attn"] = new_attn
        aux_cache["ssm"] = new_ssm
    else:
        def body(x, inp):
            lp, lc = inp
            return attn_block_decode(lp, x, lc)
        n_l = cfg.num_layers
        x, new_attn = lax.scan(body, x, (params["blocks"], cache["attn"]),
                               unroll=n_l if unroll in (0, -1) or unroll >= n_l
                               else unroll)
        aux_cache["attn"] = new_attn

    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(cfg, params, x)
    aux_cache["pos"] = pos + 1
    return logits, aux_cache


def _mlp_decode(lp, x, cfg, moe_impl):
    if "mlp" in lp:
        h = L.rmsnorm(x, lp["ln2"], cfg.norm_eps)
        x = _residual(cfg, x, L.mlp_apply(lp["mlp"], h))
    elif "moe" in lp:
        h = L.rmsnorm(x, lp["ln2"], cfg.norm_eps)
        out, _ = L.MOE_IMPLS[moe_impl](lp["moe"], h, cfg.moe)
        x = _residual(cfg, x, out)
    return x
