"""Core neural-net layers, functional style.

Everything here is a pair of functions: ``init_*(key, ...) -> params`` and an
apply function taking ``(params, inputs, ...)``. Params are plain dicts of
jnp arrays so that layer stacks can be initialised with ``vmap`` (leaves get
a leading ``[num_layers, ...]`` axis) and applied with ``lax.scan``.

TPU-adaptation notes (see DESIGN.md §5):
 * Attention is *blockwise* (online-softmax over KV chunks) so the O(S²)
   score matrix never materialises — the pure-JAX analogue of the Pallas
   flash kernel in ``repro.kernels.flash_attention``.
 * Mamba2 uses the SSD chunked form (dense intra-chunk matmuls for the MXU +
   tiny inter-chunk recurrence), not the GPU selective-scan kernel.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig, MoEConfig, SSMConfig

# ---------------------------------------------------------------------------
# basics
# ---------------------------------------------------------------------------


def init_dense(key, in_dim: int, out_dim: int, dtype, scale: Optional[float] = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(in_dim)
    return (jax.random.normal(key, (in_dim, out_dim), jnp.float32) * scale).astype(dtype)


def rmsnorm(x, weight, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * lax.rsqrt(var + eps)).astype(dt) * weight


def silu(x):
    return x * jax.nn.sigmoid(x)


def linear(x, p, name: str):
    """``x @ p[name]``, plus a LoRA adapter's ``(x @ A) @ B`` beside it
    where ``p`` holds one under ``name + "_lora"`` (the pair ``(A, B)``,
    its scale folded into ``B``): the adapted weight is never formed."""
    y = x @ p[name]
    lora = p.get(name + "_lora")
    if lora is not None:
        a, b = lora
        y = y + (x @ a) @ b
    return y


# ---------------------------------------------------------------------------
# rotary position embedding
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: [B, S, H, D]; positions: [B, S] or [S]."""
    d = x.shape[-1]
    freqs = rope_frequencies(d, theta)                       # [D/2]
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[..., None].astype(jnp.float32) * freqs   # [B, S, D/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (GQA, optional sliding window, blockwise online softmax)
# ---------------------------------------------------------------------------


def init_attention(key, cfg: ModelConfig, dtype):
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": init_dense(ks[0], cfg.d_model, cfg.num_heads * hd, dtype),
        "wk": init_dense(ks[1], cfg.d_model, cfg.num_kv_heads * hd, dtype),
        "wv": init_dense(ks[2], cfg.d_model, cfg.num_kv_heads * hd, dtype),
        "wo": init_dense(ks[3], cfg.num_heads * hd, cfg.d_model, dtype,
                         scale=1.0 / math.sqrt(cfg.num_heads * hd)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.num_heads * hd,), dtype)
        p["bk"] = jnp.zeros((cfg.num_kv_heads * hd,), dtype)
        p["bv"] = jnp.zeros((cfg.num_kv_heads * hd,), dtype)
    return p


NEG_INF = -1e30


def _attn_block(q_blk, k_blk, v_blk, q_pos, k_pos, causal, window, kv_valid):
    """One (q-chunk × kv-chunk) tile of online-softmax attention.

    q_blk: [B, Tq, K, G, D]; k_blk/v_blk: [B, Tk, K, D].
    Returns (scores_max [B,K,G,Tq], exp_sum, weighted_v [B,Tq,K,G,D]).
    """
    logits = jnp.einsum("btkgd,bskd->bkgts", q_blk.astype(jnp.float32),
                        k_blk.astype(jnp.float32))
    mask = jnp.ones(logits.shape[-2:], dtype=bool)
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        mask &= (q_pos[:, None] - k_pos[None, :]) < window
    if kv_valid is not None:
        mask &= kv_valid[None, :]
    logits = jnp.where(mask, logits, NEG_INF)
    return logits


def blockwise_attention(q, k, v, *, causal: bool = True, window: Optional[int] = None,
                        q_positions=None, k_positions=None, kv_valid=None,
                        q_chunk: int = 512, kv_chunk: int = 1024,
                        scale: Optional[float] = None):
    """Memory-efficient attention: never materialises the [Sq, Sk] matrix.

    q: [B, Sq, H, D]; k, v: [B, Sk, K, D] with H % K == 0 (GQA).
    Positions default to aligned ranges (prefill). Output: [B, Sq, H, D].
    ``scale`` multiplies the scores (None = 1/sqrt(D)).
    """
    B, Sq, H, D = q.shape
    _, Sk, K, _ = k.shape
    G = H // K
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    if q_positions is None:
        q_positions = jnp.arange(Sq)
    if k_positions is None:
        k_positions = jnp.arange(Sk)

    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Sk)
    # pad to multiples
    nq = -(-Sq // q_chunk)
    nk = -(-Sk // kv_chunk)
    pad_q = nq * q_chunk - Sq
    pad_k = nk * kv_chunk - Sk
    qg = q.reshape(B, Sq, K, G, D) * scale
    if pad_q:
        qg = jnp.pad(qg, ((0, 0), (0, pad_q), (0, 0), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, (0, pad_q), constant_values=-1)
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        k_positions = jnp.pad(k_positions, (0, pad_k), constant_values=2**30)
        if kv_valid is None:
            kv_valid = jnp.arange(nk * kv_chunk) < Sk
        else:
            kv_valid = jnp.pad(kv_valid, (0, pad_k), constant_values=False)

    qg = qg.reshape(B, nq, q_chunk, K, G, D).transpose(1, 0, 2, 3, 4, 5)
    kb = k.reshape(B, nk, kv_chunk, K, D).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nk, kv_chunk, K, D).transpose(1, 0, 2, 3, 4)
    qp = q_positions.reshape(nq, q_chunk)
    kp = k_positions.reshape(nk, kv_chunk)
    kvld = None if kv_valid is None else kv_valid.reshape(nk, kv_chunk)

    def q_block_body(args):
        q_blk, q_pos = args
        m0 = jnp.full((B, K, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, K, G, q_chunk), jnp.float32)
        acc0 = jnp.zeros((B, q_chunk, K, G, D), jnp.float32)

        @functools.partial(jax.checkpoint, prevent_cse=False)
        def kv_body(carry, inp):
            m, l, acc = carry
            if kvld is None:
                k_blk, v_blk, k_pos = inp
                valid = None
            else:
                k_blk, v_blk, k_pos, valid = inp
            logits = _attn_block(q_blk, k_blk, v_blk, q_pos, k_pos,
                                 causal, window, valid)      # [B,K,G,Tq,Tk]
            blk_max = jnp.max(logits, axis=-1)
            new_m = jnp.maximum(m, blk_max)
            correction = jnp.exp(m - new_m)
            # fully-masked tiles: keep probs exactly 0 (avoid exp(-inf - -inf))
            probs = jnp.where(logits > NEG_INF * 0.5,
                              jnp.exp(logits - new_m[..., None]), 0.0)
            new_l = l * correction + jnp.sum(probs, axis=-1)
            pv = jnp.einsum("bkgts,bskd->btkgd", probs, v_blk.astype(jnp.float32))
            new_acc = acc * correction.transpose(0, 3, 1, 2)[..., None] + pv
            return (new_m, new_l, new_acc), None

        xs = (kb, vb, kp) if kvld is None else (kb, vb, kp, kvld)
        (m, l, acc), _ = lax.scan(kv_body, (m0, l0, acc0), xs)
        denom = jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
        return acc / denom

    out = lax.map(q_block_body, (qg, qp))                    # [nq,B,Tq,K,G,D]
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, nq * q_chunk, H, D)
    return out[:, :Sq].astype(q.dtype)


def full_attention_1q(q, k, v, k_positions, q_position, *, window=None, kv_valid=None,
                      scale: Optional[float] = None):
    """Single-query decode attention over a (possibly ring-buffer) cache.

    q: [B, 1, H, D]; k/v: [B, C, K, D]; k_positions: [B, C] absolute positions;
    q_position: [B] absolute position of the new token. ``scale`` as in
    :func:`blockwise_attention`.
    """
    B, _, H, D = q.shape
    _, C, K, _ = k.shape
    G = H // K
    qg = q.reshape(B, K, G, D).astype(jnp.float32)
    qg = qg / math.sqrt(D) if scale is None else qg * scale
    logits = jnp.einsum("bkgd,bskd->bkgs", qg, k.astype(jnp.float32))
    mask = k_positions[:, None, None, :] <= q_position[:, None, None, None]
    if window is not None:
        mask &= (q_position[:, None, None, None] - k_positions[:, None, None, :]) < window
    if kv_valid is not None:
        mask &= kv_valid[:, None, None, :]
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", probs, v.astype(jnp.float32))
    return out.reshape(B, 1, H, D).astype(q.dtype)


def attention_qkv(p, x, cfg: ModelConfig, kv_x=None):
    """Project hidden states to (q, k, v). ``kv_x`` enables cross-attention."""
    hd = cfg.resolved_head_dim
    kv_src = x if kv_x is None else kv_x
    q = linear(x, p, "wq")
    k = linear(kv_src, p, "wk")
    v = linear(kv_src, p, "wv")
    if "bq" in p:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    B, Sq = x.shape[:2]
    Skv = kv_src.shape[1]
    q = q.reshape(B, Sq, cfg.num_heads, hd)
    k = k.reshape(B, Skv, cfg.num_kv_heads, hd)
    v = v.reshape(B, Skv, cfg.num_kv_heads, hd)
    return q, k, v


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def init_mlp(key, d_model: int, d_ff: int, dtype):
    ks = jax.random.split(key, 3)
    return {
        "w_gate": init_dense(ks[0], d_model, d_ff, dtype),
        "w_up": init_dense(ks[1], d_model, d_ff, dtype),
        "w_down": init_dense(ks[2], d_ff, d_model, dtype, scale=1.0 / math.sqrt(d_ff)),
    }


def mlp_apply(p, x):
    return (silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


# ---------------------------------------------------------------------------
# Mixture of Experts
# ---------------------------------------------------------------------------


def init_moe(key, d_model: int, moe: MoEConfig, dtype):
    ks = jax.random.split(key, 4)
    E, F = moe.num_experts, moe.d_ff

    def stack(k, ind, outd, scale=None):
        keys = jax.random.split(k, E)
        return jnp.stack([init_dense(kk, ind, outd, dtype, scale) for kk in keys])

    return {
        "router": init_dense(ks[0], d_model, E, dtype, scale=0.02),
        "w_gate": stack(ks[1], d_model, F),
        "w_up": stack(ks[2], d_model, F),
        "w_down": stack(ks[3], F, d_model, 1.0 / math.sqrt(F)),
    }


def moe_apply_dense(p, x, moe: MoEConfig):
    """Paper-faithful-simple MoE: evaluate every expert, combine with sparse
    top-k router weights. HLO FLOPs = num_experts/top_k × the useful FLOPs —
    this shows up in the roofline "useful ratio" and is the baseline the
    dispatch implementation improves on (§Perf).
    """
    B, S, D = x.shape
    t = x.reshape(B * S, D)
    logits = (t @ p["router"]).astype(jnp.float32)           # [T, E]
    topw, topi = lax.top_k(logits, moe.top_k)
    topw = jax.nn.softmax(topw, axis=-1)
    gates = jnp.zeros_like(logits).at[
        jnp.arange(t.shape[0])[:, None], topi].set(topw)     # [T, E]
    h = jnp.einsum("td,edf->tef", t, p["w_gate"])
    u = jnp.einsum("td,edf->tef", t, p["w_up"])
    y = jnp.einsum("tef,efd->ted", silu(h) * u, p["w_down"])
    out = jnp.einsum("te,ted->td", gates.astype(y.dtype), y)
    aux = _load_balance_loss(logits, topi, moe)
    return out.reshape(B, S, D), aux


def moe_apply_dispatch(p, x, moe: MoEConfig, capacity_factor: float = 1.25):
    """Sort-based capacity MoE dispatch (gather → grouped matmul → scatter).

    FLOPs ∝ tokens × top_k × capacity_factor instead of × num_experts, and
    memory is O(T·k·D + E·C·D) — no [T, E, C] one-hot tensor (which is
    O(T²) since C ∝ T and explodes at 65k tokens/device). Tokens over
    capacity are dropped (residual passthrough), the standard TPU
    capacity-based scheme.
    """
    B, S, D = x.shape
    T = B * S
    E, K = moe.num_experts, moe.top_k
    cap = max(int(capacity_factor * T * K / E), 1)
    t = x.reshape(T, D)
    logits = (t @ p["router"]).astype(jnp.float32)
    topw, topi = lax.top_k(logits, K)                        # [T, K]
    topw = jax.nn.softmax(topw, axis=-1)
    aux = _load_balance_loss(logits, topi, moe)

    # flatten (token, slot) pairs and sort by expert
    expert_flat = topi.reshape(T * K)                        # [TK]
    token_flat = jnp.repeat(jnp.arange(T), K)                # [TK]
    gate_flat = topw.reshape(T * K)
    order = jnp.argsort(expert_flat)
    e_sorted = expert_flat[order]
    tok_sorted = token_flat[order]
    gate_sorted = gate_flat[order]

    # rank within expert segment: i − (first index of this expert id);
    # searchsorted on the sorted ids gives segment starts in O(log)
    seg_start = jnp.searchsorted(e_sorted, e_sorted, side="left")
    rank = jnp.arange(T * K) - seg_start
    keep = rank < cap

    # scatter tokens into [E, C, D] buffers
    buf = jnp.zeros((E, cap, D), jnp.float32)
    rows = jnp.where(keep, e_sorted, E - 1)
    cols = jnp.where(keep, rank, cap - 1)
    vals = jnp.where(keep[:, None], t[tok_sorted].astype(jnp.float32), 0.0)
    buf = buf.at[rows, cols].add(vals)

    h = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"].astype(jnp.float32))
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(jnp.float32))
    ye = jnp.einsum("ecf,efd->ecd", silu(h) * u,
                    p["w_down"].astype(jnp.float32))         # [E, C, D]

    # gather results back to (token, slot) order and combine
    contrib = ye[rows, cols] * gate_sorted[:, None]
    out = jnp.zeros((T, D), jnp.float32).at[tok_sorted].add(
        jnp.where(keep[:, None], contrib, 0.0))
    return out.astype(x.dtype).reshape(B, S, D), aux


def _load_balance_loss(router_logits, topi, moe: MoEConfig):
    """Switch-transformer load-balance auxiliary loss."""
    probs = jax.nn.softmax(router_logits, axis=-1)           # [T, E]
    E = moe.num_experts
    frac_tokens = jnp.mean(
        jax.nn.one_hot(topi[:, 0], E, dtype=jnp.float32), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    return moe.load_balance_coef * E * jnp.sum(frac_tokens * frac_probs)


def moe_apply_dense_fused(p, x, moe: MoEConfig):
    """Dense-einsum MoE with the gate applied BEFORE the down-projection
    contraction (§Perf lever).

    With expert/FFN-sharded weights, the naive order produces per-expert
    partial outputs [T, E, D] that must be all-reduced across the model
    axis — E× more collective traffic than necessary. Weighting the hidden
    activations by the router gates first lets XLA contract (e, f) in one
    dot, so the cross-shard reduction carries only [T, D].
    """
    B, S, D = x.shape
    t = x.reshape(B * S, D)
    logits = (t @ p["router"]).astype(jnp.float32)           # [T, E]
    topw, topi = lax.top_k(logits, moe.top_k)
    topw = jax.nn.softmax(topw, axis=-1)
    gates = jnp.zeros_like(logits).at[
        jnp.arange(t.shape[0])[:, None], topi].set(topw)     # [T, E]
    h = jnp.einsum("td,edf->tef", t, p["w_gate"])
    u = jnp.einsum("td,edf->tef", t, p["w_up"])
    hu = silu(h) * u
    hu = hu * gates.astype(hu.dtype)[:, :, None]             # gate EARLY
    out = jnp.einsum("tef,efd->td", hu, p["w_down"])         # e,f contracted
    aux = _load_balance_loss(logits, topi, moe)
    return out.reshape(B, S, D), aux


MOE_IMPLS = {"dense": moe_apply_dense, "dispatch": moe_apply_dispatch,
             "dense_fused": moe_apply_dense_fused}


# ---------------------------------------------------------------------------
# Mamba2 / SSD
# ---------------------------------------------------------------------------


def init_mamba2(key, cfg: ModelConfig, dtype):
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    n_heads = d_inner // s.head_dim
    conv_ch = d_inner + 2 * s.n_groups * s.d_state
    ks = jax.random.split(key, 5)
    dt = jnp.exp(jax.random.uniform(ks[3], (n_heads,), jnp.float32)
                 * (math.log(s.dt_max) - math.log(s.dt_min)) + math.log(s.dt_min))
    inv_softplus_dt = dt + jnp.log(-jnp.expm1(-dt))
    return {
        "in_proj": init_dense(ks[0], cfg.d_model,
                              2 * d_inner + 2 * s.n_groups * s.d_state + n_heads, dtype),
        "conv_w": (jax.random.normal(ks[1], (s.conv_width, conv_ch), jnp.float32)
                   * (1.0 / math.sqrt(s.conv_width))).astype(dtype),
        "conv_b": jnp.zeros((conv_ch,), dtype),
        "A_log": jnp.log(jnp.arange(1, n_heads + 1, dtype=jnp.float32)),
        "D": jnp.ones((n_heads,), jnp.float32),
        "dt_bias": inv_softplus_dt.astype(jnp.float32),
        "norm": jnp.ones((d_inner,), dtype),
        "out_proj": init_dense(ks[4], d_inner, cfg.d_model, dtype,
                               scale=1.0 / math.sqrt(d_inner)),
    }


def _segsum(x):
    """Stable segment-sum: out[..., i, j] = sum_{k=j+1..i} x[..., k] (i >= j)."""
    T = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((T, T), dtype=bool))
    return jnp.where(mask, seg, -jnp.inf)


def ssd_chunked(X, A, Bm, Cm, chunk: int, initial_state=None):
    """SSD (state-space duality) chunked scan — Mamba2's parallel form.

    X: [B, S, H, P] (pre-multiplied by dt); A: [B, S, H] log-decay (dt*A_raw,
    negative); Bm, Cm: [B, S, G, N]. Heads are grouped: G divides H.
    Returns (Y: [B, S, H, P], final_state: [B, H, P, N]).
    """
    B, S, H, P = X.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    pad = (-S) % chunk
    if pad:
        X = jnp.pad(X, ((0, 0), (0, pad), (0, 0), (0, 0)))
        A = jnp.pad(A, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    S_p = S + pad
    nc = S_p // chunk
    Xc = X.reshape(B, nc, chunk, H, P)
    Ac = A.reshape(B, nc, chunk, H).transpose(0, 3, 1, 2)    # [B,H,nc,Q]
    Bc = Bm.reshape(B, nc, chunk, G, N)
    Cc = Cm.reshape(B, nc, chunk, G, N)
    Bh = jnp.repeat(Bc, rep, axis=3)                          # [B,nc,Q,H,N]
    Ch = jnp.repeat(Cc, rep, axis=3)

    A_cum = jnp.cumsum(Ac, axis=-1)                           # [B,H,nc,Q]

    # 1. intra-chunk (diagonal blocks): dense MXU matmuls
    L = jnp.exp(_segsum(Ac))                                  # [B,H,nc,Q,Q]
    scores = jnp.einsum("bcqhn,bcshn->bhcqs", Ch, Bh)         # [B,H,nc,Q,Q]
    Y_diag = jnp.einsum("bhcqs,bhcqs,bcshp->bcqhp", scores, L, Xc)

    # 2. per-chunk final states
    decay_states = jnp.exp(A_cum[..., -1:] - A_cum)           # [B,H,nc,Q]
    states = jnp.einsum("bcqhn,bhcq,bcqhp->bchpn", Bh, decay_states, Xc)

    # 3. inter-chunk recurrence over nc (tiny scan)
    chunk_decay = jnp.exp(A_cum[..., -1])                     # [B,H,nc]
    if initial_state is None:
        initial_state = jnp.zeros((B, H, P, N), jnp.float32)

    def chunk_step(h_prev, inp):
        st, dec = inp                                          # [B,H,P,N], [B,H]
        h_new = h_prev * dec[..., None, None] + st
        return h_new, h_prev

    (h_final, h_prevs) = lax.scan(
        chunk_step, initial_state,
        (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(2, 0, 1)))
    h_prevs = h_prevs.transpose(1, 0, 2, 3, 4)                 # [B,nc,H,P,N]

    # 4. off-diagonal contribution from carried state
    state_decay = jnp.exp(A_cum)                               # [B,H,nc,Q]
    Y_off = jnp.einsum("bcqhn,bchpn,bhcq->bcqhp", Ch, h_prevs, state_decay)

    Y = (Y_diag + Y_off).reshape(B, S_p, H, P)[:, :S]
    return Y, h_final


def ssd_decode_step(x, dt, A_raw, Bm, Cm, D, state):
    """Single-token SSD recurrence.

    x: [B, H, P]; dt: [B, H]; A_raw: [H] (negative); Bm, Cm: [B, G, N];
    state: [B, H, P, N]. Returns (y: [B, H, P], new_state).
    """
    H = x.shape[1]
    G = Bm.shape[1]
    rep = H // G
    Bh = jnp.repeat(Bm, rep, axis=1)                           # [B,H,N]
    Ch = jnp.repeat(Cm, rep, axis=1)
    dA = jnp.exp(dt * A_raw[None, :])                          # [B,H]
    dBx = jnp.einsum("bh,bhn,bhp->bhpn", dt, Bh, x)
    new_state = state * dA[..., None, None] + dBx
    y = jnp.einsum("bhpn,bhn->bhp", new_state, Ch)
    y = y + D[None, :, None] * x
    return y, new_state


def causal_conv1d(x, w, b):
    """Depthwise causal conv. x: [B, S, C]; w: [W, C]; b: [C]."""
    W = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    out = lax.conv_general_dilated(
        xp, w[:, None, :].astype(x.dtype),
        window_strides=(1,), padding="VALID",
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=x.shape[-1])
    return out + b


def causal_conv1d_step(x_t, conv_state, w, b):
    """One decode step of the depthwise conv.

    x_t: [B, C]; conv_state: [B, W-1, C] (previous inputs). Returns
    (y_t: [B, C], new_conv_state).
    """
    W = w.shape[0]
    window = jnp.concatenate([conv_state, x_t[:, None, :]], axis=1)  # [B,W,C]
    y = jnp.einsum("bwc,wc->bc", window.astype(jnp.float32),
                   w.astype(jnp.float32)) + b.astype(jnp.float32)
    return y.astype(x_t.dtype), window[:, 1:]


def mamba2_split_dims(cfg: ModelConfig):
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    n_heads = d_inner // s.head_dim
    conv_ch = d_inner + 2 * s.n_groups * s.d_state
    return d_inner, n_heads, conv_ch


def mamba2_apply(p, x, cfg: ModelConfig, initial_state=None,
                 return_state=False, use_pallas=None):
    """Mamba2 block over a full sequence (train / prefill).

    x: [B, S, D] -> [B, S, D].

    ``use_pallas`` routes the inner SSD recurrence to the Pallas
    ``ssd_scan`` kernel under the ``repro.kernels.ops`` dispatch policy
    (fresh-state sequences only — a carried ``initial_state`` stays on the
    chunked jnp path, which the kernel has no entry point for).
    """
    s = cfg.ssm
    d_inner, n_heads, conv_ch = mamba2_split_dims(cfg)
    B, S, _ = x.shape
    zxbcdt = linear(x, p, "in_proj")
    z, xBC, dt = jnp.split(zxbcdt, [d_inner, d_inner + conv_ch], axis=-1)
    xBC = silu(causal_conv1d(xBC, p["conv_w"], p["conv_b"]))
    xs, Bm, Cm = jnp.split(xBC, [d_inner, d_inner + s.n_groups * s.d_state], axis=-1)
    xs = xs.reshape(B, S, n_heads, s.head_dim)
    Bm = Bm.reshape(B, S, s.n_groups, s.d_state)
    Cm = Cm.reshape(B, S, s.n_groups, s.d_state)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])       # [B,S,H]
    A_raw = -jnp.exp(p["A_log"])                                       # [H]
    A_log_disc = dt * A_raw[None, None, :]
    Xdt = xs.astype(jnp.float32) * dt[..., None]
    from repro.kernels import ops
    if initial_state is None and ops.kernel_dispatch(use_pallas):
        Y, h_final = ops.ssd(Xdt, A_log_disc, Bm.astype(jnp.float32),
                             Cm.astype(jnp.float32), chunk=s.chunk_size,
                             n_groups=s.n_groups, use_pallas=use_pallas)
    else:
        Y, h_final = ssd_chunked(Xdt, A_log_disc, Bm.astype(jnp.float32),
                                 Cm.astype(jnp.float32), s.chunk_size,
                                 initial_state)
    Y = Y + p["D"][None, None, :, None] * xs.astype(jnp.float32)
    Y = Y.reshape(B, S, d_inner).astype(x.dtype)
    Y = rmsnorm(Y * silu(z), p["norm"], cfg.norm_eps)
    out = linear(Y, p, "out_proj")
    if return_state:
        return out, h_final
    return out


def mamba2_decode(p, x_t, cfg: ModelConfig, ssm_state, conv_state):
    """One decode step. x_t: [B, D]. Returns (y_t [B, D], ssm_state, conv_state)."""
    s = cfg.ssm
    d_inner, n_heads, conv_ch = mamba2_split_dims(cfg)
    B = x_t.shape[0]
    zxbcdt = x_t @ p["in_proj"]
    z, xBC, dt = jnp.split(zxbcdt, [d_inner, d_inner + conv_ch], axis=-1)
    xBC, conv_state = causal_conv1d_step(xBC, conv_state, p["conv_w"], p["conv_b"])
    xBC = silu(xBC)
    xs, Bm, Cm = jnp.split(xBC, [d_inner, d_inner + s.n_groups * s.d_state], axis=-1)
    xs = xs.reshape(B, n_heads, s.head_dim).astype(jnp.float32)
    Bm = Bm.reshape(B, s.n_groups, s.d_state).astype(jnp.float32)
    Cm = Cm.reshape(B, s.n_groups, s.d_state).astype(jnp.float32)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])        # [B,H]
    A_raw = -jnp.exp(p["A_log"])
    y, ssm_state = ssd_decode_step(xs, dt, A_raw, Bm, Cm, p["D"], ssm_state)
    y = y.reshape(B, d_inner).astype(x_t.dtype)
    y = rmsnorm(y * silu(z), p["norm"], cfg.norm_eps)
    return y @ p["out_proj"], ssm_state, conv_state
