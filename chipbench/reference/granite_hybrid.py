"""Plain reference of federated LoRA over a granite-4.0-h hybrid stack:
Mamba-2 layers (SSD, arXiv 2405.21060) and NoPE grouped-query attention
layers in the published order, each followed by a gated-SiLU MLP, with
per-client low-rank adapters (LoRA, arXiv 2106.09685) beside a frozen base,
trained on next-token cross-entropy.

The configuration file carries the model's published ``config.json`` keys
(``hidden_size``, ``layer_types``, ``num_attention_heads``,
``num_key_value_heads``, ``mamba_n_heads``, ``mamba_d_head``,
``mamba_d_state``, ``mamba_n_groups``, ``mamba_d_conv``,
``shared_intermediate_size``, ``vocab_size``, ``rms_norm_eps``,
``attention_multiplier``, ``embedding_multiplier``,
``residual_multiplier``, ``logits_scaling``, ...) and the adapter's
(``seq_len``, ``rank``, ``alpha``, ``base_seed``, ``num_dialects``,
``base_dtype``). Each layer is

    x = x + residual_multiplier * mixer(rmsnorm(x))
    x = x + residual_multiplier * W_down(silu(h W_gate) * (h W_up)),
        h = rmsnorm(x)

after ``x = embed[tokens] * embedding_multiplier``, and the logits are
``rmsnorm(x) @ embed.T / logits_scaling`` (tied). The attention mixer is
GQA without positions (NoPE): scores ``q.k * attention_multiplier``, a
causal mask and a dense softmax. The Mamba-2 mixer: ``in_proj -> [z, xBC,
dt]``, a causal convolution with bias and SiLU on ``xBC``, the SSD with
``dt = softplus(dt + dt_bias)`` and ``A = -exp(A_log)`` plus the ``D``
skip, ``rmsnorm(y * silu(z))``, ``out_proj``. LoRA wraps the attention's
``wq`` and ``wv`` and the Mamba-2 ``in_proj`` and ``out_proj``: ``y = h @
W + (alpha / rank) * (h @ A) @ B``.

Written in plain ``jax.numpy``, every contraction at the precision it is
given (the configuration states HIGHEST), every array in the dtype it is
given, and independent of the program's arithmetic: the adapter is two
products beside the base weight; the convolution is a sum of shifted
slices; the SSD is the step-by-step recurrence over positions (``h_t =
exp(dt_t A) h_{t-1} + dt_t x_t B_t``, ``y_t = C_t h_t + D x_t``), not a
chunked form; attention is the dense masked softmax, not an online one.
What a chip's memory forces, and changes no value's formula: each layer
is recomputed in the backward pass; the recurrence runs ``SSD_HEADS``
heads at a time, keeps its state at every ``SSD_BLOCK``-th position and
recomputes the steps between; the MLP runs ``HEAD_ROWS`` positions at a
time; the
attention runs one key/value head's group at a time; and the head and its
log-softmax (over the whole vocabulary for each position) run ``HEAD_ROWS``
positions at a time.

The initial weights follow the program's initialisation rule draw for
draw (a copy of it, here): the adapter from the experiment's key, the
frozen base from ``base_seed`` alone, stored in ``base_dtype`` (bfloat16
for every weight but the per-head SSM scalars ``A_log``, ``D`` and
``dt_bias``, which stay float32), and laid out as the program lays out a
hybrid stack: ``groups/pos<j>`` for position j of the period, each leaf
stacked over the periods. The check holds the program's base to it
element for element. The reference computes on those same values.

This module is one model of the benchmark's model interface (see
``chipbench/reference/fl.py``). Its data are ``lora_lm``'s dialect token
windows.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.reference import lora_lm

#: positions between the states the SSD recurrence keeps for its backward
#: pass (the steps between are recomputed)
SSD_BLOCK = 32
#: heads the SSD recurrence runs at once
SSD_HEADS = 16
#: positions whose logits are alive at once in the loss and the accuracy
HEAD_ROWS = 256


def _dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    m_heads, m_p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    G, N = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    d_inner = m_heads * m_p
    if d_inner != cfg["mamba_expand"] * d:
        raise ValueError("mamba_n_heads x mamba_d_head must equal "
                         "mamba_expand x hidden_size")
    return {"d": d, "heads": heads, "kv": cfg["num_key_value_heads"],
            "hd": d // heads, "m_heads": m_heads, "P": m_p, "G": G, "N": N,
            "d_inner": d_inner, "conv_ch": d_inner + 2 * G * N,
            "W": cfg["mamba_d_conv"], "F": cfg["shared_intermediate_size"],
            "V": cfg["vocab_size"], "L": cfg["num_hidden_layers"]}


def _period(cfg: dict):
    """``(period, kinds)``: the layer pattern's period and its kinds,
    ``attn`` or ``mamba`` by position, from ``layer_types``."""
    types = cfg["layer_types"]
    kinds = ["attn" if t == "attention" else "mamba" for t in types]
    for p in range(1, len(kinds) + 1):
        if len(kinds) % p == 0 and kinds == kinds[:p] * (len(kinds) // p):
            return p, kinds[:p]


def targets(cfg: dict) -> dict:
    """``kind -> {name: (d_in, d_out)}`` of the base projections LoRA
    wraps."""
    k = _dims(cfg)
    return {"attn": {"wq": (k["d"], k["heads"] * k["hd"]),
                     "wv": (k["d"], k["kv"] * k["hd"])},
            "mamba": {"in_proj": (k["d"],
                                  k["d_inner"] + k["conv_ch"] + k["m_heads"]),
                      "out_proj": (k["d_inner"], k["d"])}}


def _layers_of(cfg: dict) -> dict:
    types = cfg["layer_types"]
    return {"attn": sum(t == "attention" for t in types),
            "mamba": sum(t == "mamba" for t in types)}


def adapter_params(cfg: dict) -> int:
    """P_adapter: one client's trainable parameters."""
    n = _layers_of(cfg)
    return sum(n[kind] * cfg["rank"] * (d_in + d_out)
               for kind, t in targets(cfg).items()
               for d_in, d_out in t.values())


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def init(cfg: dict, key):
    """One client's adapter from ``key``: ``{"blocks": {kind: {
    "<target>_a", "<target>_b"}}}``, A ``[layers of the kind, d_in, rank]``
    normal / sqrt(d_in), B zero; one key per target, in sorted (kind,
    target) order."""
    n, r = _layers_of(cfg), cfg["rank"]
    t = targets(cfg)
    order = [(kind, name) for kind in sorted(t) for name in sorted(t[kind])]
    ks = jax.random.split(key, len(order))
    blocks = {kind: {} for kind in t}
    for k, (kind, name) in zip(ks, order):
        d_in, d_out = t[kind][name]
        blocks[kind][f"{name}_a"] = (
            jax.random.normal(k, (n[kind], d_in, r), jnp.float32)
            * (1.0 / math.sqrt(d_in)))
        blocks[kind][f"{name}_b"] = jnp.zeros((n[kind], r, d_out),
                                              jnp.float32)
    return {"blocks": blocks}


def frozen(cfg: dict):
    """The frozen base from ``base_seed``, op by op as the program makes
    it: ``embed`` (normal x 0.02), ``final_norm``, and ``groups/pos<j>``
    for each position of the period (``ln1``, the mixer, ``ln2``, ``mlp``),
    each leaf stacked over the periods, every position and period from its
    own split of the key. Weights in ``base_dtype``."""
    k = _dims(cfg)
    dt = jnp.dtype(cfg["base_dtype"])
    period, kinds = _period(cfg)
    d = k["d"]
    ks = jax.random.split(jax.random.PRNGKey(cfg["base_seed"]), 8)

    def dense(key, d_in, d_out, scale=None):
        scale = 1.0 / math.sqrt(d_in) if scale is None else scale
        return (jax.random.normal(key, (d_in, d_out), jnp.float32)
                * scale).astype(dt)

    def attn(key):
        a = jax.random.split(key, 4)
        hq, hkv = k["heads"] * k["hd"], k["kv"] * k["hd"]
        return {"wq": dense(a[0], d, hq), "wk": dense(a[1], d, hkv),
                "wv": dense(a[2], d, hkv),
                "wo": dense(a[3], hq, d, 1.0 / math.sqrt(hq))}

    def mamba(key):
        a = jax.random.split(key, 5)
        H = k["m_heads"]
        lo, hi = math.log(cfg["mamba_dt_min"]), math.log(cfg["mamba_dt_max"])
        dts = jnp.exp(jax.random.uniform(a[3], (H,), jnp.float32)
                      * (hi - lo) + lo)
        return {
            "in_proj": dense(a[0], d, k["d_inner"] + k["conv_ch"] + H),
            "conv_w": (jax.random.normal(a[1], (k["W"], k["conv_ch"]),
                                         jnp.float32)
                       * (1.0 / math.sqrt(k["W"]))).astype(dt),
            "conv_b": jnp.zeros((k["conv_ch"],), dt),
            "A_log": jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)),
            "D": jnp.ones((H,), jnp.float32),
            "dt_bias": (dts + jnp.log(-jnp.expm1(-dts))).astype(jnp.float32),
            "norm": jnp.ones((k["d_inner"],), dt),
            "out_proj": dense(a[4], k["d_inner"], d,
                              1.0 / math.sqrt(k["d_inner"])),
        }

    def block(key, kind):
        b = jax.random.split(key, 4)
        F = k["F"]
        m = jax.random.split(b[1], 3)
        out = {"ln1": jnp.ones((d,), dt), "ln2": jnp.ones((d,), dt),
               "mlp": {"w_gate": dense(m[0], d, F), "w_up": dense(m[1], d, F),
                       "w_down": dense(m[2], F, d, 1.0 / math.sqrt(F))}}
        out[kind] = attn(b[0]) if kind == "attn" else mamba(b[0])
        return out

    n_groups = k["L"] // period
    pos_keys = jax.random.split(ks[2], period)
    groups = {f"pos{j}": jax.vmap(lambda key, kind=kinds[j]: block(key, kind))(
        jax.random.split(pos_keys[j], n_groups)) for j in range(period)}
    return {"embed": (jax.random.normal(ks[0], (k["V"], d), jnp.float32)
                      * 0.02).astype(dt),
            "final_norm": jnp.ones((d,), dt), "groups": groups}


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------


def _dot(a, b, precision):
    return jnp.dot(a, b, precision=precision)


def _proj(v, w, ad, name, scale, precision):
    """``v @ W``, plus the adapter's two products where it wraps ``name``."""
    y = _dot(v, w, precision)
    if ad is not None and f"{name}_a" in ad:
        y = y + scale * _dot(_dot(v, ad[f"{name}_a"], precision),
                             ad[f"{name}_b"], precision)
    return y


def _ssd(x, dt, A, Bm, Cm, precision):
    """The SSD recurrence, position by position: ``SSD_HEADS`` heads at a
    time, keeping the state every ``SSD_BLOCK`` positions for the backward
    pass. x [B, S, H, P]; dt [B, S, H]; A [H] (negative); Bm, Cm [B, S, G,
    N], head h reading group h // (H / G). Returns y [B, S, H, P] (without
    the D skip)."""
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    Hc = SSD_HEADS if H % SSD_HEADS == 0 and (H // G) % SSD_HEADS == 0 \
        else H // G
    T = SSD_BLOCK if S % SSD_BLOCK == 0 else S

    def step(h, t):                       # h [B, Hc, P, N]
        xt, dtt, a, bt, ct = t            # [B,Hc,P], [B,Hc], [Hc], [B,N]
        h = (jnp.exp(dtt * a)[..., None, None] * h
             + (dtt[..., None] * xt)[..., None] * bt[:, None, None, :])
        return h, jnp.einsum("bhpn,bn->bhp", h, ct, precision=precision)

    @jax.checkpoint
    def heads(args):
        """One chunk of ``Hc`` heads of one group over the whole window."""
        xc, dtc, ac, bc, cc = args        # [B,S,Hc,P], [B,S,Hc], [Hc], [B,S,N]
        seq = [jnp.moveaxis(v, 1, 0) for v in (xc, dtc, bc, cc)]
        blocks = [v.reshape((S // T, T) + v.shape[1:]) for v in seq]

        @jax.checkpoint
        def run_block(h, blk):
            xb, db, bb, cb = blk
            return jax.lax.scan(lambda h, t: step(
                h, (t[0], t[1], ac, t[2], t[3])), h, (xb, db, bb, cb))

        h0 = jnp.zeros((Bsz, Hc, P, N), jnp.float32)
        _, y = jax.lax.scan(run_block, h0, tuple(blocks))
        return jnp.moveaxis(y.reshape((S, Bsz, Hc, P)), 0, 1)

    n = H // Hc                          # chunks; chunk i reads group
    grp = (jnp.arange(n) * Hc) // (H // G)   # i * Hc // (H / G)
    xs = jnp.moveaxis(x.reshape(Bsz, S, n, Hc, P), 2, 0)
    dts = jnp.moveaxis(dt.reshape(Bsz, S, n, Hc), 2, 0)
    bs = jnp.moveaxis(Bm, 2, 0)[grp]     # [n, B, S, N]
    cs = jnp.moveaxis(Cm, 2, 0)[grp]
    y = jax.lax.map(heads, (xs, dts, A.reshape(n, Hc), bs, cs))
    return jnp.moveaxis(y, 0, 2).reshape(Bsz, S, H, P)


def _rmsnorm(x, w, eps):
    return lora_lm._rmsnorm(x, w, eps)


def _mamba(blk, ad, h, cfg, scale, precision):
    """The Mamba-2 mixer. The projection with the convolution and the
    readout act on ``HEAD_ROWS`` positions at a time (the convolution's
    block reads the ``W - 1`` positions before it as well), each block
    recomputed in the backward pass; the SSD runs over the whole window."""
    k = _dims(cfg)
    d_inner, conv_ch, H = k["d_inner"], k["conv_ch"], k["m_heads"]
    G, N, P, W = k["G"], k["N"], k["P"], k["W"]
    Bsz, S, _ = h.shape
    R = HEAD_ROWS if S % HEAD_ROWS == 0 else S

    @jax.checkpoint
    def project(r):
        """Positions [r R, (r + 1) R): in_proj of them and of the W - 1
        before (zero rows before the window: no bias, so their xBC is
        the convolution's zero padding), the causal convolution, SiLU."""
        hb = jax.lax.dynamic_slice_in_dim(hp, r * R, R + W - 1, axis=1)
        zxbcdt = _proj(hb, blk["in_proj"], ad, "in_proj", scale, precision)
        z = zxbcdt[:, W - 1:, :d_inner]
        xbc = zxbcdt[..., d_inner:d_inner + conv_ch]
        dt = zxbcdt[:, W - 1:, d_inner + conv_ch:]
        conv = sum(xbc[:, i:i + R] * blk["conv_w"][i] for i in range(W))
        xbc = lora_lm._silu(conv + blk["conv_b"])
        dt = jax.nn.softplus(dt.astype(jnp.float32) + blk["dt_bias"])
        return z, xbc, dt

    def readout(y, xs, z):
        y = y + blk["D"].astype(jnp.float32)[:, None] * xs.astype(jnp.float32)
        y = y.reshape(y.shape[0], y.shape[1], d_inner).astype(h.dtype)
        y = _rmsnorm(y * lora_lm._silu(z), blk["norm"], cfg["rms_norm_eps"])
        return _proj(y, blk["out_proj"], ad, "out_proj", scale, precision)

    hp = jnp.pad(h, ((0, 0), (W - 1, 0), (0, 0)))
    z, xbc, dt = (jnp.moveaxis(v, 0, 1).reshape((Bsz, S) + v.shape[3:])
                  for v in jax.lax.map(project, jnp.arange(S // R)))
    xs = xbc[..., :d_inner].reshape(Bsz, S, H, P)
    Bm = xbc[..., d_inner:d_inner + G * N].reshape(Bsz, S, G, N)
    Cm = xbc[..., d_inner + G * N:].reshape(Bsz, S, G, N)
    A = -jnp.exp(blk["A_log"].astype(jnp.float32))
    y = _ssd(xs.astype(jnp.float32), dt, A, Bm.astype(jnp.float32),
             Cm.astype(jnp.float32), precision)
    return _by_rows(readout, y, xs, z)


def _attention(blk, ad, h, cfg, scale, precision):
    """NoPE GQA: the dense causal softmax, one key/value head's group of
    query heads at a time."""
    k = _dims(cfg)
    Bsz, S, _ = h.shape
    K, hd = k["kv"], k["hd"]
    Gq = k["heads"] // K
    q = _proj(h, blk["wq"], ad, "wq", scale, precision)
    kk = _proj(h, blk["wk"], ad, "wk", scale, precision)
    v = _proj(h, blk["wv"], ad, "wv", scale, precision)
    q = q.reshape(Bsz, S, K, Gq, hd).transpose(2, 0, 3, 1, 4)  # K,B,G,S,hd
    kk = kk.reshape(Bsz, S, K, hd).transpose(2, 0, 1, 3)       # K,B,S,hd
    v = v.reshape(Bsz, S, K, hd).transpose(2, 0, 1, 3)
    causal = jnp.tril(jnp.ones((S, S), bool))

    @jax.checkpoint
    def group(args):
        qg, kg, vg = args
        s = jnp.einsum("bgqd,bkd->bgqk", qg, kg, precision=precision)
        s = jnp.where(causal, s * cfg["attention_multiplier"], -jnp.inf)
        p = jax.nn.softmax(s.astype(jnp.float32), -1)
        return jnp.einsum("bgqk,bkd->bgqd", p, vg, precision=precision)

    out = jax.lax.map(group, (q, kk, v))                       # K,B,G,S,hd
    out = out.transpose(1, 3, 0, 2, 4).reshape(Bsz, S, K * Gq * hd)
    return _dot(out, blk["wo"], precision)


def _mlp(blk, h, precision):
    """The gated-SiLU MLP, ``HEAD_ROWS`` positions at a time (it acts on
    each position alone)."""
    def rows(hr):
        return _dot(lora_lm._silu(_dot(hr, blk["w_gate"], precision))
                    * _dot(hr, blk["w_up"], precision), blk["w_down"],
                    precision)

    return _by_rows(rows, h)


def hidden(params, base, tokens, cfg: dict, precision):
    """tokens [B, S] int32 -> the final-normed hidden states [B, S, d],
    the mixer and the MLP of each layer recomputed in the backward pass."""
    period, kinds = _period(cfg)
    scale = cfg["alpha"] / cfg["rank"]
    eps, rm = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    x = base["embed"][tokens] * cfg["embedding_multiplier"]
    seen = {"attn": 0, "mamba": 0}
    for layer in range(cfg["num_hidden_layers"]):
        g, j = divmod(layer, period)
        kind = kinds[j]
        blk = jax.tree_util.tree_map(lambda v: v[g],
                                     base["groups"][f"pos{j}"])
        i = seen[kind]
        seen[kind] += 1
        ad = {n: v[i] for n, v in params["blocks"][kind].items()}

        @jax.checkpoint
        def mixer(x, blk, ad, kind=kind):
            h = _rmsnorm(x, blk["ln1"], eps)
            return x + rm * (_attention if kind == "attn" else _mamba)(
                blk[kind], ad, h, cfg, scale, precision)

        @jax.checkpoint
        def mlp(x, blk):
            h = _rmsnorm(x, blk["ln2"], eps)
            return x + rm * _mlp(blk["mlp"], h, precision)

        x = mlp(mixer(x, blk, ad), blk)
    return _rmsnorm(x, base["final_norm"], eps)


def _by_rows(fn, *arrays):
    """``fn`` over ``HEAD_ROWS`` positions at a time of ``[B, S, ...]``
    arrays (a position-wise map: each block recomputed in the backward
    pass), the results put back in order ``[B, S, ...]``."""
    S = arrays[0].shape[1]
    R = HEAD_ROWS if S % HEAD_ROWS == 0 else S
    blocks = tuple(a.reshape((a.shape[0], S // R, R) + a.shape[2:])
                   .swapaxes(0, 1) for a in arrays)
    out = jax.lax.map(jax.checkpoint(lambda xs: fn(*xs)), blocks)
    return out.swapaxes(0, 1).reshape((out.shape[1], S) + out.shape[3:])


def _head_rows(fn, x, t):
    """``fn(x_rows, t_rows)`` over ``HEAD_ROWS`` positions at a time of
    ``x`` [B, S, d] and ``t`` [B, S], stacked."""
    S = x.shape[1]
    R = HEAD_ROWS if S % HEAD_ROWS == 0 else S
    xs = x.reshape(x.shape[0], S // R, R, -1).swapaxes(0, 1)
    ts = t.reshape(t.shape[0], S // R, R).swapaxes(0, 1)
    return jax.lax.map(jax.checkpoint(lambda a: fn(*a)), (xs, ts))


def _logits(base, x, cfg, precision):
    return (_dot(x, base["embed"].T, precision)
            / cfg["logits_scaling"]).astype(jnp.float32)


def loss(params, x, y, cfg: dict, precision, frozen=None):
    """Next-token cross-entropy over the window shift: ``x`` [B, S+1]
    token windows; ``y`` (the dialect) is partition metadata only."""
    h = hidden(params, frozen, x[:, :-1], cfg, precision)

    def nll(hr, tr):
        logp = jax.nn.log_softmax(_logits(frozen, hr, cfg, precision), -1)
        return -jnp.sum(jnp.take_along_axis(logp, tr[..., None], -1))

    return jnp.sum(_head_rows(nll, h, x[:, 1:])) / x[:, 1:].size


def evaluate(params, x, y, cfg: dict, precision, frozen=None):
    """Next-token accuracy: per window the share of positions whose
    largest logit is the next token, averaged over windows."""
    h = hidden(params, frozen, x[:, :-1], cfg, precision)

    def hits(hr, tr):
        pred = jnp.argmax(_logits(frozen, hr, cfg, precision), -1)
        return jnp.sum((pred == tr).astype(jnp.float32), -1)    # [B]

    per_window = jnp.sum(_head_rows(hits, h, x[:, 1:]), 0) / h.shape[1]
    return jnp.mean(per_window)


def features(clients) -> jnp.ndarray:
    """Alg. 2's K-means input: the adapter's last leaf in flatten order
    (``blocks/mamba/out_proj_b``), the leaf the program's
    ``feature_layer="auto"`` falls back to, one row per client."""
    return lora_lm.features(clients)


def as_input(x, dtype):
    """Token ids stay int32 whatever the weights' dtype."""
    return jnp.asarray(x, jnp.int32)


def upload_mbit(cfg: dict) -> float:
    """A client uploads its adapter alone: P_adapter float32 values."""
    return adapter_params(cfg) * 32 / 1e6


def make_data(cfg: dict, spec: dict, num_samples: int, seed: int):
    """``lora_lm``'s dialect token windows over ``model.vocab_size``."""
    return lora_lm.make_data(cfg, spec, num_samples, seed)


# ---------------------------------------------------------------------------
# FLOPs per sample
# ---------------------------------------------------------------------------


def _layer_flops(cfg: dict) -> dict:
    """Forward FLOPs of one window (``seq_len`` positions), 2 a
    multiply-add: per kind of mixer, the MLP, the head; the adapters'
    products apart (``adapters``), and the causal attention's scores and
    weighted values (each query against itself and the positions before
    it) apart (``scores``)."""
    k = _dims(cfg)
    S, r = cfg["seq_len"], cfg["rank"]
    t = targets(cfg)
    d, hq, hkv = k["d"], k["heads"] * k["hd"], k["kv"] * k["hd"]
    H, P, N = k["m_heads"], k["P"], k["N"]
    return {
        "mamba": S * (2 * d * t["mamba"]["in_proj"][1]
                      + 2 * k["W"] * k["conv_ch"]
                      + 4 * H * P * N
                      + 2 * k["d_inner"] * d),
        "attn": S * 2 * d * (hq + 2 * hkv + hq),
        "scores": 4 * hq * S * (S + 1) // 2,
        "mlp": S * 3 * 2 * d * k["F"],
        "adapters": {kind: S * sum(2 * r * (a + b) for a, b in tt.values())
                     for kind, tt in t.items()},
        "head": S * 2 * d * k["V"],
        "first_input": S * 2 * d * (t["mamba"]["in_proj"][1] + r),
        "ssd": S * 4 * H * P * N,
    }


def eval_flops(cfg: dict) -> int:
    """One test window: the forward pass over its ``seq_len`` positions."""
    f, n = _layer_flops(cfg), _layers_of(cfg)
    return (n["mamba"] * (f["mamba"] + f["adapters"]["mamba"])
            + n["attn"] * (f["attn"] + f["scores"] + f["adapters"]["attn"])
            + (n["mamba"] + n["attn"]) * f["mlp"] + f["head"])


def train_flops(cfg: dict) -> int:
    """One training window: the forward pass plus a backward pass that
    takes no gradient of a base weight. The backward repeats every base
    contraction once (its activation's gradient), the SSD and the
    attention's scores and weighted values twice (all their operands are
    activations), each adapter product twice (its activation's gradient
    and its factor's); the first layer's input needs no gradient (its
    ``in_proj`` and ``A`` products are not repeated for it). Recomputation
    is not counted."""
    f, n = _layer_flops(cfg), _layers_of(cfg)
    backward = (n["mamba"] * (f["mamba"] + f["ssd"]
                              + 2 * f["adapters"]["mamba"])
                + n["attn"] * (f["attn"] + 2 * f["scores"]
                               + 2 * f["adapters"]["attn"])
                + (n["mamba"] + n["attn"]) * f["mlp"] + f["head"]
                - f["first_input"])
    return eval_flops(cfg) + backward
