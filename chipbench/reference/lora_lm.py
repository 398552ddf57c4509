"""Plain reference of a federated LoRA language model: per-client low-rank
adapters over a frozen Mamba-2 stack (SSD, arXiv 2405.21060; LoRA, arXiv
2106.09685), trained on next-token cross-entropy.

The configuration file gives the frozen model under ``model`` (``family``
``ssm``; ``num_layers``, ``d_model``, ``vocab_size``, ``tie_embeddings``,
``norm_eps`` and the ``ssm`` group: ``d_state``, ``head_dim``, ``expand``,
``n_groups``, ``conv_width``, ``dt_min``, ``dt_max``) and the adapter at
the top level (``seq_len``, ``rank``, ``alpha``, ``base_seed``,
``num_dialects``). Each layer is

    h = rmsnorm(x);  x = x + mamba(h)

and the logits are ``rmsnorm(x) @ embed.T`` (tied) or ``@ lm_head``.
LoRA wraps the Mamba block's ``in_proj`` and ``out_proj``: ``y = h @ W +
(alpha / rank) * (h @ A) @ B``, with ``A`` ``[L, d_in, rank]`` drawn
normal / sqrt(d_in) and ``B`` ``[L, rank, d_out]`` zero, so a fresh
adapter leaves the base unchanged.

Written in plain ``jax.numpy``, every contraction at the precision it is
given (the configuration states it; ``jax.lax.Precision.HIGHEST`` keeps a
TPU's float32 matmuls in float32), every array in the dtype it is given,
and independent of the program's arithmetic: the adapter is applied as
two products beside the base weight, never merged into it; the causal
convolution is a sum of shifted slices; and the SSD is the step-by-step
recurrence over positions (``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t``,
``y_t = C_t h_t + D x_t``), not a chunked form. The initial weights follow
the program's initialisation rule draw for draw (a copy of it, here), so
that the reference starts where the experiment starts; the frozen base
is made from ``base_seed`` alone, and the check holds the program's base
to it element for element.

This module is one model of the benchmark's model interface (see
``chipbench/reference/fl.py``). Its data, ``make_data``, are dialect
token windows: a copy of the program's ``repro.data.lm_data`` and
``repro.data.synthetic.make_token_stream``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

#: decorrelates per-dialect stream seeds from the dataset seed
DIALECT_SEED_STRIDE = 1009


def _model(cfg: dict) -> dict:
    m = cfg["model"]
    if m["family"] != "ssm":
        raise ValueError(f"lora_lm: family {m['family']!r}; this reference "
                         "holds the Mamba-2 (ssm) stack only")
    return m


def ssm_dims(m: dict):
    """``(d_inner, heads, conv_channels)`` of one Mamba-2 block."""
    s = m["ssm"]
    d_inner = s["expand"] * m["d_model"]
    return (d_inner, d_inner // s["head_dim"],
            d_inner + 2 * s["n_groups"] * s["d_state"])


def targets(cfg: dict) -> dict:
    """``name -> (d_in, d_out)`` of the base projections LoRA wraps."""
    m = _model(cfg)
    d_inner, heads, conv_ch = ssm_dims(m)
    return {"in_proj": (m["d_model"], d_inner + conv_ch + heads),
            "out_proj": (d_inner, m["d_model"])}


def adapter_params(cfg: dict) -> int:
    """P_adapter: one client's trainable parameters."""
    return cfg["model"]["num_layers"] * sum(
        cfg["rank"] * (d_in + d_out) for d_in, d_out in targets(cfg).values())


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def init(cfg: dict, key):
    """One client's adapter from ``key``: ``{"blocks": {"mamba": {
    "<target>_a", "<target>_b"}}}``, targets in sorted order, one key
    each."""
    L, r = cfg["model"]["num_layers"], cfg["rank"]
    t = targets(cfg)
    ks = jax.random.split(key, len(t))
    leaves = {}
    for k, (name, (d_in, d_out)) in zip(ks, sorted(t.items())):
        leaves[f"{name}_a"] = (jax.random.normal(k, (L, d_in, r), jnp.float32)
                               * (1.0 / math.sqrt(d_in)))
        leaves[f"{name}_b"] = jnp.zeros((L, r, d_out), jnp.float32)
    return {"blocks": {"mamba": leaves}}


def _normal(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * scale


def _mamba_block(key, m: dict):
    s = m["ssm"]
    d = m["d_model"]
    d_inner, heads, conv_ch = ssm_dims(m)
    ks = jax.random.split(key, 5)
    dt = jnp.exp(jax.random.uniform(ks[3], (heads,), jnp.float32)
                 * (math.log(s["dt_max"]) - math.log(s["dt_min"]))
                 + math.log(s["dt_min"]))
    return {
        "in_proj": _normal(ks[0], (d, d_inner + conv_ch + heads),
                           1.0 / math.sqrt(d)),
        "conv_w": _normal(ks[1], (s["conv_width"], conv_ch),
                          1.0 / math.sqrt(s["conv_width"])),
        "conv_b": jnp.zeros((conv_ch,), jnp.float32),
        "A_log": jnp.log(jnp.arange(1, heads + 1, dtype=jnp.float32)),
        "D": jnp.ones((heads,), jnp.float32),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),     # softplus^-1(dt)
        "norm": jnp.ones((d_inner,), jnp.float32),
        "out_proj": _normal(ks[4], (d_inner, d), 1.0 / math.sqrt(d_inner)),
    }


def frozen(cfg: dict):
    """The frozen base from ``base_seed``: ``embed`` (normal x 0.02),
    ``final_norm``, ``lm_head`` where the embedding is not tied, and the
    layer-stacked ``blocks`` (``ln1`` and the Mamba-2 block), each layer
    from its own split of the key, op by op as the program makes it."""
    m = _model(cfg)
    d, V = m["d_model"], m["vocab_size"]
    ks = jax.random.split(jax.random.PRNGKey(cfg["base_seed"]), 8)
    base = {"embed": _normal(ks[0], (V, d), 0.02),
            "final_norm": jnp.ones((d,), jnp.float32)}
    if not m["tie_embeddings"]:
        base["lm_head"] = _normal(ks[1], (d, V), 1.0 / math.sqrt(d))

    def block(key):
        k = jax.random.split(key, 4)
        return {"ln1": jnp.ones((d,), jnp.float32),
                "mamba": _mamba_block(k[0], m)}

    base["blocks"] = jax.vmap(block)(
        jax.random.split(ks[2], m["num_layers"]))
    return base


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------


def _rmsnorm(x, w, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), -1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _ssd(x, dt, A, Bm, Cm, precision):
    """The SSD recurrence, position by position. x [B, S, H, P]; dt [B, S,
    H]; A [H] (negative); Bm, Cm [B, S, G, N], head h reading group
    h // (H / G). Returns y [B, S, H, P] in float32 (without the D
    skip)."""
    H, G = x.shape[2], Bm.shape[2]
    Bh = jnp.repeat(Bm, H // G, axis=2)                       # [B,S,H,N]
    Ch = jnp.repeat(Cm, H // G, axis=2)

    def step(h, t):
        xt, dtt, bt, ct = t
        h = (jnp.exp(dtt * A)[..., None, None] * h
             + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :])
        return h, jnp.einsum("bhpn,bhn->bhp", h, ct, precision=precision)

    Bsz, _, _, P = x.shape
    h0 = jnp.zeros((Bsz, H, P, Bm.shape[3]), jnp.float32)
    seq = (jnp.moveaxis(x, 1, 0), jnp.moveaxis(dt, 1, 0),
           jnp.moveaxis(Bh, 1, 0), jnp.moveaxis(Ch, 1, 0))
    _, y = jax.lax.scan(step, h0, seq)
    return jnp.moveaxis(y, 0, 1)


def _mamba(blk, ad, h, m: dict, scale, precision):
    s = m["ssm"]
    d_inner, heads, conv_ch = ssm_dims(m)
    G, N, P = s["n_groups"], s["d_state"], s["head_dim"]
    Bsz, S, _ = h.shape

    def proj(v, w, a, b):
        dot = lambda p, q: jnp.dot(p, q, precision=precision)
        return dot(v, w) + scale * dot(dot(v, a), b)

    zxbcdt = proj(h, blk["in_proj"], ad["in_proj_a"], ad["in_proj_b"])
    z = zxbcdt[..., :d_inner]
    xbc = zxbcdt[..., d_inner:d_inner + conv_ch]
    dt = zxbcdt[..., d_inner + conv_ch:]
    W = s["conv_width"]
    padded = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    conv = sum(padded[:, k:k + S] * blk["conv_w"][k] for k in range(W))
    xbc = _silu(conv + blk["conv_b"])
    xs = xbc[..., :d_inner].reshape(Bsz, S, heads, P)
    Bm = xbc[..., d_inner:d_inner + G * N].reshape(Bsz, S, G, N)
    Cm = xbc[..., d_inner + G * N:].reshape(Bsz, S, G, N)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + blk["dt_bias"])
    A = -jnp.exp(blk["A_log"].astype(jnp.float32))
    y = _ssd(xs.astype(jnp.float32), dt, A, Bm.astype(jnp.float32),
             Cm.astype(jnp.float32), precision)
    y = y + blk["D"].astype(jnp.float32)[:, None] * xs.astype(jnp.float32)
    y = y.reshape(Bsz, S, d_inner).astype(h.dtype)
    y = _rmsnorm(y * _silu(z), blk["norm"], m["norm_eps"])
    return proj(y, blk["out_proj"], ad["out_proj_a"], ad["out_proj_b"])


def forward(params, base, tokens, cfg: dict, precision):
    """tokens [B, S] int32 -> logits [B, S, vocab]."""
    m = _model(cfg)
    scale = cfg["alpha"] / cfg["rank"]
    ad = params["blocks"]["mamba"]
    x = base["embed"][tokens]
    for layer in range(m["num_layers"]):
        blk = jax.tree_util.tree_map(lambda v: v[layer], base["blocks"])
        h = _rmsnorm(x, blk["ln1"], m["norm_eps"])
        x = x + _mamba(blk["mamba"], {k: v[layer] for k, v in ad.items()},
                       h, m, scale, precision)
    x = _rmsnorm(x, base["final_norm"], m["norm_eps"])
    head = base["embed"].T if m["tie_embeddings"] else base["lm_head"]
    return jnp.dot(x, head, precision=precision)


def loss(params, x, y, cfg: dict, precision, frozen=None):
    """Next-token cross-entropy over the window shift: ``x`` [B, S+1]
    token windows; ``y`` (the dialect) is partition metadata only."""
    logits = forward(params, frozen, x[:, :-1], cfg, precision)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    return -jnp.mean(jnp.take_along_axis(logp, x[:, 1:, None], -1))


def evaluate(params, x, y, cfg: dict, precision, frozen=None):
    """Next-token accuracy: per window the share of positions whose
    largest logit is the next token, averaged over windows."""
    logits = forward(params, frozen, x[:, :-1], cfg, precision)
    hit = (jnp.argmax(logits, -1) == x[:, 1:]).astype(jnp.float32)
    return jnp.mean(jnp.mean(hit, -1))


def features(clients) -> jnp.ndarray:
    """Alg. 2's K-means input: the adapter's last leaf in flatten order
    (``blocks/mamba/out_proj_b``), the leaf the program's
    ``feature_layer="auto"`` falls back to, one row per client."""
    leaf = jax.tree_util.tree_leaves(clients)[-1]
    return leaf.reshape(leaf.shape[0], -1)


def as_input(x, dtype):
    """Token ids stay int32 whatever the weights' dtype."""
    return jnp.asarray(x, jnp.int32)


def upload_mbit(cfg: dict) -> float:
    """A client uploads its adapter alone: P_adapter float32 values."""
    return adapter_params(cfg) * 32 / 1e6


# ---------------------------------------------------------------------------
# data: dialect token windows
# ---------------------------------------------------------------------------


def token_stream(vocab_size: int, num_tokens: int, seed: int) -> np.ndarray:
    """A first-order Markov stream over min(64, vocab) states with
    Dirichlet(0.1) transitions, drawn token by token."""
    rng = np.random.default_rng(seed)
    ctx = min(64, vocab_size)
    trans = rng.dirichlet(np.ones(ctx) * 0.1, size=ctx)
    toks = np.zeros(num_tokens, np.int64)
    s = 0
    for i in range(num_tokens):
        s = rng.choice(ctx, p=trans[s])
        toks[i] = s % vocab_size
    return toks.astype(np.int32)


def make_data(cfg: dict, spec: dict, num_samples: int, seed: int):
    """``(windows [n, seq_len+1] int32, dialects [n] int32, dialects)``:
    each dialect its own Markov stream cut into windows, the windows
    shuffled from ``seed``."""
    k, width = cfg["num_dialects"], cfg["seq_len"] + 1
    V = cfg["model"]["vocab_size"]
    per = -(-num_samples // k)
    windows = np.empty((k * per, width), np.int32)
    dialects = np.empty((k * per,), np.int32)
    for d in range(k):
        stream = token_stream(V, per * width,
                              seed * DIALECT_SEED_STRIDE + d)
        windows[d * per:(d + 1) * per] = stream.reshape(per, width)
        dialects[d * per:(d + 1) * per] = d
    order = np.random.default_rng(seed).permutation(k * per)[:num_samples]
    return windows[order], dialects[order], k


# ---------------------------------------------------------------------------
# FLOPs per sample
# ---------------------------------------------------------------------------


def _token_flops(cfg: dict):
    """Per token and layer: the base's contractions (``in_proj``, the
    depthwise convolution, ``out_proj``), the SSD recurrence (the decayed
    state plus ``x B``, and ``C h``: two multiply-adds per state element
    and head), the adapters' two products per target, and the head; 2
    FLOPs a multiply-add."""
    m = _model(cfg)
    s = m["ssm"]
    d, r = m["d_model"], cfg["rank"]
    d_inner, heads, conv_ch = ssm_dims(m)
    t = targets(cfg)
    return {"in_proj": 2 * d * t["in_proj"][1],
            "conv": 2 * s["conv_width"] * conv_ch,
            "out_proj": 2 * d_inner * d,
            "ssd": 4 * heads * s["head_dim"] * s["d_state"],
            "adapters": sum(2 * r * (a + b) for a, b in t.values()),
            "head": 2 * d * m["vocab_size"],
            "first_input": 2 * d * (t["in_proj"][1] + r)}


def eval_flops(cfg: dict) -> int:
    """One test window: the forward pass over its ``seq_len`` positions."""
    f = _token_flops(cfg)
    L = cfg["model"]["num_layers"]
    per_layer = (f["in_proj"] + f["conv"] + f["out_proj"] + f["ssd"]
                 + f["adapters"])
    return cfg["seq_len"] * (L * per_layer + f["head"])


def train_flops(cfg: dict) -> int:
    """One training window: the forward pass plus a backward pass through
    the frozen base that takes no gradient of a base weight. The backward
    pass repeats every base contraction once, for its activation's
    gradient; the SSD twice (all its operands are activations); each
    adapter product twice (its activation's gradient and its factor's);
    except that the first layer's input needs no gradient (its
    ``in_proj`` and ``A`` products are not repeated for it)."""
    f = _token_flops(cfg)
    L = cfg["model"]["num_layers"]
    backward = (L * (f["in_proj"] + f["conv"] + f["out_proj"]
                     + 2 * f["ssd"] + 2 * f["adapters"])
                + f["head"] - f["first_input"])
    return eval_flops(cfg) + cfg["seq_len"] * backward
