"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

These are deliberately written in the most *naive* correct form — e.g. the
SSD oracle is the token-by-token recurrence, not the chunked algorithm — so
kernel tests compare two genuinely independent implementations.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def pairwise_l2_ref(x: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    """Squared Euclidean distances. x: [N, F]; c: [M, F] -> [N, M] fp32.

    Naive O(N·M·F)-memory difference form — the kernel-test oracle only.
    Production call sites go through ``repro.kernels.ops.pairwise_sq_dists``
    (the streaming ‖x‖²+‖c‖²−2x·c expansion, clamped at zero).
    """
    x = x.astype(jnp.float32)
    c = c.astype(jnp.float32)
    diff = x[:, None, :] - c[None, :, :]
    return jnp.sum(jnp.square(diff), axis=-1)


def flat_aggregate_ref(flat: jnp.ndarray, weights: jnp.ndarray) -> jnp.ndarray:
    """Weighted row sum over the flat client plane: [N, P] × [N] -> [P].

    Spelled as an elementwise multiply + axis-0 reduce (NOT a dot) so the
    summation order matches ``tree_weighted_mean_stacked`` column for
    column — the flat FedAvg path stays bit-identical to the pytree path
    in fp32. Doubles as the production jnp path off-TPU.
    """
    w = weights.astype(jnp.float32)
    return jnp.sum(flat.astype(jnp.float32) * w[:, None], axis=0)


def flash_attention_ref(q, k, v, *, causal: bool = True,
                        window: int | None = None,
                        scale: float | None = None) -> jnp.ndarray:
    """Plain softmax attention. q: [B, H, Sq, D]; k, v: [B, H, Sk, D].
    ``scale`` multiplies the scores (None = 1/sqrt(D))."""
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32))
    logits = (logits / jnp.sqrt(jnp.float32(D)) if scale is None
              else logits * scale)
    qpos = jnp.arange(Sq)[:, None] + (Sk - Sq)   # right-aligned positions
    kpos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= (qpos - kpos) < window
    logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def ssd_ref(X, A, Bm, Cm) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Token-by-token SSD recurrence (the definitionally-correct oracle).

    X: [B, S, H, P] (pre-scaled by dt); A: [B, S, H] log-decay; Bm, Cm:
    [B, S, H, N] (already head-expanded). Returns (Y [B,S,H,P], h [B,H,P,N]).

      h_t = exp(A_t)·h_{t-1} + B_t ⊗ X_t ;   y_t = h_t · C_t
    """
    B, S, H, P = X.shape
    N = Bm.shape[-1]
    h0 = jnp.zeros((B, H, P, N), jnp.float32)

    def step(h, inp):
        x_t, a_t, b_t, c_t = inp
        h = h * jnp.exp(a_t)[..., None, None] + jnp.einsum(
            "bhp,bhn->bhpn", x_t, b_t)
        y = jnp.einsum("bhpn,bhn->bhp", h, c_t)
        return h, y

    xs = (X.transpose(1, 0, 2, 3).astype(jnp.float32),
          A.transpose(1, 0, 2).astype(jnp.float32),
          Bm.transpose(1, 0, 2, 3).astype(jnp.float32),
          Cm.transpose(1, 0, 2, 3).astype(jnp.float32))
    h, ys = lax.scan(step, h0, xs)
    return ys.transpose(1, 0, 2, 3), h
