"""Plain reference of the paper's local model (arXiv 2212.13544, Fig. 3 and
Table II): conv 5x5 -> ReLU -> 2x2 max-pool -> conv 5x5 -> ReLU -> 2x2
max-pool -> fc1 -> ReLU -> fc2, with cross-entropy loss.

Written from the paper in plain ``jax.numpy``: the convolutions are
``lax.conv_general_dilated`` (VALID, NHWC/HWIO), every contraction takes the
precision it is given, and every array is held in the dtype it is given.
The widths come from the configuration file. ``init`` draws the weights
from a key exactly as the paper's experiments are seeded (one normal draw
per weight matrix, scaled by 1/sqrt(fan-in), zero biases), so that the
reference starts where the experiment starts.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

LAYERS = ("w_c1", "b_c1", "w_c2", "b_c2", "w_fc1", "b_fc1", "w_fc2", "b_fc2")


def flat_features(cfg: dict) -> int:
    h, w = cfg["input_hw"]
    k, p = cfg["kernel"], cfg["pool"]
    for _ in range(2):
        h, w = (h - k + 1) // p, (w - k + 1) // p
    return h * w * cfg["conv2_out"]


def num_params(cfg: dict) -> int:
    k, cin = cfg["kernel"], cfg["input_channels"]
    c1, c2, f1, nc = (cfg["conv1_out"], cfg["conv2_out"], cfg["fc1_out"],
                      cfg["num_classes"])
    return (k * k * cin * c1 + c1 + k * k * c1 * c2 + c2
            + flat_features(cfg) * f1 + f1 + f1 * nc + nc)


def init(cfg: dict, key):
    """float32 weights from ``key``."""
    ks = jax.random.split(key, 4)
    k, cin = cfg["kernel"], cfg["input_channels"]
    c1, c2, f1, nc = (cfg["conv1_out"], cfg["conv2_out"], cfg["fc1_out"],
                      cfg["num_classes"])
    F = flat_features(cfg)

    def normal(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * (1.0 / math.sqrt(fan_in)))

    return {"w_c1": normal(ks[0], (k, k, cin, c1), k * k * cin),
            "b_c1": jnp.zeros((c1,), jnp.float32),
            "w_c2": normal(ks[1], (k, k, c1, c2), k * k * c1),
            "b_c2": jnp.zeros((c2,), jnp.float32),
            "w_fc1": normal(ks[2], (F, f1), F),
            "b_fc1": jnp.zeros((f1,), jnp.float32),
            "w_fc2": normal(ks[3], (f1, nc), f1),
            "b_fc2": jnp.zeros((nc,), jnp.float32)}


def forward(params, images, cfg: dict, precision):
    """images [B, H, W, C] -> logits [B, classes], in the params' dtype."""
    dt = params["w_c1"].dtype
    p = cfg["pool"]

    def conv(x, w, b):
        y = lax.conv_general_dilated(
            x, w, (1, 1), "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=precision, preferred_element_type=dt)
        return y + b

    def pool(x):
        return lax.reduce_window(x, -jnp.inf, lax.max,
                                 (1, p, p, 1), (1, p, p, 1), "VALID")

    x = images.astype(dt)
    x = pool(jax.nn.relu(conv(x, params["w_c1"], params["b_c1"])))
    x = pool(jax.nn.relu(conv(x, params["w_c2"], params["b_c2"])))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(jnp.dot(x, params["w_fc1"], precision=precision,
                            preferred_element_type=dt) + params["b_fc1"])
    return jnp.dot(x, params["w_fc2"], precision=precision,
                   preferred_element_type=dt) + params["b_fc2"]


def loss(params, images, labels, cfg: dict, precision):
    """Mean cross-entropy (the paper's loss, section III-C)."""
    logp = jax.nn.log_softmax(forward(params, images, cfg, precision), -1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], -1)[:, 0])
