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

This module is one model of the benchmark's model interface (see
``chipbench/reference/fl.py``): ``init``, ``frozen``, ``loss``,
``evaluate``, ``features``, ``make_data``, ``upload_mbit``,
``train_flops``, ``eval_flops`` and ``as_input``.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

LAYERS = ("w_c1", "b_c1", "w_c2", "b_c2", "w_fc1", "b_fc1", "w_fc2", "b_fc2")

#: the paper's upload payload z: the 448 KB MNIST model of Table II
UPLOAD_MBIT = 448 * 8 * 1024 / 1e6
#: pixel noise of the synthetic images
NOISE = 0.25


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


def frozen(cfg: dict):
    """The whole CNN trains: nothing is frozen."""
    return None


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


def loss(params, images, labels, cfg: dict, precision, frozen=None):
    """Mean cross-entropy (the paper's loss, section III-C)."""
    logp = jax.nn.log_softmax(forward(params, images, cfg, precision), -1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], -1)[:, 0])


def evaluate(params, images, labels, cfg: dict, precision, frozen=None):
    """Test accuracy: the share of images whose largest logit is their
    class."""
    logits = forward(params, images, cfg, precision)
    return jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))


def features(clients) -> jnp.ndarray:
    """Alg. 2's K-means input: each client's ``w_fc2`` (the paper's
    choice, Fig. 8), one row per client."""
    w = clients["w_fc2"]
    return w.reshape(w.shape[0], -1)


def as_input(x, dtype):
    """Images enter the model in the weights' dtype."""
    return jnp.asarray(x, dtype)


def upload_mbit(cfg: dict) -> float:
    return UPLOAD_MBIT


# ---------------------------------------------------------------------------
# data: class-template images at the dataset's shape
# ---------------------------------------------------------------------------


def _class_templates(rng, num_classes, h, w, c):
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
    yy, xx = yy / h, xx / w
    templates = np.zeros((num_classes, h, w, c), np.float32)
    for k in range(num_classes):
        img = np.zeros((h, w, c), np.float32)
        for _ in range(6):
            fy, fx = rng.uniform(0.5, 4.0, 2)
            ph = rng.uniform(0, 2 * np.pi, c)
            amp = rng.uniform(0.3, 1.0)
            img += amp * np.sin(2 * np.pi * (fy * yy + fx * xx))[..., None]
            img += amp * 0.3 * np.cos(ph)[None, None, :]
        templates[k] = img
    templates -= templates.min()
    templates /= max(templates.max(), 1e-6)
    return templates


def make_data(cfg: dict, spec: dict, num_samples: int, seed: int):
    """``(images [n, H, W, C] float32 in [0, 1], labels [n] int32,
    classes)``: per-class smooth templates (keyed by the dataset name
    alone, so train and test share classes), shifted by up to 2 pixels,
    plus noise. A copy of the program's ``repro.data.synthetic.
    make_dataset``."""
    h, w = cfg["input_hw"]
    c = cfg["input_channels"]
    k = cfg["num_classes"]
    templates = _class_templates(
        np.random.default_rng(zlib.crc32(spec["dataset"].encode())),
        k, h, w, c)
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, k, num_samples).astype(np.int32)
    shift = rng.integers(-2, 3, (num_samples, 2))
    images = np.empty((num_samples, h, w, c), np.float32)
    base = templates[labels]
    for i in range(num_samples):
        images[i] = np.roll(base[i], tuple(shift[i]), axis=(0, 1))
    images += rng.normal(0.0, NOISE, images.shape).astype(np.float32)
    return np.clip(images, 0.0, 1.0), labels, k


# ---------------------------------------------------------------------------
# FLOPs per sample
# ---------------------------------------------------------------------------


def forward_flops(cfg: dict) -> int:
    """FLOPs of one sample's forward pass: the multiply-adds of the
    convolutions and dense layers, 2 FLOPs each; biases, ReLU and pooling
    are not counted."""
    h, w = cfg["input_hw"]
    k, p, cin = cfg["kernel"], cfg["pool"], cfg["input_channels"]
    c1, c2, f1, nc = (cfg["conv1_out"], cfg["conv2_out"], cfg["fc1_out"],
                      cfg["num_classes"])
    h1, w1 = h - k + 1, w - k + 1
    conv1 = h1 * w1 * c1 * k * k * cin
    h2, w2 = h1 // p - k + 1, w1 // p - k + 1
    conv2 = h2 * w2 * c2 * k * k * c1
    flat = (h2 // p) * (w2 // p) * c2
    return 2 * (conv1 + conv2 + flat * f1 + f1 * nc)


def train_flops(cfg: dict) -> int:
    """One training sample: its forward and backward pass, 3x the
    forward."""
    return 3 * forward_flops(cfg)


def eval_flops(cfg: dict) -> int:
    return forward_flops(cfg)
