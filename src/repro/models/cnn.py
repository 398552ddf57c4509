"""The paper's local model (Fig. 3): conv5x5 -> pool -> conv5x5 -> pool ->
fc1 -> fc2, with per-layer named params so the K-means feature-layer study
(Fig. 4 / Fig. 8 / Fig. 9) can select ``w_c1 … b_fc2`` exactly as the paper
does.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.paper_cnn import CNNConfig

PAPER_LAYER_NAMES = ("w_c1", "b_c1", "w_c2", "b_c2",
                     "w_fc1", "b_fc1", "w_fc2", "b_fc2")


def init_cnn(cfg: CNNConfig, key, dtype=jnp.float32) -> Dict[str, jnp.ndarray]:
    ks = jax.random.split(key, 4)
    k5 = cfg.kernel

    def conv_init(k, cin, cout):
        scale = 1.0 / math.sqrt(k5 * k5 * cin)
        return (jax.random.normal(k, (k5, k5, cin, cout), jnp.float32)
                * scale).astype(dtype)

    def fc_init(k, din, dout):
        scale = 1.0 / math.sqrt(din)
        return (jax.random.normal(k, (din, dout), jnp.float32) * scale).astype(dtype)

    return {
        "w_c1": conv_init(ks[0], cfg.input_channels, cfg.conv1_out),
        "b_c1": jnp.zeros((cfg.conv1_out,), dtype),
        "w_c2": conv_init(ks[1], cfg.conv1_out, cfg.conv2_out),
        "b_c2": jnp.zeros((cfg.conv2_out,), dtype),
        "w_fc1": fc_init(ks[2], cfg.flat_features, cfg.fc1_out),
        "b_fc1": jnp.zeros((cfg.fc1_out,), dtype),
        "w_fc2": fc_init(ks[3], cfg.fc1_out, cfg.num_classes),
        "b_fc2": jnp.zeros((cfg.num_classes,), dtype),
    }


def _conv(x, w, b):
    """5x5 VALID convolution, NHWC input and HWIO kernel, at XLA's default
    precision.

    One ``lax.conv_general_dilated``. The FL round vmaps it over per-client
    kernels (and the cohort engine over seeds on top), which JAX lowers to
    a grouped convolution that XLA:TPU's convolution emitter runs as it is.
    An im2col spelling (25 shifted slices concatenated on the channel axis,
    then one GEMM) put the patch matrix on the TPU's 128-wide lane axis,
    where conv1's one-channel slices each pad to 128 lanes: on a v5e the
    padded copies and layout changes took two thirds of the paper
    experiment's device time. XLA CPU runs the vmapped grouped convolution
    about 3x slower than that GEMM; the TPU is the target.
    """
    y = lax.conv_general_dilated(x, w, (1, 1), "VALID",
                                 dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + b


def _maxpool(x, p):
    return lax.reduce_window(x, -jnp.inf, lax.max,
                             (1, p, p, 1), (1, p, p, 1), "VALID")


def cnn_forward(params, images, cfg: CNNConfig):
    """images: [B, H, W, C] -> logits [B, num_classes]."""
    x = jax.nn.relu(_conv(images, params["w_c1"], params["b_c1"]))
    x = _maxpool(x, cfg.pool)
    x = jax.nn.relu(_conv(x, params["w_c2"], params["b_c2"]))
    x = _maxpool(x, cfg.pool)
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ params["w_fc1"] + params["b_fc1"])
    return x @ params["w_fc2"] + params["b_fc2"]


def cnn_loss(params, batch, cfg: CNNConfig):
    """Cross-entropy loss (the paper's loss, §III-C)."""
    logits = cnn_forward(params, batch["images"], cfg)
    labels = batch["labels"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(nll)


def cnn_accuracy(params, batch, cfg: CNNConfig):
    logits = cnn_forward(params, batch["images"], cfg)
    return jnp.mean((jnp.argmax(logits, -1) == batch["labels"]).astype(jnp.float32))
