"""Operations and bytes the benchmark's work needs, from shapes alone.

Model FLOPs count the multiply-adds of the convolutions and dense layers
(2 FLOPs each); a training sample costs its forward and backward pass,
3x the forward. Biases, ReLU, pooling, recomputation and padding are not
counted. Kernel bytes and FLOPs are those of one call at its logical
(unpadded) operand shapes, in float32.
"""
from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def peaks(device_kind: str) -> dict:
    """The chip's peaks; a device kind not in the table is an error."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


def cnn_forward_flops(cfg: dict) -> int:
    """FLOPs of one sample's forward pass through the paper's CNN."""
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


def experiment_flops(cfg: dict, spec: dict, updates: int) -> int:
    """Model FLOPs of one experiment with ``updates`` client local updates
    (the initial round's N and each round's selected clients): L SGD steps
    of ``batch_size`` samples each, forward and backward, plus a forward
    pass over the test set after the initial round and after each round."""
    fwd = cnn_forward_flops(cfg)
    train = updates * spec["local_iters"] * spec["batch_size"] * 3 * fwd
    evals = (spec["rounds"] + 1) * spec["test_samples"] * fwd
    return train + evals


def moved_bytes(shapes) -> int:
    """Bytes a kernel call moves: every operand read once and the result
    written once, at the shapes the trace gives (``[[dtype, dims], ...]``,
    result first)."""
    from chipbench.trace import nbytes

    return sum(nbytes(s) for s in shapes)


def batch(dims, own: int = 2) -> int:
    """The vmapped batch of an operand: the product of its dims beyond the
    kernel's own ``own`` trailing ones."""
    n = 1
    for d in dims[:-own]:
        n *= d
    return n


def least_seconds(flops: float, nbytes: float, pk: dict):
    """The least time the chip needs, and which of the two terms bounds
    it: ``(seconds, "flops" | "bytes")``."""
    t_f = flops / pk["bf16_flops_per_s"]
    t_b = nbytes / pk["hbm_bytes_per_s"]
    return (t_b, "bytes") if t_b >= t_f else (t_f, "flops")


def roofline_pct(ctx, kernel: str, cost):
    """A kernel's share of its roofline over the traced window, in
    percent: the least time of every call in the window (``cost(shapes)
    -> (FLOPs, bytes)`` of one call, from its shapes) over the summed
    device time of those calls, all devices together. None where the
    window holds no call."""
    calls = [c for kc in ctx.red["calls"].values() for c in kc
             if c[0] == kernel]
    if ctx.peaks is None or not calls:
        return None
    least = sum(least_seconds(*cost(c[3]), ctx.peaks)[0]
                for c in calls)
    return 100.0 * least / (sum(c[2] for c in calls) * 1e-9)
