"""Operations and bytes the benchmark's work needs, from shapes alone.

Model FLOPs per training and per test sample come from the configuration's
reference module (``train_flops``, ``eval_flops``; each states its rule:
for the paper's CNN the multiply-adds of the convolutions and dense
layers, 2 FLOPs each, and 3x the forward for a training sample).
Recomputation and padding are never counted. Kernel bytes and FLOPs are
those of one call at its logical (unpadded) operand shapes, in float32.
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


def experiment_flops(cfg: dict, spec: dict, updates: int) -> int:
    """Model FLOPs of one experiment with ``updates`` client local updates
    (the initial round's N and each round's selected clients): L SGD steps
    of ``batch_size`` training samples each, plus the test set after the
    initial round and after each round."""
    from chipbench.reference import fl

    m = fl.model_module(cfg["reference"])
    train = (updates * spec["local_iters"] * spec["batch_size"]
             * m.train_flops(cfg))
    evals = (spec["rounds"] + 1) * spec["test_samples"] * m.eval_flops(cfg)
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
