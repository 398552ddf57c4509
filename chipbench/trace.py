"""Reduction of a profiler trace to the benchmark's per-layer numbers.

On a TPU the profiler names each event of a core's ``XLA Ops`` line by
its HLO instruction text, e.g. ``%flat_aggregate.8 = f32[1,114176]{...}
custom-call(f32[1,40]{...} %w, f32[40,114176]{...} %x),
custom_call_target="tpu_custom_call", ...``. A Pallas kernel is a
``custom-call`` whose instruction is named after its ``pallas_call``
(``flat_aggregate``, ``pairwise_l2``); its operand and result shapes are
in the text. A ``while`` (a scan, a ``fori_loop``) or ``conditional`` is
an event that spans the ops of its body, which are events of their own.

``load`` reads the ``.xplane.pb`` the JAX profiler writes into a small,
JSON-serialisable form: per TPU core (``/device:TPU:<i>`` planes), each op
as ``[instruction, start_ns, dur_ns, opcode]`` and each kernel call as
``[kernel, start_ns, dur_ns, [result and operand shapes]]``; and the
benchmark's host spans (``TraceAnnotation`` events named
``chipbench.<span>``, on the host planes). Device and host events share
the trace's clock.

``reduce`` takes that form and gives, within the benchmark's ``window``
span: per device the union of busy intervals (containers included: the
core is executing their body), the idle gaps (each labelled by the
innermost benchmark span over most of it), the kernel calls, and the ops
that took most time (containers left out, so that time is not counted
twice).
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

SPAN_PREFIX = "chipbench."
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
CONTAINERS = ("while", "conditional", "call")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_SHAPE = re.compile(r"\b(f32|bf16|f16|s32|u32|s8|u8|pred)\[([0-9,]*)\]")
_ITEMSIZE = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2, "s8": 1,
             "u8": 1, "pred": 1}


def parse_op(text: str):
    """``(instruction, opcode)`` of an HLO instruction's text."""
    head, _, rest = text.partition(" = ")
    m = _OPCODE.search(rest)
    return head.lstrip("%"), (m.group(1) if m else "")


def shapes(text: str):
    """``[[dtype, [dims]], ...]``: the result, then the operands, of an
    instruction's text (up to its attributes)."""
    body = text.split(", custom_call_target", 1)[0]
    return [[d, [int(x) for x in dims.split(",") if x]]
            for d, dims in _SHAPE.findall(body)]


def nbytes(shape) -> int:
    dtype, dims = shape
    n = _ITEMSIZE[dtype]
    for d in dims:
        n *= d
    return n


def load(logdir: str, kernels=()) -> dict:
    """The newest trace under ``logdir`` in the benchmark's form; calls of
    the named ``kernels`` are kept with their shapes."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    pd = ProfileData.from_file(paths[-1])
    devices, calls, host = {}, {}, []
    for plane in pd.planes:
        name = plane.name
        dev = name[len(DEVICE_PREFIX):]
        if name.startswith(DEVICE_PREFIX) and dev.isdigit():
            ops, kc = [], []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    inst, opcode = parse_op(ev.name)
                    ops.append([inst, ev.start_ns, ev.duration_ns, opcode])
                    if opcode == "custom-call":
                        base = inst.rsplit(".", 1)[0]
                        if base in kernels:
                            kc.append([base, ev.start_ns, ev.duration_ns,
                                       shapes(ev.name)])
            devices[dev], calls[dev] = ops, kc
        elif name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        host.append([ev.name[len(SPAN_PREFIX):],
                                     ev.start_ns, ev.duration_ns])
    return {"devices": devices, "calls": calls, "host": host}


def union(intervals):
    """Merged, sorted ``[start, end]`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def window_of(tr: dict, span: str = "window"):
    """``(start_ns, end_ns)`` of the benchmark's window span."""
    for name, s, d in tr["host"]:
        if name == span:
            return s, s + d
    raise KeyError(f"no {SPAN_PREFIX}{span} span in the trace")


def _label(spans, s, e):
    """The innermost (shortest) benchmark span covering most of [s, e]."""
    best, best_len = "none", None
    for name, hs, hd in spans:
        ov = min(e, hs + hd) - max(s, hs)
        if ov * 2 > (e - s) and (best_len is None or hd < best_len):
            best, best_len = name, hd
    return best


def reduce(tr: dict, top: int = 10) -> dict:
    """Busy and idle time per device within the window, the idle gaps by
    host span, the kernel calls within the window (per device), and the
    ops that took most time, summed over devices."""
    w0, w1 = window_of(tr)
    spans = [h for h in tr["host"] if h[0] != "window"]
    busy, gaps = {}, []
    optime = defaultdict(float)
    for dev, ops in tr["devices"].items():
        ivs = []
        for name, s, d, opcode in ops:
            s0, e0 = max(s, w0), min(s + d, w1)
            if e0 <= s0:
                continue
            ivs.append([s0, e0])
            if opcode not in CONTAINERS:
                optime[name] += (e0 - s0) * 1e-9
        merged = union(ivs)
        busy[dev] = sum(e - s for s, e in merged) * 1e-9
        prev = w0
        for s, e in merged + [[w1, w1]]:
            if s > prev:
                gaps.append((s - prev, prev, s, dev))
            prev = max(prev, e)
    gaps.sort(reverse=True)
    calls = {dev: [c for c in kc if c[1] >= w0 and c[1] + c[2] <= w1]
             for dev, kc in tr.get("calls", {}).items()}
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy,
        "idle_gaps": [[_label(spans, s, e), g * 1e-9]
                      for g, s, e, _ in gaps[:top]],
        "calls": calls,
        "device_ops": sorted(([n, t] for n, t in optime.items()),
                             key=lambda x: -x[1])[:top],
        "spans": spans,
    }
