"""Framework-level kernel microbenchmarks (interpret-mode wall times are NOT
TPU perf — the derived column is the correctness gap vs the jnp oracle; the
TPU roofline lives in EXPERIMENTS.md §Roofline).

``--smoke`` is the per-PR CI gate: the quick workload, a printed summary,
``results/BENCH_kernels.json``, and a NON-ZERO EXIT when any kernel's
interpret-mode output drifts past its oracle tolerance — so a kernel
regression fails the tier-1 workflow instead of hiding in an artifact.

    PYTHONPATH=src:. python benchmarks/bench_kernels.py [--quick|--smoke]
"""
from __future__ import annotations

import argparse
import json
import warnings

# every kernel here runs with an explicit use_pallas=True as a deliberate
# interpret-mode validation — silence the dispatch guard's off-TPU warning
warnings.filterwarnings("ignore", message=".*interpret mode.*",
                        category=RuntimeWarning)
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_fn
from repro.kernels import ops, ref
from repro.kernels.flat_aggregate import flat_aggregate
from repro.kernels.pairwise_l2 import pairwise_l2
from repro.kernels.flash_attention import flash_attention

# interpret-mode-vs-oracle drift ceilings (fp32 shapes; the smoke gate).
# pairwise_l2's ceiling leaves real headroom: fp32 cancellation in the
# ‖x‖²+‖c‖²−2x·c expansion vs the naive oracle measures ~1e-3 at F=2240
# and shifts with XLA's matmul reduction order across versions/CPUs.
TOLERANCES = {
    "pairwise_l2_100x10x2240": 5e-3,
    "flat_aggregate_100x113744": 1e-4,
    "flash_attn": 1e-4,
    "ssd_scan": 1e-4,
}


def run(quick: bool = False):
    entries = []
    k = jax.random.PRNGKey(0)

    # pairwise_l2 at the paper's real scale: 100 clients × w_fc2 (2240)
    x = jax.random.normal(k, (100, 2240))
    c = jax.random.normal(jax.random.PRNGKey(1), (10, 2240))
    out, us = time_fn(lambda: pairwise_l2(x, c).block_until_ready(),
                      repeats=3)
    err = float(jnp.max(jnp.abs(out - ref.pairwise_l2_ref(x, c))))
    emit("kernels/pairwise_l2_100x10x2240", us, f"maxerr={err:.2e}")
    entries.append({"name": "pairwise_l2_100x10x2240", "us": us,
                    "maxerr": err})

    # flat_aggregate at the FL round's real scale: the [N, P] client plane
    # of the paper CNN (P = 113744), 100-client eq.-(4) reduction
    flat = jax.random.normal(k, (100, 113744))
    w = jax.random.uniform(jax.random.PRNGKey(2), (100,))
    out, us = time_fn(lambda: flat_aggregate(flat, w).block_until_ready(),
                      repeats=3)
    err = float(jnp.max(jnp.abs(out - ref.flat_aggregate_ref(flat, w))))
    emit("kernels/flat_aggregate_100x113744", us, f"maxerr={err:.2e}")
    entries.append({"name": "flat_aggregate_100x113744", "us": us,
                    "maxerr": err})

    s = 128 if quick else 256
    q = jax.random.normal(k, (1, 4, s, 64))
    kk = jax.random.normal(jax.random.PRNGKey(2), (1, 4, s, 64))
    v = jax.random.normal(jax.random.PRNGKey(3), (1, 4, s, 64))
    out, us = time_fn(lambda: flash_attention(q, kk, v, bq=128, bk=128)
                      .block_until_ready(), repeats=2)
    err = float(jnp.max(jnp.abs(out - ref.flash_attention_ref(q, kk, v))))
    emit(f"kernels/flash_attn_s{s}", us, f"maxerr={err:.2e}")
    entries.append({"name": "flash_attn", "us": us, "maxerr": err})

    B, S, H, P, N = 1, 256, 4, 32, 16
    xs = jax.random.normal(k, (B, S, H, P)) * 0.5
    a = -jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(4), (B, S, H)))
    bm = jax.random.normal(jax.random.PRNGKey(5), (B, S, 1, N)) * 0.3
    cm = jax.random.normal(jax.random.PRNGKey(6), (B, S, 1, N)) * 0.3
    (y, h), us = time_fn(lambda: jax.block_until_ready(
        ops.ssd(xs, a, bm, cm, chunk=64, use_pallas=True)), repeats=2)
    y_r, _ = ops.ssd(xs, a, bm, cm, use_pallas=False)
    err = float(jnp.max(jnp.abs(y - y_r)))
    emit(f"kernels/ssd_scan_s{S}", us, f"maxerr={err:.2e}")
    entries.append({"name": "ssd_scan", "us": us, "maxerr": err})
    return entries


def smoke(out: str | None = None) -> bool:
    """Quick run + kernel-vs-oracle drift gate; writes BENCH_kernels.json."""
    entries = run(quick=True)
    ok = True
    for e in entries:
        tol = TOLERANCES[e["name"]]
        verdict = "ok" if e["maxerr"] <= tol else "KERNEL DRIFT"
        print(f"smoke {e['name']}: maxerr={e['maxerr']:.2e} "
              f"(tol {tol:.0e}) ... {verdict}")
        ok &= e["maxerr"] <= tol
    payload = {"benchmark": "kernels", "mode": "interpret",
               "backend": jax.default_backend(),
               "note": ("interpret-mode wall times validate correctness, "
                        "not TPU perf; maxerr is vs the naive jnp oracle"),
               "kernels": entries}
    out = out or os.path.join(os.path.dirname(__file__), "..", "results",
                              "BENCH_kernels.json")
    with open(out, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    print(f"wrote {os.path.abspath(out)}")
    return ok


if __name__ == "__main__":
    import sys

    from repro.utils.compile_cache import configure_compile_cache

    configure_compile_cache()

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="quick run + kernel-drift gate (non-zero exit on "
                         "oracle mismatch; the tier-1 CI step)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    if args.smoke:
        sys.exit(0 if smoke(out=args.out) else 1)
    run(quick=args.quick)
