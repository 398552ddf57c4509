"""Cohort-engine scaling: rounds/sec for the three execution tiers —

  python_loop : the legacy round-at-a-time host driver (per-round host
                syncs for selection, allocation, accuracy), timed over
                sequential seeds — what an 8-seed sweep of ``run()`` calls
                cost before the device-resident pipeline
  scanned     : the same experiment as ONE lax.scan program
                (``engine.run_rounds``; what ``FLExperiment.run`` now
                dispatches to for traceable strategy bundles)
  cohort      : 8 seeds vmapped over the scanned program (``CohortRunner``;
                shard_map'd across local devices when more than one exists)
                — one dispatch, one transfer for the whole sweep

at N = 50 / 100 devices, on an overhead-sensitive round shape (small local
compute, shared evaluation set) — the regime the device-resident pipeline
targets. Every tier executes identical math; compile/build time excluded
via warmup. ``speedup_cohort8_vs_sequential_runs`` is cohort rounds/sec
over sequential legacy ``run()`` calls (8 sequential runs amortize nothing
beyond the shared XLA cache, so their rounds/sec equals the sequential
measurement).

NOTE the absolute ratio is hardware-bound: on a single compute device the
cohort can only amortize host overhead (its per-seed-round cost stays
within ~1.1x of the single-seed scan), while on an M-core host with real
parallel devices the sharded cohort scales toward min(M, 8)x on top.

Writes ``results/BENCH_cohort.json`` (the perf-trajectory artifact the CI
workflow uploads) plus the usual CSV rows.

``--smoke`` is the per-PR CI gate: the quick workload, a printed summary,
and a NON-ZERO EXIT when the scanned path has regressed below
``SMOKE_MIN_SPEEDUP`` × the python loop — so a pipeline slowdown fails the
tier-1 workflow instead of hiding in an artifact. NOTE the flat parameter
plane (PR 5) roughly doubled the PYTHON loop's rounds/sec (its per-round
tree ops collapsed to fused row ops and its stores donate in place), so
on a single CPU device the two tiers now run neck and neck (~0.85-1.9×
depending on load) — the floor sits below that band to catch only a
genuine scanned-path collapse; absolute scanned rps is tracked in
``BENCH_flat.json``'s gate instead.

    PYTHONPATH=src:. python benchmarks/bench_cohort_scaling.py [--quick|--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax

from benchmarks.common import emit, fl_spec
from repro.api import build_cohort, build_experiment

COHORT = 8
SMOKE_MIN_SPEEDUP = 0.6        # scanned/python rounds-per-sec floor (gate;
                               # see module docstring — the flat plane sped
                               # the python loop up to near-parity on CPU)


def _workload(clients: int, rounds: int):
    return fl_spec(clients=clients, rounds=rounds, samples_per_client=8,
                   train_samples=400, test_samples=100, local_iters=1,
                   batch_size=4, devices_per_round=10, num_clusters=10,
                   test_seed=90_000)


def bench_python_loop(spec, rounds: int, n_seeds: int = 2):
    """Legacy-loop rounds/sec (seeds timed sequentially, compile excluded)."""
    warm = build_experiment(spec.replace(seed=1234))
    warm.traceable = lambda *a, **k: False
    warm.run(rounds=2)                       # compile train/eval/SAO
    exps = [build_experiment(spec.replace(seed=s)) for s in range(n_seeds)]
    for e in exps:
        e.traceable = lambda *a, **k: False
    t0 = time.perf_counter()
    for e in exps:
        e.run(rounds=rounds)
    dt = time.perf_counter() - t0
    return n_seeds * (rounds + 1) / dt


def bench_scanned(spec, rounds: int):
    """Single-seed scanned-program rounds/sec (compile excluded)."""
    build_experiment(spec.replace(seed=1234)).run(rounds=rounds)   # compile
    exp = build_experiment(spec)
    t0 = time.perf_counter()
    exp.run(rounds=rounds)
    dt = time.perf_counter() - t0
    return (rounds + 1) / dt


def bench_cohort(spec, rounds: int):
    """8-seed cohort rounds/sec (compile + build excluded, best of 2)."""
    runner = build_cohort(spec.replace(cohort=COHORT))
    runner.run(rounds=rounds)                # build + compile
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        runner.run(rounds=rounds, reuse_experiments=True)
        best = min(best, time.perf_counter() - t0)
    return COHORT * (rounds + 1) / best


def run(quick: bool = False, out: str | None = None):
    rounds = 8 if quick else 15
    sizes = [50] if quick else [50, 100]
    return _run(rounds, sizes, quick, out)


def _run(rounds, sizes, quick, out):
    configs = []
    for clients in sizes:
        spec = _workload(clients, rounds)
        rps_py = bench_python_loop(spec, rounds)
        rps_scan = bench_scanned(spec, rounds)
        rps_cohort = bench_cohort(spec, rounds)
        cfg = {"clients": clients, "rounds": rounds, "cohort": COHORT,
               "python_loop_rps": round(rps_py, 3),
               "scanned_rps": round(rps_scan, 3),
               "cohort8_rps": round(rps_cohort, 3),
               "speedup_scanned_vs_python": round(rps_scan / rps_py, 2),
               "speedup_cohort8_vs_sequential_runs":
                   round(rps_cohort / rps_py, 2)}
        configs.append(cfg)
        emit(f"cohort/N{clients}_python_loop_rps", 1e6 / rps_py,
             f"{rps_py:.2f}")
        emit(f"cohort/N{clients}_scanned_rps", 1e6 / rps_scan,
             f"{rps_scan:.2f}")
        emit(f"cohort/N{clients}_cohort{COHORT}_rps", 1e6 / rps_cohort,
             f"{rps_cohort:.2f}")
        emit(f"cohort/N{clients}_speedup_vs_sequential", 0.0,
             f"{rps_cohort / rps_py:.2f}")

    payload = {"benchmark": "cohort_scaling", "quick": quick,
               "cohort": COHORT,
               "environment": {"devices": len(jax.devices()),
                               "backend": jax.default_backend(),
                               "cpu_count": os.cpu_count()},
               "note": ("single-device hosts only amortize host overhead; "
                        "multi-device hosts additionally shard the cohort "
                        "axis (see CohortRunner)"),
               "configs": configs}
    out = out or os.path.join(os.path.dirname(__file__), "..", "results",
                              "BENCH_cohort.json")
    with open(out, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    print(f"wrote {os.path.abspath(out)}")
    return payload


def smoke(out: str | None = None) -> bool:
    """The per-PR CI gate: quick workload + regression check. Returns
    True when the scanned pipeline still clears the speedup floor."""
    payload = _run(rounds=8, sizes=[50], quick=True, out=out)
    ok = True
    for cfg in payload["configs"]:
        ratio = cfg["speedup_scanned_vs_python"]
        verdict = "ok" if ratio >= SMOKE_MIN_SPEEDUP else "REGRESSION"
        print(f"smoke N{cfg['clients']}: scanned/python = {ratio:.2f}x "
              f"(floor {SMOKE_MIN_SPEEDUP}x) ... {verdict}")
        ok &= ratio >= SMOKE_MIN_SPEEDUP
    print(json.dumps(payload["configs"], indent=1))
    return ok


if __name__ == "__main__":
    import sys

    from repro.utils.compile_cache import configure_compile_cache

    configure_compile_cache()

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="quick run + scanned-vs-python regression gate "
                         "(non-zero exit on regression; the tier-1 CI step)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    if args.smoke:
        sys.exit(0 if smoke(out=args.out) else 1)
    run(quick=args.quick, out=args.out)
