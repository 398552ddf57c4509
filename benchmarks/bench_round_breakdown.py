"""End-to-end rounds/sec of the scanned FL round on the flat parameter
plane vs the recorded scanned baseline. (Where the round's time goes
is read from a profiler trace of the program itself: the phases are named
scopes, README "Profiling".)

End-to-end rounds/sec runs the full scanned program (``FLExperiment.run``
on the traceable bundle) on the clients=100 workload of
``bench_cohort_scaling`` and compares against that benchmark's RECORDED
``results/BENCH_cohort.json`` scanned_rps — the PR-4 perf artifact. Writes
``results/BENCH_flat.json``.

``--smoke`` is the per-PR CI gate: a NON-ZERO EXIT when the flat-plane
pipeline drops below ``SMOKE_MIN_RATIO`` × the recorded baseline — so a
hot-path regression fails the tier-1 workflow instead of hiding in an
artifact. (The floor is deliberately below 1.0: the recorded baseline and
the CI runner differ in load; the tracked headline is ``speedup_vs_
recorded_baseline`` in the artifact.)

``--n-scaling`` sweeps the fleet size on the micro CNN workload and
writes ``results/BENCH_scale.json``: per-round wall time of the PAGED
active/cold store at N ∈ {1e3, 1e4, 1e5} (selection sweep timed
separately — it is the one O(N) step the design keeps), against the dense
plane's O(N·P) divergence sweep at N ∈ {1e3, 1e4}. With ``--smoke`` it
gates: paged rest-of-round at N=1e5 within ``SCALE_MAX_RATIO``× of
N=1e4 (flat in N), dense divergence growing ≥ ``DENSE_MIN_RATIO``× per
10× N (~linear — the cost the paged store removes). ``--million`` adds a
N=1e6 end-to-end paged run to the sweep.

    PYTHONPATH=src:. python benchmarks/bench_round_breakdown.py [--smoke]
    PYTHONPATH=src:. python benchmarks/bench_round_breakdown.py \
        --n-scaling [--smoke] [--million]
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, fl_spec
from repro.api import build_experiment
from repro.kernels import ops

CLIENTS = 100
ROUNDS = 15
SMOKE_MIN_RATIO = 0.9          # new rps / recorded PR-4 scanned rps (gate)
# PR-4's recorded scanned_rps for this exact workload (BENCH_cohort.json at
# the PR-4 commit) — the fallback when the artifact is missing or was
# overwritten by a --quick cohort run that dropped the clients=100 entry.
PR4_SCANNED_RPS_FALLBACK = 11.491


def _workload():
    """bench_cohort_scaling's clients=100 workload, verbatim."""
    return fl_spec(clients=CLIENTS, rounds=ROUNDS, samples_per_client=8,
                   train_samples=400, test_samples=100, local_iters=1,
                   batch_size=4, devices_per_round=10, num_clusters=10,
                   test_seed=90_000)


def _best_ms(fn, repeats: int = 10):
    fn()                                     # compile / warm
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def scanned_rps(spec, repeats: int = 3) -> float:
    """End-to-end scanned-program rounds/sec (compile excluded, best-of-N)."""
    build_experiment(spec.replace(seed=1234)).run(rounds=ROUNDS)  # compile
    exp = build_experiment(spec)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        exp.run(rounds=ROUNDS)
        best = min(best, time.perf_counter() - t0)
    return (ROUNDS + 1) / best


def recorded_baseline() -> tuple[float, str]:
    """PR-4's scanned_rps for the clients=100 workload, from the recorded
    BENCH_cohort.json artifact (fallback: the pinned PR-4 number)."""
    path = os.path.join(os.path.dirname(__file__), "..", "results",
                        "BENCH_cohort.json")
    try:
        with open(path) as f:
            payload = json.load(f)
        # only trust a FULL-run artifact for this exact workload — a
        # --quick/--smoke cohort run overwrites the file with clients=50
        # rounds=8 numbers, and must not silently become the baseline
        # (makes the gate independent of CI step ordering)
        if payload.get("quick") is False:
            for cfg in payload.get("configs", []):
                if (cfg.get("clients") == CLIENTS
                        and cfg.get("rounds") == ROUNDS
                        and "scanned_rps" in cfg):
                    return (float(cfg["scanned_rps"]),
                            "results/BENCH_cohort.json")
    except (OSError, ValueError):
        pass
    return PR4_SCANNED_RPS_FALLBACK, "pinned PR-4 fallback"


def run(out: str | None = None):
    spec = _workload()
    rps = scanned_rps(spec)
    baseline, source = recorded_baseline()
    speedup = rps / baseline

    emit(f"flat/N{CLIENTS}_scanned_rps", 1e6 / rps, f"{rps:.2f}")
    emit(f"flat/N{CLIENTS}_speedup_vs_pr4_scanned", 0.0, f"{speedup:.2f}")

    payload = {
        "benchmark": "round_breakdown", "clients": CLIENTS, "rounds": ROUNDS,
        "environment": {"devices": len(jax.devices()),
                        "backend": jax.default_backend(),
                        "cpu_count": os.cpu_count()},
        "rounds_per_sec": round(rps, 3),
        "baseline_scanned_rps": baseline,
        "baseline_source": source,
        "speedup_vs_recorded_baseline": round(speedup, 2),
        "note": ("aggregation and divergence are each ONE fused op over "
                 "the [N, P] flat plane (ops.flat_aggregate / "
                 "ops.client_divergence) — no per-leaf tree_map remains "
                 "in the traced round body"),
    }
    out = out or os.path.join(os.path.dirname(__file__), "..", "results",
                              "BENCH_flat.json")
    with open(out, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    print(f"wrote {os.path.abspath(out)}")
    return payload


# ---------------------------------------------------------------------------
# --n-scaling: paged active/cold store vs the dense plane across fleet sizes
# ---------------------------------------------------------------------------

SCALE_PAGED_NS = (1_000, 10_000, 100_000)
SCALE_DENSE_NS = (1_000, 10_000)       # 1e5 dense = a 2.4 GB plane; skipped
SCALE_ROUNDS = 4                       # timed rounds per N (min taken)
SCALE_MAX_RATIO = 1.5                  # paged rest-of-round t(1e5)/t(1e4)
DENSE_MIN_RATIO = 3.0                  # dense divergence t(1e4)/t(1e3) floor


def _scale_spec(n: int, store: str):
    """The N-scaling workload: micro CNN (P ≈ 6k), cluster-free random
    selection (no all-device Alg.-2 round), tiny local work — so per-round
    time is dominated by the store machinery being measured."""
    return fl_spec(dataset="micro", clients=n, samples_per_client=8,
                   train_samples=512, test_samples=128, local_iters=1,
                   batch_size=4, devices_per_round=16, num_clusters=10,
                   selection="random", store=store, test_seed=91_000)


def _paged_point(n: int) -> dict:
    """One paged sweep point: per-round wall time with the O(N) selection
    sweep measured separately (it is the one deliberate O(N) step; the
    gate applies to the rest of the round)."""
    exp = build_experiment(_scale_spec(n, "paged"))
    exp.round("random")                          # compile + warm the store
    sel_ms = _best_ms(lambda: exp.select("random"), repeats=3)
    best = float("inf")
    for _ in range(SCALE_ROUNDS):
        t0 = time.perf_counter()
        exp.round("random")
        best = min(best, time.perf_counter() - t0)
    round_ms = best * 1e3
    return {"clients": n, "round_ms": round(round_ms, 3),
            "select_ms": round(sel_ms, 3),
            "rest_ms": round(max(round_ms - sel_ms, 0.0), 3),
            "store_mb": round(exp.store.nbytes / 2**20, 2),
            "lazy_data": bool(getattr(exp.fed, "lazy", False))}


def _dense_point(n: int) -> dict:
    """One dense probe point: the O(N·P) divergence sweep over the full
    plane — the per-round cost the paged store replaces with the O(N)
    stats table."""
    exp = build_experiment(_scale_spec(n, "dense"))
    gvec = jnp.asarray(np.asarray(exp.client_params[0]))
    div = jax.jit(lambda f, g: ops.client_divergence(f, g))
    div_ms = _best_ms(lambda: div(exp.client_params, gvec)
                      .block_until_ready(), repeats=5)
    return {"clients": n, "divergence_ms": round(div_ms, 3),
            "plane_mb": round(exp.store.nbytes / 2**20, 2)}


def run_n_scaling(out: str | None = None, million: bool = False) -> dict:
    paged_ns = SCALE_PAGED_NS + ((1_000_000,) if million else ())
    paged = []
    for n in paged_ns:
        p = _paged_point(n)
        paged.append(p)
        emit(f"scale/paged_N{n}_round", p["round_ms"] * 1e3,
             f"{p['round_ms']:.1f}ms (select {p['select_ms']:.1f}ms)")
    dense = []
    for n in SCALE_DENSE_NS:
        d = _dense_point(n)
        dense.append(d)
        emit(f"scale/dense_N{n}_divergence", d["divergence_ms"] * 1e3,
             f"{d['divergence_ms']:.2f}ms")

    by_n = {p["clients"]: p for p in paged}
    paged_ratio = (by_n[100_000]["rest_ms"]
                   / max(by_n[10_000]["rest_ms"], 1e-9))
    dense_ratio = (dense[-1]["divergence_ms"]
                   / max(dense[0]["divergence_ms"], 1e-9))
    payload = {
        "benchmark": "n_scaling",
        "environment": {"devices": len(jax.devices()),
                        "backend": jax.default_backend(),
                        "cpu_count": os.cpu_count()},
        "paged": paged,
        "dense": dense,
        "paged_rest_ratio_1e5_over_1e4": round(paged_ratio, 2),
        "dense_divergence_ratio_1e4_over_1e3": round(dense_ratio, 2),
        "note": ("paged rest_ms = round_ms - select_ms: per-round cost "
                 "excluding the O(N) selection sweep, flat in N by "
                 "design (active [K, P] plane + O(N) stats table); dense "
                 "divergence_ms is the O(N*P) full-plane reduction the "
                 "paged store replaces"),
    }
    out = out or os.path.join(os.path.dirname(__file__), "..", "results",
                              "BENCH_scale.json")
    with open(out, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    print(f"wrote {os.path.abspath(out)}")
    return payload


def smoke_n_scaling(out: str | None = None, million: bool = False) -> bool:
    payload = run_n_scaling(out=out, million=million)
    paged_ratio = payload["paged_rest_ratio_1e5_over_1e4"]
    if paged_ratio > SCALE_MAX_RATIO:
        # host-loop timings on shared runners are load-sensitive —
        # re-measure the two gated points once before failing
        print(f"scale smoke: paged ratio {paged_ratio:.2f} above ceiling, "
              "re-measuring...")
        pts = {n: _paged_point(n) for n in (10_000, 100_000)}
        paged_ratio = min(paged_ratio,
                          pts[100_000]["rest_ms"]
                          / max(pts[10_000]["rest_ms"], 1e-9))
        payload["paged_rest_ratio_1e5_over_1e4"] = round(paged_ratio, 2)
        path = out or os.path.join(os.path.dirname(__file__), "..",
                                   "results", "BENCH_scale.json")
        with open(path, "w") as f:
            json.dump(payload, f, indent=1)
            f.write("\n")
    dense_ratio = payload["dense_divergence_ratio_1e4_over_1e3"]
    ok_paged = paged_ratio <= SCALE_MAX_RATIO
    ok_dense = dense_ratio >= DENSE_MIN_RATIO
    print(f"scale smoke: paged rest-of-round 1e5/1e4 = {paged_ratio:.2f}x "
          f"(ceiling {SCALE_MAX_RATIO}x) ... "
          f"{'ok' if ok_paged else 'REGRESSION'}")
    print(f"scale smoke: dense divergence 1e4/1e3 = {dense_ratio:.2f}x "
          f"(floor {DENSE_MIN_RATIO}x, ~linear) ... "
          f"{'ok' if ok_dense else 'NOT LINEAR?'}")
    return ok_paged and ok_dense


def smoke(out: str | None = None) -> bool:
    payload = run(out=out)
    ratio = payload["rounds_per_sec"] / payload["baseline_scanned_rps"]
    if ratio < SMOKE_MIN_RATIO:
        # absolute rps vs a recorded number is load-sensitive on shared
        # runners (±40% observed between minutes) — re-measure once with
        # more repeats before declaring a regression
        print(f"smoke N{CLIENTS}: {ratio:.2f}x below floor, re-measuring...")
        rps = scanned_rps(_workload(), repeats=6)
        payload["rounds_per_sec"] = round(max(rps, payload["rounds_per_sec"]),
                                          3)
        payload["speedup_vs_recorded_baseline"] = round(
            payload["rounds_per_sec"] / payload["baseline_scanned_rps"], 2)
        path = out or os.path.join(os.path.dirname(__file__), "..",
                                   "results", "BENCH_flat.json")
        with open(path, "w") as f:
            json.dump(payload, f, indent=1)
            f.write("\n")
        ratio = payload["speedup_vs_recorded_baseline"]
    verdict = "ok" if ratio >= SMOKE_MIN_RATIO else "REGRESSION"
    print(f"smoke N{CLIENTS}: flat/scanned vs recorded PR-4 baseline = "
          f"{ratio:.2f}x (floor {SMOKE_MIN_RATIO}x) ... {verdict}")
    return ratio >= SMOKE_MIN_RATIO


if __name__ == "__main__":
    import sys

    from repro.utils.compile_cache import configure_compile_cache

    configure_compile_cache()

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="regression gate vs the recorded PR-4 scanned "
                         "baseline (non-zero exit; the tier-1 CI step)")
    ap.add_argument("--n-scaling", action="store_true",
                    help="sweep fleet size: paged per-round time vs the "
                         "dense plane's O(N*P) sweep; writes "
                         "results/BENCH_scale.json")
    ap.add_argument("--million", action="store_true",
                    help="with --n-scaling: add a N=1e6 paged point")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    if args.n_scaling:
        if args.smoke:
            sys.exit(0 if smoke_n_scaling(out=args.out,
                                          million=args.million) else 1)
        run_n_scaling(out=args.out, million=args.million)
        sys.exit(0)
    if args.smoke:
        sys.exit(0 if smoke(out=args.out) else 1)
    run(out=args.out)
