"""Buffered-async CI gate: the tick loop stays ONE scanned program.

The async engine replaces the round barrier with a virtual-time tick loop
(``repro.core.async_engine``) — the easiest thing for a refactor to
silently break is the "rounds are events, yet still one compiled
``lax.scan``" property (e.g. by reintroducing a host loop over ticks or a
mid-tick device→host sync for the buffer decision). This bench proves it
structurally, not by timing:

  * the whole multi-tick cohort must go through EXACTLY ONE
    compiled-callable dispatch (``engine.run_rounds`` wrapped with a
    counter), and
  * that dispatch runs under ``jax.transfer_guard_device_to_host
    ("disallow")`` (``CohortRunner.run(transfer_guard=True)``) — any
    mid-program sync raises instead of silently serializing;
  * staleness sanity: with the buffer smaller than the padded selection
    (M < K) stragglers must age, so the mean fired-age trace is positive;

plus the usual ticks/sec measurement for the perf trajectory. Writes
``results/BENCH_async.json`` (uploaded as a CI artifact); ``--smoke`` is
the per-PR gate with a NON-ZERO EXIT on a structural failure.

``--n-scaling`` sweeps the fleet size over the PAGED buffered-async
composition (``FLExperiment._run_async_paged``) at N ∈ {1e3, 1e4, 1e5}
and writes ``results/BENCH_async_scale.json``: per-tick wall time with
the O(N) scheduler portion (the ``sched`` + ``plan`` jitted pieces —
churn, selection, completion pricing, the fire plan) timed separately,
so the gate applies to the rest of the tick (O(k_max·P) train +
O(M·P) fire + store staging), which must stay flat in N. With
``--smoke`` it gates rest-of-tick t(1e5)/t(1e4) ≤ ``SCALE_MAX_RATIO``;
``--million`` adds an end-to-end N=1e6 point — the issue's acceptance
run: a million-client fleet ticking in O(k_max·P + M·P) device memory.

    PYTHONPATH=src:. python benchmarks/bench_async.py [--smoke]
    PYTHONPATH=src:. python benchmarks/bench_async.py \
        --n-scaling [--smoke] [--million]
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax

from benchmarks.common import emit, fl_spec
from repro.api import build_cohort, build_experiment


def _workload(rounds: int):
    # buffer M=3 < padded selection 6: every tick leaves stragglers in
    # flight (staleness must grow), with mild churn flipping the fleet
    return fl_spec(clients=10, rounds=rounds, samples_per_client=8,
                   train_samples=400, test_samples=100, local_iters=1,
                   batch_size=4, devices_per_round=6, num_clusters=4,
                   cohort=2, test_seed=91_000,
                   aggregator="fedbuff:3:0.5",
                   churn_leave=0.05, churn_join=0.2)


def run(rounds: int = 6, out: str | None = None):
    spec = _workload(rounds)
    runner = build_cohort(spec)

    # count compiled-callable dispatches: the whole cohort must be ONE
    import repro.core.cohort as cohort_mod
    import repro.core.engine as engine_mod
    calls = {"n": 0}
    real_run_rounds = engine_mod.run_rounds

    def counting_run_rounds(*a, **kw):
        fn = real_run_rounds(*a, **kw)

        def counted(*fa, **fkw):
            calls["n"] += 1
            return fn(*fa, **fkw)

        return counted

    cohort_mod.run_rounds = counting_run_rounds
    try:
        # warmup (build + compile), then the guarded, counted run
        runner.run(transfer_guard=True)
        calls["n"] = 0
        t0 = time.perf_counter()
        ch = runner.run(reuse_experiments=True, transfer_guard=True)
        jax.block_until_ready(ch.accuracy)
        dt = time.perf_counter() - t0
    finally:
        cohort_mod.run_rounds = real_run_rounds

    lanes = len(ch.seeds)
    single_program = calls["n"] == 1
    mean_staleness = float(ch.staleness.mean())
    staleness_positive = bool(ch.staleness.max() > 0)
    buffer_bounded = bool((ch.participation <= 3).all())
    rps = lanes * (rounds + 1) / dt

    payload = {
        "benchmark": "async_engine",
        "environment": {"devices": len(jax.devices()),
                        "backend": jax.default_backend(),
                        "cpu_count": os.cpu_count()},
        "workload": {"cohort": 2, "rounds": rounds, "clients": 10,
                     "aggregator": "fedbuff:3:0.5",
                     "churn": [0.05, 0.2]},
        "single_scanned_program": single_program,
        "dispatches": calls["n"],
        "no_host_round_trips": True,       # transfer guard would have raised
        "staleness_positive": staleness_positive,
        "buffer_bounded": buffer_bounded,
        "mean_staleness": round(mean_staleness, 4),
        "mean_participation": round(float(ch.participation.mean()), 4),
        "mean_active": round(float(ch.active.mean()), 4),
        "cohort_ticks_per_sec": round(rps, 3),
    }
    emit("async/fedbuff_tps", 1e6 / rps, f"{rps:.2f}")
    emit("async/dispatches", 0.0, str(calls["n"]))
    out = out or os.path.join(os.path.dirname(__file__), "..", "results",
                              "BENCH_async.json")
    with open(out, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    print(f"wrote {os.path.abspath(out)}")
    return payload


def smoke(out: str | None = None) -> bool:
    """Per-PR CI gate: structural properties of the buffered-async path."""
    payload = run(rounds=4, out=out)
    ok = True
    for key in ("single_scanned_program", "staleness_positive",
                "buffer_bounded"):
        verdict = "ok" if payload[key] else "FAIL"
        print(f"smoke {key}: {payload[key]} ... {verdict}")
        ok &= bool(payload[key])
    print(json.dumps(payload, indent=1))
    return ok


# ---------------------------------------------------------------------------
# --n-scaling: the paged buffered-async composition across fleet sizes
# ---------------------------------------------------------------------------

SCALE_NS = (1_000, 10_000, 100_000)
SCALE_TICKS = 4                        # timed ticks per N (min taken)
SCALE_MAX_RATIO = 1.5                  # rest-of-tick t(1e5)/t(1e4) ceiling


def _scale_spec(n: int):
    """bench_round_breakdown's N-scaling workload (micro CNN, cluster-free
    random selection, tiny local work) routed onto the paged async engine:
    fedbuff:4 with the pad-16 selection keeps stragglers in flight every
    tick, so the fire path (staging gather + O(M·P) fold) is exercised."""
    return fl_spec(dataset="micro", clients=n, samples_per_client=8,
                   train_samples=512, test_samples=128, local_iters=1,
                   batch_size=4, devices_per_round=16, num_clusters=10,
                   selection="random", store="paged",
                   aggregator="fedbuff:4", test_seed=91_000)


def _best_ms(fn, repeats: int):
    fn()                                     # compile / warm
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def _async_point(n: int) -> dict:
    """One sweep point: per-tick wall time of the full host composition,
    with the O(N) scheduler portion (sched + plan) probed standalone on
    the SAME cached jitted pieces the driver dispatches."""
    from repro.core.async_engine import _paged_async_step_program
    from repro.core.wireless import fleet_arrays

    exp = build_experiment(_scale_spec(n))
    exp.run(rounds=1, include_initial_round=False)    # compile + warm

    # several ticks per timed run: the per-RUN O(N) carry snapshot and
    # fold-back amortize away, so the number is the steady-state tick
    ticks_per_run = 4

    def ticks_once():
        exp.run(rounds=ticks_per_run, include_initial_round=False)

    tick_ms = _best_ms(ticks_once, repeats=SCALE_TICKS) / ticks_per_run

    prog = _paged_async_step_program(
        exp.engine.cfg, exp.selector, exp.allocator,
        exp.aggregator.registry_name,
        tuple(sorted(exp.aggregator.params().items())),
        exp.compressor, exp.traced_context(), exp.fl.feature_layer,
        exp.channel, exp.churn)
    arr = dict(fleet_arrays(exp.fleet))
    arr.pop("xgain", None)
    state = prog.init_channel(exp.traced_state(), arr)
    sizes = exp._sizes

    def sched_plan_once():
        s, arr_f, idx, mask = prog.sched(state, arr)
        _, _, _, cand, *_ = prog.plan(s, arr_f, idx, mask, sizes)
        jax.block_until_ready(cand)

    sched_ms = _best_ms(sched_plan_once, repeats=3)
    return {"clients": n, "tick_ms": round(tick_ms, 3),
            "sched_ms": round(sched_ms, 3),
            "rest_ms": round(max(tick_ms - sched_ms, 0.0), 3),
            "k_max": exp.k_max, "buffer": prog.M,
            "store_mb": round(exp.store.nbytes / 2**20, 2),
            "lazy_data": bool(getattr(exp.fed, "lazy", False))}


def run_n_scaling(out: str | None = None, million: bool = False) -> dict:
    points = []
    for n in SCALE_NS + ((1_000_000,) if million else ()):
        p = _async_point(n)
        points.append(p)
        emit(f"async/paged_N{n}_tick", p["tick_ms"] * 1e3,
             f"{p['tick_ms']:.1f}ms (sched {p['sched_ms']:.1f}ms)")
    by_n = {p["clients"]: p for p in points}
    ratio = by_n[100_000]["rest_ms"] / max(by_n[10_000]["rest_ms"], 1e-9)
    payload = {
        "benchmark": "async_n_scaling",
        "environment": {"devices": len(jax.devices()),
                        "backend": jax.default_backend(),
                        "cpu_count": os.cpu_count()},
        "paged_async": points,
        "rest_ratio_1e5_over_1e4": round(ratio, 2),
        "note": ("rest_ms = tick_ms - sched_ms: per-tick cost excluding "
                 "the O(N) scheduler (churn/select/completion-pricing/"
                 "fire-plan jitted pieces), flat in N by design — the "
                 "tick's device state is the [k_max, P] staging plane + "
                 "[M, P] fire candidates + O(N) stats columns, never an "
                 "[N, P] plane"),
    }
    out = out or os.path.join(os.path.dirname(__file__), "..", "results",
                              "BENCH_async_scale.json")
    with open(out, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    print(f"wrote {os.path.abspath(out)}")
    return payload


def smoke_n_scaling(out: str | None = None, million: bool = False) -> bool:
    payload = run_n_scaling(out=out, million=million)
    ratio = payload["rest_ratio_1e5_over_1e4"]
    if ratio > SCALE_MAX_RATIO:
        # host-loop timings on shared runners are load-sensitive —
        # re-measure the two gated points once before failing
        print(f"async scale smoke: rest ratio {ratio:.2f} above ceiling, "
              "re-measuring...")
        pts = {n: _async_point(n) for n in (10_000, 100_000)}
        ratio = min(ratio, pts[100_000]["rest_ms"]
                    / max(pts[10_000]["rest_ms"], 1e-9))
        payload["rest_ratio_1e5_over_1e4"] = round(ratio, 2)
        path = out or os.path.join(os.path.dirname(__file__), "..",
                                   "results", "BENCH_async_scale.json")
        with open(path, "w") as f:
            json.dump(payload, f, indent=1)
            f.write("\n")
    ok = ratio <= SCALE_MAX_RATIO
    print(f"async scale smoke: paged rest-of-tick 1e5/1e4 = {ratio:.2f}x "
          f"(ceiling {SCALE_MAX_RATIO}x) ... "
          f"{'ok' if ok else 'REGRESSION'}")
    return ok


if __name__ == "__main__":
    import sys

    from repro.utils.compile_cache import configure_compile_cache

    configure_compile_cache()

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="structural gate: one scanned program, no host "
                         "round-trips, positive staleness under M < K "
                         "(non-zero exit on failure; the tier-1 CI step)")
    ap.add_argument("--n-scaling", action="store_true",
                    help="sweep fleet size over the paged buffered-async "
                         "composition; writes results/BENCH_async_scale"
                         ".json (with --smoke: gate rest-of-tick flat "
                         "in N)")
    ap.add_argument("--million", action="store_true",
                    help="with --n-scaling: add an end-to-end N=1e6 point")
    ap.add_argument("--rounds", type=int, default=6)
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
    run(rounds=args.rounds, out=args.out)
