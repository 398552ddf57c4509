"""Channel-dynamics CI gate: the coupled multi-cell program stays ONE scan.

The dynamic-interference path (`multicell-dynamic` + `gauss-markov`) is the
first place the cells of a seed interact INSIDE the traced program — the
easiest thing for a refactor to silently break is the "one scanned program,
no per-round host round-trips" property (e.g. by reintroducing a host loop
over rounds or cells). This bench proves it structurally, not by timing:

  * the whole multi-round (seeds × cells) cohort must go through EXACTLY
    ONE compiled-callable dispatch (``engine.run_rounds`` is wrapped with a
    counter), and
  * that dispatch runs under ``jax.transfer_guard_device_to_host
    ("disallow")`` (``CohortRunner.run(transfer_guard=True)``) — any
    mid-program device→host sync raises instead of silently serializing;

plus the usual rounds/sec measurement for the perf trajectory. Writes
``results/BENCH_channel.json`` (uploaded as a CI artifact); ``--smoke`` is
the per-PR gate with a NON-ZERO EXIT on a structural failure.

    PYTHONPATH=src:. python benchmarks/bench_channel_dynamics.py [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax

from benchmarks.common import emit, fl_spec
from repro.api import build_cohort, multicell_fleet_spec


def _workload(rounds: int):
    # 2 coupled cells × 2 seeds, correlated fading + dynamic interference:
    # the full new scenario family in one program
    return fl_spec(clients=10, rounds=rounds, samples_per_client=8,
                   train_samples=400, test_samples=100, local_iters=1,
                   batch_size=4, devices_per_round=4, num_clusters=4,
                   cohort=2, test_seed=90_000,
                   fleet=multicell_fleet_spec(2, channel="multicell-dynamic"))


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
    inr_dynamic = (ch.inr is not None
                   and bool((ch.inr.std(axis=1) > 0).any()))
    rps = lanes * (rounds + 1) / dt

    payload = {
        "benchmark": "channel_dynamics",
        "environment": {"devices": len(jax.devices()),
                        "backend": jax.default_backend(),
                        "cpu_count": os.cpu_count()},
        "workload": {"cells": 2, "cohort": 2, "rounds": rounds,
                     "clients_per_cell": 10,
                     "channel": "multicell-dynamic"},
        "single_scanned_program": single_program,
        "dispatches": calls["n"],
        "no_host_round_trips": True,       # transfer guard would have raised
        "inr_selection_driven": inr_dynamic,
        "cohort_rounds_per_sec": round(rps, 3),
    }
    emit("channel/dynamic2cell_rps", 1e6 / rps, f"{rps:.2f}")
    emit("channel/dispatches", 0.0, str(calls["n"]))
    out = out or os.path.join(os.path.dirname(__file__), "..", "results",
                              "BENCH_channel.json")
    with open(out, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    print(f"wrote {os.path.abspath(out)}")
    return payload


def smoke(out: str | None = None) -> bool:
    """Per-PR CI gate: structural properties of the dynamic path."""
    payload = run(rounds=4, out=out)
    ok = True
    for key in ("single_scanned_program", "inr_selection_driven"):
        verdict = "ok" if payload[key] else "FAIL"
        print(f"smoke {key}: {payload[key]} ... {verdict}")
        ok &= bool(payload[key])
    print(json.dumps(payload, indent=1))
    return ok


if __name__ == "__main__":
    import sys

    from repro.utils.compile_cache import configure_compile_cache

    configure_compile_cache()

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="structural gate: one scanned program, no host "
                         "round-trips, selection-driven inr (non-zero exit "
                         "on failure; the tier-1 CI step)")
    ap.add_argument("--rounds", type=int, default=6)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    if args.smoke:
        sys.exit(0 if smoke(out=args.out) else 1)
    run(rounds=args.rounds, out=args.out)
