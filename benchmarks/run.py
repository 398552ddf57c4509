"""Benchmark harness — one module per paper table/figure (DESIGN.md §9).

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig5,fig8_9]

Prints ``name,us_per_call,derived`` CSV rows.
"""
from __future__ import annotations

import argparse
import importlib
import sys
import time
import traceback

from repro.utils.compile_cache import configure_compile_cache

MODULES = [
    ("fig5", "benchmarks.fig5_sao_vs_fedl"),
    ("fig6_7", "benchmarks.fig6_7_delay_sweeps"),
    ("fig8_9", "benchmarks.fig8_9_kmeans"),
    ("fig10_11", "benchmarks.fig10_11_convergence"),
    ("table1", "benchmarks.table1_divergence_accuracy"),
    ("fig13", "benchmarks.fig13_interplay"),
    ("fig14", "benchmarks.fig14_power_opt"),
    ("kernels", "benchmarks.bench_kernels"),
    ("sao_scaling", "benchmarks.bench_sao_scaling"),
    ("compression", "benchmarks.beyond_compression"),
]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced sweeps/rounds (CI-friendly)")
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark keys")
    args = ap.parse_args(argv)
    configure_compile_cache()
    only = set(args.only.split(",")) if args.only else None

    print("name,us_per_call,derived")
    failures = []
    for key, module in MODULES:
        if only and key not in only:
            continue
        print(f"# --- {module} ---", flush=True)
        t0 = time.time()
        try:
            importlib.import_module(module).run(quick=args.quick)
            print(f"# {key} done in {time.time()-t0:.1f}s", flush=True)
        except Exception:
            traceback.print_exc()
            failures.append(key)
    if failures:
        print(f"# FAILED: {failures}")
        sys.exit(1)
    print("# all benchmarks OK")


if __name__ == "__main__":
    main()
