"""Scheduler-throughput benchmark: SAO solve latency vs selected-set size
(the paper's complexity claim: O(S²·log³(1/ε)) — ours vectorizes the inner
per-device bisections, so wall time grows sub-quadratically)."""
from __future__ import annotations

import numpy as np

from benchmarks.common import emit, time_fn
from repro.core.wireless import sample_fleet, fleet_arrays
from repro.core.sao import solve_sao


def run(quick: bool = False):
    fleet = sample_fleet(200, seed=0)
    sizes = [10, 50] if quick else [5, 10, 25, 50, 100, 200]
    for S in sizes:
        arr = fleet_arrays(fleet.select(np.arange(S)))
        T, us = time_fn(lambda: float(solve_sao(arr, 20.0 * S / 10.0).T),
                        repeats=3, warmup=1)
        emit(f"sao_scaling/S{S}", us, f"T={T*1e3:.1f}ms")


if __name__ == "__main__":
    from repro.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    run()
