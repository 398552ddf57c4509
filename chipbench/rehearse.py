#!/usr/bin/env python3
"""Compile each cell's scanned round program for a described TPU v5e on a
machine without one, and print its ``memory_analysis``.

    JAX_PLATFORMS=cpu python chipbench/rehearse.py [workload ...]

Each cell compiles for one chip of a described ``v5e:2x2``. The kernel
seams are steered onto the Mosaic kernels (``interpret=False``) as on a
TPU backend. Nothing runs: the compile shows whether the program fits a
chip and which Mosaic kernels it holds, not how fast it is. Prints one
JSON line per cell.
"""
from __future__ import annotations

import json
import os
import re
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for p in (os.path.join(ROOT, "src"), ROOT):
        sys.path.insert(0, p)
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import run as bench
    from repro.api import ExperimentSpec, build_experiment
    from repro.core import engine
    from repro.core.wireless import fleet_arrays
    from repro.kernels import ops

    jax.config.update("jax_enable_compilation_cache", False)
    ops._on_tpu = lambda: True
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    names = argv if argv else [w["name"] for w in bench.read_json(
        ROOT, "BENCHMARK.json")["workloads"]]
    for name in names:
        cell = bench.load_cell(name)
        spec = bench.spec_dict(cell)
        exp = build_experiment(ExperimentSpec(**spec))
        args = (exp.traced_state(), exp._images, exp._labels, exp._sizes,
                fleet_arrays(exp.fleet), exp.test_images, exp.test_labels)
        one = SingleDeviceSharding(topo.devices[0])
        sds = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
            args)
        engine._RUN_FN_CACHE.clear()
        fn = engine.run_rounds(
            exp.engine.cfg, selector=exp.selector, allocator=exp.allocator,
            aggregator=exp.aggregator, compressor=exp.compressor,
            tctx=exp.traced_context(), feature_layer=exp.fl.feature_layer,
            rounds=spec["rounds"], with_init=True, cohort=False,
            test_shared=True, mesh=None, channel=exp.channel)
        t0 = time.perf_counter()
        lowered = fn.lower(*sds)
        kernels = sorted(set(re.findall(r'kernel_name = "(\w+)"',
                                        lowered.as_text())))
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        print(json.dumps({
            "workload": name, "rounds": spec["rounds"],
            "compile_s": round(time.perf_counter() - t0, 1),
            "kernels": kernels,
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes}), flush=True)
        del exp, args, lowered, compiled
        jax.clear_caches()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
