#!/usr/bin/env python3
"""The readings that the limits of ``limits/<cell>.json`` are set from.

    python chipbench/readings.py --workload mnist.paper --seed 5 \
        --program 12 --control 3 --train-control 3 \
        --faults keep_global,half_slate,latency,labels --fault-runs 3 \
        [--experiments SEED,...] [--out FILE]

On the chip, at the cell's own size, each experiment on its own seed:

* ``--program``: experiments of the program, each compared with the
  reference exactly as a benchmark run compares its sampled experiment
  (the lower readings); ``--experiments`` adds these experiment seeds
  first (a benchmark run's sampled experiment, say);
* ``--control``: the control, the reference itself computed in the
  precision below the one the configuration states (bfloat16 weights,
  activations, SGD, aggregation, K-means and allocation), put in the
  program's place and compared the same way (the upper readings);
* ``--train-control``: the same, with only the model in bfloat16 and the
  allocation at the float32 the configuration states: does a program whose
  training alone drops below the stated precision fail?
* ``--faults``: ``--fault-runs`` experiments of the program with each of
  ``chipbench/faults.py``'s faults planted under it.

Prints, and appends to ``--out``, one JSON line per experiment:
``{"kind", "seed", "numbers", "leaves", "rounds", "T", "seconds"}``
(``leaves``: the per-leaf gaps behind ``param_gap``; ``rounds``: per
round, ``select_gap``'s shortfall and the run's accuracy less the
reference's; ``T``: the run's
and the reference's T_k per round). The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
if os.path.dirname(HERE) not in sys.path:
    sys.path.insert(0, os.path.dirname(HERE))

from chipbench import run as bench  # noqa: E402


def control_outputs(model_cfg: dict, spec: dict, gen: dict,
                    alloc_dtype=None) -> dict:
    """The control's experiment, shaped like ``run.program_outputs``: the
    reference in bfloat16, its allocation in ``alloc_dtype`` (bfloat16
    unless given)."""
    import jax
    import jax.numpy as jnp
    import ml_dtypes
    import numpy as np

    from chipbench.reference import fl

    ctl = fl.Experiment(model_cfg, spec, dtype=jnp.bfloat16,
                        precision=jax.lax.Precision.DEFAULT,
                        alloc_dtype=alloc_dtype or ml_dtypes.bfloat16
                        ).run(gen)
    return {"seed": gen["seed"], "accuracy": ctl["accuracy"],
            "T": ctl["T"], "E": ctl["E"], "lanes": ctl["lanes"],
            "lanes_bad": np.zeros(len(ctl["lanes"]), bool),
            "labels": ctl["labels"], "global": ctl["global"],
            "clients": ctl["clients"]}


def main(argv=None, *, require_tpu: bool = True, overrides=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--program", type=int, default=12)
    ap.add_argument("--experiments", default="",
                    help="experiment seeds, comma-separated, run as "
                    "program experiments before the others")
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--train-control", type=int, default=0)
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-runs", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = bench.load_cell(args.workload)
    bench.prepare_env()
    import jax
    import numpy as np

    devs = jax.devices()
    chips = int(cell["workload"]["chips"])
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        print(f"readings: {args.workload} needs {chips} TPU chip(s)",
              file=sys.stderr)
        return 3
    from chipbench import check, faults, generate
    from chipbench.reference import fl

    spec = bench.spec_dict(cell, overrides)
    model = cell["model"]
    runner = bench.Runner(spec, bench.Spans())
    ref = fl.Experiment(model, spec)
    given = [int(x) for x in args.experiments.split(",") if x]
    groups = ([(None, "program", args.program)]
              + [(f, "fault:" + f, args.fault_runs)
                 for f in args.faults.split(",") if f]
              + [(None, "control", args.control),
                 (None, "train-control", args.train_control)])
    seeds = iter(given + generate.experiment_seeds(
        args.seed, sum(count for _, _, count in groups)))
    groups[0] = (None, "program", args.program + len(given))

    def emit(kind, seed, res, prog, seconds):
        rec = {"workload": args.workload, "kind": kind, "seed": seed,
               "numbers": res["numbers"], "leaves": res["leaves"],
               "inputs": res["inputs_detail"], "rounds": res["rounds"],
               "T": [list(map(float, prog["T"])),
                     list(map(float, res["reference"]["T"]))],
               "seconds": seconds}
        line = json.dumps(rec)
        print(line, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")

    for fault, kind, count in groups:
        with (faults.planted(fault) if fault
              else contextlib.nullcontext()):
            for _ in range(count):
                seed = next(seeds)
                t0 = time.perf_counter()
                if kind.endswith("control"):
                    gen = generate.experiment(seed, spec, model)
                    prog = control_outputs(
                        model, spec, gen,
                        np.float32 if kind == "train-control" else None)
                else:
                    gen = None
                    unit = runner.run(seed)
                    prog = bench.program_outputs(unit, spec, model)
                    del unit
                t1 = time.perf_counter()
                res = check.numbers(prog, model, spec, reference=ref,
                                    gen=gen)
                emit(kind, seed, res, prog,
                     {"run": t1 - t0,
                      "reference": time.perf_counter() - t1})
    return 0


if __name__ == "__main__":
    sys.exit(main())
