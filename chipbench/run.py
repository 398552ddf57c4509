#!/usr/bin/env python3
"""Chip benchmark of the FL experiment program: one cell, one run.

    python chipbench/run.py --workload mnist.paper --seed 7 --seconds 20 \
        --trace 0

A cell (``BENCHMARK.json`` ``workloads``) is a model configuration
(``chipbench/configs/<config>.json``) under a traffic mix
(``chipbench/traffic/<traffic>.json``, the ``ExperimentSpec`` fields of one
experiment). The configuration names its plain reference
(``chipbench/reference/<reference>.py``, the model interface of
``reference/fl.py``), may add ``ExperimentSpec`` fields under ``spec``
(``{"model": "mamba2-130m"}``; a ``dataset`` key sets that field), lists
under ``widths`` the dotted attributes of the program's model config that
must equal its own values (``model.d_model``, ``rank``), and may name
under ``program_frozen`` the program's function ``module:name`` that
gives the frozen tree for the model config. The run

1. refuses to start without a TPU, or with fewer chips than the cell asks;
2. set-up: imports, backend start, one whole experiment (build and run)
   that loads every program of the cell from the compile cache (or
   compiles it, on a checkout's first run);
3. window: whole experiments back to back, each on its own seed drawn from
   ``--seed``, until ``--seconds`` have passed; the last one runs to its
   end. Each calls ``build_experiment(spec)`` then ``FLExperiment.run``.
   Compiles inside the window are counted. Of each experiment the window
   keeps its counts; the experiment itself it keeps for one alone, drawn
   from the seed as it goes (``Window``);
4. with ``--trace 1`` the window runs under the JAX profiler and the
   per-layer metrics (``chipbench/metrics/<metric>.py``) read the trace
   and the benchmark's own host spans;
5. check: the kept experiment is compared with the plain reference
   (``chipbench/check.py``), after the program's state is freed.

The last stdout line is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and
last ``checks`` (each compared number with its limit, also printed as the
last lines of stderr).
"""
from __future__ import annotations

import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
SPAN_PREFIX = "chipbench."
ROOFLINE = "_roofline"


class CellError(Exception):
    """The cell cannot run here (no chip, missing files)."""


def read_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """Everything one cell is made of, found by name from BENCHMARK.json."""
    bench_path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.exists(bench_path):
        raise CellError(f"no BENCHMARK.json at {ROOT}")
    bench = read_json(bench_path)
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise CellError(f"unknown workload {name!r}; known: {sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    model = read_json(ROOT, conf["file"])
    traffic = read_json(HERE, "traffic", w["traffic"] + ".json")
    limits = read_json(HERE, "limits", name + ".json")
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name])]
    end_to_end = [m for m in bench["end_to_end"]
                  if name in m.get("workloads", [name])]
    return {"name": name, "workload": w, "model": model, "traffic": traffic,
            "limits": limits, "per_layer": per_layer,
            "end_to_end": end_to_end}


def spec_dict(cell: dict, overrides: dict = None) -> dict:
    """The experiment's settings: the traffic's spec with the
    configuration's ``dataset`` and ``spec`` fields (``overrides`` shrink
    it for tests on the CPU)."""
    spec = dict(cell["traffic"]["spec"])
    if "dataset" in cell["model"]:
        spec["dataset"] = cell["model"]["dataset"]
    spec.update(cell["model"].get("spec", {}))
    spec.update(overrides or {})
    return spec


# ---------------------------------------------------------------------------
# host spans
# ---------------------------------------------------------------------------


class Spans:
    """The benchmark's host spans: each is kept as ``(name, t0, t1)`` on
    the host clock and written into the profiler's trace as a
    ``TraceAnnotation`` named ``chipbench.<name>``."""

    def __init__(self):
        self.items = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        from jax.profiler import TraceAnnotation

        t0 = time.perf_counter()
        with TraceAnnotation(SPAN_PREFIX + name):
            try:
                yield
            finally:
                self.items.append((name, t0, time.perf_counter()))


class CompileClock:
    """Sums JAX's backend-compile durations while active."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.active = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.active and event == self.EVENT:
            self.seconds += duration

    @contextlib.contextmanager
    def measure(self):
        self.seconds, self.active = 0.0, True
        try:
            yield self
        finally:
            self.active = False


# ---------------------------------------------------------------------------
# the experiments the window drives
# ---------------------------------------------------------------------------


class Runner:
    """Builds and runs one experiment: ``build_experiment(spec)`` then
    ``FLExperiment.run``, the two calls of ``fl_sim.run_spec``, each in a
    host span of its own."""

    def __init__(self, spec: dict, spans: Spans):
        from repro.api import ExperimentSpec

        self.spec = spec
        self.spans = spans
        self.base = ExperimentSpec(**spec)

    def run(self, seed: int):
        from repro.api import build_experiment

        spec = self.base.replace(seed=int(seed))
        with self.spans("build"):
            exp = build_experiment(spec)
        with self.spans("dispatch"):
            hist = exp.run(rounds=spec.rounds)
        return SimpleNamespace(seed=int(seed), exp=exp, hist=hist,
                               updates=sum(len(s) for s in hist.selected),
                               t_end=time.perf_counter())


class Window:
    """The experiments of the timed window. Of each it keeps the seed, the
    count of client updates and the end time; the experiment itself it
    keeps for one alone, the one the check reads. The k-th experiment
    replaces the kept one with probability 1/k (a reservoir draw from
    ``default_rng([seed, 1])``), so the checked experiment is uniform over
    the window and fixed by the seed, and every other one is freed before
    the next is built: at most two are alive at once."""

    def __init__(self, seed: int):
        import numpy as np

        self.rng = np.random.default_rng([int(seed), 1])
        self.units = []
        self.kept = None

    def add(self, unit) -> None:
        self.units.append(SimpleNamespace(
            seed=unit.seed, updates=unit.updates, t_end=unit.t_end))
        if self.rng.integers(len(self.units)) == 0:
            self.kept = unit


def program_attr(dotted: str):
    """The object a ``module:name`` string names."""
    module, name = dotted.split(":")
    return getattr(importlib.import_module(module), name)


def program_width(model_cfg, dotted: str):
    """A dotted attribute of the program's model config, a tuple as a
    list (as the configuration file writes it)."""
    v = model_cfg
    for part in dotted.split("."):
        v = getattr(v, part)
    return list(v) if isinstance(v, tuple) else v


def program_outputs(unit, spec: dict, model_cfg: dict) -> dict:
    """What the sampled experiment produced and consumed, on the host."""
    import numpy as np
    from repro.core.wireless import fleet_arrays

    from chipbench import check
    from chipbench.reference import fl

    exp, hist = unit.exp, unit.hist
    N, c = spec["clients"], spec["num_clusters"]
    labels = np.asarray(exp.cluster_labels)
    lanes, bad = check.lanes_from_selection(hist.selected[1:], labels, c, N)
    widths = {k: program_width(exp.model_cfg, k)
              for k in model_cfg["widths"]}
    frozen = (check.frozen_view(
        program_attr(model_cfg["program_frozen"])(exp.model_cfg))
        if "program_frozen" in model_cfg else None)
    settings = {
        "clients": int(exp.fed.num_clients),
        "samples_per_client": int(exp.fed.images.shape[1]),
        "devices_per_round": int(exp.fl.devices_per_round),
        "num_clusters": int(exp.fl.num_clusters),
        "selected_per_cluster": int(exp.fl.selected_per_cluster),
        "local_iters": int(exp.engine.cfg.local_iters),
        "batch_size": int(exp.engine.cfg.batch_size),
        "learning_rate": float(exp.engine.cfg.learning_rate),
        "bandwidth_mhz": float(exp.B),
        "rounds": len(hist.accuracy) - 1,
        "allocator": exp.allocator.registry_name,
        "selection": exp.selector.registry_name,
        "aggregator": exp.aggregator.registry_name,
    }
    return {
        "seed": int(exp.spec.seed),
        "accuracy": np.asarray(hist.accuracy), "T": np.asarray(hist.T_k),
        "E": np.asarray(hist.E_k), "lanes": lanes, "lanes_bad": bad,
        "labels": labels,
        "global": fl.leaves_by_path(exp.global_params),
        "clients": fl.leaves_by_path(exp.client_tree()),
        "inputs": {
            "x": exp.fed.images, "y": exp.fed.labels,
            "sizes": exp.fed.sizes,
            "test_x": np.asarray(exp.test_images),
            "test_y": np.asarray(exp.test_labels),
            "fleet": {k: np.asarray(v)
                      for k, v in fleet_arrays(exp.fleet).items()},
            "frozen": frozen, "widths": widths, "settings": settings},
    }


# ---------------------------------------------------------------------------
# per-layer metrics
# ---------------------------------------------------------------------------


def load_metric(name: str):
    """The module ``chipbench/metrics/<name>.py`` (its ``read(ctx)`` gives
    the metric, or None where it finds nothing to read)."""
    path = os.path.join(HERE, "metrics", name + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def device_info(devs) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


# ---------------------------------------------------------------------------


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def prepare_env():
    """Before JAX starts: the compile cache inside the checkout, at a
    fixed path; the program on the path."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)


def main(argv=None, *, require_tpu: bool = True, overrides: dict = None,
         out=None) -> int:
    """One run. ``require_tpu=False`` and ``overrides`` (smaller settings)
    serve the tests on the CPU; the command line always requires a TPU."""
    args = parse(argv)
    out = out or sys.stdout
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"chipbench: no program under {ROOT}/src", file=sys.stderr)
        return 2
    try:
        cell = load_cell(args.workload)
    except (CellError, OSError, KeyError) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    chips = int(cell["workload"]["chips"])
    prepare_env()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        print(f"chipbench: {args.workload} needs {chips} TPU chip(s); JAX "
              f"found {len(devs)} {devs[0].platform!r} device(s)",
              file=sys.stderr)
        return 3
    devs = devs[:chips]
    from chipbench import check, generate, trace
    from chipbench.reference import fl

    spec = spec_dict(cell, overrides)
    spans = Spans()
    clock = CompileClock()
    runner = Runner(spec, spans)
    seeds = generate.experiment_seeds(args.seed, 100_000)

    # ---- set-up: one whole experiment loads (or compiles) every program
    runner.run(seeds[0])
    gc.collect()
    setup_s = time.time() - T_PROCESS

    # ---- window
    logdir = tempfile.mkdtemp(prefix="chipbench-trace-") \
        if args.trace else None
    if logdir:
        jax.profiler.start_trace(logdir)
    spans.items.clear()
    window = Window(args.seed)
    units = window.units
    t0 = time.perf_counter()
    with clock.measure(), spans("window"):
        while True:
            window.add(runner.run(seeds[len(units) + 1]))
            if units[-1].t_end - t0 >= args.seconds:
                break
    window_compile_s = clock.seconds
    elapsed = units[-1].t_end - t0
    if logdir:
        jax.profiler.stop_trace()
    updates = sum(u.updates for u in units)
    attempted = len(units)
    device = device_info(devs)

    metrics, breakdown = {}, None
    if args.trace:
        # a metric ``<kernel>_roofline`` asks for the calls of the Pallas
        # kernel whose ``pallas_call`` is named ``<kernel>``
        kernels = tuple(m["name"][:-len(ROOFLINE)]
                        for m in cell["per_layer"]
                        if m["name"].endswith(ROOFLINE))
        red = trace.reduce(trace.load(logdir, kernels=kernels))
        shutil.rmtree(logdir, ignore_errors=True)
        from chipbench import flops

        ctx = SimpleNamespace(
            red=red, spans=[s for s in spans.items if s[0] != "window"],
            units=units, model=cell["model"], spec=spec, chips=chips,
            peaks=flops.peaks(
                devs[0].device_kind) if require_tpu else None)
        for m in cell["per_layer"]:
            v = load_metric(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        n_dev = max(len(red["busy_s"]), 1)
        device["busy_s"] = sum(red["busy_s"].values()) / n_dev
        device["window_s"] = red["window_s"]
        breakdown = {"device_ops": [[n, t / n_dev]
                                    for n, t in red["device_ops"]],
                     "idle_gaps": red["idle_gaps"]}
    else:
        values = {"updates_per_s": updates / elapsed, "setup_s": setup_s}
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}

    # ---- check the one experiment the window kept
    prog = program_outputs(window.kept, spec, cell["model"])
    del window, runner
    gc.collect()
    ref = fl.Experiment(cell["model"], spec)
    res = check.numbers(prog, cell["model"], spec, reference=ref)
    values = dict(res["numbers"], window_compile_s=window_compile_s)
    correct, rows = check.verdict(values, cell["limits"])
    if res["inputs_detail"] and any(res["inputs_detail"].values()):
        print(f"inputs differing from the generator: {res['inputs_detail']}",
              file=sys.stderr)

    result = {"correct": bool(correct),
              "attempted": attempted,
              "failed": 0 if correct else 1,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in rows}
    for n, v, lim in rows:
        print(f"check {n}: {v!r} (limit {lim!r})", file=sys.stderr)
    print(json.dumps(result), file=out, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
