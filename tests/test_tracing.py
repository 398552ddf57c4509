"""The program's own trace names: the round phases as named scopes in the
compiled scan (every op's HLO ``op_name`` says its phase), the host spans
of the build, the run and the paged rounds (``repro.*``, each with the
experiment's seed), and the program-cache miss marker."""
import glob
import os
import re

import jax
import pytest

from repro.api import ExperimentSpec, build_experiment
from repro.core.engine import PHASES, phase_scope, run_rounds
from repro.core.wireless import fleet_arrays
from repro.launch import fl_sim

TINY = dict(dataset="mnist", clients=8, samples_per_client=16,
            train_samples=160, test_samples=80, local_iters=2, batch_size=8,
            rounds=2, devices_per_round=4, num_clusters=4)

_PHASE = re.compile(r"(?<![\w.])fl\.([a-z]+)")
_INSTRUCTION = re.compile(r'\s+(?:ROOT )?%\S+ = .*op_name="([^"]*)"')


def phase_of(op_name: str) -> str:
    """An op's phase: the last ``fl.<phase>`` in its ``op_name``, wherever
    it stands (``transpose(jvp(...))`` included); ``other`` if none."""
    found = [p for p in _PHASE.findall(op_name) if p in PHASES]
    return found[-1] if found else "other"


def compiled_op_names(exp, rounds=2):
    """``op_name`` of every instruction of the optimised HLO of ``exp``'s
    scanned program (the one ``FLExperiment.run`` dispatches), parameters
    and reducer bodies left out."""
    fn = run_rounds(exp.engine.cfg, selector=exp.selector,
                    allocator=exp.allocator, aggregator=exp.aggregator,
                    compressor=exp.compressor, tctx=exp.traced_context(),
                    feature_layer=exp.fl.feature_layer, rounds=rounds,
                    with_init=True, channel=exp.channel, churn=exp.churn,
                    faults=exp.faults,
                    quarantine_after=exp.quarantine_after)
    text = fn.lower(exp.traced_state(), exp._images, exp._labels,
                    exp._sizes, fleet_arrays(exp.fleet), exp.test_images,
                    exp.test_labels, exp.engine.frozen).compile().as_text()
    names, region = [], False
    for line in text.splitlines():
        if line and not line[0].isspace():
            # a computation's header; the scalar reducer bodies of reduce,
            # reduce-window and select-and-scatter ("region_*") never run
            # as ops of their own and carry relative names
            region = line.startswith("%region_")
            continue
        m = _INSTRUCTION.match(line)
        if m and not region and " parameter(" not in line:
            names.append(m.group(1))
    return names


def host_events(logdir):
    """``(name, start_ns, end_ns, stats)`` of every ``repro.*`` host event
    in the profiler trace under ``logdir``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


def traced(logdir, fn):
    with jax.profiler.trace(str(logdir)):
        out = fn()
    return out, host_events(str(logdir))


# ---------------------------------------------------------------------------
# device phases
# ---------------------------------------------------------------------------


def test_phase_of_takes_the_last_phase_in_the_path():
    assert phase_of("jit(run)/while/body/closed_call/fl.select/argsort") \
        == "select"
    assert phase_of("jit(run)/while/body/fl.train/vmap(while)/body/"
                    "transpose(jvp(fl.train))/conv") == "train"
    assert phase_of("jit(run)/fl.train/fl.compress/round") == "compress"
    assert phase_of("jit(run)/while/body/dynamic_slice") == "other"
    assert phase_of("jit(run)/nfl.train/add") == "other"


def test_phase_scope_knows_only_the_round_phases():
    with pytest.raises(ValueError, match="unknown round phase"):
        phase_scope("sao")


def test_sync_scan_names_every_phase_in_the_optimised_hlo():
    names = compiled_op_names(build_experiment(ExperimentSpec(**TINY)))
    phases = {phase_of(n) for n in names}
    assert {"select", "allocate", "train", "aggregate", "eval"} <= phases
    # local SGD's backward ops keep the phase they were traced under
    backward = [n for n in names if "transpose(" in n]
    assert backward
    assert {phase_of(n) for n in backward} == {"train"}


def test_async_tick_names_its_phases_too():
    spec = ExperimentSpec(**dict(TINY, aggregator="fedbuff:2:0.5",
                                 selection="icas"))
    phases = {phase_of(n)
              for n in compiled_op_names(build_experiment(spec))}
    assert {"select", "allocate", "train", "aggregate", "eval"} <= phases


GRANITE = dict(model="granite-4.0-h-smoke", clients=4, train_samples=20,
               test_samples=2, samples_per_client=4, devices_per_round=2,
               num_clusters=2, local_iters=1, batch_size=1, rounds=1,
               learning_rate=0.02)


def test_lm_layers_are_named_under_train_and_eval():
    """The hybrid LM's mixers, MLPs and head carry ``lm.*`` scopes nested
    under the round phase that ran them (forward and backward in
    ``fl.train``, the forward in ``fl.eval``)."""
    names = compiled_op_names(build_experiment(ExperimentSpec(**GRANITE)),
                              rounds=1)
    for phase in ("train", "eval"):
        scoped = {layer for n in names if phase_of(n) == phase
                  for layer in ("lm.mamba", "lm.attn", "lm.mlp", "lm.loss")
                  if layer in n}
        assert scoped == {"lm.mamba", "lm.attn", "lm.mlp", "lm.loss"}, phase
    backward = {layer for n in names if "transpose(" in n
                for layer in ("lm.mamba", "lm.attn", "lm.mlp", "lm.loss")
                if layer in n}
    assert backward == {"lm.mamba", "lm.attn", "lm.mlp", "lm.loss"}


# ---------------------------------------------------------------------------
# host spans and the cache-miss marker
# ---------------------------------------------------------------------------


def _encloses(outer, inner):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.mark.parametrize("seed", [11, 12])
def test_build_and_run_spans_nest_and_carry_the_seed(tmp_path, seed):
    spec = ExperimentSpec(**dict(TINY, seed=seed))
    _, events = traced(tmp_path, lambda: build_experiment(spec).run())
    spans = {e[0]: e for e in events if not e[0].startswith("repro.count")}
    assert set(spans) == {
        "repro.build", "repro.build.fleet", "repro.build.data",
        "repro.build.driver", "repro.run", "repro.run.launch",
        "repro.run.wait", "repro.run.fetch"}
    assert all(e[3]["seed"] == seed for e in spans.values())
    for child, parent in [("build.fleet", "build"), ("build.data", "build"),
                          ("build.driver", "build"), ("run.launch", "run"),
                          ("run.wait", "run"), ("run.fetch", "run")]:
        assert _encloses(spans["repro." + parent], spans["repro." + child])
    # the three run spans follow each other
    assert (spans["repro.run.launch"][2] <= spans["repro.run.wait"][1]
            <= spans["repro.run.wait"][2] <= spans["repro.run.fetch"][1])


def test_program_miss_marks_the_first_run_only(tmp_path):
    # a learning rate no other test uses: both program caches miss once
    spec = ExperimentSpec(**dict(TINY, learning_rate=0.0431))

    def misses(logdir, seed):
        _, events = traced(logdir, lambda: build_experiment(
            spec.replace(seed=seed)).run())
        return sorted(e[3]["cache"] for e in events
                      if e[0] == "repro.count.program_miss")

    assert misses(tmp_path / "first", 1) == ["engine", "run_rounds"]
    assert misses(tmp_path / "second", 2) == []


@pytest.mark.parametrize("aggregator", ["fedavg", "fedbuff:2:0.5"])
def test_paged_host_loops_span_every_round(tmp_path, aggregator):
    spec = ExperimentSpec(**dict(TINY, seed=5, store="paged", k_max=8,
                                 aggregator=aggregator))
    _, events = traced(tmp_path, lambda: build_experiment(spec).run())
    rounds = [e for e in events if e[0] == "repro.round"]
    assert [(e[3]["seed"], e[3]["round"]) for e in rounds] == [(5, 0),
                                                               (5, 1)]


def test_fl_sim_profile_writes_a_trace_with_the_spans(tmp_path, capsys):
    spec = tmp_path / "spec.json"
    spec.write_text(ExperimentSpec(**dict(TINY, rounds=1,
                                          seed=21)).to_json())
    fl_sim.main(["--spec", str(spec), "--profile", str(tmp_path / "prof")])
    assert "final_accuracy" in capsys.readouterr().out
    names = {e[0] for e in host_events(str(tmp_path / "prof"))}
    assert {"repro.build", "repro.run", "repro.run.wait"} <= names


def test_frozen_base_is_placed_once_per_process_and_config(tmp_path):
    """``repro.build.frozen`` spans the base's placement, and
    ``repro.count.frozen_upload`` (id ``config``) marks it once per process
    and config, not once per experiment."""
    from repro.core.engine import frozen_params

    frozen_params.cache_clear()
    # a learning rate no other test uses: the first build makes a new
    # engine, which asks for the base
    spec = ExperimentSpec(**dict(GRANITE, learning_rate=0.0173))

    def two_experiments():
        for seed in (1, 2):
            build_experiment(spec.replace(seed=seed)).run()

    _, events = traced(tmp_path / "first", two_experiments)
    uploads = [e for e in events if e[0] == "repro.count.frozen_upload"]
    spans = [e for e in events if e[0] == "repro.build.frozen"]
    assert [e[3]["config"] for e in uploads] == ["granite-4.0-h-smoke"]
    assert len(spans) == 1 and spans[0][3]["config"] == "granite-4.0-h-smoke"
    builds = [e for e in events if e[0] == "repro.build"]
    assert _encloses(builds[0], spans[0])
    _, events = traced(tmp_path / "second", two_experiments)
    assert not [e for e in events if "frozen" in e[0]]


def test_the_cnn_places_no_frozen_tree(tmp_path):
    _, events = traced(tmp_path, lambda: build_experiment(
        ExperimentSpec(**dict(TINY, seed=3))).run())
    assert not [e for e in events if "frozen" in e[0]]
