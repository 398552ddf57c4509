"""The harness end to end on the CPU at a size a test run can hold: an
honest run is correct; a run with the timed path broken underneath, or the
control in the program's place, is not; without a TPU, or without the
program, the command prints no result."""
import io
import json
import os
import shutil
import subprocess
import sys
import weakref
from types import SimpleNamespace

import numpy as np
import pytest

from conftest import FAULT_SIZE, ROOT, TINY

from chipbench import check, faults, generate, run


def run_cell(workload="mnist.paper", seed=3_000_000_001, overrides=TINY,
             seconds=0.5):
    out = io.StringIO()
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", "0"],
                  require_tpu=False, overrides=overrides, out=out)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_honest_run_is_correct():
    res = run_cell()
    assert res["correct"] is True, res["checks"]
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"updates_per_s", "setup_s"}


def test_the_window_keeps_at_most_two_experiments_alive(monkeypatch):
    """Each experiment of the window but the kept one is freed before the
    next is built (weak references to every experiment built)."""
    refs, before, after = [], [], []
    real = run.Runner.run

    def tracked(self, seed):
        before.append(sum(r() is not None for r in refs))
        unit = real(self, seed)
        refs.append(weakref.ref(unit.exp))
        after.append(sum(r() is not None for r in refs))
        return unit

    monkeypatch.setattr(run.Runner, "run", tracked)
    res = run_cell(seconds=3.0)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 3
    assert max(before) <= 1 and max(after) <= 2, (before, after)


def test_the_checked_experiment_is_drawn_uniformly_by_the_seed():
    """The reservoir draw: one seed keeps the same experiment of the same
    window every time; over seeds each of five is kept about as often."""
    def kept(seed, n=5):
        w = run.Window(seed)
        for k in range(n):
            w.add(SimpleNamespace(seed=k, updates=1, t_end=float(k),
                                  exp=k))
        assert [u.seed for u in w.units] == list(range(n))
        return w.kept.exp

    assert [kept(2 ** 40 + 9) for _ in range(3)] == [kept(2 ** 40 + 9)] * 3
    counts = np.bincount([kept(s) for s in range(2000)], minlength=5)
    assert counts.min() > 330 and counts.max() < 470, counts


def test_a_wrong_small_leaf_shows_in_param_gap():
    """``param_gap`` takes the worst leaf: a bias that moved 10% wrong in
    one client row reads 0.1 (it is the row's median leaf), where the
    whole model's norm would hide it behind w_fc1."""
    rng = np.random.default_rng(0)
    shapes = {"w_fc1": (400, 224), "b_fc1": (224,), "b_fc2": (10,)}
    init = {n: rng.normal(size=s).astype(np.float32)
            for n, s in shapes.items()}
    move = {n: rng.normal(size=s).astype(np.float32)
            for n, s in shapes.items()}
    glob = {n: init[n] + move[n] for n in shapes}
    clients = {n: np.stack([init[n] + k * move[n] for k in (1, 2, 3)])
               for n in shapes}
    ref = {"init": init, "global": glob, "clients": clients}
    wrong = {n: v.copy() for n, v in clients.items()}
    wrong["b_fc1"][1] += 0.1 * 2 * move["b_fc1"]
    gaps = check.leaf_gaps({"global": glob, "clients": wrong}, ref)
    assert gaps["b_fc1"] == pytest.approx(0.1, rel=1e-5)
    assert gaps["w_fc1"] == 0.0
    assert max(check.leaf_gaps({"global": glob, "clients": clients},
                               ref).values()) == 0.0


def test_select_gap_judges_early_rounds_and_the_layout_in_all():
    """A pick below its cluster's largest reference divergence counts in
    the first ``SELECT_ROUNDS`` rounds only; a broken layout in any."""
    N, late = 4, check.SELECT_ROUNDS + 2
    labels = np.array([0, 0, 1, 1])
    rounds = late + 1
    lanes = np.tile([0, 2], (rounds, 1))
    d = np.tile([1.0, 0.5, 1.0, 0.5], (rounds, 1))
    d[late] = [0.5, 1.0, 1.0, 0.5]
    prog = {"labels": labels, "lanes": lanes,
            "lanes_bad": np.zeros(rounds, bool)}
    short = check.select_shortfalls(prog, {"divergences": d}, N)
    assert short[late] == 0.5 and check.select_gap(short) == 0.0
    d[0] = [0.8, 1.0, 1.0, 0.5]
    short = check.select_shortfalls(prog, {"divergences": d}, N)
    assert check.select_gap(short) == pytest.approx(0.2)
    prog["lanes"] = lanes.copy()
    prog["lanes"][late] = [2, 0]
    assert check.select_gap(check.select_shortfalls(
        prog, {"divergences": d}, N)) == 1.0


@pytest.mark.parametrize("fault,number,size", [
    ("keep_global", "acc_mean_gap", FAULT_SIZE),
    ("half_slate", "acc_mean_gap", FAULT_SIZE),
    ("latency", "T_gap", TINY), ("labels", "select_gap", TINY)])
def test_broken_timed_path_is_not_correct(fault, number, size):
    with faults.planted(fault):
        res = run_cell(overrides=size)
    assert res["correct"] is False
    chk = res["checks"][number]
    assert chk["value"] > chk["limit"], res["checks"]


def test_control_in_the_programs_place_is_not_correct():
    """The control (the reference in bfloat16) read as the program."""
    from chipbench.readings import control_outputs

    cell = run.load_cell("mnist.paper")
    spec = run.spec_dict(cell, TINY)
    gen = generate.experiment(1234, spec, cell["model"])
    prog = control_outputs(cell["model"], spec, gen)
    res = check.numbers(prog, cell["model"], spec, gen=gen)
    ok, rows = check.verdict(res["numbers"], cell["limits"])
    assert not ok, rows


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "mnist.paper",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_without_a_tpu_no_result_is_printed():
    p = _cli(ROOT)
    assert p.returncode != 0
    assert p.stdout == ""


def test_benchmark_files_alone_print_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""


@pytest.mark.parametrize("digest_min", [check.DIGEST_MIN, 0])
def test_a_frozen_base_is_compared_element_for_element_or_by_digest(
        monkeypatch, digest_min):
    """A frozen leaf small enough is compared on the host element by
    element; a larger one by its digest on the device, where one changed
    or moved element counts the leaf whole."""
    import jax.numpy as jnp

    monkeypatch.setattr(check, "DIGEST_MIN", digest_min)
    base = {"embed": jnp.arange(12.0).reshape(3, 4),
            "blocks": {"w": jnp.ones((2, 5), jnp.bfloat16)}}
    want = check.frozen_view(base)
    assert check._frozen_mismatch(check.frozen_view(base), want) == 0
    one = dict(base, embed=base["embed"].at[1, 2].add(1e-3))
    moved = dict(base, embed=base["embed"][::-1])
    by_digest = digest_min == 0
    assert check._frozen_mismatch(check.frozen_view(one), want) == (
        12 if by_digest else 1)
    # the middle row stays in place: on the host, 8 elements differ
    assert check._frozen_mismatch(check.frozen_view(moved), want) == (
        12 if by_digest else 8)
    assert check._frozen_mismatch(None, want) == 1
    assert check._frozen_mismatch(None, None) == 0
