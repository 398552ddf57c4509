"""A cell of another FL workload joins the benchmark by new files alone.

In a copy of the checkout, the program's ``mamba2-130m`` LoRA workload (a
frozen two-layer Mamba-2 smoke base with rank-4 adapters on ``in_proj`` and
``out_proj``) gets a configuration, a traffic mix and limits, each a new
file, and entries appended to ``BENCHMARK.json``; no file of the harness
changes. Its reference is ``chipbench/reference/lora_lm.py``. There its
honest run is correct, a run whose rounds keep the global model is not,
and ``readings.py`` reads its numbers, all on the CPU.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import ROOT

from chipbench import check

CELL = "mamba2.lora_smoke"

#: the program's registered ``mamba2-130m`` workload (``repro.models.lm``:
#: ``LMConfig(model=get_smoke_config("mamba2-130m"))``)
CONFIG = {
    "name": "mamba2_lora_smoke",
    "source": "https://arxiv.org/abs/2405.21060",
    "described_as": "LoRA (rank 4, alpha 8) on in_proj/out_proj of a frozen "
                    "2-layer Mamba-2 smoke base (d_model 128, vocab 256)",
    "reference": "lora_lm",
    "spec": {"model": "mamba2-130m"},
    "program_frozen": "repro.models.lm:base_params",
    "model": {"family": "ssm", "num_layers": 2, "d_model": 128,
              "vocab_size": 256, "tie_embeddings": True, "norm_eps": 1e-5,
              "ssm": {"d_state": 16, "head_dim": 32, "expand": 2,
                      "n_groups": 1, "conv_width": 4, "chunk_size": 32,
                      "dt_min": 0.001, "dt_max": 0.1}},
    "seq_len": 32, "rank": 4, "alpha": 8.0, "base_seed": 0,
    "num_dialects": 10,
    "widths": ["model.family", "model.num_layers", "model.d_model",
               "model.vocab_size", "model.tie_embeddings", "model.norm_eps",
               "model.ssm.d_state", "model.ssm.head_dim", "model.ssm.expand",
               "model.ssm.n_groups", "model.ssm.conv_width",
               "model.ssm.chunk_size", "model.ssm.dt_min",
               "model.ssm.dt_max", "seq_len", "rank", "alpha", "base_seed",
               "num_dialects"],
    "precision": {"params": "float32", "matmul": "highest",
                  "control": "bfloat16"},
    "reduced": [],
}

TRAFFIC = {
    "why": "a CPU-sized federated LoRA experiment: N=8, S=4, D_n=16, "
           "L=4, batch 8, 3 rounds",
    "spec": {"train_samples": 200, "test_samples": 40, "clients": 8,
             "samples_per_client": 16, "sigma": 0.8, "bandwidth_mhz": 20.0,
             "rounds": 3, "devices_per_round": 4, "selected_per_cluster": 1,
             "local_iters": 4, "num_clusters": 4, "learning_rate": 0.5,
             "batch_size": 8, "selection": "divergence", "allocator": "sao",
             "aggregator": "fedavg"},
}

#: set from CPU readings of this traffic (program and reference both in
#: float32 at HIGHEST): sound runs read ``param_gap`` under 1e-6 and every
#: other gap 0; the bfloat16 control reads ``param_gap`` 0.10-0.11,
#: ``T_gap`` 0.0068-0.011 and ``E_gap`` 0.0016-0.0029; ``keep_global``
#: reads ``param_gap`` 1.0 and ``half_slate`` 0.82-0.91. The accuracy of a
#: random base hardly moves in three rounds, so ``acc_mean_gap`` tells
#: nothing apart here.
LIMITS = {"inputs_mismatch": 0, "param_gap": 0.01, "acc_mean_gap": 0.02,
          "select_gap": 0.2, "T_gap": 0.002, "E_gap": 0.0005,
          "window_compile_s": 0}

SCRIPT = """
import contextlib, io, json, sys
sys.path[:0] = ["src", "."]
import jax
jax.config.update("jax_enable_compilation_cache", False)
from chipbench import faults, readings, run
entry, fault, argv = json.loads(sys.argv[1])
with (faults.planted(fault) if fault else contextlib.nullcontext()):
    if entry == "run":
        out = io.StringIO()
        rc = run.main(argv, require_tpu=False, out=out)
        print(out.getvalue().strip().splitlines()[-1])
    else:
        rc = readings.main(argv, require_tpu=False)
sys.exit(rc)
"""


def _write_new(path, obj):
    assert not os.path.exists(path), f"{path} is not a new file"
    with open(path, "w") as f:
        json.dump(obj, f, indent=2)


def make_checkout(root):
    """A copy of the checkout at ``root`` with the LoRA cell added by new
    files and appended entries alone."""
    skip = shutil.ignore_patterns("__pycache__", ".jax_cache")
    shutil.copytree(os.path.join(ROOT, "src"), root / "src", ignore=skip)
    shutil.copytree(os.path.join(ROOT, "chipbench"), root / "chipbench",
                    ignore=skip)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    bench = root / "chipbench"
    _write_new(bench / "configs" / "mamba2_lora_smoke.json", CONFIG)
    _write_new(bench / "traffic" / "lm_smoke.json", TRAFFIC)
    _write_new(bench / "limits" / f"{CELL}.json", LIMITS)
    with open(root / "BENCHMARK.json") as f:
        spec = json.load(f)
    spec["configs"].append({
        "name": CONFIG["name"], "source": CONFIG["source"],
        "file": "chipbench/configs/mamba2_lora_smoke.json", "reduced": [],
        "why": "a LoRA LM client over a frozen SSM base"})
    spec["workloads"].append({
        "name": CELL, "config": CONFIG["name"], "traffic": "lm_smoke",
        "chips": 1, "why": "federated LoRA on a frozen Mamba-2 base"})
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(spec, f, indent=2)
    return root


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return make_checkout(tmp_path_factory.mktemp("checkout"))


def _drive(root, entry, argv, fault=None):
    p = subprocess.run(
        [sys.executable, "-c", SCRIPT, json.dumps([entry, fault, argv])],
        cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return p.stdout


def _run(root, seed, fault=None):
    out = _drive(root, "run", ["--workload", CELL, "--seed", str(seed),
                               "--seconds", "0.5", "--trace", "0"], fault)
    return json.loads(out.strip().splitlines()[-1])


def test_a_lora_cell_by_new_files_is_correct(checkout):
    res = _run(checkout, 4_100_000_003)
    assert res["correct"] is True, res["checks"]
    assert res["checks"]["inputs_mismatch"]["value"] == 0


def test_a_lora_cell_that_keeps_the_global_model_is_not_correct(checkout):
    res = _run(checkout, 4_100_000_005, fault="keep_global")
    assert res["correct"] is False
    chk = res["checks"]["param_gap"]
    assert chk["value"] > chk["limit"], res["checks"]


def test_readings_run_for_the_lora_cell(checkout):
    out = _drive(checkout, "readings",
                 ["--workload", CELL, "--seed", "7", "--program", "1",
                  "--control", "1"])
    recs = {json.loads(line)["kind"]: json.loads(line)["numbers"]
            for line in out.splitlines() if line.startswith("{")}
    assert sorted(recs) == ["control", "program"]
    assert check.verdict(recs["program"], LIMITS)[0]
    assert not check.verdict(recs["control"], LIMITS)[0]
