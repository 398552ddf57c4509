"""The granite-4.0-h cell's benchmark files, run at the smoke widths on the
CPU: in a copy of the checkout, a configuration of the program's
``granite-4.0-h-smoke`` workload (the same hybrid period, multipliers and
reference, ``chipbench/reference/granite_hybrid.py``) joins the benchmark
by new files and appended entries alone, as the at-size cell does. Its
honest run is correct; a run whose rounds keep the global model is not."""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "granite_h_smoke.lora_smoke"


def smoke_config() -> dict:
    """The committed at-size configuration with the smoke widths of
    ``repro.configs.granite_4_0_h_micro.smoke_config``."""
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "granite_4_0_h_micro.json")) as f:
        conf = json.load(f)
    conf.update({
        "name": "granite_h_smoke", "spec": {"model": "granite-4.0-h-smoke"},
        "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "mamba_n_heads": 8, "mamba_d_head": 16,
        "mamba_d_state": 16, "mamba_chunk_size": 16,
        "intermediate_size": 128, "shared_intermediate_size": 128,
        "vocab_size": 256, "seq_len": 32, "rank": 4, "alpha": 8.0,
        "base_dtype": "float32"})
    conf["model"] = dict(conf["model"], d_model=64, num_heads=4,
                         num_kv_heads=2, head_dim=16, d_ff=128,
                         vocab_size=256,
                         ssm=dict(conf["model"]["ssm"], d_state=16,
                                  head_dim=16, chunk_size=16))
    return conf


TRAFFIC = {
    "why": "a CPU-sized federated LoRA experiment on the hybrid stack: "
           "N=8, S=4, D_n=8, L=4, batch 4, 3 rounds",
    "spec": {"train_samples": 40, "test_samples": 8, "clients": 8,
             "samples_per_client": 8, "sigma": 0.8, "bandwidth_mhz": 20.0,
             "rounds": 3, "devices_per_round": 4, "selected_per_cluster": 1,
             "local_iters": 4, "num_clusters": 4, "learning_rate": 0.5,
             "batch_size": 4, "selection": "divergence", "allocator": "sao",
             "aggregator": "fedavg"},
}

#: program and reference both float32 on the CPU: sound runs read
#: ``param_gap`` about 1e-5 and every allocation gap under 1e-6;
#: ``keep_global`` reads ``param_gap`` 1.0
LIMITS = {"inputs_mismatch": 0, "param_gap": 0.01, "select_gap": 0.2,
          "T_gap": 0.002, "E_gap": 0.0005, "window_compile_s": 0}

SCRIPT = """
import contextlib, io, json, sys
sys.path[:0] = ["src", "."]
import jax
jax.config.update("jax_enable_compilation_cache", False)
from chipbench import faults, run
fault, argv = json.loads(sys.argv[1])
with (faults.planted(fault) if fault else contextlib.nullcontext()):
    out = io.StringIO()
    rc = run.main(argv, require_tpu=False, out=out)
    print(out.getvalue().strip().splitlines()[-1])
sys.exit(rc)
"""


def _write_new(path, obj):
    assert not os.path.exists(path), f"{path} is not a new file"
    with open(path, "w") as f:
        json.dump(obj, f, indent=2)


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    skip = shutil.ignore_patterns("__pycache__", ".jax_cache")
    shutil.copytree(os.path.join(ROOT, "src"), root / "src", ignore=skip)
    shutil.copytree(os.path.join(ROOT, "chipbench"), root / "chipbench",
                    ignore=skip)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    bench = root / "chipbench"
    _write_new(bench / "configs" / "granite_h_smoke.json", smoke_config())
    _write_new(bench / "traffic" / "granite_smoke.json", TRAFFIC)
    _write_new(bench / "limits" / f"{CELL}.json", LIMITS)
    with open(root / "BENCHMARK.json") as f:
        spec = json.load(f)
    spec["configs"].append({
        "name": "granite_h_smoke", "source": "https://huggingface.co/"
        "ibm-granite/granite-4.0-h-micro/blob/main/config.json",
        "file": "chipbench/configs/granite_h_smoke.json",
        "reduced": [], "why": "the hybrid stack at smoke widths"})
    spec["workloads"].append({
        "name": CELL, "config": "granite_h_smoke",
        "traffic": "granite_smoke", "chips": 1,
        "why": "federated LoRA on the hybrid stack"})
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(spec, f, indent=2)
    return root


def _run(root, seed, fault=None):
    p = subprocess.run(
        [sys.executable, "-c", SCRIPT, json.dumps([fault, [
            "--workload", CELL, "--seed", str(seed), "--seconds", "0.5",
            "--trace", "0"]])],
        cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_the_granite_cell_by_new_files_is_correct(checkout):
    res = _run(checkout, 3_170_000_011)
    assert res["correct"] is True, res["checks"]
    assert res["checks"]["inputs_mismatch"]["value"] == 0
    assert res["checks"]["param_gap"]["value"] < 1e-3, res["checks"]


def test_the_granite_cell_that_keeps_the_global_model_is_not_correct(
        checkout):
    res = _run(checkout, 3_170_000_013, fault="keep_global")
    assert res["correct"] is False
    chk = res["checks"]["param_gap"]
    assert chk["value"] > chk["limit"], res["checks"]
