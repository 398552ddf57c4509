"""Federated LM benchmark: the LoRA-adapter transformer workload on the
flat parameter plane, dispatched as ONE scanned program.

``--smoke`` is the per-PR CI gate. It:

  * runs the tinyllama smoke workload through ``CohortRunner`` with
    ``transfer_guard=True`` — the whole multi-round federated run is a
    SINGLE device dispatch of the same ``lax.scan`` traced program the CNN
    uses (any mid-run device→host sync raises instead of serializing);
  * asserts upload pricing scales with P_adapter, not P_base: the fleet's
    payload ``z`` must equal ``P_adapter * 32 / 1e6`` Mbit and sit far
    below a P_base-priced payload (the LoRA economics the subsystem
    exists for);
  * records tokens/sec to ``results/BENCH_lm.json``.

    PYTHONPATH=src:. python benchmarks/bench_lm.py [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

from benchmarks.common import emit
from repro.api import ExperimentSpec, build_cohort, build_experiment
from repro.models.lm import adapter_num_params, base_params
from repro.utils.trees import tree_num_params

CLIENTS = 12
ROUNDS = 6
LOCAL_ITERS = 4
BATCH = 4
DIALECTS = 4


def _spec(model: str = "tinyllama") -> ExperimentSpec:
    return ExperimentSpec(
        model=model, clients=CLIENTS, train_samples=CLIENTS * 16,
        test_samples=48, samples_per_client=16, sigma=0.8, rounds=ROUNDS,
        devices_per_round=DIALECTS, num_clusters=DIALECTS,
        local_iters=LOCAL_ITERS, batch_size=BATCH, learning_rate=0.1,
        selection="divergence", allocator="sao", seed=0, test_seed=92_000)


def run(out: str | None = None, model: str = "tinyllama") -> dict:
    spec = _spec(model)
    exp = build_experiment(spec)
    model_cfg = exp.model_cfg
    p_adapter = adapter_num_params(model_cfg)
    p_base = tree_num_params(base_params(model_cfg))
    seq_len = model_cfg.seq_len

    # ---- upload pricing: z rides P_adapter, never P_base --------------
    z = float(exp.fleet.z[0])
    z_adapter = p_adapter * 32 / 1e6
    z_base = p_base * 32 / 1e6
    assert np.allclose(exp.fleet.z, z_adapter), (
        f"fleet z={z} Mbit != P_adapter*32/1e6={z_adapter} Mbit")
    assert z < z_base / 10, (
        f"adapter payload {z} Mbit not well below base {z_base} Mbit")

    # ---- one transfer-guarded scanned dispatch ------------------------
    assert exp.traceable(), "LM strategy bundle must be fully traceable"
    runner = build_cohort(spec.replace(cohort=1))
    runner.run(transfer_guard=True)          # compile
    t0 = time.perf_counter()
    ch = runner.run(reuse_experiments=True, transfer_guard=True)
    wall = time.perf_counter() - t0
    # tokens processed by local training across the scanned run (the init
    # round trains ALL clients; each scan round trains the selected S)
    steps = (CLIENTS + ROUNDS * DIALECTS) * LOCAL_ITERS
    tokens = steps * BATCH * seq_len
    tok_per_sec = tokens / wall

    emit(f"lm/{model}_tokens_per_sec", 1e6 / max(tok_per_sec, 1e-9),
         f"{tok_per_sec:.0f}")
    emit(f"lm/{model}_z_mbit", 0.0, f"{z:.4f}")

    payload = {
        "benchmark": "federated_lm", "model": model, "clients": CLIENTS,
        "rounds": ROUNDS, "local_iters": LOCAL_ITERS, "batch": BATCH,
        "seq_len": seq_len,
        "environment": {"devices": len(jax.devices()),
                        "backend": jax.default_backend(),
                        "cpu_count": os.cpu_count()},
        "p_adapter": int(p_adapter), "p_base": int(p_base),
        "upload_z_mbit": round(z, 6),
        "upload_z_base_mbit": round(z_base, 3),
        "scanned_wall_s": round(wall, 3),
        "tokens_per_sec": round(tok_per_sec, 1),
        "final_accuracy": float(np.asarray(ch.accuracy)[0, -1]),
        "note": ("whole run = ONE transfer-guarded dispatch of the same "
                 "scanned round program as the CNN; per-client state is a "
                 "[P_adapter] LoRA row, the frozen base never enters the "
                 "plane or the uplink"),
    }
    out = out or os.path.join(os.path.dirname(__file__), "..", "results",
                              "BENCH_lm.json")
    with open(out, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    print(f"wrote {os.path.abspath(out)}")
    return payload


def smoke(out: str | None = None) -> bool:
    payload = run(out=out)
    ok = (payload["p_adapter"] * 20 < payload["p_base"]
          and payload["tokens_per_sec"] > 0
          and np.isfinite(payload["final_accuracy"]))
    print(f"lm smoke: P_adapter={payload['p_adapter']} vs "
          f"P_base={payload['p_base']} "
          f"(z={payload['upload_z_mbit']} Mbit, base would be "
          f"{payload['upload_z_base_mbit']} Mbit); "
          f"{payload['tokens_per_sec']:.0f} tok/s ... "
          f"{'ok' if ok else 'REGRESSION'}")
    return ok


if __name__ == "__main__":
    import sys

    from repro.utils.compile_cache import configure_compile_cache

    configure_compile_cache()

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: transfer-guarded single-dispatch LM run "
                         "+ P_adapter upload-pricing assertions")
    ap.add_argument("--model", default="tinyllama",
                    choices=["tinyllama", "mamba2-130m"])
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    if args.smoke:
        sys.exit(0 if smoke(out=args.out) else 1)
    run(out=args.out, model=args.model)
