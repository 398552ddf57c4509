"""Fault-tolerance CI gate: injection accounting, kill/--resume, and
robust aggregation under a byzantine cohort.

Three structural gates, none timing-based:

  * **accounting** — a scanned run under ``outage:0.2,corrupt:0.1`` must
    stay finite end to end and charge the O(N) fault counters at the
    configured rate (a binomial-tolerance window around rate·S·rounds);
    an injector that silently stops firing, or fires on padding lanes,
    moves the total out of the window.
  * **kill_resume** — the acceptance run: ``fl_sim`` on the hardest
    route (paged store + fedbuff + churn + ``outage:0.1``), SIGKILLed
    mid-run after its first checkpoint commits, then ``--resume``d in a
    FRESH interpreter. The stitched history must equal the uninterrupted
    run's bit for bit — which exercises atomic snapshots, the LATEST
    pointer, and cross-process dataset determinism all at once.
  * **byzantine** — 10% of the fleet negates-and-amplifies (×50). Plain
    eq. (4) must visibly degrade below its own fault-free run;
    ``trimmed:0.2`` must hold the final accuracy within 2 points of ITS
    fault-free run (same estimator — the trim bias is not the attack).

Writes ``results/BENCH_faults.json`` (uploaded as a CI artifact);
``--smoke`` is the per-PR gate with a NON-ZERO EXIT on failure.

    PYTHONPATH=src:. python benchmarks/bench_faults.py [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

import numpy as np

from benchmarks.common import emit, fl_spec
from repro.api import build_experiment

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


# ---------------------------------------------------------------------------
# gate 1: fault accounting on the scanned route
# ---------------------------------------------------------------------------

ACC_ROUNDS = 8
ACC_RATE = 1.0 - (1.0 - 0.2) * (1.0 - 0.1)   # P(drop or corrupt) per lane


def _accounting() -> dict:
    spec = fl_spec(clients=10, rounds=ACC_ROUNDS, samples_per_client=16,
                   train_samples=400, test_samples=100, local_iters=2,
                   batch_size=8, devices_per_round=10,
                   selection="divergence",
                   faults="outage:0.2,corrupt:0.1", quarantine_after=3)
    exp = build_experiment(spec)
    hist = exp.run(rounds=ACC_ROUNDS)
    total = float(exp.stats.faults.sum())
    # expectation from the ACTUAL dispatch counts (the initial clustering
    # round is fault-free by design, so it is excluded)
    lanes = sum(len(np.asarray(s)) for s in hist.selected[1:])
    mean = ACC_RATE * lanes
    sd = (mean * (1.0 - ACC_RATE)) ** 0.5
    lo, hi = mean - 4 * sd, mean + 4 * sd
    finite = bool(np.all(np.isfinite(np.asarray(hist.accuracy))))
    return {
        "fault_events": total,
        "expected_mean": round(mean, 2),
        "window": [round(lo, 2), round(hi, 2)],
        "history_finite": finite,
        "in_window": bool(lo <= total <= hi),
        "accounting_ok": bool(finite and lo <= total <= hi),
    }


# ---------------------------------------------------------------------------
# gate 2: mid-run SIGKILL + --resume, bit-identical
# ---------------------------------------------------------------------------

_SIM = ["--dataset", "fashion", "--clients", "10", "--per-round", "4",
        "--rounds", "6", "--local-iters", "2", "--selection", "divergence",
        "--store", "paged", "--async-buffer", "2", "--churn", "0.05:0.1",
        "--faults", "outage:0.1", "--checkpoint-every", "2"]


def _sim(extra, out):
    env = {**os.environ, "PYTHONPATH": "src"}
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.fl_sim", *extra, "--out", out],
        cwd=ROOT, env=env, capture_output=True, text=True)


def _kill_resume(tmp: str) -> dict:
    full_out = os.path.join(tmp, "full.jsonl")
    res_out = os.path.join(tmp, "resumed.jsonl")
    ck_full = os.path.join(tmp, "ck_full")
    ck_kill = os.path.join(tmp, "ck_kill")

    r = _sim([*_SIM, "--checkpoint-dir", ck_full], full_out)
    if r.returncode != 0:
        return {"resume_ok": False, "error": r.stderr[-800:]}

    # the killed run: SIGKILL as soon as the first snapshot COMMITS
    env = {**os.environ, "PYTHONPATH": "src"}
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.fl_sim", *_SIM,
         "--checkpoint-dir", ck_kill],
        cwd=ROOT, env=env, stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL)
    deadline = time.time() + 300
    while (proc.poll() is None and time.time() < deadline
           and not os.path.exists(os.path.join(ck_kill, "LATEST"))):
        time.sleep(0.2)
    killed = proc.poll() is None
    if killed:
        proc.send_signal(signal.SIGKILL)
    proc.wait()

    r = _sim(["--resume", ck_kill], res_out)
    if r.returncode != 0:
        return {"resume_ok": False, "killed_mid_run": killed,
                "error": r.stderr[-800:]}

    with open(full_out) as f:
        full = json.loads(f.read().splitlines()[-1])
    with open(res_out) as f:
        res = json.loads(f.read().splitlines()[-1])
    bitwise = (full["accuracy"] == res["accuracy"]
               and full["total_T_s"] == res["total_T_s"]
               and full["total_E_J"] == res["total_E_J"])
    return {
        "killed_mid_run": killed,
        "accuracy_full": [round(a, 4) for a in full["accuracy"]],
        "accuracy_resumed": [round(a, 4) for a in res["accuracy"]],
        "bitwise_identical": bool(bitwise),
        "resume_ok": bool(bitwise),
    }


# ---------------------------------------------------------------------------
# gate 3: byzantine cohort vs trimmed-mean defense
# ---------------------------------------------------------------------------

BYZ_ROUNDS = 8
# seed:5 puts exactly ONE of the 10 clients (10%) in the adversarial set
BYZ = "byzantine:0.1,byz_scale:50,seed:5"
TOL_POINTS = 0.02                   # "within 2 points of fault-free"
DEGRADE_POINTS = 0.05


def _final_acc(hist) -> float:
    return float(np.mean(hist.accuracy[-3:]))


def _byzantine(rounds: int = BYZ_ROUNDS) -> dict:
    """Each aggregator against its OWN fault-free run: the trimmed mean
    trades convergence speed for robustness (it discards 2·⌊f·k⌋ updates
    per coordinate even when none are adversarial), so the attack's
    effect is isolated by holding the estimator fixed."""
    # the default 10 clusters select ~10 clients a round: ⌊0.2·k⌋ >= 1,
    # so the single adversary actually lands in the trimmed tail (with a
    # 4-client selection t would be 0 and NOTHING would be trimmed)
    base = dict(clients=10, rounds=rounds, devices_per_round=10,
                selection="divergence")

    def acc(**kw):
        return _final_acc(build_experiment(fl_spec(**base, **kw)).run(
            rounds=rounds))

    a_plain = acc()
    a_plain_byz = acc(faults=BYZ)
    a_trim = acc(aggregator="trimmed:0.2")
    a_trim_byz = acc(faults=BYZ, aggregator="trimmed:0.2")
    return {
        "acc_fedavg_fault_free": round(a_plain, 4),
        "acc_fedavg_byzantine": round(a_plain_byz, 4),
        "acc_trimmed_fault_free": round(a_trim, 4),
        "acc_trimmed_byzantine": round(a_trim_byz, 4),
        "plain_degrades": bool(a_plain_byz <= a_plain - DEGRADE_POINTS),
        "trimmed_within_tol": bool(a_trim_byz >= a_trim - TOL_POINTS),
        "byzantine_ok": bool(a_plain_byz <= a_plain - DEGRADE_POINTS
                             and a_trim_byz >= a_trim - TOL_POINTS),
    }


# ---------------------------------------------------------------------------


def run(out: str | None = None) -> dict:
    import jax

    t0 = time.perf_counter()
    # the fl_sim children run FIRST: an accelerator belongs to one process
    # at a time, so this parent must not initialise a backend (which
    # _accounting and _byzantine do) until the children have exited
    with tempfile.TemporaryDirectory() as tmp:
        kr = _kill_resume(tmp)
    emit("faults/kill_resume", 0.0, str(kr.get("bitwise_identical")))
    acc = _accounting()
    emit("faults/accounting", 0.0,
         f"{acc['fault_events']:.0f} in {acc['window']}")
    byz = _byzantine()
    emit("faults/byzantine", 0.0,
         f"fedavg={byz['acc_fedavg_fault_free']}->"
         f"{byz['acc_fedavg_byzantine']} "
         f"trimmed={byz['acc_trimmed_fault_free']}->"
         f"{byz['acc_trimmed_byzantine']}")

    payload = {
        "benchmark": "faults",
        "environment": {"devices": len(jax.devices()),
                        "backend": jax.default_backend(),
                        "cpu_count": os.cpu_count()},
        "accounting": acc,
        "kill_resume": kr,
        "byzantine": byz,
        "wall_s": round(time.perf_counter() - t0, 1),
    }
    out = out or os.path.join(ROOT, "results", "BENCH_faults.json")
    with open(out, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    print(f"wrote {os.path.abspath(out)}")
    return payload


def smoke(out: str | None = None) -> bool:
    payload = run(out=out)
    ok = True
    for section, key in (("accounting", "accounting_ok"),
                         ("kill_resume", "resume_ok"),
                         ("byzantine", "byzantine_ok")):
        val = payload[section].get(key, False)
        print(f"smoke {section}.{key}: {val} ... "
              f"{'ok' if val else 'FAIL'}")
        ok &= bool(val)
    print(json.dumps(payload, indent=1))
    return ok


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    if args.smoke:
        sys.exit(0 if smoke(out=args.out) else 1)
    run(out=args.out)


if __name__ == "__main__":
    from repro.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    main()
