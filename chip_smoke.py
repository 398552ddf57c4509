#!/usr/bin/env python3
"""Chip smoke test: the paper's FL round program, end to end, on a TPU.

    python chip_smoke.py              # one chip: the default fl_sim experiment
    python chip_smoke.py --chips 4    # four chips: cohort shard_map, p_shards

One chip (no arguments) runs ``ExperimentSpec()`` — MNIST, the Table II
CNN (113,744 parameters), N=40 clients, S=10 per round, divergence
selection + SAO allocation + FedAvg — for 3 rounds through
``repro.launch.fl_sim.run_spec`` (the scanned ``lax.scan`` route), then
checks on the chip:

* the round program holds the Mosaic kernels (``tpu_custom_call``:
  ``flat_aggregate`` and ``pairwise_l2``);
* the history is finite, and accuracy after round 3 beats the initial
  all-device round and chance;
* SAO (Alg. 5) on the run's fleet: the round-1 selection's solve reports
  ``converged`` exactly when an independent float64 bound says problem
  (19) is feasible, and on a feasible selection the solution keeps every
  energy budget and the band (paper claim 1);
* the plane kernels agree with their jnp references and with float64 at
  the run's own ``[40, 113744]`` shapes.

``--chips 4`` runs only the paths that exist across chips, each beside
what it is compared with: the cohort ``shard_map`` (8 seeds over 4 chips)
against the same lanes vmapped on one chip, and ``p_shards=4`` against
``p_shards=0``.

Exits non-zero, printing no result, without a TPU or outside a checkout
of the repository. Any failed check exits 1. The last stdout line is
``{"ok": true, "device": {...}}`` only when every check passed. Times and
memory printed on the way are information, not measurements of speed.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
ROUNDS = 3
SPEC_KW = {"rounds": ROUNDS}     # on top of the ExperimentSpec() defaults

# Tolerances, stated up front, relative to each result's magnitude scale
# (Σ|w·x| for the aggregate, ‖x‖²+‖c‖² for squared distances). f32
# epsilon is 1.19e-7; one bf16 MXU pass leaves 3e-4..4e-3 (measured on a
# v5e), which REL_F64 refuses. Two f32 implementations of a K-term sum
# differ by up to ~sqrt(K)·eps: 4e-5 at K = 113,744 columns.
REL_F64 = 1e-5          # plane kernel vs float64
REL_REF = 1e-4          # plane kernel vs its f32 jnp reference
E_TOL_J = 1e-4          # SAO: per-device energy over budget [J]
BAND_RTOL = 1e-4        # SAO: Σb over B, relative
T_RTOL = 1e-4           # cross-layout T_k / E_k agreement, relative
ACC_ATOL = 0.01         # cross-layout accuracy agreement (10 test samples)

FAILED: list = []


def check(name: str, ok: bool, detail: str = "") -> None:
    print(f"check {name}: {'ok' if ok else 'FAIL'}"
          + (f" ({detail})" if detail else ""), flush=True)
    if not ok:
        FAILED.append(name)


def info(name: str, value) -> None:
    print(f"info {name}: {value}", flush=True)


# ---------------------------------------------------------------------------
# capture of the scanned dispatch
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def captured_dispatch(module):
    """Patch ``module.run_rounds`` so every scanned program it hands out
    records its argument specs (taken before the carry is donated) and its
    result. Yields the list of records, one per dispatch."""
    import jax

    records = []
    real = module.run_rounds

    def spec_of(x):
        # an uncommitted array follows the computation; keep that freedom
        committed = getattr(x, "committed", False)
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=(
            x.sharding if committed else None))

    def spy(*a, **k):
        fn = real(*a, **k)

        def call(*args):
            rec = {"fn": fn, "kwargs": k,
                   "args": jax.tree_util.tree_map(spec_of, args)}
            rec["res"] = fn(*args)
            records.append(rec)
            return rec["res"]
        return call

    module.run_rounds = spy
    try:
        yield records
    finally:
        module.run_rounds = real


def custom_calls(lowered_text: str):
    """``(kernel_name, operand types)`` of every Mosaic call in a lowered
    module."""
    calls = []
    for line in lowered_text.splitlines():
        if "@tpu_custom_call" not in line:
            continue
        name = re.search(r'kernel_name = "([^"]*)"', line)
        sig = re.search(r":\s*\(([^)]*)\)\s*->", line)
        calls.append((name.group(1) if name else "?",
                      sig.group(1) if sig else "?"))
    return calls


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
# one chip
# ---------------------------------------------------------------------------


def history_checks(tag: str, hist) -> None:
    import numpy as np

    finite = all(np.all(np.isfinite(np.asarray(v, np.float64)))
                 for v in (hist.accuracy, hist.T_k, hist.E_k))
    check(f"{tag} history finite", finite)
    acc = hist.accuracy
    check(f"{tag} accuracy improves", acc[-1] > acc[0] and acc[-1] > 0.1,
          f"initial {acc[0]} -> round {len(acc) - 1} {acc[-1]}; chance 0.1")


def run_default(clock: CompileClock):
    """The default experiment through run_spec; returns (exp, hist)."""
    import jax
    import numpy as np
    import repro.core.fedavg as fedavg
    from repro.api import ExperimentSpec, build_experiment
    from repro.launch.fl_sim import run_spec

    spec = ExperimentSpec(**SPEC_KW)
    t0 = time.perf_counter()
    with captured_dispatch(fedavg) as recs, clock.measure():
        exp, hist, ari = run_spec(spec)
    first_s = time.perf_counter() - t0
    N, P = (int(d) for d in exp.client_params.shape)
    strategies = "+".join(ref["name"] if isinstance(ref, dict) else ref
                          for ref in (spec.selection, spec.allocator,
                                      spec.aggregator))
    info("experiment", f"{spec.dataset} CNN P={P}, N={N}, "
         f"S={spec.devices_per_round}, L={spec.local_iters}, "
         f"batch={spec.batch_size}, rounds={spec.rounds}, {strategies}")
    check("one scanned dispatch", len(recs) == 1, f"{len(recs)} dispatches")
    check("plane on chip", exp.client_params.devices()
          == {jax.devices()[0]}, str(exp.client_params.devices()))
    info("compile_s", round(clock.seconds, 3))
    info("first run_spec wall_s (build+compile+run)", round(first_s, 3))
    info("accuracy", hist.accuracy)
    info("T_k", hist.T_k)
    info("E_k", hist.E_k)
    info("clustering ARI", ari)

    lowered = recs[0]["fn"].lower(*recs[0]["args"])
    calls = custom_calls(lowered.as_text())
    names = sorted({n for n, _ in calls})
    info("mosaic calls in round program", f"{len(calls)}: {names}")
    check("round program holds the kernels",
          {"_flat_aggregate_kernel", "_pairwise_l2_kernel"} <= set(names),
          str(names))
    mem = lowered.compile().memory_analysis()
    info("round program memory_analysis bytes (arguments, outputs, temp)",
         (mem.argument_size_in_bytes, mem.output_size_in_bytes,
          mem.temp_size_in_bytes))
    history_checks("run", hist)

    # warm timing: same program from the in-process executable cache
    exp2 = build_experiment(spec)
    t0 = time.perf_counter()
    hist2 = exp2.run(rounds=spec.rounds)
    warm_s = time.perf_counter() - t0
    info("warm run wall_s per round (1 dispatch, initial round + "
         f"{spec.rounds})", round(warm_s / (spec.rounds + 1), 4))
    info("warm run repeats the history bitwise",
         hist2.accuracy == hist.accuracy and hist2.T_k == hist.T_k)
    return exp, hist


def feasible_band(arr, B: float):
    """Independent float64 feasibility of problem (19) for one selection.

    At f = f_min a device spends the least computation energy, so its
    upload needs at least ``b_min`` with ``H / Q(b_min) = e_cons − G·f_min²``
    (Q increasing, Lemma 2). (19) is feasible iff Σ b_min ≤ B. Returns
    (feasible, Σ b_min, per-device b_min)."""
    import numpy as np

    a = {k: np.asarray(v, np.float64) for k, v in arr.items()}
    J = a["J"] / (1.0 + a["inr"])
    resid = a["e_cons"] - a["G"] * a["f_min"] ** 2
    target = a["H"] / np.where(resid > 0, resid, np.nan)
    reachable = (resid > 0) & (target < J / np.log(2.0))
    lo, hi = np.full(J.shape, 1e-12), np.full(J.shape, 1e6)
    for _ in range(200):
        mid = 0.5 * (lo + hi)
        ge = mid * np.log2(1.0 + J / mid) >= target
        lo, hi = np.where(ge, lo, mid), np.where(ge, mid, hi)
    b_min = np.where(reachable, hi, np.inf)
    return bool(b_min.sum() <= B), float(b_min.sum()), b_min


def sao_checks(exp, hist) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.sao import kkt_residuals, solve_sao
    from repro.core.wireless import fleet_arrays

    B = float(exp.B)
    fleet_all = fleet_arrays(exp.fleet)
    _, _, b_min_all = feasible_band(fleet_all, B)
    S = int(exp.fl.devices_per_round)

    def solve(sel):
        arr = fleet_arrays(exp.fleet.select(np.asarray(sel)))
        sol = solve_sao(arr, B)
        return arr, sol, kkt_residuals(sol, arr, B)

    sel1 = np.sort(np.asarray(hist.selected[1]))
    arr, sol, _ = solve(sel1)
    feasible, need, _ = feasible_band(arr, B)
    check("SAO ran on the chip", sol.T.devices() == {jax.devices()[0]})
    check("SAO round-1 converged iff (19) feasible",
          bool(sol.converged) == feasible,
          f"selection {sel1.tolist()}: converged={bool(sol.converged)}, "
          f"Σb/B={float(sol.ratio):.6f}; float64 bound Σb_min={need:.6f} "
          f"MHz vs B={B} MHz")

    # claim (1) on a feasible selection: the first feasible round of the
    # run, else the S devices that need the least band
    cands = [(f"round {k}", np.sort(np.asarray(hist.selected[k])))
             for k in range(1, len(hist.selected))]
    cands.append(("least-band S", np.sort(np.argsort(b_min_all)[:S])))
    for tag, sel in cands:
        arr, sol, r = solve(sel)
        if feasible_band(arr, B)[0]:
            break
    over_e = float(jnp.max(-r["energy_slack"]))
    band = float(jnp.sum(sol.b))
    check("SAO claim (1) converged", bool(sol.converged),
          f"{tag} selection {sel.tolist()}")
    check("SAO claim (1) energy budgets kept", over_e <= E_TOL_J,
          f"max(e_n - e_cons_n) = {over_e:.3e} J <= {E_TOL_J} J")
    check("SAO claim (1) band kept", band <= B * (1.0 + BAND_RTOL),
          f"Σb = {band:.6f} MHz <= B = {B} MHz (rtol {BAND_RTOL})")
    info("SAO claim (1) T*_s", float(sol.T))


def kernel_checks(exp, hist) -> None:
    """Plane kernels vs their jnp references and float64, on the chip, at
    the run's own shapes (the trained [N, P] client plane)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops
    from repro.utils.trees import tree_flatten_vector

    flat = exp.client_params
    gvec = tree_flatten_vector(exp.global_params)
    N = flat.shape[0]
    mask = jnp.zeros((N,), bool).at[jnp.asarray(hist.selected[1])].set(True)
    sizes = exp._sizes
    labels = np.asarray(exp.cluster_labels)
    K = int(exp.fl.num_clusters)
    x64 = np.asarray(flat, np.float64)
    g64 = np.asarray(gvec, np.float64)
    cents64 = np.stack([x64[labels == k].mean(0) if np.any(labels == k)
                        else x64[k] for k in range(K)])
    cents = jnp.asarray(cents64, jnp.float32)
    c64 = np.asarray(cents, np.float64)

    def report(name, got, ref, f64, scale):
        got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
        e_ref = float(np.max(np.abs(got - ref) / scale))
        e_64 = float(np.max(np.abs(got - f64) / scale))
        check(f"kernel {name} vs jnp reference", e_ref <= REL_REF,
              f"max|k-ref|/scale = {e_ref:.3e} <= {REL_REF}")
        check(f"kernel {name} vs float64", e_64 <= REL_F64,
              f"max|k-f64|/scale = {e_64:.3e} <= {REL_F64}")
        info(f"jnp reference {name} vs float64, max|ref-f64|/scale",
             f"{float(np.max(np.abs(ref - f64) / scale)):.3e}")

    info("kernel operand shapes", f"plane {tuple(flat.shape)}, "
         f"centroids {tuple(cents.shape)}")
    # flat_aggregate: eq.-(4) weighted mean over the round-1 selection
    k = ops.flat_aggregate(flat, sizes, mask=mask, use_pallas=True)
    r = ops.flat_aggregate(flat, sizes, mask=mask, use_pallas=False)
    w = np.where(np.asarray(mask), np.asarray(sizes, np.float64), 0.0)
    w = w / w.sum()
    report("flat_aggregate", k, r, w @ x64, np.abs(w) @ np.abs(x64) + 1e-30)

    # client_divergence (squared): scale ‖x_n‖² + ‖g‖²
    xx = np.sum(x64 ** 2, 1)
    k = ops.client_divergence(flat, gvec, use_pallas=True) ** 2
    r = ops.client_divergence(flat, gvec, use_pallas=False) ** 2
    report("client_divergence^2", k, r, np.sum((x64 - g64) ** 2, 1),
           xx + np.sum(g64 ** 2))

    # pairwise_sq_dists at the K-means shape: [N, P] rows x [K, P] cents
    scale = xx[:, None] + np.sum(c64 ** 2, 1)[None, :]
    d64 = np.sum((x64[:, None, :] - c64[None, :, :]) ** 2, -1)
    k = ops.pairwise_sq_dists(flat, cents, use_pallas=True)
    with jax.default_matmul_precision("highest"):
        r = ops.pairwise_sq_dists(flat, cents, use_pallas=False)
    report("pairwise_sq_dists", k, r, d64, scale)
    r_def = np.asarray(ops.pairwise_sq_dists(flat, cents, use_pallas=False),
                       np.float64)
    info("jnp pairwise reference at XLA's default matmul precision, "
         "max|ref-f64|/scale", f"{np.max(np.abs(r_def - d64) / scale):.3e}")


def one_chip() -> None:
    import jax

    clock = CompileClock()
    exp, hist = run_default(clock)
    sao_checks(exp, hist)
    kernel_checks(exp, hist)
    stats = jax.devices()[0].memory_stats() or {}
    info("peak_bytes_in_use", stats.get("peak_bytes_in_use", "not reported"))


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def lane_view(hist_like):
    """(selections as sorted tuples per round, T, E, accuracy) of a
    history-shaped object."""
    import numpy as np

    sels = [tuple(sorted(np.asarray(s).tolist())) for s in hist_like.selected]
    return (sels, np.asarray(hist_like.T_k), np.asarray(hist_like.E_k),
            np.asarray(hist_like.accuracy))


def compare(tag: str, a, b, *, may_part: bool = False) -> None:
    """Two runs of one experiment in different layouts. T_k, E_k and
    accuracy must agree on every round up to the first whose selection
    differs. ``may_part``: a selection that parts is reported, not failed
    (past it the runs train different clients and legitimately differ)."""
    import numpy as np

    sa, Ta, Ea, Aa = a
    sb, Tb, Eb, Ab = b
    parted = next((k for k, (x, y) in enumerate(zip(sa, sb)) if x != y),
                  None)
    what = ("the same in every round" if parted is None else
            f"first part at round {parted}: {sa[parted]} vs {sb[parted]}")
    if may_part:
        info(f"{tag} selections", what)
    else:
        check(f"{tag} selections match", parted is None, what)
    upto = len(sa) if parted is None else parted

    def diffs(lo, hi):
        sl = slice(lo, hi)
        return (float(np.max(np.abs(Ta[sl] - Tb[sl]) / np.abs(Tb[sl]))),
                float(np.max(np.abs(Ea[sl] - Eb[sl]) / np.abs(Eb[sl]))),
                float(np.max(np.abs(Aa[sl] - Ab[sl]))))

    dT, dE, dA = diffs(0, upto)
    span = f"rounds 0..{upto - 1}"
    check(f"{tag} T_k agree", dT <= T_RTOL, f"{span}: max rel {dT:.3e}")
    check(f"{tag} E_k agree", dE <= T_RTOL, f"{span}: max rel {dE:.3e}")
    check(f"{tag} accuracy agree", dA <= ACC_ATOL,
          f"{span}: max abs {dA:.4f}")
    if parted is not None:
        dT, dE, dA = diffs(parted, len(sa))
        info(f"{tag} from round {parted} on",
             f"max rel T {dT:.3e}, max rel E {dE:.3e}, max abs accuracy "
             f"{dA:.4f}")


def cohort_phase(devs) -> None:
    import numpy as np
    import repro.core.cohort as cohort
    from repro.api import ExperimentSpec, build_cohort

    lanes = 8
    spec = ExperimentSpec(**SPEC_KW, cohort=lanes)
    with captured_dispatch(cohort) as recs:
        ch = build_cohort(spec).run()
    res = recs[0]["res"]
    mesh = recs[0]["kwargs"]["mesh"]
    check("cohort mesh spans 4 chips",
          mesh is not None and mesh.devices.size == 4,
          "no mesh" if mesh is None else str(mesh.devices.size))
    for name, arr in (("history", res.rounds.accuracy),
                      ("client plane", res.state.client_params)):
        held = sorted((s.device.id, s.index[0].start, s.index[0].stop)
                      for s in arr.addressable_shards)
        per_dev = {d: (lo, hi) for d, lo, hi in held}
        ok = (len(per_dev) == 4 and len(held) == 4
              and all(hi - lo == lanes // 4 for lo, hi in per_dev.values())
              and sorted(lo for lo, _ in per_dev.values())
              == list(range(0, lanes, lanes // 4)))
        check(f"cohort {name} lanes split 2 per chip", ok,
              f"shape {tuple(arr.shape)}: (device, lanes) "
              f"{[(d, f'{lo}:{hi}') for d, lo, hi in held]}")

    # the same 8 lanes vmapped on one chip, as four 2-lane vmapped
    # dispatches — each the very program one chip of the sharded run
    # executes
    real_mesh = cohort.cohort_mesh
    cohort.cohort_mesh = lambda n: None
    ref = []
    try:
        with captured_dispatch(cohort) as rrecs:
            for g in range(0, lanes, 2):
                ref.append(build_cohort(spec.replace(seed=spec.seed + g,
                                                     cohort=2)).run())
    finally:
        cohort.cohort_mesh = real_mesh
    on_one = {d.id for r in rrecs for d in r["res"].rounds.accuracy.devices()}
    check("reference lanes on one chip", on_one == {devs[0].id},
          str(sorted(on_one)))
    for lane in range(lanes):
        g, i = divmod(lane, 2)
        compare(f"cohort lane {lane} (seed {ch.seeds[lane]})",
                lane_view(ch.history(lane)), lane_view(ref[g].history(i)))
    info("cohort final accuracy", np.round(ch.final_accuracy, 4).tolist())


def p_shards_phase() -> None:
    import jax
    import repro.core.fedavg as fedavg
    from repro.api import ExperimentSpec
    from repro.launch.fl_sim import run_spec
    from repro.sharding.specs import plane_mesh

    spec = ExperimentSpec(**SPEC_KW, p_shards=4)
    with captured_dispatch(fedavg) as recs:
        exp4, h4, _ = run_spec(spec)
    _, h0, _ = run_spec(spec.replace(p_shards=0))
    history_checks("p_shards=4", h4)
    for tag, h in (("p_shards=4", h4), ("p_shards=0", h0)):
        info(f"{tag} accuracy / T_k", f"{h.accuracy} / {h.T_k}")
    # the sharded layout changes how XLA rounds the local-training convs
    # (f32 at the TPU's default one-pass bf16 precision), so a near-tie in
    # a cluster's divergence ranking may flip: report where the runs part
    compare("p_shards=4 vs 0", lane_view(h4), lane_view(h0), may_part=True)
    P = int(exp4.client_params.shape[1])
    check("p_shards=4 plane split over 4 chips",
          len(exp4.client_params.addressable_shards) == 4
          and {tuple(s.data.shape) for s in
               exp4.client_params.addressable_shards}
          == {(exp4.client_params.shape[0], P // 4)},
          str({tuple(s.data.shape)
               for s in exp4.client_params.addressable_shards}))

    with jax.set_mesh(plane_mesh(4)):
        lowered = recs[0]["fn"].lower(*recs[0]["args"])
    calls = custom_calls(lowered.as_text())
    for name, sig in calls:
        info("p_shards=4 mosaic call", f"{name}: ({sig})")
    plane_calls = [(n, s) for n, s in calls
                   if n in ("_flat_aggregate_kernel", "_pairwise_l2_kernel")
                   and re.search(rf"x{P // 4 + (-(P // 4)) % 512}xf32", s)]
    check("p_shards=4 plane kernels run on column shards",
          any(n == "_flat_aggregate_kernel" for n, _ in plane_calls)
          and any(n == "_pairwise_l2_kernel" for n, _ in plane_calls),
          f"{len(plane_calls)} calls on [*, {P // 4}] shards (padded to "
          "the 512-column block)")
    text = lowered.compile().as_text()
    gathers = [ln.strip()[:160] for ln in text.splitlines()
               if re.search(r"all-gather(-start)?\(", ln)
               and re.search(rf"f32\[\d+,{P}\]", ln)]
    check("p_shards=4 no all-gather of the [N, P] plane",
          not gathers, f"{len(gathers)} found" + (f": {gathers[0]}"
                                                 if gathers else ""))
    counts = {op: len(re.findall(rf"\b{op}(?:-start)?\(", text))
              for op in ("all-gather", "all-reduce", "reduce-scatter",
                         "all-to-all", "collective-permute")}
    info("p_shards=4 collectives in the compiled program", counts)


def four_chips() -> None:
    import jax

    devs = jax.devices()
    if len(devs) < 4:
        check("four chips present", False, f"{len(devs)} devices")
        return
    cohort_phase(devs)
    p_shards_phase()


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the cross-chip paths (cohort "
                         "shard_map, p_shards) against their one-chip "
                         "counterparts")
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}; run it from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from repro.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devs[0].platform!r}); refusing to run on it",
              file=sys.stderr)
        return 2
    info("device", f"{devs[0].device_kind} x{len(devs)}")
    info("jax", jax.__version__)

    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    if FAILED:
        print(f"chip_smoke: {len(FAILED)} check(s) failed: {FAILED}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
