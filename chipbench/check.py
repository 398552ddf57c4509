"""The comparison that decides ``correct``: one experiment of the window,
drawn from the run's seed, against the plain reference.

Numbers (a cell compares those that ``limits/<cell>.json`` gives a limit,
in ``ORDER``, and ``ALWAYS`` in every cell; ``readings.py`` records them
all):

``inputs_mismatch``
    Elements of the experiment's inputs (client samples, labels and
    sizes, test set, fleet arrays, the frozen base where the model has
    one, model widths and FL settings) that differ from what ``generate``
    and the reference's ``frozen`` say the seed and the configuration
    give. Exact: 0.
``param_gap``
    The reference replays the experiment on the run's own choices
    (selections, lane layout, clusters). By the worst leaf, over the final
    global model and every client's final model: the largest ``|w_run -
    w_ref| / |w_ref - w_init|`` of one leaf (see ``leaf_gaps``). Covers
    local training (forward, backward, SGD), eq.-(4) aggregation and the
    scatter to the plane.
``acc_mean_gap``
    Mean gap between the run's test accuracy and the reference's, over
    the initial round and every round (a steady number: the largest gap
    of one round swings from seed to seed, PERF.md section 2).
``select_gap``
    Divergence selection judged by the reference's divergences: 1 where
    a round's lanes break the layout (any round); else, over the first
    ``SELECT_ROUNDS`` rounds and every cluster, the largest shortfall of
    the chosen client's divergence below the cluster's largest, as a share
    of that largest. A near-tie that a last-bit difference decides reads
    near 0.
``kmeans_gap``
    The run's clusters judged as a K-means (Lloyd) solution on the
    reference's features (the model's ``features`` after the initial
    round):
    over clients, the largest excess of the squared distance to the mean
    of its own cluster over that to the nearest cluster mean, as a share
    of the former. 0 at a Lloyd fixed point, whichever seeding reached it.
``T_gap``, ``E_gap``
    The round latency T_k and energy E_k of the run against the float64
    allocation reference on the same selection: the largest relative gap
    over rounds (claim 1's allocation).
``window_compile_s``
    Seconds of backend compilation inside the timed window. Exact: 0.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import generate
from chipbench.reference import fl

#: the numbers a cell may compare, in the order they print. Which ones a
#: cell compares is its limits file's to say: a number whose two readings
#: no limit can separate in that cell has none there (``mnist.paper``
#: compares neither ``param_gap`` nor ``kmeans_gap``: on the chip no
#: control or fault reads them far enough above sound runs, PERF.md
#: section 2)
ORDER = ("inputs_mismatch", "param_gap", "acc_mean_gap", "select_gap",
         "kmeans_gap", "T_gap", "E_gap", "window_compile_s")

#: compared in every cell, exactly
ALWAYS = ("inputs_mismatch", "window_compile_s")

#: the rounds after the initial one whose picks ``select_gap`` judges by
#: the reference's divergences: later, the summation order's last bits
#: carry the reference's weights, and so its divergences, too far from the
#: run's for a shortfall to tell a fault (PERF.md section 2)
SELECT_ROUNDS = 1

#: a frozen leaf of more elements than this is compared by an exact digest
#: taken on the device, not copied to the host (a published LoRA base
#: holds gigabytes)
DIGEST_MIN = 1 << 26


def _mismatch(a, b) -> int:
    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape:
        return max(a.size, b.size, 1)
    return int(np.sum(a != b))


def lanes_from_selection(selected, labels, c: int, N: int):
    """The round's lane layout (s = 1: lane j holds cluster j's pick, N for
    an empty lane) from the chosen clients and the run's clusters. A pick
    that repeats a cluster lands in no lane and shows in ``select_gap``."""
    out = np.full((len(selected), c), N, np.int64)
    bad = np.zeros(len(selected), bool)
    for k, sel in enumerate(selected):
        for n in np.asarray(sel):
            j = int(labels[n])
            if out[k, j] != N:
                bad[k] = True
            out[k, j] = n
    return out, bad


@jax.jit
def _digest(x):
    """Two wrapping uint32 sums over the leaf's bits, each element
    weighted by its position: equal arrays give equal digests on any
    summation order, and a changed element or a moved one changes them."""
    if jnp.issubdtype(x.dtype, jnp.floating):
        x = jax.lax.bitcast_convert_type(
            x, jnp.dtype(f"uint{8 * x.dtype.itemsize}"))
    bits = x.reshape(-1).astype(jnp.uint32)
    i = jnp.arange(bits.size, dtype=jnp.uint32)
    return jnp.stack([jnp.sum(bits * (i * np.uint32(2654435761) + 1)),
                      jnp.sum(bits ^ (i * np.uint32(40503) + 7))])


def frozen_view(tree) -> dict:
    """A frozen tree by leaf path: small leaves as host arrays, large ones
    (over ``DIGEST_MIN`` elements) as ``(shape, dtype, digest)``; None
    where the model has no frozen tree."""
    if tree is None:
        return None
    out = {}
    for name, leaf in fl.paths(tree):
        if leaf.size > DIGEST_MIN:
            out[name] = (tuple(leaf.shape), str(leaf.dtype),
                         tuple(int(d) for d in np.asarray(_digest(leaf))))
        else:
            out[name] = np.asarray(leaf)
    return out


def _frozen_mismatch(run: dict, want: dict) -> int:
    """Elements of the frozen tree that differ; a leaf compared by digest,
    or found on one side only, counts whole."""
    if run is None or want is None:
        return 0 if run is None and want is None else 1
    count = 0
    for name in set(run) | set(want):
        a, b = run.get(name), want.get(name)
        if isinstance(a, np.ndarray) and isinstance(b, np.ndarray):
            count += _mismatch(a, b)
        elif not (isinstance(a, tuple) and a == b):
            v = a if a is not None else b
            count += max(int(np.prod(v[0])) if isinstance(v, tuple)
                         else v.size, 1)
    return count


def width(model_cfg: dict, dotted: str):
    """The configuration's value of a dotted width (``model.d_model``)."""
    v = model_cfg
    for part in dotted.split("."):
        v = v[part]
    return v


def input_mismatches(prog: dict, gen: dict, model_cfg: dict, spec: dict,
                     frozen: dict = None) -> dict:
    """Per input, elements that differ from the generator's; ``frozen`` is
    the reference's frozen tree in ``frozen_view`` form."""
    pin = prog["inputs"]
    counts = {
        "x": _mismatch(pin["x"], gen["x"]),
        "y": _mismatch(pin["y"], gen["y"]),
        "sizes": _mismatch(pin["sizes"], gen["sizes"]),
        "test_x": _mismatch(pin["test_x"], gen["test_x"]),
        "test_y": _mismatch(pin["test_y"], gen["test_y"]),
        "fleet": sum(_mismatch(pin["fleet"][k],
                               np.asarray(gen["fleet"][k], np.float32))
                     for k in gen["fleet"]),
        "frozen": _frozen_mismatch(pin["frozen"], frozen),
        "widths": sum(int(pin["widths"].get(k) != width(model_cfg, k))
                      for k in model_cfg["widths"]),
        "settings": sum(int(pin["settings"][k] != spec[k])
                        for k in pin["settings"]),
    }
    return counts


def leaf_gaps(prog: dict, ref: dict) -> dict:
    """Per leaf (by path, ``fl.leaves_by_path``), the largest relative gap
    over the global model and every client row: ``|w_run - w_ref|`` over the
    reference's move of that leaf from the initial weights, or over the
    median leaf's move in the same row where that is larger (a leaf that
    barely moves is judged on the row's scale, not on its own)."""
    names = sorted(ref["init"])

    def rows(tree, n, stacked):
        a = np.asarray(tree[n], np.float64)
        return a.reshape(a.shape[0], -1) if stacked else a.reshape(1, -1)

    diff, moved = {}, {}
    for n in names:
        run = np.concatenate([rows(prog["global"], n, False),
                              rows(prog["clients"], n, True)])
        want = np.concatenate([rows(ref["global"], n, False),
                               rows(ref["clients"], n, True)])
        if run.shape != want.shape:
            return {n: float("inf") for n in names}
        moved[n] = np.linalg.norm(want - rows(ref["init"], n, False), axis=1)
        diff[n] = np.linalg.norm(run - want, axis=1)
    median = np.median(np.stack([moved[n] for n in names]), axis=0)
    return {n: float(np.max(diff[n] / np.maximum(
        np.maximum(moved[n], median), 1e-30))) for n in names}


def select_shortfalls(prog: dict, ref: dict, N: int):
    """Per round, the largest shortfall of a chosen client's reference
    divergence below its cluster's largest, as a share of that largest;
    None where a lane breaks the layout (a cluster picked twice or not at
    all, a pick outside its cluster, a pick in an empty cluster)."""
    labels = np.asarray(prog["labels"])
    out = []
    for k, lanes in enumerate(np.asarray(prog["lanes"])):
        if prog["lanes_bad"][k]:
            return None
        d = ref["divergences"][k]
        worst = 0.0
        for j, n in enumerate(lanes):
            members = np.flatnonzero(labels == j)
            if not members.size:
                if n != N:
                    return None
                continue
            top = float(np.max(d[members]))
            if n == N or labels[n] != j:
                return None
            worst = max(worst, (top - float(d[n])) / max(top, 1e-30))
        out.append(worst)
    return np.asarray(out)


def select_gap(shortfalls) -> float:
    """1 where the layout breaks in any round; else the largest shortfall
    over the first ``SELECT_ROUNDS`` rounds."""
    if shortfalls is None:
        return 1.0
    return float(np.max(shortfalls[:SELECT_ROUNDS], initial=0.0))


def kmeans_gap(prog: dict, ref: dict) -> float:
    x = np.asarray(ref["features"], np.float64)
    labels = np.asarray(prog["labels"])
    ids = np.unique(labels)
    means = np.stack([x[labels == j].mean(0) for j in ids])
    d = np.sum((x[:, None, :] - means[None]) ** 2, -1)
    own = d[np.arange(len(x)), np.searchsorted(ids, labels)]
    return float(np.max((own - d.min(1)) / np.maximum(own, 1e-30)))


def rel_gap(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if a.shape != b.shape:
        return float("inf")
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))


def numbers(prog: dict, model_cfg: dict, spec: dict, *,
            reference: fl.Experiment = None, gen: dict = None) -> dict:
    """Every compared number for one experiment's outputs ``prog`` (see
    ``run.program_outputs``). Builds the inputs from the seed and runs
    the reference unless given."""
    N = spec["clients"]
    if gen is None:
        gen = generate.experiment(prog["seed"], spec, model_cfg)
    if reference is None:
        reference = fl.Experiment(model_cfg, spec)
    mism = (input_mismatches(prog, gen, model_cfg, spec,
                             frozen_view(reference.frozen))
            if "inputs" in prog else {})
    ref = reference.run(gen, forced={"lanes": prog["lanes"],
                                     "labels": prog["labels"]})
    acc = np.asarray(prog["accuracy"], np.float64)
    acc_diff = (acc - ref["accuracy"]
                if acc.shape == ref["accuracy"].shape else None)
    leaves = leaf_gaps(prog, ref)
    short = select_shortfalls(prog, ref, N)
    out = {
        "inputs_mismatch": float(sum(mism.values())),
        "param_gap": max(leaves.values()),
        "acc_mean_gap": (float(np.mean(np.abs(acc_diff)))
                         if acc_diff is not None else float("inf")),
        "select_gap": select_gap(short),
        "kmeans_gap": kmeans_gap(prog, ref),
        "T_gap": rel_gap(prog["T"], ref["T"]),
        "E_gap": rel_gap(prog["E"], ref["E"]),
    }
    rounds = {"select": None if short is None else short.tolist(),
              "acc": None if acc_diff is None else acc_diff.tolist()}
    return {"numbers": out, "inputs_detail": mism, "leaves": leaves,
            "rounds": rounds, "reference": ref}


def verdict(values: dict, limits: dict):
    """``(correct, [(name, value, limit)])`` in the fixed order, over the
    numbers that the cell's ``limits`` name and those of ``ALWAYS``; a
    number that is not finite, or has no limit, fails."""
    rows, ok = [], True
    for name in ORDER:
        if name not in values or (name not in limits
                                  and name not in ALWAYS):
            continue
        v, lim = values[name], limits.get(name)
        good = lim is not None and np.isfinite(v) and v <= lim
        ok = ok and good
        rows.append((name, v, lim))
    return ok, rows
