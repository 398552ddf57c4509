"""The benchmark's traffic generator: everything one experiment consumes,
drawn from the experiment's seed.

An experiment's traffic is its data and its fleet: the model's samples
(``make_data`` of the configuration's reference module: class-template
images for the paper's CNN, dialect token windows for a LoRA LM), the
paper's sigma-biased non-iid partition of them over N clients, the
held-out test set, and the paper's section-VI single-cell fleet (3GPP path
loss, shadowing, energy budgets) with each client's upload priced at the
model's payload. These are copies of the program's own generators
(``repro.data``, ``repro.data.partition.partition_bias``,
``repro.core.wireless.sample_fleet`` and the seed rules of
``repro.api.spec.ExperimentSpec``), kept here so that no change to the
program can change what the benchmark says a seed generates. The check
holds the program's inputs to them element for element.

Every parameter comes from the cell's traffic file (``traffic/<mix>.json``)
and its configuration file (``configs/<config>.json``): adding a traffic
mix is adding a data file.
"""
from __future__ import annotations

import numpy as np

from chipbench.reference import fl

# the paper's section-VI constants (the program's repro.core.wireless)
CELL_RADIUS_KM = 0.3
SHADOW_STD_DB = 8.0
NOISE_DBM_PER_HZ = -174.0
P_DBM = 23.0
ALPHA = 2e-28
FLEET_LOCAL_ITERS = 5
F_MIN_GHZ, F_MAX_GHZ = 0.2, 2.0
E_CONS_RANGE = (30e-3, 60e-3)
CYCLES_RANGE = (1e4, 3e4)
SAMPLES_RANGE = (300, 700)
TEST_SEED_OFFSET = 10_000
SEED_SPAN = 2 ** 31 - 2 ** 20           # experiment seeds stay in int32


def experiment_seeds(seed: int, count: int) -> list:
    """The seeds of the experiments one run makes, in order, from the
    run's ``--seed`` (any non-negative integer, also past 32 bits)."""
    rng = np.random.default_rng(int(seed))
    return [int(s) for s in rng.integers(0, SEED_SPAN, count)]


def derived_seeds(seed: int) -> dict:
    """The program's seed rules for one experiment seed."""
    return {"data": seed, "test": seed + TEST_SEED_OFFSET,
            "partition": seed + 1, "fleet": seed, "model": seed}


def dbm_to_watt(dbm):
    return 10.0 ** (np.asarray(dbm) / 10.0) / 1e3


def partition(labels: np.ndarray, num_classes: int, clients: int,
              per_client: int, sigma: float, seed: int):
    """The paper's sigma-bias partition: each client draws sigma of its
    samples from its majority class (round-robin, shuffled) and the rest
    uniformly from the other classes. Returns sample indices [N, D]."""
    rng = np.random.default_rng(seed)
    by_class = [np.flatnonzero(labels == k) for k in range(num_classes)]
    majority = np.arange(clients) % num_classes
    rng.shuffle(majority)
    idx = np.empty((clients, per_client), np.int64)
    n_major = int(round(float(sigma) * per_client))
    for n in range(clients):
        m = majority[n]
        others = np.concatenate([by_class[k] for k in range(num_classes)
                                 if k != m])
        rest = rng.choice(others, per_client - n_major)
        major = rng.choice(by_class[m], n_major)
        sel = np.concatenate([major, rest])
        rng.shuffle(sel)
        idx[n] = sel
    return idx


def fleet(clients: int, seed: int, z_mbit: float) -> dict:
    """The section-VI single-cell fleet as the solver-facing float64
    arrays (eqs. 15-18 in the scaled units of docs/UNITS.md), every
    upload ``z_mbit`` Mbit."""
    rng = np.random.default_rng(seed)
    r_km = CELL_RADIUS_KM * np.sqrt(rng.uniform(0.01, 1.0, clients))
    pl_db = (128.1 + 37.6 * np.log10(np.maximum(r_km, 1e-3))
             + rng.normal(0.0, SHADOW_STD_DB, clients))
    h = 10.0 ** (-pl_db / 10.0)
    p = np.full(clients, dbm_to_watt(P_DBM))
    z = np.full(clients, float(z_mbit))
    C = rng.uniform(*CYCLES_RANGE, clients)
    D = rng.integers(SAMPLES_RANGE[0], SAMPLES_RANGE[1] + 1,
                     clients).astype(np.float64)
    alpha = np.full(clients, ALPHA)
    e_cons = rng.uniform(*E_CONS_RANGE, clients)
    N0 = dbm_to_watt(NOISE_DBM_PER_HZ)
    L = FLEET_LOCAL_ITERS
    return {"J": h * p / N0 / 1e6, "U": L * C * D / 1e9,
            "G": 0.5 * alpha * L * C * D * 1e18, "H": z * p, "z": z,
            "e_cons": e_cons, "f_min": np.full(clients, F_MIN_GHZ),
            "f_max": np.full(clients, F_MAX_GHZ),
            "inr": np.zeros(clients, np.float64)}


def experiment(seed: int, spec: dict, model_cfg: dict) -> dict:
    """Everything experiment ``seed`` consumes: client samples and labels
    ``x``, ``y`` [N, D, ...], equal eq.-(4) sizes, the test set
    ``test_x``, ``test_y`` and the fleet."""
    m = fl.model_module(model_cfg["reference"])
    s = derived_seeds(seed)
    x, y, classes = m.make_data(model_cfg, spec, spec["train_samples"],
                                s["data"])
    idx = partition(y, classes, spec["clients"], spec["samples_per_client"],
                    spec["sigma"], s["partition"])
    test_x, test_y, _ = m.make_data(model_cfg, spec, spec["test_samples"],
                                    s["test"])
    return {"seed": seed, "x": x[idx], "y": y[idx],
            "sizes": np.full(spec["clients"], spec["samples_per_client"],
                             np.float64),
            "test_x": test_x, "test_y": test_y,
            "fleet": fleet(spec["clients"], s["fleet"],
                           m.upload_mbit(model_cfg))}
