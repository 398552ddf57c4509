"""The benchmark's traffic generator: everything one experiment consumes,
drawn from the experiment's seed.

An experiment's traffic is its data and its fleet: class-template images
at the dataset's shape, the paper's sigma-biased non-iid partition over N
clients, the held-out test set, and the paper's section-VI single-cell
fleet (3GPP path loss, shadowing, energy budgets). These are copies of
the program's own generators (``repro.data.synthetic.make_dataset``,
``repro.data.partition.partition_bias``, ``repro.core.wireless.
sample_fleet`` and the seed rules of ``repro.api.spec.ExperimentSpec``),
kept here so that no change to the program can change what the benchmark
says a seed generates. The check holds the program's inputs to them
element for element.

Every parameter comes from the cell's traffic file (``traffic/<mix>.json``)
and its configuration file (``configs/<config>.json``): adding a traffic
mix is adding a data file.
"""
from __future__ import annotations

import zlib

import numpy as np

# the paper's section-VI constants (the program's repro.core.wireless)
CELL_RADIUS_KM = 0.3
SHADOW_STD_DB = 8.0
NOISE_DBM_PER_HZ = -174.0
P_DBM = 23.0
Z_MBIT = 448 * 8 * 1024 / 1e6           # 448 KB model (MNIST CNN, Table II)
ALPHA = 2e-28
FLEET_LOCAL_ITERS = 5
F_MIN_GHZ, F_MAX_GHZ = 0.2, 2.0
E_CONS_RANGE = (30e-3, 60e-3)
CYCLES_RANGE = (1e4, 3e4)
SAMPLES_RANGE = (300, 700)
NOISE = 0.25                            # pixel noise of the synthetic images
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


def _class_templates(rng, num_classes, h, w, c):
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
    yy, xx = yy / h, xx / w
    templates = np.zeros((num_classes, h, w, c), np.float32)
    for k in range(num_classes):
        img = np.zeros((h, w, c), np.float32)
        for _ in range(6):
            fy, fx = rng.uniform(0.5, 4.0, 2)
            ph = rng.uniform(0, 2 * np.pi, c)
            amp = rng.uniform(0.3, 1.0)
            img += amp * np.sin(2 * np.pi * (fy * yy + fx * xx))[..., None]
            img += amp * 0.3 * np.cos(ph)[None, None, :]
        templates[k] = img
    templates -= templates.min()
    templates /= max(templates.max(), 1e-6)
    return templates


def make_images(dataset: str, model: dict, num_samples: int, seed: int):
    """``(images [n, H, W, C] float32 in [0, 1], labels [n] int32)``:
    per-class smooth templates (keyed by the dataset name alone, so train
    and test share classes), shifted by up to 2 pixels, plus noise."""
    h, w = model["input_hw"]
    c = model["input_channels"]
    k = model["num_classes"]
    templates = _class_templates(
        np.random.default_rng(zlib.crc32(dataset.encode())), k, h, w, c)
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, k, num_samples).astype(np.int32)
    shift = rng.integers(-2, 3, (num_samples, 2))
    images = np.empty((num_samples, h, w, c), np.float32)
    base = templates[labels]
    for i in range(num_samples):
        images[i] = np.roll(base[i], tuple(shift[i]), axis=(0, 1))
    images += rng.normal(0.0, NOISE, images.shape).astype(np.float32)
    return np.clip(images, 0.0, 1.0), labels


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


def fleet(clients: int, seed: int) -> dict:
    """The section-VI single-cell fleet as the solver-facing float64
    arrays (eqs. 15-18 in the scaled units of docs/UNITS.md)."""
    rng = np.random.default_rng(seed)
    r_km = CELL_RADIUS_KM * np.sqrt(rng.uniform(0.01, 1.0, clients))
    pl_db = (128.1 + 37.6 * np.log10(np.maximum(r_km, 1e-3))
             + rng.normal(0.0, SHADOW_STD_DB, clients))
    h = 10.0 ** (-pl_db / 10.0)
    p = np.full(clients, dbm_to_watt(P_DBM))
    z = np.full(clients, Z_MBIT)
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


def experiment(seed: int, spec: dict, model: dict) -> dict:
    """Everything experiment ``seed`` consumes: client images and labels
    [N, D, ...], equal eq.-(4) sizes, the test set and the fleet."""
    s = derived_seeds(seed)
    dataset = spec["dataset"]
    images, labels = make_images(dataset, model, spec["train_samples"],
                                 s["data"])
    idx = partition(labels, model["num_classes"], spec["clients"],
                    spec["samples_per_client"], spec["sigma"],
                    s["partition"])
    test_x, test_y = make_images(dataset, model, spec["test_samples"],
                                 s["test"])
    return {"seed": seed, "images": images[idx], "labels": labels[idx],
            "sizes": np.full(spec["clients"], spec["samples_per_client"],
                             np.float64),
            "test_images": test_x, "test_labels": test_y,
            "fleet": fleet(spec["clients"], s["fleet"])}
