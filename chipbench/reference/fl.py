"""Plain reference of one FL experiment of the paper (arXiv 2212.13544):
Alg. 1 with the Alg.-2 initial round, divergence selection (Alg. 4),
eq.-(4) FedAvg, and the spectrum allocation of each round (Alg. 5 SAO, or
Baseline 1's equal band).

It follows the experiment's seed the way the paper's runs are seeded: one
split of ``PRNGKey(seed)`` for the initial weights, then per round one
split whose subkey is split over the round's client lanes (each lane's key
split over its L SGD steps, one ``randint`` batch draw per step), and one
split for K-means++ after the initial round. So the reference trains on
the same minibatches as the experiment it checks.

Two modes:

* ``forced``: the round's selected clients, their lane layout and the
  cluster labels are given (the choices the checked run made); the
  reference replays the experiment on them and records, for each choice,
  its own divergences and its own K-means solution, so that a choice can
  be judged by the reference's numbers even where a last-bit difference
  made the run choose otherwise than the reference would.
* free: the reference makes every choice itself (the control).

The model is a module of its own (``reference/<name>.py``, named by the
configuration's ``reference`` key) that gives the model interface:

``init(cfg, key)``
    the trainable tree (one client's model, as the program's plane holds
    it), drawn from the experiment's key;
``frozen(cfg)``
    the frozen tree that every client shares, made from the
    configuration alone (a LoRA base from its ``base_seed``), or None;
``loss(params, x, y, cfg, precision, frozen=None)``
    the local training loss on one minibatch;
``evaluate(params, x, y, cfg, precision, frozen=None)``
    the test accuracy, a scalar;
``features(clients)``
    Alg. 2's K-means input, ``[N, F]``, from the stacked client trees;
``make_data(cfg, spec, num, seed)``
    ``(x, y, classes)``: ``num`` samples and their classes (the
    partition's labels), from ``seed``;
``upload_mbit(cfg)``
    the upload payload z of one client, in Mbit;
``train_flops(cfg)``, ``eval_flops(cfg)``
    model FLOPs of one training sample and one test sample;
``as_input(x, dtype)``
    the host array ``x`` as the model takes it, for weights in ``dtype``.

Arithmetic: the model (trainable and frozen trees) in ``dtype`` at matmul
``precision``, by default the precision the configuration states
(``precision.matmul``: where the program runs its contractions at XLA's
default, one bf16 pass on a TPU, the reference does too; for the paper's
CNN at HIGHEST, 31 rounds of SGD turn that one-pass rounding into a 3-9%
gap in the final weights, as wide as the bfloat16 control's); aggregation,
divergences and K-means in ``dtype`` elementwise; the allocation in NumPy
at ``alloc_dtype`` (float64 for the reference; ``ml_dtypes.bfloat16`` for
the control).
"""
from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import alloc

KMEANS_ITERS = 50
PRECISIONS = {"default": jax.lax.Precision.DEFAULT,
              "high": jax.lax.Precision.HIGH,
              "highest": jax.lax.Precision.HIGHEST}


def model_module(name: str):
    """The model module ``chipbench/reference/<name>.py``."""
    return importlib.import_module(f"chipbench.reference.{name}")


def paths(tree) -> list:
    """A tree's ``(path, leaf)`` pairs in flatten order, each path its keys
    joined by ``/`` (a flat dict keeps its keys; a nested adapter's leaf
    reads ``blocks/mamba/in_proj_a``): the names the program's flat plane
    gives its leaves."""
    return [("/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                      for k in path), leaf)
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]]


def leaves_by_path(tree, dtype=None) -> dict:
    """A tree's leaves as host arrays keyed by their path (``paths``)."""
    return {name: np.asarray(leaf, dtype) for name, leaf in paths(tree)}


class Experiment:
    """One experiment's reference, for a configuration and a traffic."""

    def __init__(self, model_cfg: dict, spec: dict, *, dtype=jnp.float32,
                 precision=None, alloc_dtype=np.float64):
        if precision is None:
            precision = PRECISIONS[model_cfg["precision"]["matmul"]]
        self.cfg = model_cfg
        self.spec = spec
        self.dtype = dtype
        self.precision = precision
        self.alloc_dtype = alloc_dtype
        self.m = model_module(model_cfg["reference"])
        base = self.m.frozen(model_cfg)
        self.frozen = None if base is None else jax.tree_util.tree_map(
            lambda x: x.astype(dtype)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, base)
        cfg, L, bs, lr = (model_cfg, spec["local_iters"], spec["batch_size"],
                          spec["learning_rate"])
        m, prec = self.m, precision

        def local(params, frozen, x, y, key):
            def step(p, k):
                idx = jax.random.randint(k, (bs,), 0, x.shape[0])
                g = jax.grad(m.loss)(p, x[idx], y[idx], cfg, prec, frozen)
                return jax.tree_util.tree_map(
                    lambda w, gw: (w - jnp.asarray(lr, w.dtype) * gw)
                    .astype(w.dtype), p, g), None
            p, _ = jax.lax.scan(step, params, jax.random.split(key, L))
            return p

        def train(params, frozen, x, y, keys, weights):
            rows = jax.vmap(local, in_axes=(None, None, 0, 0, 0))(
                params, frozen, x, y, keys)
            w = (weights / jnp.sum(weights)).astype(dtype)
            new = jax.tree_util.tree_map(
                lambda r: jnp.sum(w.reshape((-1,) + (1,) * (r.ndim - 1)) * r,
                                  0).astype(dtype), rows)
            return rows, new

        def evaluate(params, frozen, x, y):
            return m.evaluate(params, x, y, cfg, prec, frozen)

        def divergence(clients, g):
            sq = jax.tree_util.tree_map(
                lambda c, gl: jnp.sum(jnp.square(c - gl[None]).reshape(
                    c.shape[0], -1), 1), clients, g)
            return jnp.sqrt(sum(jax.tree_util.tree_leaves(sq)))

        self._train = jax.jit(train)
        self._eval = jax.jit(evaluate)
        self._div = jax.jit(divergence)
        self._kmeans = jax.jit(functools.partial(
            kmeans, c=spec["num_clusters"], iters=KMEANS_ITERS))

    # ------------------------------------------------------------------
    def run(self, inputs: dict, forced: dict = None) -> dict:
        """Run the experiment on ``inputs`` (``generate.experiment``).

        ``forced``: ``{"lanes": [R, S_pad] int (N = empty lane),
        "labels": [N]}``. Returns the history (accuracy, T, E per round,
        initial round first), the lane layout and labels used, the final
        global and client weights and the initial weights (float32 host
        arrays by leaf path, ``leaves_by_path``), and, per round, the
        reference's divergences."""
        spec, dt = self.spec, self.dtype
        N = spec["clients"]
        c, s = spec["num_clusters"], spec["selected_per_cluster"]
        if s != 1:
            raise ValueError("the reference replays s = 1 per cluster")
        B = spec["bandwidth_mhz"]
        allocate = alloc.ALLOCATORS[spec["allocator"]]
        key = jax.random.PRNGKey(inputs["seed"])
        key, sub = jax.random.split(key)
        w0 = self.m.init(self.cfg, sub)
        g = jax.tree_util.tree_map(lambda x: x.astype(dt), w0)
        base = self.frozen
        x = self.m.as_input(inputs["x"], dt)
        y = jnp.asarray(inputs["y"])
        sizes = np.asarray(inputs["sizes"], np.float32)
        tx = self.m.as_input(inputs["test_x"], dt)
        ty = jnp.asarray(inputs["test_y"])
        fleet = inputs["fleet"]

        # Alg. 2 initial round: every client trains from the initial model
        key, sub = jax.random.split(key)
        clients, g = self._train(g, base, x, y,
                                 jax.random.split(sub, N),
                                 jnp.asarray(sizes))
        key, sub = jax.random.split(key)
        feats = self.m.features(clients)
        use_labels = (np.asarray(self._kmeans(sub, feats)) if forced is None
                      else np.asarray(forced["labels"]))
        T0, E0 = allocate(fleet, np.arange(N), B, self.alloc_dtype)
        acc = [float(self._eval(g, base, tx, ty))]
        Ts, Es = [T0], [E0]
        lanes_all, divs = [], []
        for k in range(spec["rounds"]):
            d = np.asarray(self._div(clients, g), np.float64)
            divs.append(d)
            lanes = (select(d, use_labels, c, N) if forced is None
                     else np.asarray(forced["lanes"][k]))
            lanes_all.append(lanes)
            valid = lanes < N
            sel = lanes[valid]
            T, E = allocate(fleet, sel, B, self.alloc_dtype,
                            lanes=lanes.shape[0])
            Ts.append(T)
            Es.append(E)
            key, sub = jax.random.split(key)
            keys = jax.random.split(sub, lanes.shape[0])[valid]
            rows, g = self._train(g, base, x[sel], y[sel], keys,
                                  jnp.asarray(sizes[sel]))
            clients = jax.tree_util.tree_map(
                lambda cl, r: cl.at[sel].set(r), clients, rows)
            acc.append(float(self._eval(g, base, tx, ty)))
        as_np = lambda t: leaves_by_path(t, np.float32)
        return {"accuracy": np.asarray(acc), "T": np.asarray(Ts),
                "E": np.asarray(Es), "lanes": np.stack(lanes_all),
                "labels": use_labels, "features": np.asarray(feats, np.float64),
                "divergences": np.stack(divs),
                "global": as_np(g), "clients": as_np(clients),
                "init": as_np(w0)}


def select(d: np.ndarray, labels: np.ndarray, c: int, N: int) -> np.ndarray:
    """Alg. 4 with s = 1: lane j holds cluster j's client of largest
    divergence (the lowest index on a tie), or N where cluster j is
    empty."""
    lanes = np.full(c, N, np.int64)
    for j in range(c):
        members = np.flatnonzero(labels == j)
        if members.size:
            lanes[j] = members[np.argmax(d[members])]
    return lanes


def sq_dists(x, cents):
    return jnp.sum(jnp.square(x[:, None, :] - cents[None, :, :]), -1)


def kmeans(key, x, *, c: int, iters: int):
    """K-means++ seeding (one key per centroid) and Lloyd's iterations,
    eqs. (13)-(14); an empty cluster keeps its centroid. Returns labels."""
    n = x.shape[0]
    keys = jax.random.split(key, c)
    i0 = jax.random.randint(keys[0], (), 0, n)
    cents = jnp.zeros((c, x.shape[1]), x.dtype).at[0].set(x[i0])

    def add(i, cents):
        d = jnp.where((jnp.arange(c) < i)[None, :], sq_dists(x, cents),
                      jnp.inf)
        dmin = jnp.min(d, 1)
        p = dmin / jnp.maximum(jnp.sum(dmin), 1e-12)
        return cents.at[i].set(x[jax.random.choice(keys[i], n, p=p)])

    cents = jax.lax.fori_loop(1, c, add, cents)

    def lloyd(_, cents):
        lab = jnp.argmin(sq_dists(x, cents), 1)
        onehot = (lab[:, None] == jnp.arange(c)[None, :]).astype(x.dtype)
        counts = jnp.sum(onehot, 0)
        sums = jnp.sum(onehot[:, :, None] * x[:, None, :], 0)
        new = sums / jnp.maximum(counts, 1.0)[:, None]
        return jnp.where((counts > 0)[:, None], new, cents)

    cents = jax.lax.fori_loop(0, iters, lloyd, cents)
    return jnp.argmin(sq_dists(x, cents), 1)
