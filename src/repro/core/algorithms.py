"""Beyond-paper FL aggregation/objective variants on the same substrate.

  FedProx  (Li et al. 2020): proximal term μ/2‖w − w_global‖² in the client
           objective — stabilizes non-iid local updates.
  FedAvgM  (Hsu et al. 2019): server momentum over the pseudo-gradient
           Δ_k = w_k − aggregate(w_locals).

These compose with the paper's selection + SAO layers unchanged (selection
sees the same weight-divergence signal; SAO the same payloads).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.models.registry import model_def_for
from repro.utils.trees import tree_sub, tree_scale, tree_add


def make_fedprox_local_update(model_cfg, lr: float,
                              local_iters: int, batch_size: int,
                              mu: float = 0.01):
    """FedProx client update: SGD on  f_n(w) + μ/2‖w − w_g‖²; ``frozen``
    (the workload's frozen tree, ``engine.frozen_params``) goes to the
    loss, as in ``engine.make_local_update``."""
    loss_fn = model_def_for(model_cfg).loss

    def local_update(global_params, images, labels, key, frozen=None):
        loss = (loss_fn if frozen is None
                else functools.partial(loss_fn, frozen=frozen))

        def prox_loss(p, batch):
            base = loss(p, batch, model_cfg)
            sq = sum(jnp.sum(jnp.square(a.astype(jnp.float32)
                                        - b.astype(jnp.float32)))
                     for a, b in zip(jax.tree_util.tree_leaves(p),
                                     jax.tree_util.tree_leaves(global_params)))
            return base + 0.5 * mu * sq

        def step(p, k):
            idx = jax.random.randint(k, (batch_size,), 0, images.shape[0])
            g = jax.grad(prox_loss)(p, {"images": images[idx],
                                        "labels": labels[idx]})
            return jax.tree_util.tree_map(lambda w, gw: w - lr * gw, p, g), None

        keys = jax.random.split(key, local_iters)
        params, _ = jax.lax.scan(step, global_params, keys)
        return params

    return local_update


class ServerMomentum:
    """FedAvgM: w ← w − η·v,  v ← β·v + (w − w_agg)."""

    def __init__(self, beta: float = 0.9, lr: float = 1.0):
        self.beta = beta
        self.lr = lr
        self.v = None

    def step(self, global_params, aggregated):
        delta = tree_sub(global_params, aggregated)       # pseudo-gradient
        if self.v is None:
            self.v = delta
        else:
            self.v = tree_add(tree_scale(self.v, self.beta), delta)
        return tree_sub(global_params, tree_scale(self.v, self.lr))
