"""Faults planted under the benchmark's timed path, to show that its check
catches them: each patches one piece of the program for the length of a
``with planted(name):`` block and drops every compiled round program
before and after, so that the broken piece is traced into programs of its
own. The benchmark's own runs never plant one; its tests do (on the CPU),
and ``readings.py --faults`` does (on the chip, at the cell's own size).

``keep_global``   a round that returns the global model unchanged
``half_slate``    half of each round's clients left out of eq. (4), the
                  mean taken over the rest
``latency``       an answer altered where it is produced: T_k 10% high
``labels``        an answer altered where it is produced: one client's
                  K-means cluster moved to the next
"""
from __future__ import annotations

import contextlib


def _keep_global(real):
    def aggregate_flat(self, global_vec, rows, weights, opt_state):
        return global_vec, opt_state
    return aggregate_flat


def _half_slate(real):
    def aggregate_flat(self, global_vec, rows, weights, opt_state):
        import jax.numpy as jnp

        n = rows.shape[0]
        w = jnp.where(jnp.arange(n) < (n + 1) // 2, weights, 0.0)
        return real(self, global_vec, rows, w, opt_state)
    return aggregate_flat


def _latency(real):
    def allocate_traced(self, arr, B, mask):
        T, E, b, f = real(self, arr, B, mask)
        return T * 1.1, E, b, f
    return allocate_traced


def _labels(real):
    def load_traced_state(self, state, **kw):
        real(self, state, **kw)
        if self.cluster_labels is not None:
            labels = self.cluster_labels.copy()
            labels[0] = (labels[0] + 1) % self.fl.num_clusters
            self.cluster_labels = labels
    return load_traced_state


def _targets():
    from repro.core.fedavg import FLExperiment
    from repro.strategies.aggregators import FedAvgAggregator
    from repro.strategies.allocators import SAOAllocator

    return {"keep_global": (FedAvgAggregator, "aggregate_flat",
                            _keep_global),
            "half_slate": (FedAvgAggregator, "aggregate_flat", _half_slate),
            "latency": (SAOAllocator, "allocate_traced", _latency),
            "labels": (FLExperiment, "load_traced_state", _labels)}


NAMES = ("keep_global", "half_slate", "latency", "labels")


def _clear_programs():
    import jax
    from repro.core import engine

    engine._RUN_FN_CACHE.clear()
    engine._traced_round_program.cache_clear()
    jax.clear_caches()


@contextlib.contextmanager
def planted(name: str):
    cls, attr, make = _targets()[name]
    real = getattr(cls, attr)
    _clear_programs()
    setattr(cls, attr, make(real))
    try:
        yield
    finally:
        setattr(cls, attr, real)
        _clear_programs()
