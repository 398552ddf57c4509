"""Federated-learning loop — paper Algorithm 1 + the Fig. 2 framework.

Per round k:
  1. device selection        — pluggable ``Selector`` (registry: SELECTORS)
  2. spectrum allocation     — pluggable ``Allocator`` (registry: ALLOCATORS)
  3. local updates (L SGD steps each) — vmapped over the selected clients
  4. weighted aggregation    — pluggable ``Aggregator`` (eq. 4 default)
  5. bookkeeping: accuracy, T_k, E_k (eqs. 10-11), weight divergences

Clustering (Algorithm 2) happens once, after an initial all-device round,
on the K-means features of the paper's chosen layer.

``FLExperiment`` is the thin host driver: it owns experiment state (models,
clusters, rngs) and strategy objects, and delegates all jitted compute to a
``RoundEngine`` shared across experiments with equal hyper-parameters
(``repro.core.engine``). Strategies resolve through the ``repro.api``
registries — construct experiments declaratively with
``repro.api.build_experiment(ExperimentSpec(...))``.
"""
from __future__ import annotations

import contextlib
import os
from dataclasses import dataclass, field
from typing import Any, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.protocols import (Allocation, RoundState, SelectionContext,
                                 TracedContext)
from repro.api.registry import (AGGREGATORS, ALLOCATORS, CHANNELS,
                                COMPRESSORS, SELECTORS)
import repro.api.scenario  # noqa: F401  (populate the channel registry)
import repro.strategies  # noqa: F401  (populate the registries)
from repro.configs.base import FLConfig
from repro.core.clustering import (kmeans_fit, kmeans_fit_minibatch,
                                   extract_features_flat,
                                   clusters_from_labels,
                                   resolve_feature_columns)
from repro.core.divergence import weight_divergence_flat
from repro.core.engine import (EngineConfig, RoundEngine, RoundResult,
                               TracedRunResult, make_local_update, run_rounds)
from repro.core.faults import FaultSpec, byzantine_clients, draw_fault_masks
from repro.core.store import ClientStats, build_store
from repro.core.wireless import Fleet, completion_times, fleet_arrays
from repro.data.partition import FederatedData
from repro.kernels.chunked import default_chunk_size, streaming_weighted_mean
from repro.utils.trace import span
from repro.utils.trees import (flatten_stacked, tree_flatten_vector,
                               tree_num_params, unflatten_rows,
                               unflatten_rows_np, unflatten_vector)

__all__ = ["FLExperiment", "FLHistory", "RoundResult", "make_local_update"]

#: the global row to the model pytree in ONE device dispatch: a scanned
#: run's carry comes back after the device has finished, where a slice and
#: a reshape per leaf, op by op, would each add a dispatch to the run
_unflatten_vector_jit = jax.jit(unflatten_vector, static_argnums=0)


@dataclass
class FLHistory:
    accuracy: List[float] = field(default_factory=list)
    T_k: List[float] = field(default_factory=list)
    E_k: List[float] = field(default_factory=list)
    selected: List[np.ndarray] = field(default_factory=list)
    rounds_to_target: Optional[int] = None
    # buffered-asynchronous per-tick traces (empty on synchronous runs):
    # updates folded per fire, their mean age at fold time, active fleet
    participation: List[float] = field(default_factory=list)
    staleness: List[float] = field(default_factory=list)
    active: List[float] = field(default_factory=list)

    @property
    def total_T(self):
        return float(np.sum(self.T_k))

    @property
    def total_E(self):
        return float(np.sum(self.E_k))

    def append(self, res: RoundResult):
        # the host boundary: allocation/eval outputs may still be device
        # scalars (the solves are jitted); coerce HERE, once per round,
        # instead of blocking inside the allocator before training even
        # dispatches — and so the stored history is plain Python floats.
        self.accuracy.append(float(res.accuracy))
        self.T_k.append(float(res.T_k))
        self.E_k.append(float(res.E_k))
        self.selected.append(np.asarray(res.selected))

    def extend(self, other: "FLHistory") -> "FLHistory":
        """Concatenate ``other``'s rounds onto this history (checkpoint
        resume: the restored prefix continues with the new run's rounds)."""
        for name in ("accuracy", "T_k", "E_k", "selected",
                     "participation", "staleness", "active"):
            getattr(self, name).extend(getattr(other, name))
        if self.rounds_to_target is None:
            self.rounds_to_target = other.rounds_to_target
        return self

    def to_dict(self) -> dict:
        """JSON-serializable form (checkpoint manifests)."""
        return {
            "accuracy": [float(x) for x in self.accuracy],
            "T_k": [float(x) for x in self.T_k],
            "E_k": [float(x) for x in self.E_k],
            "selected": [np.asarray(s).tolist() for s in self.selected],
            "rounds_to_target": self.rounds_to_target,
            "participation": [float(x) for x in self.participation],
            "staleness": [float(x) for x in self.staleness],
            "active": [float(x) for x in self.active],
        }

    @classmethod
    def from_dict(cls, d: dict) -> "FLHistory":
        return cls(
            accuracy=list(d["accuracy"]), T_k=list(d["T_k"]),
            E_k=list(d["E_k"]),
            selected=[np.asarray(s, np.int64) for s in d["selected"]],
            rounds_to_target=d.get("rounds_to_target"),
            participation=list(d.get("participation", [])),
            staleness=list(d.get("staleness", [])),
            active=list(d.get("active", [])))


class _Checkpointer:
    """Bundles the ``run()``-level checkpoint knobs for the host loops:
    fires every ``every`` completed rounds, counting from ``offset`` so a
    resumed run continues the original round numbering."""

    def __init__(self, exp: "FLExperiment", directory: str, every: int,
                 offset: int, spec_dict: Optional[dict]):
        if every <= 0:
            raise ValueError(f"checkpoint_every must be > 0; got {every}")
        self.exp = exp
        self.directory = directory
        self.every = every
        self.offset = offset
        self.spec_dict = spec_dict

    def due(self, k: int) -> bool:
        return (self.offset + k + 1) % self.every == 0

    def save(self, k: int, hist: FLHistory) -> str:
        return self.exp.save_checkpoint(
            self.directory, self.offset + k + 1, history=hist,
            spec_dict=self.spec_dict)

    def maybe(self, k: int, hist: FLHistory) -> None:
        if self.due(k):
            self.save(k, hist)


class FLExperiment:
    """Host-side driver composing a shared ``RoundEngine`` with registered
    selection/allocation/aggregation/compression strategies.

    Strategy arguments accept instances, ``{"name", "params"}`` dicts, or
    compact strings (``"sao"``, ``"fedl:2.0"``, ``"topk:0.05"``) — all
    resolved through the ``repro.api`` registries.
    """

    def __init__(self, model_cfg: Any, fed: FederatedData,
                 test_images: np.ndarray, test_labels: np.ndarray,
                 fleet: Fleet, fl: FLConfig, *, bandwidth_mhz: float = 20.0,
                 allocator: Any = "sao", seed: int = 0,
                 batch_size: int = 32, box_correct: bool = False,
                 compression: Any = "none", fedprox_mu: float = 0.0,
                 server_momentum: float = 0.0, channel: Any = "static",
                 selection: Any = None, aggregator: Any = None,
                 churn: Any = None, store: str = "dense",
                 k_max: Optional[int] = None,
                 chunk_size: Optional[int] = None,
                 div_refresh_every: int = 0, cluster: str = "full",
                 p_shards: int = 0, faults: Any = None,
                 quarantine_after: int = 0):
        self.model_cfg = model_cfg
        self.p_shards = int(p_shards)
        self.fed = fed
        self.fleet = fleet
        self.fl = fl
        self.B = bandwidth_mhz
        self.seed = seed                 # the id of this run's trace spans
        self.rng = np.random.default_rng(seed)
        self.key = jax.random.PRNGKey(seed)
        self.test_images = jnp.asarray(test_images)
        self.test_labels = jnp.asarray(test_labels)

        # -- strategy resolution (names → registered instances) --------
        self.allocator = ALLOCATORS.resolve(allocator)
        if box_correct:
            if getattr(self.allocator, "registry_name", "") != "sao":
                raise ValueError("box_correct=True only applies to the "
                                 "'sao' allocator; set allocator params "
                                 "explicitly instead")
            import dataclasses as _dc
            self.allocator = _dc.replace(self.allocator, box_correct=True)
        self.selector = SELECTORS.resolve(selection if selection is not None
                                          else fl.selection)
        if aggregator is None:
            aggregator = ("fedavgm:%s" % server_momentum
                          if server_momentum > 0 else "fedavg")
        self.aggregator = AGGREGATORS.resolve(aggregator)
        self.aggregator.reset()
        self.compressor = COMPRESSORS.resolve(compression)
        self.channel = CHANNELS.resolve(channel)
        from repro.core.async_engine import parse_churn
        self.churn = parse_churn(churn)
        if (self.churn != (0.0, 0.0) and store != "paged"
                and not getattr(self.aggregator, "async_capable", False)):
            raise ValueError(
                "client churn needs an engine that tracks availability: "
                "either the buffered-asynchronous engine (an async-capable "
                "aggregator, e.g. aggregator='fedbuff:4') or the paged "
                "client store (store='paged'), whose round loop flips the "
                "stats table's availability mask")
        if cluster not in ("full", "minibatch"):
            raise ValueError(
                f"cluster must be 'full' or 'minibatch'; got {cluster!r}")
        self.cluster_mode = cluster

        # -- fault injection / quarantine (repro.core.faults) -----------
        self.faults = FaultSpec.normalize(faults)
        self.quarantine_after = int(quarantine_after)
        if self.quarantine_after < 0:
            raise ValueError("quarantine_after must be >= 0; got "
                             f"{quarantine_after}")
        if (self.faults is not None and self.faults.chan_outage > 0.0
                and not getattr(self.channel, "stateful", False)):
            raise ValueError(
                "faults: chan_outage derives upload failures from the "
                "Gauss-Markov fade state and needs a stateful channel "
                "(e.g. channel='gauss-markov'); got "
                f"{self.channel.registry_name!r}")
        self._byz_mask = (byzantine_clients(self.faults, fed.num_clients)
                          if self.faults is not None
                          and self.faults.byzantine > 0.0 else None)

        # -- compiled compute, shared across same-config experiments ---
        self.engine = RoundEngine.shared(EngineConfig(
            model_cfg, fl.learning_rate, fl.local_iters, batch_size,
            fedprox_mu=fedprox_mu))

        self.global_params = self.engine.init_params(self._next_key())
        # the client parameter store: all N client models, either as the
        # dense device-resident [N, P] plane (row layout =
        # engine.flat_spec; updated in place for the selected rows each
        # round via the engine's donated scatter) or as the host-paged
        # active/cold split (repro.core.store) whose only O(N) hot state
        # is the per-client stats table
        gvec = tree_flatten_vector(self.global_params)
        self.chunk_size = int(chunk_size or default_chunk_size(gvec.shape[0]))
        self.k_max = int(k_max or min(fed.num_clients,
                                      max(fl.devices_per_round, 256)))
        self._store = build_store(store, gvec, fed.num_clients, self.engine,
                                  self.chunk_size, stage_rows=self.k_max)
        self._div_refresh_every = int(div_refresh_every)
        self._rounds_since_refresh = np.iinfo(np.int32).max  # force first
        self._gvec_host = (np.asarray(gvec) if store == "paged" else None)
        self.clusters: Optional[List[np.ndarray]] = None
        self.cluster_labels: Optional[np.ndarray] = None

        if getattr(fed, "lazy", False):
            # lazy federated data: per-client SAMPLE INDICES into a shared
            # pool instead of materialized [N, D, H, W, C] images — the
            # per-round gather composes on device (pool + [S, D] indices)
            if store != "paged":
                raise ValueError(
                    "lazy federated data (index-backed partition) requires "
                    "store='paged'; the dense/traced paths consume the "
                    "materialized [N, D, ...] image stack")
            self._pool_images = jnp.asarray(fed.pool_images)
            self._images = None
        else:
            self._pool_images = None
            self._images = jnp.asarray(fed.images)
        self._labels = jnp.asarray(fed.labels)
        self._sizes = jnp.asarray(fed.sizes)
        self._sizes_host = np.asarray(fed.sizes)

        # lossy uplink shrinks the payload -> z_n enters SAO via H_n, t_com
        n_par = tree_num_params(self.global_params)
        n_leaves = len(jax.tree_util.tree_leaves(self.global_params))
        z = self.compressor.payload_mbit(n_par, n_leaves)
        if z is None:
            from repro.models.registry import model_def_for
            if model_def_for(model_cfg).price_uploads:
                # adapter workloads upload the TRAINABLE parameters only:
                # price z from P (= P_adapter fp32 bits), never P_base
                z = n_par * 32 / 1e6
        if z is not None:
            import dataclasses as _dc
            self.fleet = _dc.replace(fleet, z=np.full_like(fleet.z, z))

    # ------------------------------------------------------------------
    @classmethod
    def from_spec(cls, spec) -> "FLExperiment":
        from repro.api.build import build_experiment
        return build_experiment(spec)

    def _next_key(self):
        self.key, sub = jax.random.split(self.key)
        return sub

    # ------------------------------------------------------------------
    @property
    def store(self):
        """The client parameter store (``DenseStore`` | ``PagedStore``) —
        the one ``ClientStore`` every driver consumes."""
        return self._store

    @property
    def stats(self) -> ClientStats:
        """The O(N) per-client statistics table — owned by the store, the
        SINGLE source of per-client truth (availability, age, in-flight
        completion, divergence/drift, virtual clock) for the host loops
        and the async scheduler alike."""
        return self._store.stats

    @property
    def _faults_on(self) -> bool:
        return self.faults is not None and self.faults.active

    @property
    def _track_faults(self) -> bool:
        return self._faults_on or self.quarantine_after > 0

    @property
    def client_params(self) -> jnp.ndarray:
        """The dense [N, P] plane (donation-managed by the round loop).

        A paged store keeps no materialized plane — gather the rows you
        need through the store contract instead."""
        if self._store.kind != "dense":
            raise AttributeError(
                "store='paged' keeps no [N, P] client buffer; gather "
                "active rows with exp.store.gather(idx), page the cold "
                "store with iter_client_trees()/iter_client_features(), "
                "or read the O(N) exp.stats table")
        return self._store.buffer

    @client_params.setter
    def client_params(self, value):
        if self._store.kind != "dense":
            raise AttributeError(
                "store='paged' keeps no [N, P] client buffer to assign; "
                "persist trained rows through exp.store.scatter(idx, rows)")
        self._store.buffer = value

    def _client_images(self, idx: np.ndarray) -> jnp.ndarray:
        """The selected clients' sample stacks ``[S, D, H, W, C]`` —
        a row gather for materialized data, a device-side pool gather for
        lazy (index-backed) partitions."""
        if self._pool_images is None:
            return self._images[idx]
        return self._pool_images[jnp.asarray(self.fed.indices[idx])]

    def evaluate(self):
        acc, per_class = self.engine.evaluate(
            self.global_params, self.test_images, self.test_labels)
        return float(acc), np.asarray(per_class)

    # ------------------------------------------------------------------
    def train_clients(self, idx: np.ndarray):
        """Run local updates for ``idx``; returns their new stacked params
        (after simulated lossy uplink compression, if configured)."""
        idx = np.asarray(idx)
        keys = jax.random.split(self._next_key(), len(idx))
        new_params = self.engine.train_clients(
            self.global_params, self._client_images(idx), self._labels[idx],
            keys)
        return self.compressor.apply(new_params, self.global_params)

    def aggregate(self, stacked_params, idx: np.ndarray):
        """Server aggregation over the participating local models (eq. (4)
        weighted mean by default; pluggable via the aggregator registry)."""
        weights = self._sizes[np.asarray(idx)]
        self.global_params = self.aggregator.aggregate(
            self.global_params, stacked_params, weights)

    def store_clients(self, stacked_params, idx: np.ndarray):
        """Write the clients' new models into the client store.

        Accepts flat ``[S, P]`` rows (the fused round step's output) or a
        stacked pytree (flattened here). On the dense store the scatter
        jit donates the old buffer, so the plane updates in place instead
        of double-buffering 45 MB per round — external holders of
        ``client_params`` must copy (see ``client_tree``). On the paged
        store the rows page out to the host cold store."""
        rows = (stacked_params
                if isinstance(stacked_params, jnp.ndarray)
                and stacked_params.ndim == 2
                else flatten_stacked(stacked_params))
        self._store.scatter(np.asarray(idx), rows)

    def client_tree(self, chunk_size: Optional[int] = None):
        """The client store as a stacked pytree (host-numpy leaves
        ``[N, ...]``) — always a COPY for external consumers (the dense
        buffer is donation-managed by the round loop).

        Assembled by paging the store ``chunk_size`` rows at a time, so
        peak memory beyond the (inherently O(N·P)) result is one chunk —
        use :meth:`iter_client_trees` to stream without materializing the
        full result at all."""
        spec = self.engine.flat_spec
        n = self.fed.num_clients
        leaves = [np.empty((n,) + shape, dt)
                  for shape, dt in zip(spec.shapes, spec.dtypes)]
        start = 0
        for block in self._store.iter_chunks(self._chunk(chunk_size)):
            c = block.shape[0]
            for leaf, off, size, shape in zip(leaves, spec.offsets,
                                              spec.sizes, spec.shapes):
                leaf[start:start + c] = (block[:, off:off + size]
                                         .reshape((c,) + shape))
            start += c
        return jax.tree_util.tree_unflatten(spec.treedef, leaves)

    def iter_client_trees(self, chunk_size: Optional[int] = None):
        """Stream the client store as ``(start_row, stacked pytree)``
        blocks of at most ``chunk_size`` clients — O(chunk·P) peak."""
        start = 0
        for block in self._store.iter_chunks(self._chunk(chunk_size)):
            yield start, unflatten_rows_np(self.engine.flat_spec, block)
            start += block.shape[0]

    def _chunk(self, chunk_size: Optional[int]) -> int:
        return int(chunk_size) if chunk_size else self.chunk_size

    def client_features(self, layer: Optional[str] = None,
                        chunk_size: Optional[int] = None) -> jnp.ndarray:
        """K-means feature matrix ``[N, F]`` (Alg. 2's input).

        Dense store: a zero-copy column slice of the plane
        (``layer="all"``'s view IS the buffer, so it is copied here — the
        next round's donated store would delete it out from under the
        caller otherwise). Paged store: assembled chunk-at-a-time from the
        cold store (identical columns via the shared spec resolution), so
        only the [N, F] feature block ever materializes."""
        layer = self.fl.feature_layer if layer is None else layer
        if self._store.kind == "dense":
            feats = extract_features_flat(self.client_params, layer,
                                          self.engine.flat_spec)
            return (jnp.array(feats) if feats is self._store.buffer
                    else feats)
        cols = resolve_feature_columns(self.engine.flat_spec, layer)
        blocks = [block if cols is None else block[:, cols]
                  for block in self._store.iter_chunks(
                      self._chunk(chunk_size))]
        return jnp.asarray(np.concatenate(blocks, axis=0))

    def iter_client_features(self, layer: Optional[str] = None,
                             chunk_size: Optional[int] = None):
        """Stream ``(start_row, [c, F] host feature block)`` pairs —
        the O(chunk·P) iterator variant of :meth:`client_features`."""
        layer = self.fl.feature_layer if layer is None else layer
        cols = resolve_feature_columns(self.engine.flat_spec, layer)
        start = 0
        for block in self._store.iter_chunks(self._chunk(chunk_size)):
            yield start, (np.asarray(block) if cols is None
                          else np.asarray(block[:, cols]))
            start += block.shape[0]

    # ------------------------------------------------------------------
    def initial_round(self):
        """Round 0: all devices train; then K-means clustering (Alg. 2).

        On the paged store a fleet larger than ``k_max`` trains in waves
        of ``k_max`` (the active-plane size), streaming the eq.-(4)
        weighted mean across waves — a single wave (``k_max >= N``) takes
        the dense host path verbatim and stays on the pinned numerics."""
        n = self.fed.num_clients
        idx = np.arange(n)
        if self._store.kind == "dense" or n <= self.k_max:
            new_params = self.train_clients(idx)
            self.store_clients(new_params, idx)
            self.aggregate(new_params, idx)
        else:
            self._initial_round_waves(idx)
        if self.cluster_mode == "minibatch":
            # O(chunk)-memory streaming fit: feature blocks page straight
            # from the store; a single-chunk stream IS the full fit
            chunks = lambda: (blk for _, blk in self.iter_client_features())
            _, labels, _ = kmeans_fit_minibatch(self._next_key(), chunks,
                                                self.fl.num_clusters)
        else:
            feats = self.client_features()
            _, labels, _ = kmeans_fit(self._next_key(), feats,
                                      self.fl.num_clusters)
        self.cluster_labels = np.asarray(labels)
        self.clusters = clusters_from_labels(labels, self.fl.num_clusters)
        if self._store.kind == "paged":
            self._finish_paged_round(idx)

    def _initial_round_waves(self, idx: np.ndarray):
        """All-device training in ``k_max``-sized waves: the device never
        holds more than one active [k_max, P] block; the global update is
        the streaming weighted mean over waves (not bitwise-identical to
        the one-shot eq.-(4) reduction — chunk-boundary summation — which
        is why single-wave stays on the direct path)."""
        spec = self.engine.flat_spec

        def waves():
            for s in range(0, len(idx), self.k_max):
                w_idx = idx[s:s + self.k_max]
                rows = flatten_stacked(self.train_clients(w_idx))
                self._store.scatter(w_idx, rows)
                yield np.asarray(rows), self._sizes_host[w_idx]

        mean = streaming_weighted_mean(waves(), spec.total)
        # feed the pre-aggregated mean through the aggregator as a single
        # unit-weight row, so stateful servers (momentum) see one eq.-(4)
        # mean exactly as they would from the one-shot path
        mean_tree = jax.tree_util.tree_map(
            lambda l: l[None], unflatten_vector(spec, jnp.asarray(mean)))
        self.global_params = self.aggregator.aggregate(
            self.global_params, mean_tree, np.ones(1))

    def divergences(self) -> np.ndarray:
        """Per-client ‖w_n − w_g‖ — the §IV-C selection signal.

        Dense store: one fused reduction over the [N, P] plane. Paged
        store: served from the O(N) stats table — untouched clients all
        equal the broadcast base row, so their (exact) divergence is ONE
        O(P) row op; touched clients carry the value from their last
        refresh, recomputed in streamed O(chunk·P) batches every
        ``div_refresh_every`` rounds (1 = every round = exactly the dense
        signal; 0 = never, staleness bounded by ``stats.drift``)."""
        if self._store.kind == "dense":
            return np.asarray(weight_divergence_flat(
                self.client_params, tree_flatten_vector(self.global_params)))
        return self._paged_divergences()

    def _paged_divergences(self) -> np.ndarray:
        store, stats = self._store, self.stats
        gvec = jnp.asarray(self._gvec_host)
        # every untouched row IS the base row: one [1, P] call through the
        # same fused op keeps their entries bit-identical to a dense sweep
        base_d = np.asarray(self.engine.rows_divergence(
            jnp.asarray(store.base)[None, :], gvec))[0]
        untouched = ~store.touched
        stats.divergence[untouched] = base_d
        stats.drift[untouched] = 0.0
        every = self._div_refresh_every
        # a forced refresh (sentinel) covers mass scatters that bypassed
        # the per-row update — e.g. the initial all-device round — so even
        # the lazy (every=0) policy never serves an uninitialized entry
        forced = self._rounds_since_refresh >= np.iinfo(np.int32).max
        if (store.num_touched
                and (forced or (every > 0
                                and self._rounds_since_refresh >= every))):
            tidx = np.flatnonzero(store.touched)
            for s in range(0, len(tidx), self.chunk_size):
                batch = tidx[s:s + self.chunk_size]
                stats.divergence[batch] = np.asarray(
                    self.engine.rows_divergence(store.gather(batch), gvec))
            stats.drift[store.touched] = 0.0
            self._rounds_since_refresh = 0
        return stats.divergence.copy()

    def selection_context(self) -> SelectionContext:
        return SelectionContext(
            rng=self.rng,
            num_devices=self.fed.num_clients,
            devices_per_round=self.fl.devices_per_round,
            selected_per_cluster=self.fl.selected_per_cluster,
            bandwidth_mhz=self.B,
            fleet=self.fleet,
            clusters=self.clusters,
            divergences=self.divergences)

    def select(self, method: Any = None) -> np.ndarray:
        """Device selection for one round; ``method`` may be a registered
        name, a spec dict, a Selector instance, or None for the default."""
        selector = (self.selector if method is None
                    else SELECTORS.resolve(method))
        return np.asarray(selector.select(self.selection_context()))

    def allocation(self, idx: np.ndarray) -> Allocation:
        """Spectrum allocation for the round (full per-device solution)."""
        arr = fleet_arrays(self.fleet.select(np.asarray(idx)))
        return self.allocator.allocate(arr, self.B)

    def allocate(self, idx: np.ndarray):
        """Back-compat: returns just ``(T_k, E_k)``."""
        a = self.allocation(idx)
        return a.T, a.E

    # ------------------------------------------------------------------
    def round(self, method: Any = None) -> RoundResult:
        """One full FL round: select → allocate → train → aggregate → eval.

        Uses the engine's fused jitted step when the aggregator is the
        plain eq. (4) mean and no lossy compression is configured. On the
        paged store the selection is additionally filtered by the stats
        table's availability mask (round-level churn), and the round's
        trained rows refresh the table's divergence/age entries — O(K·P)
        bookkeeping; the O(N·P) plane is never touched.
        """
        idx = np.asarray(self.select(method))
        paged = self._store.kind == "paged"
        faults_on = self._faults_on
        if paged:
            idx = idx[self.stats.avail[idx]]
        if self.quarantine_after > 0:
            idx = idx[self.stats.strikes[idx] < float(self.quarantine_after)]
        if idx.size == 0:               # everyone churned/quarantined out:
            acc, per_class = self.evaluate()    # explicit no-op round
            return RoundResult(
                selected=idx, T_k=0.0, E_k=0.0, accuracy=acc,
                per_class=per_class,
                params=jax.tree_util.tree_map(jnp.copy,
                                              self.global_params))
        alloc = self.allocation(idx)
        fused = (getattr(self.aggregator, "fuses_with_engine", False)
                 and getattr(self.compressor, "identity", False)
                 and not faults_on)
        keep = None                     # faults: lanes persisted to store
        if fused:
            keys = jax.random.split(self._next_key(), len(idx))
            # round_step donates the global params (the new global reuses
            # their buffers) and returns the clients as flat [S, P] rows
            rows, new_global, acc, per_class = self.engine.round_step(
                self.global_params, self._client_images(idx),
                self._labels[idx], keys, self._sizes[idx], self.test_images,
                self.test_labels)
            self.store_clients(rows, idx)
            self.global_params = new_global
            acc, per_class = float(acc), np.asarray(per_class)
        else:
            stacked = self.train_clients(idx)
            rows = flatten_stacked(stacked)
            if faults_on:
                rows, survive, keep = self._inject_faults_host(
                    idx, rows, alloc)
                ksel = np.flatnonzero(keep)
                if ksel.size:
                    self.store_clients(rows[jnp.asarray(ksel)], idx[ksel])
                self._aggregate_flat_host(rows, survive, idx)
            else:
                self.store_clients(rows, idx)
                self.aggregate(stacked, idx)
            acc, per_class = self.evaluate()
        if paged:
            if keep is None:
                self._finish_paged_round(idx, rows)
            elif keep.any():
                ksel = np.flatnonzero(keep)
                self._finish_paged_round(idx[ksel], rows[jnp.asarray(ksel)])
            # all-failed round: nothing landed and the global row did not
            # move, so there is no drift/divergence upkeep to do
        # params is COPIED: the next fused round donates self.global_params,
        # which would silently invalidate an earlier RoundResult's tree
        return RoundResult(selected=np.asarray(idx), T_k=alloc.T, E_k=alloc.E,
                           accuracy=acc, per_class=per_class,
                           params=jax.tree_util.tree_map(jnp.copy,
                                                         self.global_params),
                           stacked_params=rows)

    def _inject_faults_host(self, idx: np.ndarray, rows, alloc: Allocation):
        """Host twin of the traced post-train fault phase (``engine``'s
        ``inject_faults`` + ``finite_guard``): ONE key split at the same
        stream position as the traced program, the same Bernoulli draws,
        the same semantics — host ≡ scanned under faults is pinned in
        ``tests/test_faults.py``.

        Returns ``(rows, survive, keep)``: the (byzantine-transformed,
        corrupt-NaN'd) rows, the lanes whose weight survives the fold
        (``~drop & finite``), and the lanes that persist to the store
        (``~drop & ~corrupt`` — matching the traced sentinel scatter)."""
        fs = self.faults
        if fs.chan_outage > 0.0:
            raise ValueError(
                "faults: chan_outage needs the fade state the scanned "
                "program carries; the host round loop has none — run a "
                "traceable bundle with no target_accuracy (store='dense')")
        drop_j, corrupt_j = draw_fault_masks(self._next_key(), fs,
                                             (len(idx),))
        drop = np.asarray(drop_j)
        corrupt = np.asarray(corrupt_j)
        if fs.deadline > 0.0:
            d = np.asarray(completion_times(
                fleet_arrays(self.fleet.select(idx)), alloc.b, alloc.f))
            drop = drop | (d > fs.deadline)
        if self._byz_mask is not None:
            gvec = tree_flatten_vector(self.global_params)
            byz = jnp.asarray(self._byz_mask[idx])
            rows = jnp.where(byz[:, None],
                             gvec[None, :]
                             - fs.byz_scale * (rows - gvec[None, :]),
                             rows)
        if fs.corrupt > 0.0:
            rows = jnp.where(jnp.asarray(corrupt)[:, None],
                             jnp.full((), jnp.nan, rows.dtype), rows)
        finite = np.asarray(jnp.all(jnp.isfinite(rows), axis=1))
        st = self.stats
        np.add.at(st.faults, idx[drop | corrupt], 1.0)
        # strike = a non-finite payload that actually arrived (not lost)
        np.add.at(st.strikes, idx[~finite & ~drop], 1.0)
        return rows, ~drop & finite, ~drop & ~corrupt

    def _aggregate_flat_host(self, rows, survive: np.ndarray,
                             idx: np.ndarray):
        """Eq.-(4) fold of a faulty round: aggregate ALL dispatched lanes
        with the failed lanes' weights zeroed — ``ops.flat_aggregate``
        zeroes a 0-weight lane's payload, so this matches the traced
        program bitwise (and an all-failed round is an explicit no-op on
        the global row, never a 0/0)."""
        if not bool(np.any(survive)):
            return
        spec = self.engine.flat_spec
        if not hasattr(self.aggregator, "aggregate_flat"):
            # pre-flat custom aggregator: feed it the surviving subset
            # (zero-weight lanes would change stacked-contract semantics)
            sel = np.flatnonzero(survive)
            self.global_params = self.aggregator.aggregate(
                self.global_params, unflatten_rows(spec,
                                                   rows[jnp.asarray(sel)]),
                self._sizes[idx[sel]])
            return
        gvec = tree_flatten_vector(self.global_params)
        w = jnp.where(jnp.asarray(survive),
                      self._sizes[idx].astype(jnp.float32), 0.0)
        new_gvec, new_opt = self.aggregator.aggregate_flat(
            gvec, rows, w, self.aggregator.init_flat_state(gvec))
        self.global_params = unflatten_vector(spec, new_gvec)
        self.aggregator.load_flat_state(new_opt, spec)

    def _finish_paged_round(self, idx: np.ndarray, rows=None):
        """Post-round upkeep of the O(N) stats table (paged store only):
        drift bounds grow by ‖g_new − g_old‖ for stale entries, the
        round's trained rows get exact divergences (one O(K·P) row op on
        data already in hand), ages advance."""
        gvec_new = tree_flatten_vector(self.global_params)
        gvec_new_host = np.asarray(gvec_new)
        st = self.stats
        delta = float(np.linalg.norm(gvec_new_host - self._gvec_host))
        st.drift[self._store.touched] += delta
        if rows is not None:
            st.divergence[idx] = np.asarray(
                self.engine.rows_divergence(rows, gvec_new))
            st.drift[idx] = 0.0
        st.age[:] += 1
        st.age[idx] = 0
        self._gvec_host = gvec_new_host
        if rows is None:
            # mass scatter without per-row updates (initial round): force
            # the next divergences() call to refresh the touched rows
            self._rounds_since_refresh = np.iinfo(np.int32).max
        else:
            self._rounds_since_refresh = min(
                self._rounds_since_refresh + 1,
                np.iinfo(np.int32).max - 1)

    def _churn_step_host(self):
        """Round-level Bernoulli churn on the stats table's availability
        mask — a departed client's cold row stays paged out untouched and
        is picked up again verbatim on rejoin."""
        p_leave, p_join = self.churn
        n = self.fed.num_clients
        leave = self.rng.random(n) < p_leave
        join = self.rng.random(n) < p_join
        avail = self.stats.avail
        avail[:] = np.where(avail, ~leave, join)

    def run(self, method: Any = None, rounds: Optional[int] = None,
            target_accuracy: Optional[float] = None,
            include_initial_round: bool = True, *,
            checkpoint_every: int = 0,
            checkpoint_dir: Optional[str] = None,
            checkpoint_offset: int = 0,
            checkpoint_spec: Optional[dict] = None,
            history: Optional[FLHistory] = None) -> FLHistory:
        """Run the experiment; identical results from two execution paths.

        When every configured strategy advertises ``traceable=True``, the
        selection policy is deterministic (bit-parity with the host loop —
        stochastic selectors draw from ``jax.random`` when traced, which
        would silently change this reproduction's numbers for the same
        seed), and no early-stop target is set, the whole experiment runs
        as ONE compiled ``lax.scan`` program on device
        (``engine.run_rounds``) and the history comes back in a single
        transfer. Otherwise the legacy round-at-a-time Python loop below
        drives the same math. Stochastic selectors run device-resident
        through the explicit ``CohortRunner`` path, which documents the
        ``jax.random`` draw.
        """
        rounds = rounds or self.fl.max_rounds
        target = (self.fl.target_accuracy
                  if target_accuracy is None else target_accuracy)
        ck = None
        if checkpoint_every:
            if not checkpoint_dir:
                raise ValueError(
                    "checkpoint_every > 0 needs a checkpoint_dir")
            ck = _Checkpointer(self, checkpoint_dir, int(checkpoint_every),
                               int(checkpoint_offset), checkpoint_spec)
        if (getattr(self.channel, "dynamic", False)
                and self.fleet.num_cells > 1):
            raise ValueError(
                f"channel {self.channel.registry_name!r} computes per-round "
                "interference from the OTHER cells' selections; a single-"
                "cell FLExperiment cannot see them — run the multi-cell "
                "spec through CohortRunner (build_cohort / fl_sim --cells)")
        selector = (self.selector if method is None
                    else SELECTORS.resolve(method))
        if self._store.kind == "paged":
            # population-scale path: host loop over the paged store; the
            # scanned program's [N, P] carry is exactly what this mode
            # exists to avoid
            if (getattr(self.channel, "needs_rng", False)
                    or getattr(self.channel, "stateful", False)):
                raise ValueError(
                    f"channel {self.channel.registry_name!r} redraws fading "
                    "inside the scanned program; store='paged' drives the "
                    "host loop — use the static channel (or store='dense')")
            if getattr(self.aggregator, "async_capable", False):
                # buffered-asynchronous ticks over the paged store: the
                # jitted tick pieces carry only the [P] global + O(N)
                # stats columns; rows move O(k_max·P) through the store's
                # staging API between them
                if not self.traceable(selector):
                    raise ValueError(
                        "the buffered-asynchronous engine needs a fully "
                        "traceable strategy bundle (selector/allocator/"
                        "compressor/channel)")
                return self._run_async_paged(selector, rounds, target,
                                             include_initial_round,
                                             history, ck)
            return self._run_paged(selector, method, rounds, target,
                                   include_initial_round, history, ck)
        if getattr(self.aggregator, "async_capable", False):
            # the buffered-asynchronous engine exists ONLY as a scanned
            # program — there is no host-loop equivalent to fall back to
            if target:
                raise ValueError(
                    "the buffered-asynchronous engine runs as one scanned "
                    "program and cannot early-stop on target_accuracy")
            if ck is not None:
                raise ValueError(
                    "the dense buffered-asynchronous engine runs as ONE "
                    "scanned program with no host boundary to snapshot "
                    "at; checkpoint with store='paged' (the host-composed "
                    "async loop) or checkpoint_every=0")
            if not self.traceable(selector):
                raise ValueError(
                    "the buffered-asynchronous engine needs a fully "
                    "traceable strategy bundle (selector/allocator/"
                    "compressor/channel)")
            out = self._run_traced(selector, rounds, include_initial_round)
            return history.extend(out) if history is not None else out
        bit_parity = not getattr(selector, "needs_rng", True)
        if (not target and bit_parity and self.traceable(selector)
                and ck is None):
            out = self._run_traced(selector, rounds, include_initial_round)
            return history.extend(out) if history is not None else out
        if getattr(self.channel, "needs_rng", False):
            raise ValueError(
                f"channel {self.channel.registry_name!r} redraws fading "
                "inside the scanned program and has no host-loop "
                "equivalent; run it with a traceable strategy bundle and "
                "no target_accuracy (or through CohortRunner)")
        if ck is not None and getattr(self.channel, "stateful", False):
            raise ValueError(
                f"channel {self.channel.registry_name!r} carries fade "
                "state only the scanned program steps; checkpointing "
                "drives the host round loop — use the static channel or "
                "checkpoint_every=0")
        hist = history if history is not None else FLHistory()
        if include_initial_round or self.clusters is None:
            self.initial_round()
            acc, _ = self.evaluate()
            all_idx = np.arange(self.fed.num_clients)
            T0, E0 = self.allocate(all_idx)
            hist.accuracy.append(acc)
            hist.T_k.append(float(T0))
            hist.E_k.append(float(E0))
            hist.selected.append(all_idx)
        for k in range(rounds):
            res = self.round(method)
            hist.append(res)
            if ck is not None:
                ck.maybe(k, hist)
            if target and res.accuracy >= target and hist.rounds_to_target is None:
                hist.rounds_to_target = k + 1
                break
        return hist

    def _run_paged(self, selector, method, rounds: int,
                   target: float, include_initial_round: bool,
                   history: Optional[FLHistory] = None,
                   ck: Optional["_Checkpointer"] = None) -> FLHistory:
        """The population-scale host loop over the paged store.

        Differences from the dense host loop, both deliberate:
        the Alg.-2 initial round (which trains ALL N devices) runs only
        when requested or when the selector actually needs clusters — a
        million-client fleet with a cluster-free policy (random / icas /
        rra / stochastic-sched) skips it entirely; and round-level churn
        flips the stats table's availability mask between rounds, with
        selection filtered against it. With ``include_initial_round=True``
        and ``div_refresh_every=1`` the loop is bit-identical to the dense
        host loop (pinned in ``tests/test_paged_store.py``)."""
        hist = history if history is not None else FLHistory()
        if include_initial_round or (self.clusters is None and
                                     getattr(selector, "needs_clusters",
                                             False)):
            self.initial_round()
            acc, _ = self.evaluate()
            all_idx = np.arange(self.fed.num_clients)
            T0, E0 = self.allocate(all_idx)
            hist.accuracy.append(acc)
            hist.T_k.append(float(T0))
            hist.E_k.append(float(E0))
            hist.selected.append(all_idx)
        churn_on = self.churn != (0.0, 0.0)
        for k in range(rounds):
            with span("round", seed=self.seed, round=k):
                if churn_on:
                    self._churn_step_host()
                res = self.round(method)
            hist.append(res)
            if ck is not None:
                ck.maybe(k, hist)
            if (target and res.accuracy >= target
                    and hist.rounds_to_target is None):
                hist.rounds_to_target = k + 1
                break
        return hist

    def _run_async_paged(self, selector, rounds: int, target: float,
                         include_initial_round: bool,
                         history: Optional[FLHistory] = None,
                         ck: Optional["_Checkpointer"] = None) -> FLHistory:
        """Buffered-asynchronous ticks over the paged store — the host
        composition of ``async_engine._paged_async_step_program``'s jitted
        pieces, with store paging in between.

        Per tick: (host) refresh the stats table's divergence column per
        the ``div_refresh_every`` cadence (1 = every tick = exactly the
        dense select signal; 0 = never, staleness bounded by
        ``stats.drift``) and push it into the carry → ``sched`` (churn →
        select → in-flight filter) → (host) page the cohort's data in →
        ``plan`` (allocate → completion pricing → fire plan) → ``train``
        (O(K·P)) → (host) ``store.stage`` the trained rows and gather the
        M candidate rows back → ``fire`` (O(M·P) fold + eval) → (host)
        release fired staging, fold ‖g_new − g_old‖ into the drift
        bounds. Device memory is O(k_max·P + M·P) at any N; the math, op
        order and PRNG stream are the dense tick's, pinned bit-identical
        in ``tests/test_async_paged.py``.

        Unlike the dense scanned engine this is a host loop, so
        ``target_accuracy`` early stopping IS supported here."""
        from repro.core.async_engine import _paged_async_step_program
        prog = _paged_async_step_program(
            self.engine.cfg, selector, self.allocator,
            self.aggregator.registry_name,
            tuple(sorted(self.aggregator.params().items())),
            self.compressor, self.traced_context(), self.fl.feature_layer,
            self.channel, self.churn, self.faults, self.quarantine_after)
        hist = history if history is not None else FLHistory()
        if include_initial_round or (self.clusters is None and
                                     getattr(selector, "needs_clusters",
                                             False)):
            self.initial_round()
            acc, _ = self.evaluate()
            all_idx = np.arange(self.fed.num_clients)
            T0, E0 = self.allocate(all_idx)
            hist.accuracy.append(acc)
            hist.T_k.append(float(T0))
            hist.E_k.append(float(E0))
            hist.selected.append(all_idx)
        arr = dict(fleet_arrays(self.fleet))
        arr.pop("xgain", None)           # single-cell: no cross gains
        store, stats = self._store, self.stats
        n = self.fed.num_clients
        needs_div = getattr(selector, "needs_divergence", False)
        state = self.traced_state()
        state = prog.init_channel(state, arr)
        for k in range(rounds):
            with span("round", seed=self.seed, round=k):
                if needs_div:
                    # serve selection from the refreshed stats table — the
                    # paged replacement for the dense full-plane reduction
                    div = self._paged_divergences()
                    state = state._replace(sched=state.sched._replace(
                        divergence=jnp.asarray(div)))
                state, arr_f, idx, mask = prog.sched(state, arr)
                idx_h = np.asarray(idx)
                mask_h = np.asarray(mask)
                # the host-side mirror of the device gather's clamped OOB
                # sentinel: padding lanes read client N-1's data, train, and
                # are dropped by the mask — identical PRNG consumption
                idx_c = np.minimum(idx_h, n - 1)
                images_sel = self._client_images(idx_c)
                labels_sel = self._labels[jnp.asarray(idx_c)]
                (state, T, E, cand, fired_cand, w_cand, good,
                 traces) = prog.plan(state, arr_f, idx, mask, self._sizes)
                state, rows = prog.train(state, idx, images_sel, labels_sel,
                                         self.engine.frozen)
                live = idx_h[mask_h]
                # persist the GOOD lanes only (== mask when fault-free): a
                # dropped/corrupted dispatch never reaches the store, exactly
                # like the dense tick's sentinel scatter
                good_h = np.asarray(good)
                stored = idx_h[good_h]
                if stored.size:
                    store.stage(stored,
                                rows[jnp.asarray(np.flatnonzero(good_h))])
                cand_h = np.asarray(cand)
                cand_rows = store.gather_staged(cand_h)
                state, acc, div_cand, g_delta, ok_cand = prog.fire(
                    state, cand, cand_rows, w_cand, fired_cand,
                    self.test_images, self.test_labels, self.engine.frozen)
                fired_h = np.asarray(fired_cand)
                fired_ids = cand_h[fired_h]
                store.release_staged(fired_ids)
                # stats-table upkeep, the per-tick version of the sync loop's
                # _finish_paged_round: every stale bound grows by this fold's
                # global step (exactly 0 on an empty fire); fired clients get
                # their exact refreshed divergence back from the fold —
                # except lanes the non-finite guard rejected (ok_cand=False),
                # whose divergence entry must not turn NaN
                stats.drift[store.touched] += float(g_delta)
                ok_h = np.asarray(ok_cand)
                ok_ids = cand_h[ok_h]
                if ok_ids.size:
                    stats.divergence[ok_ids] = np.asarray(div_cand)[ok_h]
                    stats.drift[ok_ids] = 0.0
                self._gvec_host = np.asarray(state.params)
                self._rounds_since_refresh = min(
                    self._rounds_since_refresh + 1, np.iinfo(np.int32).max - 1)
                part, stale, active = traces
                acc = float(acc)
                hist.accuracy.append(acc)
                hist.T_k.append(float(T))
                hist.E_k.append(float(E))
                hist.selected.append(live)
                hist.participation.append(float(part))
                hist.staleness.append(float(stale))
                hist.active.append(float(active))
            if ck is not None and ck.due(k):
                # fold the carry into the host tables (read-only on the
                # device state), snapshot, keep driving the same carry
                self._fold_async_carry(state)
                ck.save(k, hist)
            if (target and acc >= target
                    and hist.rounds_to_target is None):
                hist.rounds_to_target = k + 1
                break
        self._fold_async_carry(state)
        return hist

    def _fold_async_carry(self, state: RoundState):
        """Fold an async carry back into the host source of truth:
        params/key/opt state, plus the scheduler columns. divergence/
        drift stay host-maintained (the table already holds the refreshed
        values). Read-only on ``state`` — callable mid-loop (checkpoint
        snapshots) as well as at the end of the run."""
        spec = self.engine.flat_spec
        self.global_params = unflatten_vector(spec, state.params)
        self.key = state.key
        self.aggregator.load_flat_state(state.opt_state, spec)
        sched = state.sched
        stats = self.stats
        for col in ("age", "t_done", "avail", "t_now", "faults", "strikes"):
            np.copyto(getattr(stats, col), np.asarray(getattr(sched, col)))

    # ------------------------------------------------------------------
    # checkpoint / resume (repro.train.checkpoint under the hood)
    # ------------------------------------------------------------------
    def save_checkpoint(self, directory: str, round_idx: int,
                        history: Optional[FLHistory] = None,
                        spec_dict: Optional[dict] = None,
                        keep_last: int = 3) -> str:
        """Atomic full-state snapshot → ``directory/round_%06d/``.

        Contents: the flat global row, the JAX PRNG key, the aggregator's
        flat optimizer state, cluster labels, the O(N) stats table
        (``leaves.npz`` + ``manifest.json`` via ``repro.train.checkpoint``)
        and the client store's rows as chunk-streamed ``store_*.npz``
        blocks — O(chunk·P) peak host memory; a paged store writes only
        its touched rows (the base row is rebuilt from the spec). The
        numpy RNG state, the run history and the (optional) spec ride in
        the manifest extras. The snapshot directory is written under a
        temporary name and ``os.replace``d into place, then the
        ``LATEST`` pointer flips — a killed writer can never leave a
        half-readable snapshot behind. Returns the snapshot path.
        """
        from repro.train import checkpoint as ckpt
        os.makedirs(directory, exist_ok=True)
        final = os.path.join(directory, "round_%06d" % int(round_idx))
        tmp = final + ".tmp"
        import shutil
        for stale in (tmp, final):
            if os.path.isdir(stale):
                shutil.rmtree(stale)
        gvec = tree_flatten_vector(self.global_params)
        opt = self.aggregator.init_flat_state(gvec)
        tree = {
            "gvec": np.asarray(gvec),
            "key": np.asarray(self.key),
            "labels": (np.zeros(self.fed.num_clients, np.int32)
                       if self.cluster_labels is None
                       else np.asarray(self.cluster_labels, np.int32)),
            "opt": (np.zeros((0,), np.float32) if opt is None
                    else np.asarray(opt)),
            "stats": {k: np.asarray(v)
                      for k, v in self.stats._asdict().items()},
        }
        extra = {
            "round": int(round_idx),
            "store_kind": self._store.kind,
            "opt_none": opt is None,
            "has_clusters": self.cluster_labels is not None,
            "rounds_since_refresh": int(self._rounds_since_refresh),
            "rng_state": self.rng.bit_generator.state,
            "spec": spec_dict,
            "history": None if history is None else history.to_dict(),
        }
        ckpt.save_checkpoint(tmp, tree, step=int(round_idx), extra=extra)
        self._save_store_rows(tmp)
        os.replace(tmp, final)
        ckpt.write_latest(directory, os.path.basename(final))
        if keep_last:
            snaps = sorted(d for d in os.listdir(directory)
                           if d.startswith("round_")
                           and not d.endswith(".tmp"))
            for name in snaps[:-keep_last]:
                shutil.rmtree(os.path.join(directory, name),
                              ignore_errors=True)
        return final

    def _save_store_rows(self, path: str) -> None:
        """Stream the client store into ``store_*.npz`` blocks of
        ``{idx, rows}`` pairs — O(chunk·P) peak beyond the store itself."""
        store = self._store
        if store.kind == "paged":
            tidx = np.flatnonzero(store.touched)
            for ci, s in enumerate(range(0, tidx.size, self.chunk_size)):
                b = tidx[s:s + self.chunk_size]
                np.savez(os.path.join(path, "store_%05d.npz" % ci),
                         idx=b, rows=np.asarray(store.gather(b)))
            return
        start, ci = 0, 0
        for block in store.iter_chunks(self.chunk_size):
            c = block.shape[0]
            np.savez(os.path.join(path, "store_%05d.npz" % ci),
                     idx=np.arange(start, start + c), rows=np.asarray(block))
            start += c
            ci += 1

    def load_checkpoint(self, directory: str,
                        expected_spec: Optional[dict] = None):
        """Restore a :meth:`save_checkpoint` snapshot into this FRESHLY
        BUILT experiment (same spec — pass ``expected_spec`` to have the
        manifest's recorded spec verified). ``directory`` may be the
        snapshot itself or a parent holding ``round_*`` dirs + ``LATEST``.
        Returns ``(round_idx, history)`` — feed them back into
        :meth:`run` as ``checkpoint_offset``/``history`` with
        ``include_initial_round=False`` for a bit-identical continuation.
        """
        from repro.train import checkpoint as ckpt
        path = ckpt.latest_checkpoint(directory)
        extra = ckpt.checkpoint_extra(path)
        if extra.get("store_kind") != self._store.kind:
            raise ValueError(
                f"checkpoint was taken on store={extra.get('store_kind')!r}"
                f" but this experiment runs store={self._store.kind!r}")
        if (expected_spec is not None and extra.get("spec") is not None
                and extra["spec"] != expected_spec):
            diff = sorted(k for k in set(extra["spec"]) | set(expected_spec)
                          if extra["spec"].get(k) != expected_spec.get(k))
            raise ValueError(
                "checkpoint spec does not match this experiment's spec "
                f"(differing fields: {diff}); resume rebuilds from the "
                "checkpoint's own spec")
        gvec = tree_flatten_vector(self.global_params)
        template = {
            "gvec": np.asarray(gvec),
            "key": np.asarray(self.key),
            "labels": np.zeros(self.fed.num_clients, np.int32),
            "opt": (np.zeros((0,), np.float32) if extra["opt_none"]
                    else np.zeros(gvec.shape, np.float32)),
            "stats": {k: np.asarray(v)
                      for k, v in self.stats._asdict().items()},
        }
        tree = ckpt.load_checkpoint(path, template)
        spec = self.engine.flat_spec
        self.global_params = unflatten_vector(spec, jnp.asarray(tree["gvec"]))
        self.key = jnp.asarray(tree["key"])
        if extra["has_clusters"]:
            self.cluster_labels = np.asarray(tree["labels"])
            self.clusters = clusters_from_labels(self.cluster_labels,
                                                 self.fl.num_clusters)
        else:
            self.cluster_labels = None
            self.clusters = None
        self.aggregator.reset()
        if not extra["opt_none"]:
            self.aggregator.load_flat_state(jnp.asarray(tree["opt"]), spec)
        st = self.stats
        for name, arr in tree["stats"].items():
            np.copyto(getattr(st, name), arr)
        self.rng.bit_generator.state = extra["rng_state"]
        self._rounds_since_refresh = int(extra["rounds_since_refresh"])
        self._load_store_rows(path)
        if self._store.kind == "paged":
            self._gvec_host = np.asarray(tree["gvec"], np.float32)
        hist = (None if extra.get("history") is None
                else FLHistory.from_dict(extra["history"]))
        return int(extra["round"]), hist

    def _load_store_rows(self, path: str) -> None:
        import glob
        for fn in sorted(glob.glob(os.path.join(path, "store_*.npz"))):
            with np.load(fn) as data:
                idx, rows = data["idx"], data["rows"]
            if idx.size:
                self._store.scatter(idx, jnp.asarray(rows))

    # ------------------------------------------------------------------
    # device-resident path: the whole experiment as one lax.scan program
    # ------------------------------------------------------------------
    def traceable(self, selector: Any = None) -> bool:
        """True when the configured strategy bundle supports the scanned
        device-resident pipeline. The pipeline drives the FLAT-plane
        contract, so aggregators/compressors must implement it on top of
        ``traceable=True`` — a strategy written against the pre-flat
        stacked contract falls back to the host loop instead of failing
        mid-trace."""
        selector = self.selector if selector is None else selector
        return (all(getattr(s, "traceable", False)
                    for s in (selector, self.allocator, self.aggregator,
                              self.compressor, self.channel))
                and all(hasattr(self.aggregator, m)
                        for m in ("aggregate_flat", "init_flat_state",
                                  "load_flat_state"))
                and hasattr(self.compressor, "apply_flat"))

    def traced_context(self) -> TracedContext:
        return TracedContext(num_devices=self.fed.num_clients,
                             devices_per_round=self.fl.devices_per_round,
                             selected_per_cluster=self.fl.selected_per_cluster,
                             num_clusters=self.fl.num_clusters,
                             bandwidth_mhz=self.B)

    def traced_state(self) -> RoundState:
        """Snapshot the experiment's mutable state as the scan carry —
        weights on the flat parameter plane (global as one [P] row, the
        client buffer as-is). The scanned program DONATES this state, so
        every leaf handed over here is consumed; ``load_traced_state``
        rebinds the driver's references from the result."""
        labels = (jnp.zeros((self.fed.num_clients,), jnp.int32)
                  if self.cluster_labels is None
                  else jnp.asarray(self.cluster_labels, jnp.int32))
        gvec = tree_flatten_vector(self.global_params)
        # the stats plane: async-capable programs carry the store's stats
        # table (device copy) in the sched slot — incremental run() calls
        # continue the virtual clock because load_traced_state folds it
        # back. Synchronous programs carry None, UNLESS fault tracking /
        # quarantine needs the fault-counter columns in the carry. A
        # paged store has no [N, P] buffer; its programs run
        # plane="stats" and never read client_params, so a zero-row
        # placeholder rides the slot.
        sched = (self.stats.device()
                 if (getattr(self.aggregator, "async_capable", False)
                     or self._track_faults)
                 else None)
        client_plane = (self._store.buffer
                        if self._store.kind == "dense"
                        else jnp.zeros((0,), jnp.float32))
        return RoundState(
            params=gvec, client_params=client_plane,
            opt_state=self.aggregator.init_flat_state(gvec),
            key=self.key, labels=labels, sched=sched)

    def load_traced_state(self, state: RoundState, *,
                          clusters_valid: bool = True):
        """Sync a (final) scan carry back into the host driver, so a traced
        run can be inspected or continued by the Python loop."""
        spec = self.engine.flat_spec
        self.global_params = _unflatten_vector_jit(spec, state.params)
        if self._store.kind == "dense":
            self.client_params = state.client_params
        self.key = state.key
        sched = getattr(state, "sched", None)
        if sched is not None:
            # fold the scheduler carry back into the store's stats table
            # (the single source of per-client truth)
            self.stats.load(sched)
        self.aggregator.load_flat_state(state.opt_state, spec)
        if clusters_valid:
            self.cluster_labels = np.asarray(state.labels)
            self.clusters = clusters_from_labels(self.cluster_labels,
                                                 self.fl.num_clusters)

    def _run_traced(self, selector, rounds: int,
                    include_initial_round: bool) -> FLHistory:
        """The scanned program in three host spans under ``repro.run``:
        ``launch`` (program lookup, carry, placement, the asynchronous
        call), ``wait`` (the device's execution) and ``fetch`` (the carry
        back into the driver, the history to the host)."""
        with span("run", seed=self.seed):
            with span("run.launch", seed=self.seed):
                with_init, res = self._launch_traced(selector, rounds,
                                                     include_initial_round)
            with span("run.wait", seed=self.seed):
                jax.block_until_ready(res)
            with span("run.fetch", seed=self.seed):
                self.load_traced_state(res.state,
                                       clusters_valid=with_init
                                       or self.cluster_labels is not None)
                return self.history_from_traced(res, with_init,
                                                self.fed.num_clients)

    def _launch_traced(self, selector, rounds: int,
                       include_initial_round: bool):
        """Dispatch the scanned program; returns ``(with_init, result)``
        with the result still computing on the device."""
        with_init = include_initial_round or self.clusters is None
        fn = run_rounds(self.engine.cfg, selector=selector,
                        allocator=self.allocator, aggregator=self.aggregator,
                        compressor=self.compressor,
                        tctx=self.traced_context(),
                        feature_layer=self.fl.feature_layer,
                        rounds=rounds, with_init=with_init,
                        channel=self.channel, churn=self.churn,
                        faults=self.faults,
                        quarantine_after=self.quarantine_after)
        state = self.traced_state()
        mesh = None
        if self.p_shards:
            # P-axis GSPMD: lay the carry's P-sized dims out over a `model`
            # mesh before dispatch — the scanned program's donated carry
            # keeps the layout for the whole run. Composes with the cohort
            # shard_map (which owns the lane axis, never P).
            from repro.sharding.specs import plane_mesh, plane_shardings
            mesh = plane_mesh(self.p_shards)
            if mesh is not None:
                state = jax.device_put(
                    state, plane_shardings(state, mesh,
                                           int(state.params.shape[0])))
        # the mesh rides the trace context so the kernel seams
        # (repro.kernels.ops) can put their Mosaic calls in a shard_map
        with (jax.set_mesh(mesh) if mesh is not None
              else contextlib.nullcontext()):
            res = fn(state, self._images, self._labels,
                     self._sizes, fleet_arrays(self.fleet),
                     self.test_images, self.test_labels, self.engine.frozen)
        return with_init, res

    @staticmethod
    def history_from_traced(res: TracedRunResult, with_init: bool,
                            num_devices: int) -> FLHistory:
        """One device→host transfer of a scanned run's stacked history."""
        hist = FLHistory()
        accs, Ts, Es, sel, msk = (np.asarray(x) for x in (
            res.rounds.accuracy, res.rounds.T, res.rounds.E,
            res.rounds.selected, res.rounds.mask))
        if with_init:
            hist.accuracy.append(float(res.init_accuracy))
            hist.T_k.append(float(res.init_T))
            hist.E_k.append(float(res.init_E))
            hist.selected.append(np.arange(num_devices))
        hist.accuracy.extend(float(a) for a in accs)
        hist.T_k.extend(float(t) for t in Ts)
        hist.E_k.extend(float(e) for e in Es)
        hist.selected.extend(sel[k][msk[k]] for k in range(sel.shape[0]))
        if res.rounds.participation is not None:
            hist.participation.extend(
                float(x) for x in np.asarray(res.rounds.participation))
            hist.staleness.extend(
                float(x) for x in np.asarray(res.rounds.staleness))
            hist.active.extend(
                float(x) for x in np.asarray(res.rounds.active))
        return hist
