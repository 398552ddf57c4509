"""``build_experiment(spec)`` — the single factory from a declarative
``ExperimentSpec`` to a runnable ``FLExperiment``. Replaces the scattered
kwargs of the legacy ``FLExperiment.__init__`` / ``fl_sim.run`` call sites.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Optional, Tuple

import numpy as np

from repro.api.registry import (AGGREGATORS, ALLOCATORS, CHANNELS,
                                COMPRESSORS, SELECTORS)
from repro.api.scenario import CELL_SEED_STRIDE, build_fleet
from repro.api.spec import ExperimentSpec
from repro.configs.base import FLConfig
from repro.configs.paper_cnn import CNN_CONFIGS
from repro.utils.trace import span


#: clients at/above which a paged build keeps the data partition lazy
#: (index-backed); below it even paged experiments materialize the
#: per-client image stack so the host round loop stays simple
LAZY_PARTITION_MIN = 50_000


def fl_config_from_spec(spec: ExperimentSpec,
                        num_devices: Optional[int] = None) -> FLConfig:
    return FLConfig(num_devices=num_devices or spec.clients,
                    devices_per_round=spec.devices_per_round,
                    local_iters=spec.local_iters,
                    num_clusters=spec.num_clusters,
                    selected_per_cluster=spec.selected_per_cluster,
                    learning_rate=spec.learning_rate,
                    sigma=spec.sigma,
                    target_accuracy=spec.target_accuracy,
                    max_rounds=spec.rounds,
                    selection=spec.selection["name"],
                    feature_layer=spec.feature_layer)


# a multi-cell cohort asks for every cell of the same build (seed × C
# lanes) — cache the whole-fleet build so the O(C²·N) interference
# geometry runs once per seed, not once per lane. Fleets are never
# mutated in place (select/with_power/replace all copy), so sharing the
# object across experiments is safe.
_FLEET_CACHE: "OrderedDict[tuple, object]" = OrderedDict()
_FLEET_CACHE_MAX = 16


def _built_fleet(fs, seed: int, clients: Optional[int],
                 bandwidth_mhz: float):
    key = (fs.to_json(), seed, clients, bandwidth_mhz)
    fleet = _FLEET_CACHE.get(key)
    if fleet is None:
        fleet = _FLEET_CACHE[key] = build_fleet(
            fs, seed, clients=clients, bandwidth_mhz=bandwidth_mhz)
        while len(_FLEET_CACHE) > _FLEET_CACHE_MAX:
            _FLEET_CACHE.popitem(last=False)
    else:
        _FLEET_CACHE.move_to_end(key)
    return fleet


def fleet_for_cell(spec: ExperimentSpec, cell: int = 0):
    """The (sub-)fleet cell ``cell`` serves, plus the resolved channel.

    ``spec.fleet is None`` keeps the legacy ``sample_fleet`` path (bit-
    identical by construction); a ``FleetSpec`` goes through the scenario
    builder — whose default single static cell reproduces the same draws.
    """
    from repro.core.wireless import sample_fleet

    if spec.fleet is None:
        if cell:
            raise ValueError("cell > 0 needs a multi-cell FleetSpec "
                             "(ExperimentSpec.fleet)")
        return (sample_fleet(spec.clients, seed=spec.resolved_fleet_seed),
                CHANNELS.resolve("static"))
    fs = spec.fleet
    if not 0 <= cell < fs.num_cells:
        raise ValueError(f"cell {cell} out of range for a "
                         f"{fs.num_cells}-cell FleetSpec")
    full = _built_fleet(fs, spec.resolved_fleet_seed, spec.clients,
                        spec.bandwidth_mhz)
    fleet = full.cell_fleet(cell) if fs.num_cells > 1 else full
    return fleet, CHANNELS.resolve(fs.channel)


def build_experiment(spec: ExperimentSpec, *, cell: int = 0,
                     test_data: Optional[Tuple[np.ndarray, np.ndarray]] = None):
    """Materialize dataset, partition, fleet and driver from ``spec``.

    ``cell`` selects one cell of a multi-cell ``FleetSpec`` (each cell is
    its own FL system sharing spectrum with the others; cross-cell coupling
    enters through the fleet's interference term). Cells reuse the shared
    dataset but partition it with decorrelated per-cell streams.

    ``test_data`` optionally overrides the held-out evaluation set (used by
    benchmarks that probe on a train slice instead).
    """
    from repro.core.fedavg import FLExperiment       # driver (late: cycle)
    from repro.models.registry import model_def_for, workload_config

    ids = dict(seed=spec.seed, cell=cell)
    with span("build", **ids):
        if spec.model in ("auto", "cnn"):
            model_cfg = CNN_CONFIGS[spec.dataset]
        else:
            model_cfg = workload_config(spec.model)
        mdef = model_def_for(model_cfg)

        with span("build.fleet", **ids):
            fleet, channel = fleet_for_cell(spec, cell)
        n = fleet.num_devices

        with span("build.data", **ids):
            fed, test_images, test_labels = _build_data(
                spec, model_cfg, mdef, n, cell, test_data)

        with span("build.driver", **ids):
            exp = FLExperiment(
                model_cfg, fed, test_images, test_labels, fleet,
                fl_config_from_spec(spec, num_devices=n),
                bandwidth_mhz=spec.bandwidth_mhz,
                selection=SELECTORS.resolve(spec.selection),
                allocator=ALLOCATORS.resolve(spec.allocator),
                aggregator=AGGREGATORS.resolve(spec.aggregator),
                compression=COMPRESSORS.resolve(spec.compressor),
                channel=channel,
                seed=spec.seed,
                batch_size=spec.batch_size,
                fedprox_mu=spec.fedprox_mu,
                churn=(spec.churn_leave, spec.churn_join),
                store=spec.store,
                k_max=spec.k_max,
                chunk_size=spec.chunk_size,
                div_refresh_every=spec.div_refresh_every,
                cluster=spec.cluster,
                p_shards=spec.p_shards,
                faults=spec.faults,
                quarantine_after=spec.quarantine_after)
    exp.spec = spec
    exp.cell = cell
    return exp


def _build_data(spec: ExperimentSpec, model_cfg, mdef, n: int, cell: int,
                test_data):
    """The experiment's training partition over ``n`` clients and its
    held-out evaluation set (``test_data`` when given)."""
    from repro.data import (make_dataset, partition_bias,
                            partition_bias_lazy)

    if mdef.make_dataset is not None:
        # self-synthesizing workloads (the LoRA LMs) build their own
        # datasets from the config; ``spec.dataset`` selects nothing
        ds = mdef.make_dataset(model_cfg, spec.train_samples,
                               seed=spec.resolved_data_seed)
    else:
        ds = make_dataset(spec.dataset, spec.train_samples,
                          seed=spec.resolved_data_seed)
    if test_data is None:
        test = (mdef.make_dataset(model_cfg, spec.test_samples,
                                  seed=spec.resolved_test_seed)
                if mdef.make_dataset is not None
                else make_dataset(spec.dataset, spec.test_samples,
                                  seed=spec.resolved_test_seed))
        test_images, test_labels = test.images, test.labels
    else:
        test_images, test_labels = test_data
    # population-scale paged fleets partition lazily: per-client sample
    # INDICES into the shared pool instead of a materialized
    # [N, D, H, W, C] stack (which at 1e6 clients would dwarf the model
    # plane the paged store exists to avoid)
    partition = (partition_bias_lazy
                 if spec.store == "paged" and n >= LAZY_PARTITION_MIN
                 else partition_bias)
    fed = partition(ds, n, spec.samples_per_client, spec.sigma,
                    seed=spec.resolved_partition_seed
                    + CELL_SEED_STRIDE * cell)
    return fed, test_images, test_labels


def build_cohort(spec: ExperimentSpec):
    """A ``CohortRunner`` for ``spec`` — seeds ``seed..seed+cohort-1``
    (× the FleetSpec's cells) run as one vmapped, device-sharded program
    (``repro.core.cohort``)."""
    if (spec.faults is not None and spec.faults.active) \
            or spec.quarantine_after > 0:
        raise ValueError(
            "fault injection / quarantine is not wired into the vmapped "
            "cohort program yet — run the spec through build_experiment "
            "(single-lane) instead, or drop the faults/quarantine_after "
            "fields")
    from repro.core.cohort import CohortRunner       # late: cycle
    return CohortRunner(spec)
