"""CohortRunner — vmapped (seeds × cells) cohorts over the device-resident
round pipeline.

The paper's headline figures are all sweeps (many seeds × selectors × σ);
with the whole experiment traced (``engine.run_rounds``), a cohort of
seeds/fleet draws becomes ONE compiled program: the per-seed
state/data/fleet pytrees are stacked on a leading cohort axis, ``vmap``
maps the scanned multi-round run over it, and ``jax.sharding`` splits that
axis across the local devices. One dispatch, one device→host transfer for
the entire cohort history.

Multi-cell ``FleetSpec`` scenarios with a dynamic-interference channel
(``multicell-dynamic``) vmap over SEEDS only: each seed's cells ride an
inner ``[C]`` axis INSIDE the traced program (``engine``'s cells axis),
where one cross-cell reduction per round couples their selections.
Uncoupled multi-cell sweeps (build-time interference) keep the flat
(seed, cell) lane layout so the mesh shards every lane across devices.
Either way the history exposes the flat lane layout ``s·C + c`` (seed
``s``, cell ``c``) and the sweep is ONE scanned program.

    runner = CohortRunner(ExperimentSpec(..., cohort=8))
    ch = runner.run()                  # 8 seeds (× cells), one XLA program
    ch.accuracy                        # [8·C, rounds+1]
    ch.history(3)                      # lane 3's FLHistory view

The scanned program DONATES its state argument (the stacked
``[cohort, N, P]`` flat client plane updates in place); ``stack`` builds
fresh stacked buffers per dispatch and every experiment's references are
rebound from the result, so the donation is invisible to callers.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import TracedRunResult, run_rounds
from repro.core.fedavg import FLExperiment, FLHistory
from repro.core.wireless import fleet_arrays

__all__ = ["CohortHistory", "CohortRunner"]


def _tree_stack(trees):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def cohort_mesh(cohort_size: int):
    """A 1-axis ``("cohort",)`` mesh over ``min(local devices, cohort)``
    devices, or None on a single-device host (plain vmap).

    The cohort axis need not divide the device count: the runner PADS it up
    to the next multiple (``_mesh_pad``) and strips the pad lanes from the
    history, so no local device idles. (The old behavior — shrink to the
    largest divisor — silently serialized awkward sizes: 5 lanes on 4
    devices degenerated to a single-device vmap running all 5
    sequentially.)"""
    devs = jax.devices()
    n = min(len(devs), cohort_size)
    if n <= 1:
        return None
    return jax.sharding.Mesh(np.array(devs[:n]), ("cohort",))


def _mesh_pad(lanes: int, mesh) -> int:
    """How many pad lanes make ``lanes`` divide the mesh's device count."""
    if mesh is None:
        return 0
    return (-lanes) % mesh.devices.size


def _shard_cohort(tree, mesh):
    """Pre-place every leaf's leading (cohort) axis onto the mesh devices,
    so the sharded program starts without a host→device reshuffle."""
    if mesh is None:
        return tree
    sharding = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("cohort"))
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, sharding), tree)


@dataclass
class CohortHistory:
    """Stacked round histories for a (seeds × cells) cohort; the leading
    axis is the lane ``seed_index · cells + cell`` (``cells == 1`` keeps
    the old seed-only layout)."""
    seeds: List[int]                  # per-lane seed
    accuracy: np.ndarray              # [B, rounds(+1)]
    T_k: np.ndarray                   # [B, rounds(+1)]
    E_k: np.ndarray                   # [B, rounds(+1)]
    selected: np.ndarray              # [B, rounds, S_pad] padded indices
    mask: np.ndarray                  # [B, rounds, S_pad] participation
    with_init: bool
    num_devices: int
    cells: int = 1                    # cells per seed (lane = s·cells + c)
    inr: Optional[np.ndarray] = None  # [B, rounds] per-round selection-
                                      # driven I/N0 at each lane's BS
                                      # (dynamic-interference channels only)
    # buffered-asynchronous per-tick traces (None on synchronous runs):
    participation: Optional[np.ndarray] = None  # [B, rounds] updates folded
    staleness: Optional[np.ndarray] = None      # [B, rounds] mean fired age
    active: Optional[np.ndarray] = None         # [B, rounds] available fleet

    @property
    def lane_cells(self) -> List[int]:
        """Per-lane cell index (parallel to ``seeds``)."""
        return [i % self.cells for i in range(len(self.seeds))]

    def __len__(self) -> int:
        return len(self.seeds)

    def history(self, i: int) -> FLHistory:
        """Seed ``i``'s run as a plain ``FLHistory`` (padding stripped)."""
        hist = FLHistory()
        hist.accuracy = [float(a) for a in self.accuracy[i]]
        hist.T_k = [float(t) for t in self.T_k[i]]
        hist.E_k = [float(e) for e in self.E_k[i]]
        if self.with_init:
            hist.selected.append(np.arange(self.num_devices))
        hist.selected.extend(self.selected[i][k][self.mask[i][k]]
                             for k in range(self.selected.shape[1]))
        return hist

    @property
    def final_accuracy(self) -> np.ndarray:
        return self.accuracy[:, -1]


class CohortRunner:
    """Run one ``ExperimentSpec`` across a batch of seeds as a single
    compiled, device-sharded program.

    Per-seed datasets/partitions/fleets are materialized host-side through
    the normal ``build_experiment`` factory (so seed-derivation semantics
    match single runs exactly), stacked, and handed to the vmapped
    ``engine.run_rounds``. Requires every configured strategy to be
    traceable (``FLExperiment.traceable``).

    Note on stochastic selection: random/kmeans_random/rra draw from
    ``jax.random`` here (keyed off each seed's PRNG stream), not the host
    numpy Generator the Python loop uses — per-seed histories are
    reproducible run-to-run but differ from a host-loop run of the same
    seed. Deterministic selectors (divergence, icas) match the host loop
    bit-for-bit.
    """

    def __init__(self, spec):
        if getattr(spec, "store", "dense") != "dense":
            raise ValueError(
                "CohortRunner scans the dense [N, P] client plane as a "
                "vmapped carry; a paged ClientStore serves rows on demand "
                "(store.gather / iter_client_trees) through the host "
                "drivers instead — run the seeds one at a time via "
                "build_experiment(spec) / FLExperiment.run")
        self.spec = spec
        self.experiments: List[FLExperiment] = []

    # ------------------------------------------------------------------
    @property
    def num_cells(self) -> int:
        return getattr(self.spec, "num_cells", 1)

    def _build(self, seeds: Sequence[int]) -> List[FLExperiment]:
        from repro.api.build import build_experiment
        exps = [build_experiment(self.spec.replace(seed=int(s)), cell=c)
                for s in seeds for c in range(self.num_cells)]
        counts = {e.fed.num_clients for e in exps}
        if len(counts) > 1:
            raise ValueError(
                "CohortRunner stacks (seed, cell) lanes into one vmapped "
                f"program; all cells need equal device counts, got {counts}")
        return exps

    def run(self, seeds: Optional[Sequence[int]] = None,
            rounds: Optional[int] = None,
            reuse_experiments: bool = False,
            transfer_guard: bool = False) -> CohortHistory:
        """Execute the cohort. ``reuse_experiments=True`` skips rebuilding
        the per-seed datasets/fleets when this runner already holds them
        (benchmarking repeat runs; training state continues where it was).

        ``transfer_guard=True`` wraps the single program dispatch in
        ``jax.transfer_guard_device_to_host("disallow")`` — the CI bench
        gate proving the whole multi-round cohort really executes as ONE
        scanned program with no per-round host round-trips (any mid-run
        device→host sync raises instead of silently serializing).
        """
        if seeds is None:
            seeds = [self.spec.seed + i
                     for i in range(max(int(getattr(self.spec, "cohort", 1)),
                                        1))]
        seeds = [int(s) for s in seeds]
        cells = self.num_cells
        lane_seeds = [s for s in seeds for _ in range(cells)]
        rounds = rounds or self.spec.rounds
        if reuse_experiments and len(self.experiments) == len(lane_seeds):
            exps = self.experiments
        else:
            exps = self.experiments = self._build(seeds)
        e0 = exps[0]
        if not e0.traceable():
            raise ValueError(
                "CohortRunner needs an all-traceable strategy bundle; "
                f"got selector={e0.selector.registry_name!r}, "
                f"allocator={e0.allocator.registry_name!r}, "
                f"aggregator={e0.aggregator.registry_name!r}, "
                f"compressor={e0.compressor.registry_name!r}")

        # A dynamic-interference channel needs the cells of one seed INSIDE
        # one program instance (the engine's cells axis) so their per-round
        # selections can couple — then the cohort axis is SEEDS. Uncoupled
        # multi-cell sweeps keep the flat (seed, cell) lane layout so the
        # mesh can still shard every lane across devices. Pad lanes
        # replicate the last group up to a device-count multiple and are
        # stripped from the history.
        dynamic = cells > 1 and getattr(e0.channel, "dynamic", False)
        prog_cells = cells if dynamic else 1
        if dynamic:
            groups = [exps[i * cells:(i + 1) * cells]
                      for i in range(len(seeds))]
        else:
            groups = [[e] for e in exps]
        mesh = cohort_mesh(len(groups))
        pad = _mesh_pad(len(groups), mesh)
        groups = groups + [groups[-1]] * pad

        def stack(fn):
            per_lane = [(_tree_stack([fn(e) for e in g]) if prog_cells > 1
                         else fn(g[0])) for g in groups]
            return _shard_cohort(_tree_stack(per_lane), mesh)

        state = stack(lambda e: e.traced_state())
        images = stack(lambda e: e._images)
        labels = stack(lambda e: e._labels)
        sizes = stack(lambda e: e._sizes)
        arr = stack(lambda e: fleet_arrays(e.fleet))
        # the evaluation set is shared across the cohort iff every seed
        # resolves the same test data (the common sweep protocol); it is
        # stacked per outer lane — never on the inner cells axis, which a
        # seed's cells always share
        test_shared = len({e.spec.resolved_test_seed if hasattr(e, "spec")
                           else id(e) for e in exps}) == 1
        if test_shared:
            test_images, test_labels = e0.test_images, e0.test_labels
        else:
            test_images = _shard_cohort(
                jnp.stack([g[0].test_images for g in groups]), mesh)
            test_labels = _shard_cohort(
                jnp.stack([g[0].test_labels for g in groups]), mesh)

        fn = run_rounds(e0.engine.cfg, selector=e0.selector,
                        allocator=e0.allocator, aggregator=e0.aggregator,
                        compressor=e0.compressor, tctx=e0.traced_context(),
                        feature_layer=e0.fl.feature_layer, rounds=rounds,
                        with_init=True, cohort=True,
                        test_shared=test_shared, mesh=mesh,
                        channel=e0.channel, cells=prog_cells,
                        churn=getattr(e0, "churn", (0.0, 0.0)))
        frozen = e0.engine.frozen
        if transfer_guard:
            with jax.transfer_guard_device_to_host("disallow"):
                res: TracedRunResult = fn(state, images, labels, sizes, arr,
                                          test_images, test_labels, frozen)
        else:
            res = fn(state, images, labels, sizes, arr,
                     test_images, test_labels, frozen)

        # sync each real lane's final state back into its host experiment
        # (pad lanes are dropped)
        for i, e in enumerate(exps):
            s, c = divmod(i, prog_cells)
            pick = ((lambda x, s=s, c=c: x[s, c]) if prog_cells > 1
                    else (lambda x, s=s: x[s]))
            e.load_traced_state(jax.tree_util.tree_map(pick, res.state))
        return self._history(lane_seeds, res, e0.fed.num_clients,
                             cells=cells, prog_cells=prog_cells)

    @staticmethod
    def _history(seeds, res: TracedRunResult, num_devices: int,
                 cells: int = 1, prog_cells: int = 1) -> CohortHistory:
        if prog_cells > 1:
            def lanes_first(x):
                """[S, R, C, ...] → [S·C, R, ...] (lane = s·cells + c)."""
                x = np.moveaxis(np.asarray(x), 2, 1)
                return x.reshape((-1,) + x.shape[2:])
        else:
            lanes_first = np.asarray
        accs, Ts, Es, sel, msk = (lanes_first(x) for x in (
            res.rounds.accuracy, res.rounds.T, res.rounds.E,
            res.rounds.selected, res.rounds.mask))
        acc0, T0, E0 = (np.asarray(x).reshape(-1)[:, None] for x in (
            res.init_accuracy, res.init_T, res.init_E))
        def extra(x):
            """Optional [B, R] trace (inr / async): lane-major, pads off."""
            return None if x is None else lanes_first(x)[:len(seeds)]
        B = len(seeds)                 # true lane count; pads sliced off
        return CohortHistory(
            seeds=list(seeds),
            accuracy=np.concatenate([acc0, accs], axis=1)[:B],
            T_k=np.concatenate([T0, Ts], axis=1)[:B],
            E_k=np.concatenate([E0, Es], axis=1)[:B],
            selected=sel[:B], mask=msk[:B], with_init=True,
            num_devices=num_devices, cells=cells,
            inr=extra(res.rounds.inr),
            participation=extra(res.rounds.participation),
            staleness=extra(res.rounds.staleness),
            active=extra(res.rounds.active))
