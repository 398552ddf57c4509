"""RoundEngine — the jit-compiled compute core of one FL round, split out
of the host driver so experiments that share hyper-parameters (seed sweeps,
σ sweeps, selector comparisons) also share XLA executables.

The engine is pure: it owns no model/cluster/rng state, only compiled
functions keyed by an ``EngineConfig``. The host driver
(``repro.core.fedavg.FLExperiment``) owns state and strategy objects and
calls into the engine.

``round_step`` is the fused fast path — local training of the selected
clients, eq. (4) weighted aggregation, and test-set evaluation in a single
XLA program — usable whenever the aggregator is the plain weighted mean and
no lossy uplink compression is configured; the driver otherwise composes
the unfused pieces with the strategy objects in between.

``run_rounds`` goes further: when every configured strategy is traceable,
the ENTIRE experiment — initial all-device round + K-means clustering
(Alg. 2), then K rounds of select → SAO allocate → vmapped local training →
aggregate → eval — compiles to a single ``lax.scan`` program. The whole
``FLHistory`` comes back as stacked arrays in one device→host transfer, and
the same program vmaps over a cohort axis (``repro.core.cohort``).

Model weights travel on the FLAT PARAMETER PLANE (one [P] global row, one
[N, P] client buffer; ``model_flat_spec``), every per-round reduction is a
single fused row op routed through ``repro.kernels.ops``, and the scanned
carry is donated — see ``docs/PERF.md``.

At population scale (``store="paged"``) the [N, P] plane never
materializes: the driver pages a host cold store (``repro.core.store``)
and the engine only ever sees the round's ACTIVE [K, P] rows
(``gather_rows`` / ``scatter_rows`` / ``rows_divergence``) — selection
reads the O(N) per-client statistics table instead of reducing the plane.
"""
from __future__ import annotations

import functools
from collections import OrderedDict
from dataclasses import dataclass, field
from types import SimpleNamespace
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.api.protocols import RoundState, TracedContext
from repro.core.algorithms import make_fedprox_local_update
from repro.kernels import ops
from repro.models.registry import model_def_for
from repro.utils.trace import count, span
from repro.utils.trees import (StackFlattenSpec, flatten_stacked,
                               stack_flatten_spec, unflatten_vector)

#: The round's phases, each a ``jax.named_scope`` named ``fl.<phase>``, so
#: every op a phase traces carries that name in its HLO ``op_name``
#: (backward ops as ``.../fl.train/.../transpose(jvp(...))/...``). An op's
#: phase is the LAST ``fl.<phase>`` in its ``op_name``; ops under none are
#: outside the phases, as are the copies the compiler adds after tracing
#: (they carry no ``op_name``). ``compress`` nests in ``train``.
PHASES = ("select", "allocate", "train", "compress", "faults", "aggregate",
          "eval")


def phase_scope(name: str):
    """The named scope ``fl.<name>`` of one of :data:`PHASES` (a context
    manager, or a decorator for a whole phase function). It changes op
    metadata only, never the computation."""
    if name not in PHASES:
        raise ValueError(f"unknown round phase {name!r}; known: {PHASES}")
    return jax.named_scope("fl." + name)


@functools.lru_cache(maxsize=64)
def model_flat_spec(model_cfg) -> StackFlattenSpec:
    """The flat-parameter-plane layout of ``model_cfg``'s PER-CLIENT
    trainable state — derived from shapes only (``eval_shape``), cached per
    config so every engine, driver, and traced program shares one spec
    object. ``model_cfg`` is any registered frozen model config
    (``CNNConfig`` → the full CNN pytree; ``LMConfig`` → the LoRA adapter
    tree only, so ``P = P_adapter`` across the whole plane)."""
    mdef = model_def_for(model_cfg)
    template = jax.eval_shape(functools.partial(mdef.init, model_cfg),
                              jax.ShapeDtypeStruct((2,), jnp.uint32))
    return stack_flatten_spec(template)


@functools.lru_cache(maxsize=4)
def frozen_params(model_cfg):
    """``model_cfg``'s frozen tree on the device (the :class:`ModelDef`'s
    ``frozen`` hook; None where the workload has none), placed once per
    process and config, in the span ``repro.build.frozen`` and counted by
    ``repro.count.frozen_upload``. Every program takes it as an argument
    (``frozen``), so no compiled program holds it as a constant."""
    mdef = model_def_for(model_cfg)
    if mdef.frozen is None:
        return None
    name = getattr(model_cfg, "name", type(model_cfg).__name__)
    with span("build.frozen", config=name):
        tree = jax.block_until_ready(jax.device_put(mdef.frozen(model_cfg)))
    count("frozen_upload", config=name)
    return tree


def frozen_kw(frozen) -> dict:
    """The keyword a model hook takes its frozen tree by: none at all for
    a workload without one, so its traced program is unchanged."""
    return {} if frozen is None else {"frozen": frozen}


def make_local_update(model_cfg, lr: float, local_iters: int,
                      batch_size: int):
    """One client's local training: L SGD steps on its own shard (Alg. 1
    lines 6-10, with the paper-endorsed SGD variant of §III-A). The loss
    comes from ``model_cfg``'s registered :class:`ModelDef` — for
    ``CNNConfig`` it IS the original ``cnn_loss`` function object, so the
    traced jaxpr is bit-identical to the pre-registry engine. ``frozen``
    (the workload's frozen tree, ``frozen_params``) goes to the loss."""
    loss_fn = model_def_for(model_cfg).loss

    def local_update(params, images, labels, key, frozen=None):
        loss = (loss_fn if frozen is None
                else functools.partial(loss_fn, frozen=frozen))

        def step(p, k):
            idx = jax.random.randint(k, (batch_size,), 0, images.shape[0])
            batch = {"images": images[idx], "labels": labels[idx]}
            g = jax.grad(loss)(p, batch, model_cfg)
            p = jax.tree_util.tree_map(lambda w, gw: w - lr * gw, p, g)
            return p, None

        keys = jax.random.split(key, local_iters)
        params, _ = jax.lax.scan(step, params, keys)
        return params

    return local_update


@functools.lru_cache(maxsize=64)
def model_eval(model_cfg):
    """``(params, test_x, test_y, **frozen_kw(frozen)) -> (accuracy,
    per_class)`` for ``model_cfg``'s workload (cached so every program
    traces one closure)."""
    mdef = model_def_for(model_cfg)
    return functools.partial(mdef.evaluate, cfg=model_cfg)


@dataclass(frozen=True)
class EngineConfig:
    """The static (compile-time) hyper-parameters of the round compute.

    ``model_cfg`` is the hashable frozen config of ANY registered workload
    (``CNNConfig``, ``LMConfig``, ...) — its value keys every compiled
    program and shared engine."""
    model_cfg: Any
    learning_rate: float
    local_iters: int
    batch_size: int
    fedprox_mu: float = 0.0


@dataclass
class RoundResult:
    """Everything one round produces (paper bookkeeping: eqs. 4, 10-11)."""
    selected: np.ndarray              # device indices that participated
    T_k: float                        # round delay [s]
    E_k: float                        # round energy [J]
    accuracy: float                   # test accuracy after aggregation
    per_class: np.ndarray             # per-class test accuracy
    params: Any = None                # new global model pytree (a copy —
                                      # safe to hold across rounds)
    stacked_params: Any = None        # the clients' post-training models as
                                      # flat [S, P] rows of the parameter
                                      # plane (unflatten_rows for pytrees)


class RoundEngine:
    """Compiled round compute, shared across experiments via ``shared``."""

    # LRU-bounded: sweeps over many distinct configs must not pin every
    # XLA executable for the process lifetime (live experiments keep their
    # own engine reference, so eviction only limits future sharing).
    _CACHE: "OrderedDict[EngineConfig, RoundEngine]" = OrderedDict()
    _CACHE_MAX = 16

    def __init__(self, cfg: EngineConfig):
        self.cfg = cfg
        if cfg.fedprox_mu > 0:
            local_update = make_fedprox_local_update(
                cfg.model_cfg, cfg.learning_rate, cfg.local_iters,
                cfg.batch_size, mu=cfg.fedprox_mu)
        else:
            local_update = make_local_update(
                cfg.model_cfg, cfg.learning_rate, cfg.local_iters,
                cfg.batch_size)
        self._vmapped_update = phase_scope("train")(
            jax.vmap(local_update, in_axes=(None, 0, 0, 0, None)))
        self.flat_spec = model_flat_spec(cfg.model_cfg)
        #: the workload's frozen tree on the device (None for the CNN),
        #: the last argument of every program below
        self.frozen = frozen_params(cfg.model_cfg)
        # train_clients has no input/output buffer alias to donate (its
        # output rows are param-shaped, its inputs are data-shaped); the
        # donation that stops the legacy path double-buffering the client
        # stack lives on scatter_rows, the store half of the round trip.
        self._train_clients = jax.jit(self._vmapped_update)
        self._evaluate = jax.jit(phase_scope("eval")(
            lambda params, x, y, frozen: model_eval(cfg.model_cfg)(
                params, x, y, **frozen_kw(frozen))))
        # donate the global params: the new global aliases them in place
        self._round_step = jax.jit(self._round_step_fn, donate_argnums=(0,))
        # donated in-place row scatter into the [N, P] client-weight plane
        self.scatter_rows = jax.jit(
            lambda buf, idx, rows: buf.at[idx].set(rows),
            donate_argnums=(0,))
        # active-plane row gather (the paged store ships only the round's
        # K rows to device; the dense store slices its resident plane)
        self.gather_rows = jax.jit(lambda buf, idx: buf[idx])
        # per-row divergence of an ACTIVE [K, P] block against the global
        # row — the paged driver's stats-table refresh: O(K·P) per round
        # instead of the dense select phase's O(N·P) full-plane reduction
        self.rows_divergence = jax.jit(
            lambda rows, gvec: ops.client_divergence(rows, gvec))

    @classmethod
    def shared(cls, cfg: EngineConfig) -> "RoundEngine":
        """The process-wide engine for ``cfg`` — experiments with equal
        static hyper-parameters reuse one set of XLA executables."""
        eng = cls._CACHE.get(cfg)
        if eng is None:
            count("program_miss", cache="engine")
            eng = cls._CACHE[cfg] = cls(cfg)
            while len(cls._CACHE) > cls._CACHE_MAX:
                cls._CACHE.popitem(last=False)
        else:
            cls._CACHE.move_to_end(cfg)
        return eng

    def init_params(self, key):
        return model_def_for(self.cfg.model_cfg).init(self.cfg.model_cfg, key)

    def train_clients(self, params, images, labels, keys):
        """The vmapped local update of one client per row of ``images``."""
        return self._train_clients(params, images, labels, keys, self.frozen)

    def evaluate(self, params, test_images, test_labels):
        """``(accuracy, per_class)`` of ``params`` on the test set."""
        return self._evaluate(params, test_images, test_labels, self.frozen)

    def round_step(self, global_params, images, labels, keys, weights,
                   test_images, test_labels):
        """The fused round (``_round_step_fn``); donates ``global_params``."""
        return self._round_step(global_params, images, labels, keys, weights,
                                test_images, test_labels, self.frozen)

    # -- fused fast path -----------------------------------------------
    def _round_step_fn(self, global_params, images, labels, keys, weights,
                       test_images, test_labels, frozen):
        """Train the selected clients, aggregate (eq. 4), evaluate.

        Returns the clients' post-training models as flat ``[S, P]`` rows
        of the parameter plane; aggregation is the single fused
        ``ops.flat_aggregate`` row-reduction (same numerics as the traced
        pipeline, so fused host rounds and scanned rounds agree bit for
        bit)."""
        stacked = self._vmapped_update(global_params, images, labels, keys,
                                       frozen)
        with phase_scope("train"):
            rows = flatten_stacked(stacked)
        with phase_scope("aggregate"):
            new_global = unflatten_vector(self.flat_spec,
                                          ops.flat_aggregate(rows, weights))
        with phase_scope("eval"):
            acc, per_class = model_eval(self.cfg.model_cfg)(
                new_global, test_images, test_labels, **frozen_kw(frozen))
        return rows, new_global, acc, per_class


# ---------------------------------------------------------------------------
# the device-resident round pipeline: one lax.scan over K full rounds
# ---------------------------------------------------------------------------


class RoundOutputs(NamedTuple):
    """Per-round stacked history a traced run produces ([R] / [R, S_pad];
    a cells>1 program inserts a cells axis after R). ``inr`` is the round's
    selection-driven I/N0 per cell (dynamic-interference channels only,
    None otherwise). The last three slots are the buffered-asynchronous
    engine's per-tick traces (``repro.core.async_engine``): how many
    updates the buffer folded, their mean age at fold time, and the
    churn-driven active-fleet size — None on the synchronous barrier."""
    accuracy: Any
    T: Any
    E: Any
    selected: Any
    mask: Any
    inr: Any = None
    participation: Any = None
    staleness: Any = None
    active: Any = None


class TracedRunResult(NamedTuple):
    """Everything one ``run_rounds`` call returns, still on device."""
    state: RoundState
    rounds: RoundOutputs
    # initial (all-device) round bookkeeping, or None when with_init=False
    init_accuracy: Any = None
    init_T: Any = None
    init_E: Any = None


def build_round_phases(cfg: EngineConfig, aggregator, selector, allocator,
                       compressor, tctx: TracedContext, feature_layer: str,
                       channel=None, plane: str = "full", faults=None,
                       quarantine_after: int = 0):
    """The per-round phase closures every scanned program is composed of.

    Both device-resident execution modes — the synchronous round barrier
    (:func:`_traced_round_program`) and the buffered-asynchronous tick
    loop (``repro.core.async_engine``) — build from these same closures,
    so the async engine's degenerate config (full buffer, no churn) IS
    the synchronous round body op for op, and the sync-degeneracy parity
    pin holds bit-identically by construction.

    ``plane`` selects what client state the traced carry holds:

    ``"full"``
        ``RoundState.client_params`` is the dense ``[N, P]`` buffer (the
        PR-5 layout — the dense backend degenerates to today's program,
        bit-identical): divergence is the full-plane row reduction and
        trained rows scatter into the carry.

    ``"stats"``
        The carry holds only the O(N) stats columns
        (``RoundState.sched``, a ``ClientStats`` pytree) plus whatever
        active ``[K, P]`` rows the caller gathers from its
        ``ClientStore``: ``select_phase`` reads divergence straight from
        ``sched.divergence`` (the store's refreshed table) and
        ``train_aggregate`` skips the plane scatter — persisting rows is
        the store's job at the host boundary. This is how the paged
        backend runs the scanned closures without an ``[N, P]`` buffer.

    ``aggregator`` is the resolved (possibly stateful) instance; all other
    strategies are the frozen dataclasses the program caches key on.
    Returns a namespace of pure jnp closures over the ``RoundState``
    carry: ``init_channel``/``step_channel`` (channel-state lifecycle),
    ``train_gathered`` (local SGD of already-gathered ``[S_pad, ...]``
    data → compressed flat rows — the store-agnostic core),
    ``train_rows`` (index-set wrapper over ``train_gathered``, sync-loop
    key discipline), ``train_aggregate`` (train + store + eq.-(4) masked
    aggregation), ``select_phase`` (fade → divergence → select) and
    ``init_round``/``finish_phase`` (the Alg.-2 initial round and one
    cell's allocate → train → eval round tail).

    ``faults`` (a ``repro.core.faults.FaultSpec``) arms the traced
    post-train fault phase: dispatched uploads are dropped (i.i.d.,
    channel-coupled, or past the straggler deadline), corrupted to NaN,
    or adversarially negated, with failed rows zero-weighted out of the
    fold, kept out of the client plane, and counted in the stats table's
    ``faults``/``strikes`` columns. ``quarantine_after > 0`` additionally
    filters clients with that many strikes out of every selection, like
    ``avail=False``. Either option requires the carry to hold a
    ``ClientStats`` sched table.
    """
    from repro.core.clustering import extract_features_flat, kmeans_fit
    from repro.core.divergence import weight_divergence_flat
    from repro.core.faults import (byzantine_clients, chan_outage_threshold,
                                   draw_fault_masks)
    from repro.core.wireless import completion_times

    if plane not in ("full", "stats"):
        raise ValueError(f"unknown carry plane {plane!r}; "
                         "expected 'full' or 'stats'")
    if cfg.fedprox_mu > 0:
        local_update = make_fedprox_local_update(
            cfg.model_cfg, cfg.learning_rate, cfg.local_iters, cfg.batch_size,
            mu=cfg.fedprox_mu)
    else:
        local_update = make_local_update(
            cfg.model_cfg, cfg.learning_rate, cfg.local_iters, cfg.batch_size)
    vmapped_update = jax.vmap(local_update, in_axes=(None, 0, 0, 0, None))
    spec = model_flat_spec(cfg.model_cfg)
    eval_fn = model_eval(cfg.model_cfg)
    N, B = tctx.num_devices, tctx.bandwidth_mhz
    channel_rng = channel is not None and getattr(channel, "needs_rng", False)
    channel_stateful = (channel is not None
                        and getattr(channel, "stateful", False))
    faults_on = faults is not None and faults.active
    track_faults = faults_on or quarantine_after > 0
    if faults_on and faults.chan_outage > 0.0 and not channel_stateful:
        raise ValueError(
            "chan_outage faults derive the drop probability from the fade "
            "state riding the carry; configure a stateful channel "
            "(e.g. 'gauss-markov')")
    byz_pad = None
    if faults_on and faults.byzantine > 0.0:
        # the fixed adversarial subset, padded with one False sentinel lane
        # so clamped out-of-bounds gathers stay honest
        byz_pad = jnp.asarray(np.concatenate(
            [byzantine_clients(faults, N), np.zeros(1, bool)]))

    def init_channel(state, arr):
        """Populate the carry's channel-state slot (one key split, only
        for stateful models — keyless/memoryless channels leave the PRNG
        stream untouched)."""
        if not channel_stateful:
            return state
        key, k0 = jax.random.split(state.key)
        return state._replace(key=key, channel=channel.init_state(k0, arr))

    def step_channel(state, arr):
        """Per-round fading: evolve the carried state (stateful models) or
        draw memorylessly (rng models); a no-op for everything else."""
        if not (channel_rng or channel_stateful):
            return state, arr
        if channel_rng:
            key, k_ch = jax.random.split(state.key)
            state = state._replace(key=key)
        else:
            k_ch = None
        if channel_stateful:
            ch_state, arr = channel.step_traced(k_ch, state.channel, arr)
            return state._replace(channel=ch_state), arr
        return state, channel.apply_traced(k_ch, arr)

    @phase_scope("train")
    def train_gathered(state, images_sel, labels_sel, frozen=None):
        """Local training of already-gathered ``[S_pad, ...]`` client data
        from the current global → compressed flat [S_pad, P] rows. The
        store-agnostic core: the dense path gathers by index on device
        (``train_rows``), the paged path hands in host-paged slices —
        identical PRNG consumption either way.

        Key discipline mirrors the host loop exactly: one split off the
        stream, then per-client subkeys — a traced run and the Python loop
        consume identical PRNG sequences.
        """
        key, sub = jax.random.split(state.key)
        tkeys = jax.random.split(sub, images_sel.shape[0])
        # the one pytree excursion of the round: the CNN forward/backward
        # wants named leaves, so unflatten the global row for the vmapped
        # SGD steps and flatten the results straight back onto the plane
        params = unflatten_vector(spec, state.params)
        stacked = vmapped_update(params, images_sel, labels_sel, tkeys,
                                 frozen)
        rows = flatten_stacked(stacked)                       # [S_pad, P]
        with phase_scope("compress"):
            rows = compressor.apply_flat(rows, state.params, spec)
        return state._replace(key=key), rows

    @phase_scope("train")
    def train_rows(state, idx, images, labels, frozen=None):
        """Local training of the padded index set ``idx`` — device-side
        gathers clamp the out-of-bounds padding sentinel; masked later."""
        return train_gathered(state, images[idx], labels[idx], frozen)

    @phase_scope("faults")
    def inject_faults(state, idx, mask, rows, w, d=None):
        """The traced post-train fault phase: one key split, then the
        per-dispatch drop/corrupt draws, the deterministic channel-coupled
        and deadline drops, and the byzantine row transform. Returns the
        (possibly corrupted) rows, the fold weights with lost uploads
        zeroed, and ``keep`` — the lanes whose rows may persist to the
        client plane (byzantine rows persist: the adversary's state is
        real; lost and corrupted uploads never do)."""
        key, kf = jax.random.split(state.key)
        drop, corrupt = draw_fault_masks(kf, faults, idx.shape)
        if faults.chan_outage > 0.0:
            # unit-mean exponential fade power from the Gauss-Markov carry:
            # the upload fails exactly when this round's fade is deep
            gain = jnp.sum(jnp.square(state.channel), axis=-1)
            drop = drop | (gain[idx]
                           < chan_outage_threshold(faults.chan_outage))
        if faults.deadline > 0.0 and d is not None:
            drop = drop | (d > faults.deadline)
        if byz_pad is not None:
            g = state.params
            rows = jnp.where(byz_pad[idx][:, None],
                             g[None, :] - faults.byz_scale
                             * (rows - g[None, :]),
                             rows)
        if faults.corrupt > 0.0:
            rows = jnp.where(corrupt[:, None], jnp.nan, rows)
        ev = (drop | corrupt) & mask
        sched = state.sched._replace(
            faults=state.sched.faults.at[idx].add(
                ev.astype(jnp.float32), mode="drop"))
        w = jnp.where(drop, 0.0, w)
        keep = mask & ~drop & ~corrupt
        return state._replace(key=key, sched=sched), rows, w, keep

    @phase_scope("faults")
    def finite_guard(state, idx, rows, w):
        """Receive-side non-finite guard: a NaN/Inf row is zero-weighted
        out of the fold and counted as a STRIKE against its sender —
        ``quarantine_after`` strikes exclude the client from selection."""
        finite = jnp.all(jnp.isfinite(rows), axis=1)
        bad = (~finite) & (w > 0.0)
        sched = state.sched._replace(
            strikes=state.sched.strikes.at[idx].add(
                bad.astype(jnp.float32), mode="drop"))
        return state._replace(sched=sched), jnp.where(finite, w, 0.0)

    def train_aggregate(state, idx, mask, images, labels, sizes, d=None,
                        frozen=None):
        """Local training of ``idx`` + store + aggregate (masked weights).
        ``mask is None`` marks the all-device initial round — fault
        injection only arms on real (masked) selections."""
        state, rows = train_rows(state, idx, images, labels, frozen)
        w = sizes[idx]
        if mask is not None:
            w = jnp.where(mask, w, 0.0)
        keep = mask
        if faults_on and mask is not None:
            state, rows, w, keep = inject_faults(state, idx, mask, rows, w,
                                                 d)
        if track_faults and mask is not None:
            state, w = finite_guard(state, idx, rows, w)
        with phase_scope("aggregate"):
            new_gvec, opt_state = aggregator.aggregate_flat(
                state.params, rows, w, state.opt_state)
            if faults_on and mask is not None:
                # all-failed degradation: when every upload of the round
                # was lost the global row and optimizer state pass through
                # unchanged instead of folding an empty (zeroed) cohort
                any_ok = jnp.any(w > 0.0)
                new_gvec = jnp.where(any_ok, new_gvec, state.params)
                opt_state = jax.tree_util.tree_map(
                    lambda new, old: jnp.where(any_ok, new, old),
                    opt_state, state.opt_state)
            if plane == "full":
                # ONE scatter into the [N, P] plane; sentinel rows are out
                # of bounds -> dropped (failed uploads are re-pointed at
                # the sentinel so a lost/corrupted row never lands)
                store_idx = idx
                if faults_on and keep is not None:
                    store_idx = jnp.where(keep, idx, N)
                new_client = state.client_params.at[store_idx].set(rows)
            else:
                # stats plane: the carry holds no [N, P] buffer — the
                # caller persists rows through its ClientStore at the host
                # boundary
                new_client = state.client_params
        return state._replace(params=new_gvec, client_params=new_client,
                              opt_state=opt_state)

    def init_round(state, images, labels, sizes, arr, inr_round,
                   test_images, test_labels, frozen=None):
        """Round 0 (Alg. 1 line 1 + Alg. 2): all devices train, aggregate,
        K-means-cluster on the chosen feature layer, evaluate + allocate.
        ``inr_round`` (dynamic interference, all devices active) folds into
        the allocation's rate; None otherwise."""
        all_idx = jnp.arange(N)
        state = train_aggregate(state, all_idx, None, images, labels, sizes,
                                frozen=frozen)
        with phase_scope("select"):
            feats = extract_features_flat(state.client_params,
                                          feature_layer, spec)
            key, sub = jax.random.split(state.key)
            _, k_labels, _ = kmeans_fit(sub, feats, tctx.num_clusters)
        state = state._replace(key=key, labels=k_labels.astype(jnp.int32))
        with phase_scope("eval"):
            acc0, _ = eval_fn(unflatten_vector(spec, state.params),
                              test_images, test_labels, **frozen_kw(frozen))
        state, arr = step_channel(state, arr)
        if inr_round is not None:
            arr = dict(arr)
            arr["inr"] = arr["inr"] + inr_round
        with phase_scope("allocate"):
            T0, E0, _, _ = allocator.allocate_traced(arr, B, None)
        return state, (acc0, T0, E0)

    @phase_scope("select")
    def select_phase(state, arr):
        """(fade →) divergence → select. The fading draw precedes
        selection so channel-aware policies (icas, rra) see the round's
        actual gains; returns the faded ``arr`` for the allocation."""
        state, arr = step_channel(state, arr)
        if not selector.needs_divergence:
            div = jnp.zeros((N,), jnp.float32)
        elif plane == "stats":
            # the store's refreshed per-client table rides the carry —
            # O(N) read, no [N, P] plane to reduce
            div = state.sched.divergence
        else:
            div = weight_divergence_flat(state.client_params, state.params)
        if selector.needs_rng:
            key, k_sel = jax.random.split(state.key)
            state = state._replace(key=key)
        else:
            k_sel = None
        idx, mask = selector.select_traced(k_sel, div, state.labels, arr,
                                           tctx)
        if quarantine_after > 0:
            # quarantine: clients with >= quarantine_after strikes are
            # filtered out of the selection exactly like avail=False
            # (same okpad pattern as the async in-flight filter)
            okpad = jnp.concatenate(
                [state.sched.strikes < float(quarantine_after),
                 jnp.zeros((1,), bool)])
            mask = mask & okpad[idx]
            idx = jnp.where(mask, idx, N).astype(idx.dtype)
        return state, arr, idx, mask

    def finish_phase(state, arr, idx, mask, inr_round, images, labels,
                     sizes, test_images, test_labels, frozen=None):
        """allocate → train → aggregate → eval for one cell's selection.
        ``inr_round`` adds the round's selection-driven interference on top
        of any build-time ``inr`` before the solvers fold it into J."""
        with phase_scope("allocate"):
            arr_sel = {k: v[idx] for k, v in arr.items()}
            if inr_round is not None:
                arr_sel["inr"] = arr_sel["inr"] + inr_round
            T, E, b_sel, f_sel = allocator.allocate_traced(arr_sel, B, mask)
            d = None
            if faults_on and faults.deadline > 0.0:
                # the same eq.-(5)+(8) pricing the async engine fires on:
                # an update past the deadline is a straggler the server
                # abandons
                d = completion_times(arr_sel, b_sel, f_sel, mask)
        state = train_aggregate(state, idx, mask, images, labels, sizes, d,
                                frozen)
        with phase_scope("eval"):
            acc, _ = eval_fn(unflatten_vector(spec, state.params),
                             test_images, test_labels, **frozen_kw(frozen))
        return state, RoundOutputs(
            accuracy=acc, T=T, E=E, selected=idx, mask=mask,
            inr=None if inr_round is None else inr_round[0])

    return SimpleNamespace(
        spec=spec, N=N, B=B, aggregator=aggregator, plane=plane,
        init_channel=init_channel, step_channel=step_channel,
        train_gathered=train_gathered, train_rows=train_rows,
        train_aggregate=train_aggregate, init_round=init_round,
        select_phase=select_phase, finish_phase=finish_phase)


@functools.lru_cache(maxsize=32)
def _traced_round_program(cfg: EngineConfig, selector, allocator,
                          agg_name: str, agg_params: tuple, compressor,
                          tctx: TracedContext, feature_layer: str,
                          channel=None, cells: int = 1, faults=None,
                          quarantine_after: int = 0):
    """The pure (unjitted) traced experiment fn for one strategy bundle.

    All arguments are hashable trace-time constants: ``selector`` /
    ``allocator`` / ``compressor`` / ``channel`` are frozen strategy
    dataclasses and the (stateful, unhashable) aggregator travels as its
    registry spec. The cache makes sweeps over seeds/σ share one Python
    closure → one XLA program per (rounds, with_init, cohort) variant.

    ``channel`` (a registered ``ChannelModel``) redraws per-round fading
    INSIDE the scan — memoryless models via ``apply_traced``, stateful
    models (``gauss-markov``) via ``init_state``/``step_traced`` with the
    fading state riding in the ``RoundState.channel`` carry slot; a model
    with ``needs_rng=False`` and ``stateful=False`` (``static``,
    ``multicell-interference``) leaves both the PRNG stream and the
    compiled program untouched.

    ``cells > 1`` gives every per-cell argument (state, data, fleet
    arrays) a leading cells axis INSIDE one traced program: each round is
    an inner vmap over per-cell select → allocate → train → aggregate,
    with one cross-cell reduction in between when the channel is dynamic
    (``multicell-dynamic``) — each BS's I/N0 is summed from the cross-gain
    rows of the devices the OTHER cells actually selected that round.

    Model weights travel on the FLAT PARAMETER PLANE: the carry holds the
    global model as one [P] row and all N client models as one [N, P]
    buffer (layout = ``model_flat_spec(cfg.model_cfg)``). Local training
    gathers the selected rows' data, unflattens the global row to the
    workload's trainable pytree for the vmapped SGD steps, then flattens
    the results back — so
    weight divergence is ONE fused row-norm reduction, eq.-(4) aggregation
    ONE masked weighted row-reduction (``ops.flat_aggregate``), K-means
    features a zero-copy column slice, and compression a per-row segment
    op; no per-leaf ``tree_map`` survives in the round body.
    """
    from repro.api.registry import AGGREGATORS

    aggregator = AGGREGATORS.resolve({"name": agg_name,
                                      "params": dict(agg_params)})
    ph = build_round_phases(cfg, aggregator, selector, allocator, compressor,
                            tctx, feature_layer, channel, faults=faults,
                            quarantine_after=quarantine_after)
    N = ph.N
    track_faults = ((faults is not None and faults.active)
                    or quarantine_after > 0)
    init_channel, init_round = ph.init_channel, ph.init_round
    select_phase, finish_phase = ph.select_phase, ph.finish_phase
    dynamic = (cells > 1 and channel is not None
               and getattr(channel, "dynamic", False))

    def run(state, images, labels, sizes, arr, test_images, test_labels,
            frozen=None, *, rounds: int, with_init: bool):
        arr = dict(arr)
        xg = arr.pop("xgain", None)          # [(cells,) N, C] cross gains

        if cells == 1:
            # ---- single-cell layout (the PR-2 scanned program) --------
            state = init_channel(state, arr)
            if track_faults and state.sched is None:
                # fault counters / quarantine need the stats table riding
                # the carry; the cohort path has no host table to ship in
                from repro.core.store import ClientStats
                state = state._replace(sched=ClientStats.create_traced(N))
            init_out = None
            if with_init:
                state, init_out = init_round(state, images, labels, sizes,
                                             arr, None, test_images,
                                             test_labels, frozen)

            def step(s, _):
                s, arr_f, idx, mask = select_phase(s, arr)
                return finish_phase(s, arr_f, idx, mask, None, images,
                                    labels, sizes, test_images, test_labels,
                                    frozen)
        else:
            # ---- cells axis inside the program: inner vmap over cells,
            # one cross-cell interference reduction per round ------------
            state = jax.vmap(init_channel)(state, arr)

            def cell_inr(part):
                """[C, N] participation → [C, 1] I/N0 at each BS (summed
                selected cross-gain rows; own-cell columns are 0)."""
                return jnp.einsum("cn,cnk->k", part, xg)[:, None]

            def dense_part(idx, mask):
                """Scatter each cell's padded selection to a dense [C, N]
                participation map (the OOB sentinel lanes drop)."""
                return jax.vmap(
                    lambda i, m: jnp.zeros((N,), jnp.float32)
                    .at[i].add(m.astype(jnp.float32), mode="drop"))(idx, mask)

            sel_v = jax.vmap(select_phase)
            fin_v = jax.vmap(finish_phase,
                             in_axes=(0, 0, 0, 0, 0 if dynamic else None,
                                      0, 0, 0, None, None, None))
            init_v = jax.vmap(init_round,
                              in_axes=(0, 0, 0, 0, 0,
                                       0 if dynamic else None, None, None,
                                       None))

            init_out = None
            if with_init:
                inr0 = (cell_inr(jnp.ones((cells, N), jnp.float32))
                        if dynamic else None)
                state, init_out = init_v(state, images, labels, sizes, arr,
                                         inr0, test_images, test_labels,
                                         frozen)

            def step(s, _):
                s, arr_f, idx, mask = sel_v(s, arr)
                inr_r = (cell_inr(dense_part(idx, mask))
                         if dynamic else None)
                return fin_v(s, arr_f, idx, mask, inr_r, images, labels,
                             sizes, test_images, test_labels, frozen)

        state, outs = lax.scan(step, state, None, length=rounds)
        if init_out is None:
            return TracedRunResult(state=state, rounds=outs)
        acc0, T0, E0 = init_out
        return TracedRunResult(state=state, rounds=outs, init_accuracy=acc0,
                               init_T=T0, init_E=E0)

    return run


# LRU-bounded like RoundEngine._CACHE: sweeps over many distinct
# (strategies, rounds) combos must not pin every XLA executable forever.
_RUN_FN_CACHE: "OrderedDict[tuple, Any]" = OrderedDict()
_RUN_FN_CACHE_MAX = 64


def aggregator_cache_key(aggregator) -> tuple:
    """Hashable identity of a (possibly stateful) aggregator instance."""
    return (aggregator.registry_name,
            tuple(sorted(aggregator.params().items())))


def run_rounds(cfg: EngineConfig, *, selector, allocator, aggregator,
               compressor, tctx: TracedContext, feature_layer: str,
               rounds: int, with_init: bool, cohort: bool = False,
               test_shared: bool = True, mesh=None, channel=None,
               cells: int = 1, churn=None, faults=None,
               quarantine_after: int = 0):
    """The compiled multi-round experiment fn for one strategy bundle.

    Returns a jitted callable
    ``(state, images, labels, sizes, arr, test_images, test_labels, frozen)
    -> TracedRunResult`` executing ``rounds`` full FL rounds as ONE XLA
    program (plus the Alg.-2 initial round when ``with_init``). ``frozen``
    is the workload's frozen tree (``frozen_params``; None for the CNN),
    an argument like the data, shared by every lane. With
    ``cohort=True`` every data/state argument gains a leading cohort axis
    (vmapped) — the ``CohortRunner`` path; ``test_shared`` keeps the
    evaluation set un-mapped (one copy across the cohort).

    ``cells > 1`` declares a cells axis INSIDE the program, right after
    the cohort axis: per-cell state/data leaves are ``[C, ...]`` (or
    ``[cohort, C, ...]``), each round inner-vmaps over the cells, and a
    dynamic-interference channel couples them through one cross-cell
    reduction per round. The evaluation set is always cell-shared.

    ``mesh`` (a 1-axis ``jax.sharding.Mesh`` named ``"cohort"``) splits the
    cohort axis across local devices via ``shard_map``: each device runs
    its slice of seeds as an independent per-shard vmap — embarrassingly
    parallel, no cross-device collectives inside the round.

    Compiled callables are cached process-wide, so sweeps that differ only
    in seed/data reuse one executable.

    The ``state`` argument is DONATED (``donate_argnums=(0,)``): its
    buffers — notably the ``[cohort, N, P]`` flat client plane — are
    reused in place for the returned state, so pass freshly-built (or
    no-longer-needed) arrays and rebind every reference from the result.

    An ASYNC-CAPABLE aggregator (``fedbuff:M[:alpha]``) swaps the round
    barrier for the buffered-asynchronous tick loop
    (``repro.core.async_engine``) — same signature, same single scanned
    program, but rounds become virtual-time ticks and ``churn`` (a
    ``(p_leave, p_join)`` pair of per-tick Bernoulli probabilities) may
    flip the per-client availability mask riding the carry.
    """
    churn_t = ((0.0, 0.0) if churn is None
               else (float(churn[0]), float(churn[1])))
    is_async = getattr(aggregator, "async_capable", False)
    if not is_async and churn_t != (0.0, 0.0):
        raise ValueError(
            "client churn is a property of the buffered-asynchronous "
            "engine; configure an async-capable aggregator "
            "(e.g. 'fedbuff:4') to enable it")
    if is_async and cells > 1:
        raise ValueError(
            "the buffered-asynchronous engine runs single-cell programs "
            "only; run multi-cell fleets with a synchronous aggregator")
    track_faults = ((faults is not None and faults.active)
                    or quarantine_after > 0)
    if track_faults and cells > 1:
        raise ValueError(
            "fault injection / quarantine runs single-cell programs only")
    mesh_key = (None if mesh is None
                else tuple(d.id for d in mesh.devices.flat))
    key = (cfg, selector, allocator, aggregator_cache_key(aggregator),
           compressor, tctx, feature_layer, rounds, with_init, cohort,
           test_shared, mesh_key, channel, cells, churn_t, faults,
           quarantine_after)
    fn = _RUN_FN_CACHE.get(key)
    if fn is None:
        count("program_miss", cache="run_rounds")
        if is_async:
            from repro.core.async_engine import _traced_async_program
            prog = _traced_async_program(
                cfg, selector, allocator, aggregator.registry_name,
                tuple(sorted(aggregator.params().items())), compressor,
                tctx, feature_layer, channel, churn_t, faults,
                quarantine_after)
        else:
            prog = _traced_round_program(
                cfg, selector, allocator, aggregator.registry_name,
                tuple(sorted(aggregator.params().items())), compressor,
                tctx, feature_layer, channel, cells, faults,
                quarantine_after)
        core = functools.partial(prog, rounds=rounds, with_init=with_init)
        if cohort:
            test_ax = None if test_shared else 0
            core = jax.vmap(core, in_axes=(0, 0, 0, 0, 0, test_ax, test_ax,
                                           None))
            if mesh is not None:
                from jax.sharding import PartitionSpec as P
                data_spec = P("cohort")
                test_spec = P() if test_shared else P("cohort")
                core = jax.shard_map(
                    core, mesh=mesh,
                    in_specs=(data_spec,) * 5 + (test_spec, test_spec, P()),
                    out_specs=data_spec, check_vma=False)
        # donate the carry: the (possibly [cohort, N, P]-sized) RoundState
        # buffers update in place across dispatches instead of double-
        # buffering — callers must treat the passed-in state as consumed
        # (FLExperiment/CohortRunner immediately replace their references
        # from the returned state)
        fn = _RUN_FN_CACHE[key] = jax.jit(core, donate_argnums=(0,))
        while len(_RUN_FN_CACHE) > _RUN_FN_CACHE_MAX:
            _RUN_FN_CACHE.popitem(last=False)
    else:
        _RUN_FN_CACHE.move_to_end(key)
    return fn
