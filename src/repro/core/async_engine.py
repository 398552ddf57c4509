"""The buffered-asynchronous tick loop — FL rounds as EVENTS, not
barriers, compiled into the same single ``lax.scan`` program as the
synchronous pipeline.

Production FL has no round barrier: clients are dispatched, train at their
own pace, and the server folds updates as they land. This engine replaces
``repro.core.engine._traced_round_program``'s barrier with a FedBuff-style
(Nguyen et al. 2022) virtual-time loop:

  * every dispatched client's finish time is priced by the PAPER's delay
    model — ``completion_times`` (eqs. 5+8) under the round's SAO/allocator
    bandwidth+frequency assignment and the PR-4 channel-fading carry;
  * the aggregation buffer fires when the ``M`` earliest in-flight
    completions land (``fedbuff:M[:alpha]``), folding them into the global
    row with staleness-discounted weights ``w ∝ (1 + age)^(-alpha)``
    through the same ``ops.flat_aggregate`` row-reduction — over the M
    gathered candidate rows only (O(M·P) per tick, not O(N·P));
    stragglers stay in flight and age;
  * Bernoulli churn streams flip a per-client availability mask riding the
    carry — departures cancel in-flight work, arrivals rejoin the pool —
    and selection/allocation never touch an unavailable client.

One scan iteration = one buffer fire = one history row, so the
``FLHistory`` plumbing (cohort vmap, shard_map, donation) is untouched;
``RoundOutputs`` simply gains participation / staleness / active-fleet
traces.

The engine builds its tick from the SAME phase closures as the
synchronous program (``engine.build_round_phases``), and the degenerate
config — buffer at least the padded selection size, no churn — takes a
static branch that IS the synchronous round body op for op: the
sync-degeneracy parity pin (``fedbuff:M>=K, alpha=0`` ≡ scanned fedavg)
holds bit-identically by construction, not by numerical luck.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.api.protocols import TracedContext
from repro.core.engine import (EngineConfig, RoundOutputs, TracedRunResult,
                               build_round_phases, frozen_kw, model_eval,
                               phase_scope)
from repro.core.store import ClientStats
from repro.core.wireless import completion_times, masked_max
from repro.kernels import ops
from repro.utils.trees import unflatten_vector


def parse_churn(churn):
    """Normalize a churn spec to the ``(p_leave, p_join)`` float pair.

    Accepts ``None`` (no churn), a single number / ``"0.3"`` (leave-only),
    a ``"p_leave:p_join"`` string (the CLI spelling), or a 2-sequence.
    Both entries are per-tick Bernoulli probabilities in [0, 1].
    """
    if churn is None:
        return (0.0, 0.0)
    if isinstance(churn, str):
        leave_s, _, join_s = churn.partition(":")
        parts = (leave_s, join_s or "0")
    elif isinstance(churn, (int, float)):
        parts = (churn, 0.0)
    else:
        parts = tuple(churn)
        if len(parts) != 2:
            raise ValueError(
                f"churn must be (p_leave, p_join); got {churn!r}")
    try:
        p = tuple(float(x) for x in parts)
    except (TypeError, ValueError):
        raise ValueError(
            f"churn must be numeric 'P_LEAVE[:P_JOIN]'; got {churn!r}"
        ) from None
    if not all(0.0 <= x <= 1.0 for x in p):
        raise ValueError(
            f"churn probabilities must lie in [0, 1]; got {p}")
    return p


def _async_fault_plan(faults, state, sched, idx, mask, d):
    """Dispatch-side fault plan, shared VERBATIM by the dense tick and the
    paged ``plan_fn`` (same key split position, same draws — the
    dense ≡ paged parity holds under faults by construction): one split
    off the carry, the per-dispatch drop/corrupt Bernoullis, then the
    deterministic channel-coupled and straggler-deadline drops. A failed
    upload is priced ``+inf`` — it never completes, so it can never fire
    and its row is never persisted; the event lands in the stats table's
    ``faults`` column (and ``strikes`` for corrupt payloads, detected at
    receipt). Returns ``(state, sched, d, good)`` with ``good`` the lanes
    whose trained rows may be staged/persisted."""
    from repro.core.faults import chan_outage_threshold, draw_fault_masks

    key, kf = jax.random.split(state.key)
    state = state._replace(key=key)
    drop, corrupt = draw_fault_masks(kf, faults, idx.shape)
    if faults.chan_outage > 0.0:
        # unit-mean exponential fade power from the Gauss-Markov carry
        gain = jnp.sum(jnp.square(state.channel), axis=-1)
        drop = drop | (gain[idx] < chan_outage_threshold(faults.chan_outage))
    if faults.deadline > 0.0:
        drop = drop | (d > faults.deadline)
    bad = (drop | corrupt) & mask
    d = jnp.where(bad, jnp.inf, d)
    sched = sched._replace(
        faults=sched.faults.at[idx].add(bad.astype(jnp.float32),
                                        mode="drop"),
        strikes=sched.strikes.at[idx].add(
            (corrupt & mask).astype(jnp.float32), mode="drop"))
    return state, sched, d, mask & ~bad


def _byz_transform(faults, byz_pad, idx, gvec, rows):
    """The byzantine row transform ``g − byz_scale·(w − g)`` on the fixed
    adversarial lanes — finite but extreme, so only robust aggregation
    (not the non-finite guard) defends against it."""
    return jnp.where(byz_pad[idx][:, None],
                     gvec[None, :] - faults.byz_scale
                     * (rows - gvec[None, :]),
                     rows)


@functools.lru_cache(maxsize=32)
def _traced_async_program(cfg: EngineConfig, selector, allocator,
                          agg_name: str, agg_params: tuple, compressor,
                          tctx: TracedContext, feature_layer: str,
                          channel=None, churn=(0.0, 0.0), faults=None,
                          quarantine_after: int = 0):
    """The pure (unjitted) buffered-asynchronous experiment fn.

    Same signature contract as ``engine._traced_round_program`` (all
    arguments hashable trace-time constants, aggregator travelling as its
    registry spec) and the same
    ``(state, images, labels, sizes, arr, test_images, test_labels,
    rounds, with_init) -> TracedRunResult`` call shape, so ``run_rounds``
    swaps it in transparently — cohort vmap, shard_map and carry donation
    all apply unchanged.

    One scan iteration ("tick"):

      1. churn — Bernoulli departure/arrival flips ``sched.avail``;
         a departure cancels the client's in-flight update;
      2. select — the registered selector runs on the faded fleet arrays
         (availability exposed as ``arr["avail"]``), then the engine
         post-filters the padded index set: unavailable or already
         in-flight clients drop to the OOB sentinel;
      3. dispatch — the allocator prices the cohort's bandwidth/frequency,
         ``completion_times`` (eqs. 5+8) stamps each dispatched client's
         absolute finish time ``t_now + d`` into ``sched.t_done``, and
         local training writes their rows onto the [N, P] plane;
      4. fire — the buffer collects the ``M`` earliest in-flight
         completions (fewer if the fleet can't fill the buffer: no
         deadlock), advances the virtual clock to the latest of them, and
         folds the fired rows with ``sizes × (1+age)^(-alpha)`` weights;
         an EMPTY fire (everyone churned out) is an explicit no-op — the
         global row and optimizer state pass through untouched;
      5. age — surviving in-flight clients' ``age`` grows by one server
         fold; fired/idle clients reset.
    """
    from repro.api.registry import AGGREGATORS

    aggregator = AGGREGATORS.resolve({"name": agg_name,
                                      "params": dict(agg_params)})
    M = int(aggregator.buffer_size)
    alpha = float(aggregator.staleness_alpha)
    p_leave, p_join = float(churn[0]), float(churn[1])
    churn_on = p_leave > 0.0 or p_join > 0.0
    faults_on = faults is not None and faults.active
    track_faults = faults_on or quarantine_after > 0

    ph = build_round_phases(cfg, aggregator, selector, allocator, compressor,
                            tctx, feature_layer, channel, faults=faults,
                            quarantine_after=quarantine_after)
    N, spec = ph.N, ph.spec
    byz_pad = None
    if faults_on and faults.byzantine > 0.0:
        from repro.core.faults import byzantine_clients
        byz_pad = jnp.asarray(np.concatenate(
            [byzantine_clients(faults, N), np.zeros(1, bool)]))
    S_pad = selector.pad_size(tctx)
    # With the buffer at least the padded selection size and no churn, the
    # backlog is provably empty by induction (every dispatch fires whole),
    # so the tick IS the synchronous round body — take the static branch
    # built from the very same phase closures. Bit-parity by construction.
    degenerate = (M >= S_pad) and not churn_on

    def init_sched(state):
        if state.sched is not None:      # continuing a previous run
            return state
        # same values as ClientStats.create(N).device() — the cohort path
        # builds the table inside the program, the host driver ships its
        # store's table in through RoundState.sched instead
        return state._replace(sched=ClientStats.create_traced(N))

    def churn_step(state):
        """Flip the availability mask; departures cancel in-flight work."""
        sched = state.sched
        key, kc = jax.random.split(state.key)
        k_leave, k_join = jax.random.split(kc)
        leave = jax.random.uniform(k_leave, (N,)) < p_leave
        join = jax.random.uniform(k_join, (N,)) < p_join
        avail = jnp.where(sched.avail, ~leave, join)
        sched = sched._replace(
            avail=avail,
            t_done=jnp.where(avail, sched.t_done, jnp.inf),
            age=jnp.where(avail, sched.age, 0.0))
        return state._replace(key=key, sched=sched)

    def tick(state, images, labels, sizes, arr, test_images, test_labels,
             frozen):
        if churn_on:
            state = churn_step(state)
        sched = state.sched

        # -- select on the faded fleet, availability exposed to churn-
        # aware policies, then hard-filter the padded index set ----------
        arr_in = arr
        if churn_on:
            arr_in = dict(arr)
            arr_in["avail"] = sched.avail.astype(jnp.float32)
        state, arr_f, idx, mask = ph.select_phase(state, arr_in)
        arr_f = dict(arr_f)
        arr_f.pop("avail", None)
        # a client already in flight, or churned out, must not be
        # re-dispatched: drop its lane to the OOB sentinel (okpad's
        # appended False also kills lanes that were already padding)
        ok_client = sched.avail & ~jnp.isfinite(sched.t_done)
        okpad = jnp.concatenate([ok_client, jnp.zeros((1,), bool)])
        mask = mask & okpad[idx]
        idx = jnp.where(mask, idx, N).astype(jnp.int32)

        # -- dispatch: allocate, price completions, train ----------------
        with phase_scope("allocate"):
            arr_sel = {k: v[idx] for k, v in arr_f.items()}
            T, E, b, f = allocator.allocate_traced(arr_sel, ph.B, mask)
            d = completion_times(arr_sel, b, f, mask)    # +inf on padding
        good = mask
        if faults_on:
            with phase_scope("faults"):
                state, sched, d, good = _async_fault_plan(
                    faults, state, sched, idx, mask, d)
        t_done = sched.t_done.at[idx].set(sched.t_now + d, mode="drop")
        state, rows = ph.train_rows(state, idx, images, labels, frozen)
        if byz_pad is not None:
            with phase_scope("faults"):
                rows = _byz_transform(faults, byz_pad, idx, state.params,
                                      rows)
        # sentinel rows are out of bounds -> dropped (failed uploads are
        # re-pointed at the sentinel so a lost row never lands)
        with phase_scope("aggregate"):
            store_idx = idx if not faults_on else jnp.where(good, idx, N)
            state = state._replace(
                client_params=state.client_params.at[store_idx].set(rows))

        # -- fire: the M earliest in-flight completions ------------------
        inflight = jnp.isfinite(t_done)
        # completion RANKS, not a k-th-value threshold: the SAO allocator
        # EQUALIZES its cohort's completion times (min-max optimum), so a
        # value cut would fire every tied client at once and overrun the
        # buffer. Stable argsort breaks ties by client index — exactly
        # min(M, #in-flight) fire (fewer than M in flight all fire: no
        # deadlock), the simultaneous rest stay in flight and age.
        order = jnp.argsort(t_done)
        rank = jnp.zeros((N,), jnp.int32).at[order].set(jnp.arange(
            N, dtype=jnp.int32))
        fired = inflight & (rank < M)
        t_fire = jnp.maximum(sched.t_now,
                             masked_max(t_done, fired, empty=sched.t_now))

        # the server fold touches only the M buffer-candidate rows
        # (``fired ⊆ order[:M]`` by construction) — an O(M·P) gather +
        # reduction instead of the full-plane O(N·P) masked sweep, which
        # at population scale dwarfed the actual training. Candidates are
        # sorted into CLIENT-INDEX order first, so the nonzero summation
        # order (and hence the fp32 result) matches the full-plane
        # reduction this replaces.
        cand = jnp.sort(order[:M])
        fired_cand = jnp.isfinite(t_done[cand])
        w_cand = jnp.where(fired_cand, sizes[cand], 0.0)
        if alpha != 0.0:
            w_cand = w_cand * aggregator.staleness_weights(sched.age[cand])
        cand_rows = state.client_params[cand]
        ok_cand = fired_cand
        if track_faults:
            # receive-side non-finite guard: a NaN/Inf candidate row is
            # zero-weighted out of the fold and strikes its sender
            finite_c = jnp.all(jnp.isfinite(cand_rows), axis=1)
            bad_c = fired_cand & ~finite_c
            sched = sched._replace(
                strikes=sched.strikes.at[cand].add(
                    bad_c.astype(jnp.float32), mode="drop"))
            w_cand = jnp.where(finite_c, w_cand, 0.0)
            ok_cand = fired_cand & finite_c
        with phase_scope("aggregate"):
            agg_vec, agg_opt = aggregator.aggregate_flat(
                state.params, cand_rows, w_cand, state.opt_state)
            # EMPTY-FIRE GUARD: flat_aggregate normalizes by max(Σw, eps),
            # so an all-zero weight row yields a ZERO vector — an empty
            # (or all-failed) tick must instead pass the old global (and
            # optimizer state) through
            any_fired = (jnp.any(w_cand > 0.0) if track_faults
                         else jnp.any(fired))
            new_gvec = jnp.where(any_fired, agg_vec, state.params)
            new_opt = jax.tree_util.tree_map(
                lambda a, o: jnp.where(any_fired, a, o), agg_opt,
                state.opt_state)

        # traces read the PRE-fold ages (the staleness actually applied)
        part = jnp.sum(fired.astype(jnp.float32))
        stale = (jnp.sum(jnp.where(fired, sched.age, 0.0))
                 / jnp.maximum(part, 1.0))
        active = jnp.sum(sched.avail.astype(jnp.float32))

        # -- stats-table maintenance: a fired update refreshes the
        # client's divergence against the NEW global and resets its drift
        # bound; everyone else's bound grows by this fold's global step
        # ‖g_new − g_old‖ (exactly 0 on an empty fire) — the same
        # invariant the paged sync loop keeps, so selectors reading
        # ``sched.divergence`` see refresh-on-contribution semantics on
        # either backend. Pure add-on columns: nothing here feeds the
        # history numerics or the PRNG stream.
        div_cand = ops.client_divergence(cand_rows, new_gvec)
        new_div = sched.divergence.at[cand].set(
            jnp.where(ok_cand, div_cand, sched.divergence[cand]))
        g_delta = jnp.linalg.norm(new_gvec - state.params)
        refreshed = fired
        if track_faults:
            # a fired-but-guarded (non-finite) row refreshed nothing: its
            # client leaves flight but keeps accruing drift
            bad_full = jnp.zeros((N,), bool).at[cand].set(bad_c, mode="drop")
            refreshed = fired & ~bad_full
        new_drift = jnp.where(refreshed, 0.0, sched.drift + g_delta)

        # -- age the survivors, clear the fired, advance the clock -------
        sched = sched._replace(
            divergence=new_div,
            drift=new_drift,
            age=jnp.where(inflight & ~fired, sched.age + 1.0, 0.0),
            t_done=jnp.where(fired, jnp.inf, t_done),
            t_now=t_fire)
        state = state._replace(params=new_gvec, opt_state=new_opt,
                               sched=sched)

        with phase_scope("eval"):
            acc, _ = model_eval(cfg.model_cfg)(
                unflatten_vector(spec, state.params), test_images,
                test_labels, **frozen_kw(frozen))
        return state, RoundOutputs(
            accuracy=acc, T=T, E=E, selected=idx, mask=mask,
            participation=part, staleness=stale, active=active)

    def sync_tick(state, images, labels, sizes, arr, test_images,
                  test_labels, frozen):
        """The degenerate branch: the synchronous round body verbatim,
        with the async traces welded on (staleness identically zero, the
        whole fleet active)."""
        state, arr_f, idx, mask = ph.select_phase(state, arr)
        state, outs = ph.finish_phase(state, arr_f, idx, mask, None, images,
                                      labels, sizes, test_images,
                                      test_labels, frozen)
        return state, outs._replace(
            participation=jnp.sum(mask.astype(jnp.float32)),
            staleness=jnp.zeros((), jnp.float32),
            active=jnp.full((), N, jnp.float32))

    body = sync_tick if degenerate else tick

    def run(state, images, labels, sizes, arr, test_images, test_labels,
            frozen=None, *, rounds: int, with_init: bool):
        arr = dict(arr)
        arr.pop("xgain", None)           # single-cell: no cross gains
        state = ph.init_channel(state, arr)
        if not degenerate or track_faults:
            state = init_sched(state)

        init_out = None
        if with_init:
            state, init_out = ph.init_round(state, images, labels, sizes,
                                            arr, None, test_images,
                                            test_labels, frozen)

        def step(s, _):
            return body(s, images, labels, sizes, arr, test_images,
                        test_labels, frozen)

        state, outs = lax.scan(step, state, None, length=rounds)
        if init_out is None:
            return TracedRunResult(state=state, rounds=outs)
        acc0, T0, E0 = init_out
        return TracedRunResult(state=state, rounds=outs, init_accuracy=acc0,
                               init_T=T0, init_E=E0)

    return run


@functools.lru_cache(maxsize=32)
def _paged_async_step_program(cfg: EngineConfig, selector, allocator,
                              agg_name: str, agg_params: tuple, compressor,
                              tctx: TracedContext, feature_layer: str,
                              channel=None, churn=(0.0, 0.0), faults=None,
                              quarantine_after: int = 0):
    """The jitted pieces of ONE buffered-asynchronous tick over a paged
    ``ClientStore`` — the host driver composes them with store paging in
    between (``FLExperiment._run_async_paged``).

    Same math, same PRNG discipline, same op order as the dense
    :func:`_traced_async_program` tick, but the traced carry holds only
    the O(N) stats columns (``RoundState.sched``, a ``ClientStats``
    pytree) + the [P] global row — never an [N, P] plane
    (``build_round_phases(plane="stats")``). The dispatched cohort's rows
    and data move O(K·P) per tick through the store's staging API, and
    the fire folds the M candidate rows gathered back from staging:
    device memory is O(k_max·P + M·P) at any fleet size. Pinned
    bit-identical to the dense tick at small N (``tests/
    test_async_paged.py``).

    The split into four functions is deliberate: ``sched`` (churn →
    select → in-flight post-filter) and ``plan`` (allocate → completion
    pricing → fire plan) hold every O(N)/O(N log N) scheduler op, while
    ``train`` (O(K·P) local SGD) and ``fire`` (O(M·P) fold + eval) scale
    only with the cohort — so the N-scaling benchmark can gate the
    rest-of-tick cost flat in N, exactly like the PR-7 paged sync gate.
    """
    from types import SimpleNamespace

    from repro.api.registry import AGGREGATORS

    aggregator = AGGREGATORS.resolve({"name": agg_name,
                                      "params": dict(agg_params)})
    M = int(aggregator.buffer_size)
    alpha = float(aggregator.staleness_alpha)
    p_leave, p_join = float(churn[0]), float(churn[1])
    churn_on = p_leave > 0.0 or p_join > 0.0
    faults_on = faults is not None and faults.active
    track_faults = faults_on or quarantine_after > 0

    ph = build_round_phases(cfg, aggregator, selector, allocator, compressor,
                            tctx, feature_layer, channel, plane="stats",
                            faults=faults,
                            quarantine_after=quarantine_after)
    N, spec = ph.N, ph.spec
    byz_pad = None
    if faults_on and faults.byzantine > 0.0:
        from repro.core.faults import byzantine_clients
        byz_pad = jnp.asarray(np.concatenate(
            [byzantine_clients(faults, N), np.zeros(1, bool)]))
    eval_fn = model_eval(cfg.model_cfg)

    def churn_step(state):
        """Identical to the dense tick's churn: same splits, same masks —
        the PRNG streams of the two backends stay in lockstep."""
        sched = state.sched
        key, kc = jax.random.split(state.key)
        k_leave, k_join = jax.random.split(kc)
        leave = jax.random.uniform(k_leave, (N,)) < p_leave
        join = jax.random.uniform(k_join, (N,)) < p_join
        avail = jnp.where(sched.avail, ~leave, join)
        sched = sched._replace(
            avail=avail,
            t_done=jnp.where(avail, sched.t_done, jnp.inf),
            age=jnp.where(avail, sched.age, 0.0))
        return state._replace(key=key, sched=sched)

    def sched_fn(state, arr):
        """churn → select (divergence read from the stats carry) →
        in-flight/availability post-filter. All the O(N) selection work."""
        if churn_on:
            state = churn_step(state)
        sched = state.sched
        arr_in = arr
        if churn_on:
            arr_in = dict(arr)
            arr_in["avail"] = sched.avail.astype(jnp.float32)
        state, arr_f, idx, mask = ph.select_phase(state, arr_in)
        arr_f = dict(arr_f)
        arr_f.pop("avail", None)
        ok_client = sched.avail & ~jnp.isfinite(sched.t_done)
        okpad = jnp.concatenate([ok_client, jnp.zeros((1,), bool)])
        mask = mask & okpad[idx]
        idx = jnp.where(mask, idx, N).astype(jnp.int32)
        return state, arr_f, idx, mask

    def plan_fn(state, arr_f, idx, mask, sizes):
        """allocate → price completions → stamp ``t_done`` → fire plan.
        Returns the tick's (T, E), the M buffer candidates (client-index
        sorted, exactly the dense tick's summation order), their fired
        mask and staleness-discounted weights, and the per-tick traces —
        and advances age/t_done/t_now on the stats carry."""
        sched = state.sched
        with phase_scope("allocate"):
            arr_sel = {k: v[idx] for k, v in arr_f.items()}
            T, E, b, f = allocator.allocate_traced(arr_sel, ph.B, mask)
            d = completion_times(arr_sel, b, f, mask)    # +inf on padding
        good = mask
        if faults_on:
            with phase_scope("faults"):
                state, sched, d, good = _async_fault_plan(
                    faults, state, sched, idx, mask, d)
        t_done = sched.t_done.at[idx].set(sched.t_now + d, mode="drop")
        inflight = jnp.isfinite(t_done)
        order = jnp.argsort(t_done)
        rank = jnp.zeros((N,), jnp.int32).at[order].set(
            jnp.arange(N, dtype=jnp.int32))
        fired = inflight & (rank < M)
        t_fire = jnp.maximum(sched.t_now,
                             masked_max(t_done, fired, empty=sched.t_now))
        cand = jnp.sort(order[:M])
        # fired == cand[fired_cand]: fired ⊆ order[:M] by construction,
        # and a candidate's pre-clear t_done is finite iff it fired — so
        # the host learns which staged rows to release from the [M]
        # transfer alone, never a [N] one
        fired_cand = jnp.isfinite(t_done[cand])
        w_cand = jnp.where(fired_cand, sizes[cand], 0.0)
        if alpha != 0.0:
            w_cand = w_cand * aggregator.staleness_weights(sched.age[cand])
        # traces read the PRE-fold ages (the staleness actually applied)
        part = jnp.sum(fired.astype(jnp.float32))
        stale = (jnp.sum(jnp.where(fired, sched.age, 0.0))
                 / jnp.maximum(part, 1.0))
        active = jnp.sum(sched.avail.astype(jnp.float32))
        sched = sched._replace(
            age=jnp.where(inflight & ~fired, sched.age + 1.0, 0.0),
            t_done=jnp.where(fired, jnp.inf, t_done),
            t_now=t_fire)
        state = state._replace(sched=sched)
        return (state, T, E, cand, fired_cand, w_cand, good,
                (part, stale, active))

    def train_fn(state, idx, images_sel, labels_sel, frozen):
        """O(K·P) local SGD of the host-gathered cohort data — the same
        ``train_gathered`` closure (and key split) as every other driver.
        ``idx`` only feeds the byzantine row transform (same placement as
        the dense tick: post-train, pre-staging)."""
        state, rows = ph.train_gathered(state, images_sel, labels_sel, frozen)
        if byz_pad is not None:
            with phase_scope("faults"):
                rows = _byz_transform(faults, byz_pad, idx, state.params,
                                      rows)
        return state, rows

    def fire_fn(state, cand, cand_rows, w_cand, fired_cand, test_images,
                test_labels, frozen):
        """Fold the M candidate rows (staged back from the store), guard
        the empty fire, evaluate; returns the fired candidates' refreshed
        divergence, the global step norm ‖g_new − g_old‖ (exactly 0 on an
        empty fire) for the host's stats-table bookkeeping, and the
        ``ok_cand`` mask of candidates that actually refreshed (fired AND
        finite — the non-finite guard strikes the rest)."""
        ok_cand = fired_cand
        if track_faults:
            finite_c = jnp.all(jnp.isfinite(cand_rows), axis=1)
            bad_c = fired_cand & ~finite_c
            state = state._replace(sched=state.sched._replace(
                strikes=state.sched.strikes.at[cand].add(
                    bad_c.astype(jnp.float32), mode="drop")))
            w_cand = jnp.where(finite_c, w_cand, 0.0)
            ok_cand = fired_cand & finite_c
        with phase_scope("aggregate"):
            agg_vec, agg_opt = aggregator.aggregate_flat(
                state.params, cand_rows, w_cand, state.opt_state)
            # EMPTY-FIRE GUARD — any(fired_cand) ≡ any(fired), see plan_fn
            any_fired = (jnp.any(w_cand > 0.0) if track_faults
                         else jnp.any(fired_cand))
            new_gvec = jnp.where(any_fired, agg_vec, state.params)
            new_opt = jax.tree_util.tree_map(
                lambda a, o: jnp.where(any_fired, a, o), agg_opt,
                state.opt_state)
        div_cand = ops.client_divergence(cand_rows, new_gvec)
        g_delta = jnp.linalg.norm(new_gvec - state.params)
        state = state._replace(params=new_gvec, opt_state=new_opt)
        with phase_scope("eval"):
            acc, _ = eval_fn(unflatten_vector(spec, new_gvec),
                             test_images, test_labels, **frozen_kw(frozen))
        return state, acc, div_cand, g_delta, ok_cand

    return SimpleNamespace(
        N=N, M=M, spec=spec, churn_on=churn_on,
        init_channel=ph.init_channel,
        sched=jax.jit(sched_fn), plan=jax.jit(plan_fn),
        train=jax.jit(train_fn), fire=jax.jit(fire_fn))
