"""granite-4.0-h-micro as a federated LoRA workload: the hybrid layer plan,
LoRA beside the base weight, the chunked loss, the program against the
benchmark's plain reference (``chipbench/reference/granite_hybrid.py``) at
the smoke widths on the CPU, and the frozen base reaching every program as
an argument rather than as a constant."""
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ExperimentSpec, build_experiment
from repro.configs import granite_4_0_h_micro as granite
from repro.core import engine
from repro.core.wireless import fleet_arrays
from repro.models import lm
from repro.models.lm import (LMConfig, adapter_num_params, base_params,
                             init_adapter, lm_evaluate, lm_loss,
                             with_adapter)
from repro.models.registry import register_workload, workload_config
from repro.models.transformer import _layer_plan, forward

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.reference import fl, granite_hybrid as ref  # noqa: E402

SMOKE = "granite-4.0-h-smoke"
HIGHEST = jax.lax.Precision.HIGHEST


def ref_cfg(lm: LMConfig) -> dict:
    """The reference's configuration (the published config's key names)
    for a program ``LMConfig``."""
    m, s = lm.model, lm.model.ssm
    return {
        "hidden_size": m.d_model, "num_hidden_layers": m.num_layers,
        "layer_types": ["attention" if m.is_attention_layer(i) else "mamba"
                        for i in range(m.num_layers)],
        "num_attention_heads": m.num_heads,
        "num_key_value_heads": m.num_kv_heads,
        "mamba_n_heads": s.expand * m.d_model // s.head_dim,
        "mamba_d_head": s.head_dim, "mamba_d_state": s.d_state,
        "mamba_n_groups": s.n_groups, "mamba_d_conv": s.conv_width,
        "mamba_expand": s.expand, "mamba_chunk_size": s.chunk_size,
        "shared_intermediate_size": m.d_ff, "vocab_size": m.vocab_size,
        "rms_norm_eps": m.norm_eps,
        "attention_multiplier": m.attention_multiplier,
        "embedding_multiplier": m.embedding_multiplier,
        "residual_multiplier": m.residual_multiplier,
        "logits_scaling": m.logits_scaling,
        "mamba_dt_min": s.dt_min, "mamba_dt_max": s.dt_max,
        "seq_len": lm.seq_len, "rank": lm.rank, "alpha": lm.alpha,
        "base_seed": lm.base_seed, "num_dialects": lm.num_dialects,
        "base_dtype": lm.base_dtype, "model": {"vocab_size": m.vocab_size}}


def _trained_adapter(cfg, seed=0):
    """An adapter whose B factors are not zero, so the LoRA terms count."""
    a = init_adapter(cfg, jax.random.PRNGKey(seed))
    leaves, tree = jax.tree_util.tree_flatten(a)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        l + 0.05 * jax.random.normal(k, l.shape) for l, k in zip(leaves,
                                                                   keys)])


def _tokens(cfg, n=2, seed=3):
    return jax.random.randint(jax.random.PRNGKey(seed), (n, cfg.seq_len + 1),
                              0, cfg.model.vocab_size)


# ---------------------------------------------------------------------------
# the layer plan and the configuration
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("model", [granite.CONFIG, granite.one_period(),
                                   granite.smoke_config()])
def test_attention_sits_at_index_5_of_each_period_of_10(model):
    plan = _layer_plan(model)
    attn = [i for i, (mixer, _) in enumerate(plan) if mixer == "attn"]
    assert attn == list(range(5, model.num_layers, 10))
    assert all(mlp == "dense" for _, mlp in plan)


def test_default_attn_index_keeps_attention_last_in_the_period():
    jamba = workload_config("tinyllama").model.replace(
        family="hybrid", num_layers=8, attn_period=4,
        ssm=granite.smoke_config().ssm)
    assert [m for m, _ in _layer_plan(jamba)] == (["mamba"] * 3
                                                  + ["attn"]) * 2


def test_the_benchmark_configuration_is_the_registered_workload():
    """The cell's file carries the program's one-period config under
    ``model`` (its ``widths``) and the published keys the reference reads;
    both say the same model."""
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "granite_4_0_h_micro.json")) as f:
        conf = json.load(f)
    lm = workload_config("granite-4.0-h-micro")
    want = ref_cfg(lm)
    for key, value in want.items():
        if key != "model":
            assert conf[key] == value, key
    from chipbench import check
    from chipbench.run import program_width
    for dotted in conf["widths"]:
        assert program_width(lm, dotted) == check.width(conf, dotted), dotted


def test_one_period_holds_the_published_widths():
    lm = workload_config("granite-4.0-h-micro")
    m = lm.model
    assert (m.d_model, m.num_heads, m.num_kv_heads, m.resolved_head_dim,
            m.d_ff, m.vocab_size) == (2048, 32, 8, 64, 8192, 100352)
    assert (m.ssm.d_state, m.ssm.head_dim, m.ssm.expand,
            m.ssm.chunk_size) == (128, 64, 2, 256)
    assert m.num_layers == 10 and granite.CONFIG.num_layers == 40
    assert adapter_num_params(lm) == 2_511_872
    base = jax.eval_shape(lambda: base_params(lm))
    n = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(base))
    assert 951_000_000 < n < 952_000_000
    assert {l.dtype for l in jax.tree_util.tree_leaves(base)} == {
        jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)}


# ---------------------------------------------------------------------------
# LoRA beside the base weight, the chunked loss
# ---------------------------------------------------------------------------


def _merged(cfg, base, adapter):
    """The base with ``W + (alpha/rank)·A·B`` formed for every wrapped
    weight: the old merged forward."""
    scale = cfg.alpha / cfg.rank
    m = cfg.model
    plan = _layer_plan(m)[:m.attn_period]
    groups = {}
    seen = {}
    for j, (kind, _) in enumerate(plan):
        per = sum(1 for k, _ in plan if k == kind)
        slot = seen.get(kind, 0)
        seen[kind] = slot + 1
        pos = dict(base["groups"][f"pos{j}"])
        mix = dict(pos[kind])
        ad = adapter["blocks"][kind]
        for name in (("wq", "wv") if kind == "attn"
                     else ("in_proj", "out_proj")):
            a = ad[f"{name}_a"].reshape((-1, per) + ad[f"{name}_a"]
                                        .shape[1:])[:, slot]
            b = ad[f"{name}_b"].reshape((-1, per) + ad[f"{name}_b"]
                                        .shape[1:])[:, slot]
            mix[name] = mix[name] + scale * jnp.einsum("gdr,grk->gdk", a, b)
        pos[kind] = mix
        groups[f"pos{j}"] = pos
    return {**base, "groups": groups}


def test_lora_beside_the_weight_equals_the_merged_forward():
    cfg = workload_config(SMOKE)
    base = base_params(cfg)
    adapter = _trained_adapter(cfg)
    toks = _tokens(cfg)[:, :-1]
    beside, _ = forward(cfg.model, with_adapter(cfg, base, adapter),
                        {"tokens": toks})
    merged, _ = forward(cfg.model, _merged(cfg, base, adapter),
                        {"tokens": toks})
    # the same sums in another order: float32 rounding only
    np.testing.assert_allclose(np.asarray(beside), np.asarray(merged),
                               rtol=1e-5, atol=1e-5)
    assert float(jnp.max(jnp.abs(beside - forward(
        cfg.model, base, {"tokens": toks})[0]))) > 1e-3


def test_fresh_hybrid_adapter_is_an_exact_noop():
    cfg = workload_config(SMOKE)
    base = base_params(cfg)
    toks = _tokens(cfg)[:, :-1]
    adapted = with_adapter(cfg, base, init_adapter(cfg, jax.random.PRNGKey(1)))
    np.testing.assert_array_equal(
        np.asarray(forward(cfg.model, base, {"tokens": toks})[0]),
        np.asarray(forward(cfg.model, adapted, {"tokens": toks})[0]))


def test_the_adapter_is_keyed_by_layer_kind():
    cfg = workload_config(SMOKE)
    a = init_adapter(cfg, jax.random.PRNGKey(0))
    shapes = {kind: {k: v.shape for k, v in d.items()}
              for kind, d in a["blocks"].items()}
    m = cfg.model
    d_inner = m.ssm.expand * m.d_model
    in_out = 2 * d_inner + 2 * m.ssm.d_state + d_inner // m.ssm.head_dim
    assert shapes == {
        "attn": {"wq_a": (1, 64, 4), "wq_b": (1, 4, 64),
                 "wv_a": (1, 64, 4), "wv_b": (1, 4, 32)},
        "mamba": {"in_proj_a": (9, 64, 4), "in_proj_b": (9, 4, in_out),
                  "out_proj_a": (9, d_inner, 4),
                  "out_proj_b": (9, 4, 64)}}


@pytest.mark.parametrize("chunk", [8, 16])
def test_chunked_loss_equals_the_full_loss(chunk, monkeypatch):
    cfg = workload_config(SMOKE)
    base = base_params(cfg)
    adapter = _trained_adapter(cfg)
    batch = {"images": _tokens(cfg), "labels": jnp.zeros((2,), jnp.int32)}
    lf, gf = jax.value_and_grad(lm_loss)(adapter, batch, cfg, frozen=base)
    acc_f = lm_evaluate(adapter, batch["images"], batch["labels"], cfg=cfg,
                        frozen=base)
    monkeypatch.setattr(lm, "HEAD_CHUNK", chunk)
    lp, gp = jax.value_and_grad(lm_loss)(adapter, batch, cfg, frozen=base)
    acc_p = lm_evaluate(adapter, batch["images"], batch["labels"], cfg=cfg,
                        frozen=base)
    # per-block sums added in another order: float32 rounding only
    np.testing.assert_allclose(float(lp), float(lf), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(gp),
                    jax.tree_util.tree_leaves(gf)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-7)
    assert float(acc_f[0]) == float(acc_p[0])


def test_chunked_loss_refuses_a_chunk_that_does_not_divide_the_window(
        monkeypatch):
    cfg = workload_config(SMOKE)
    monkeypatch.setattr(lm, "HEAD_CHUNK", 12)
    batch = {"images": _tokens(cfg), "labels": jnp.zeros((2,), jnp.int32)}
    with pytest.raises(ValueError, match="does not divide"):
        lm_loss(init_adapter(cfg, jax.random.PRNGKey(0)), batch, cfg,
                frozen=base_params(cfg))


# ---------------------------------------------------------------------------
# the program against the plain reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_reference_base_is_the_programs_element_for_element(dtype):
    cfg = dataclasses.replace(workload_config(SMOKE), base_dtype=dtype)
    prog = dict(fl.paths(base_params(cfg)))
    want = dict(fl.paths(ref.frozen(ref_cfg(cfg))))
    assert sorted(prog) == sorted(want)
    for name in prog:
        assert prog[name].dtype == want[name].dtype, name
        np.testing.assert_array_equal(np.asarray(prog[name]),
                                      np.asarray(want[name]), err_msg=name)


def test_reference_adapter_init_is_the_programs():
    cfg = workload_config(SMOKE)
    key = jax.random.PRNGKey(17)
    prog = dict(fl.paths(init_adapter(cfg, key)))
    want = dict(fl.paths(ref.init(ref_cfg(cfg), key)))
    assert sorted(prog) == sorted(want)
    for name in prog:
        np.testing.assert_array_equal(np.asarray(prog[name]),
                                      np.asarray(want[name]), err_msg=name)
    assert ref.adapter_params(ref_cfg(cfg)) == adapter_num_params(cfg)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_program_matches_the_reference(dtype):
    """Logits, loss and the adapter's gradients, program against the plain
    reference on the same base (the reference in float32 on the
    bf16-valued base where the program stores it in bfloat16)."""
    cfg = dataclasses.replace(workload_config(SMOKE), base_dtype=dtype)
    rc = ref_cfg(cfg)
    base = base_params(cfg)
    rbase = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), base)
    adapter = _trained_adapter(cfg)
    toks = _tokens(cfg)
    batch = {"images": toks, "labels": jnp.zeros((2,), jnp.int32)}

    params = with_adapter(cfg, base, adapter)
    logits, _ = forward(cfg.model, params, {"tokens": toks[:, :-1]},
                        act_dtype=jnp.float32)
    want = ref._logits(rbase, ref.hidden(adapter, rbase, toks[:, :-1], rc,
                                         HIGHEST), rc, HIGHEST)
    # the SSD's chunked form against its recurrence, the online softmax
    # against the dense one: float32 sums in another order, on logits of
    # size ~1
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                               rtol=2e-4, atol=2e-4)

    lp, gp = jax.value_and_grad(lm_loss)(adapter, batch, cfg, frozen=base)
    lr, gr = jax.value_and_grad(ref.loss)(adapter, toks, None, rc, HIGHEST,
                                          rbase)
    np.testing.assert_allclose(float(lp), float(lr), rtol=1e-5)
    flat_p = dict(fl.paths(gp))
    for name, g in fl.paths(gr):
        # each leaf's gradient against its own scale: the same reorderings
        # reach the gradients through the backward pass
        scale = float(jnp.max(jnp.abs(g)))
        assert scale > 0.0, name
        np.testing.assert_allclose(np.asarray(flat_p[name]) / scale,
                                   np.asarray(g) / scale, atol=1e-3,
                                   err_msg=name)

    acc_p, _ = lm_evaluate(adapter, toks, jnp.zeros((2,), jnp.int32),
                           cfg=cfg, frozen=base)
    acc_r = ref.evaluate(adapter, toks, None, rc, HIGHEST, rbase)
    assert abs(float(acc_p) - float(acc_r)) <= 1.0 / cfg.seq_len


def test_reference_counts_no_base_weight_gradient():
    rc = ref_cfg(workload_config("granite-4.0-h-micro"))
    fwd, train = ref.eval_flops(rc), ref.train_flops(rc)
    # a backward with base-weight gradients would come to about twice the
    # forward; activation gradients alone to about one forward
    assert 1.9 * fwd < train < 2.2 * fwd
    # about two FLOPs per base parameter and token: 952 M parameters
    assert 1.85e9 < fwd / rc["seq_len"] < 2.0e9


# ---------------------------------------------------------------------------
# the frozen base is an argument of every program, never a constant
# ---------------------------------------------------------------------------

#: the smoke stack with a 8192-token vocabulary: a 2 MiB embedding, so
#: a base baked into a program would show as a constant over 1 MiB
WIDE_VOCAB = "granite-4.0-h-smoke-v8192"
register_workload(WIDE_VOCAB, lambda: dataclasses.replace(
    workload_config(SMOKE),
    model=granite.smoke_config().replace(vocab_size=8192)))

TINY = dict(clients=4, train_samples=20, test_samples=2,
            samples_per_client=4, devices_per_round=2, num_clusters=2,
            local_iters=1, batch_size=1, rounds=1, learning_rate=0.02)


def _consts(closed):
    """Every constant of a closed jaxpr and of the jaxprs inside it."""
    out = list(closed.consts)
    for eqn in closed.jaxpr.eqns:
        for v in eqn.params.values():
            subs = v if isinstance(v, (list, tuple)) else [v]
            for sub in subs:
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    out += _consts(sub)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    out += _consts(jax.extend.core.ClosedJaxpr(sub, ()))
    return out


def test_no_round_program_holds_a_constant_over_1_mib():
    exp = build_experiment(ExperimentSpec(model=WIDE_VOCAB, **TINY))
    embed = exp.engine.frozen["embed"]
    assert embed.size * embed.dtype.itemsize > 2 ** 20
    fn = engine.run_rounds(
        exp.engine.cfg, selector=exp.selector, allocator=exp.allocator,
        aggregator=exp.aggregator, compressor=exp.compressor,
        tctx=exp.traced_context(), feature_layer=exp.fl.feature_layer,
        rounds=1, with_init=True, channel=exp.channel)
    args = (exp.traced_state(), exp._images, exp._labels, exp._sizes,
            fleet_arrays(exp.fleet), exp.test_images, exp.test_labels,
            exp.engine.frozen)
    eng = exp.engine
    programs = {
        "run_rounds": fn.trace(*args).jaxpr,
        "train_clients": eng._train_clients.trace(
            exp.global_params, exp._images[:2], exp._labels[:2],
            jax.random.split(jax.random.PRNGKey(0), 2), eng.frozen).jaxpr,
        "evaluate": eng._evaluate.trace(
            exp.global_params, exp.test_images, exp.test_labels,
            eng.frozen).jaxpr,
    }
    for name, closed in programs.items():
        sizes = [np.asarray(c).nbytes for c in _consts(closed)
                 if hasattr(c, "shape")]
        assert max(sizes, default=0) <= 2 ** 20, (name, max(sizes))


def test_granite_runs_through_the_normal_scanned_route():
    exp = build_experiment(ExperimentSpec(model=SMOKE, **TINY))
    assert exp.traceable()
    hist = exp.run(rounds=1)
    assert len(hist.accuracy) == 2
    assert exp.client_params.shape == (4, adapter_num_params(exp.model_cfg))
    assert all(np.isfinite(t) for t in hist.T_k)


@pytest.mark.parametrize("route", [
    {"target_accuracy": 0.99},                  # host loop, fused round
    {"fedprox_mu": 0.1},                        # FedProx local update
    {"store": "paged", "k_max": 4},             # paged host loop
    {"aggregator": "fedbuff:2"},                # buffered-async scan
    {"aggregator": "fedbuff:2", "store": "paged", "k_max": 4},
])
def test_every_route_hands_the_base_to_its_programs(route):
    hist = build_experiment(ExperimentSpec(
        model=SMOKE, **dict(TINY, **route))).run(rounds=1)
    assert len(hist.accuracy) == 2
    assert all(np.isfinite(a) for a in hist.accuracy)
