"""Federated LM subsystem: model registry, LoRA adapter rows, kernel
dispatch at LM shapes, streaming K-means, P-axis plane specs, and the
model="cnn" bit-identity pin."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ExperimentSpec, build_experiment
from repro.core.clustering import (adjusted_rand_index, kmeans_fit,
                                   kmeans_fit_minibatch)
from repro.kernels import ops
from repro.models.lm import (LMConfig, adapter_num_params, base_params,
                             init_adapter, lm_loss, with_adapter)
from repro.models.registry import (model_def_for, workload_config,
                                   workload_names)
from repro.models.transformer import forward
from repro.utils.trees import (flatten_stacked, stack_flatten_spec,
                               tree_num_params, tree_weighted_mean_stacked,
                               tree_flatten_vector, unflatten_vector)

TINY_LM = dict(model="tinyllama", clients=6, train_samples=48,
               test_samples=16, samples_per_client=8, devices_per_round=2,
               num_clusters=2, local_iters=2, batch_size=4, rounds=2,
               learning_rate=0.1, seed=0)


# ---------------------------------------------------------------------------
# model registry
# ---------------------------------------------------------------------------


def test_registry_knows_builtin_workloads():
    assert "tinyllama" in workload_names()
    assert "mamba2-130m" in workload_names()
    cfg = workload_config("tinyllama")
    assert isinstance(cfg, LMConfig)
    assert model_def_for(cfg).name == "lora-lm"
    assert model_def_for(cfg).price_uploads


def test_registry_unknown_raises():
    with pytest.raises(ValueError, match="unknown model"):
        workload_config("gpt-17")
    with pytest.raises(TypeError, match="no ModelDef"):
        model_def_for(object())


def test_spec_json_roundtrip_preserves_model():
    spec = ExperimentSpec(**TINY_LM)
    back = ExperimentSpec.from_json(spec.to_json())
    assert back == spec
    assert back.model == "tinyllama"


def test_spec_rejects_unknown_model():
    with pytest.raises(ValueError, match="unknown model"):
        ExperimentSpec(model="no-such-arch")


def test_build_resolves_lm_workload():
    exp = build_experiment(ExperimentSpec(**TINY_LM))
    assert isinstance(exp.model_cfg, LMConfig)
    p_adapter = adapter_num_params(exp.model_cfg)
    assert exp.client_params.shape == (TINY_LM["clients"], p_adapter)
    # uploads priced at P_adapter fp32 bits, never P_base
    assert np.allclose(exp.fleet.z, p_adapter * 32 / 1e6)
    assert tree_num_params(base_params(exp.model_cfg)) > 20 * p_adapter


# ---------------------------------------------------------------------------
# model="cnn" stays on the paper-CNN path, bit-identical to the default
# ---------------------------------------------------------------------------


def test_model_cnn_is_bit_identical_to_auto():
    tiny = dict(dataset="mnist", clients=8, train_samples=64,
                test_samples=32, samples_per_client=8, devices_per_round=4,
                num_clusters=2, local_iters=2, batch_size=4, rounds=2)
    e_auto = build_experiment(ExperimentSpec(**tiny))
    e_cnn = build_experiment(ExperimentSpec(model="cnn", **tiny))
    # same frozen config -> the SAME shared engine (cache key unperturbed)
    assert e_cnn.engine is e_auto.engine
    h_auto = e_auto.run(rounds=2)
    h_cnn = e_cnn.run(rounds=2)
    assert h_auto.accuracy == h_cnn.accuracy
    assert h_auto.T_k == h_cnn.T_k and h_auto.E_k == h_cnn.E_k
    np.testing.assert_array_equal(np.asarray(e_auto.client_params),
                                  np.asarray(e_cnn.client_params))


# ---------------------------------------------------------------------------
# LoRA adapter rows
# ---------------------------------------------------------------------------


def _tinyllama_cfg() -> LMConfig:
    return workload_config("tinyllama")


def test_fresh_adapter_is_exact_noop_on_base():
    cfg = _tinyllama_cfg()
    adapter = init_adapter(cfg, jax.random.PRNGKey(3))
    base = base_params(cfg)
    adapted = with_adapter(cfg, base, adapter)
    toks = jax.random.randint(jax.random.PRNGKey(0), (2, cfg.seq_len), 0,
                              cfg.model.vocab_size)
    ref, _ = forward(cfg.model, base, {"tokens": toks})
    out, _ = forward(cfg.model, adapted, {"tokens": toks})
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))


def test_lora_flat_roundtrip_and_aggregation_parity():
    """Adapter rows on the flat plane aggregate bitwise like the stacked
    pytree (the flat≡pytree contract, now for the LM workload)."""
    cfg = _tinyllama_cfg()
    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    adapters = [init_adapter(cfg, k) for k in keys]
    # make B factors nonzero so the parity check sees real values
    adapters = [jax.tree_util.tree_map(
        lambda l, s=i: l + 0.01 * (s + 1), a)
        for i, a in enumerate(adapters)]
    stacked = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *adapters)
    spec = stack_flatten_spec(adapters[0])
    rows = flatten_stacked(stacked)
    assert rows.shape == (4, adapter_num_params(cfg))
    # round-trip: row -> tree -> identical leaves
    back = unflatten_vector(spec, rows[2])
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        adapters[2], back)
    w = jnp.asarray([1.0, 2.0, 3.0, 4.0])
    flat_agg = ops.flat_aggregate(rows, w)
    tree_agg = tree_weighted_mean_stacked(stacked, w)
    np.testing.assert_array_equal(np.asarray(flat_agg),
                                  np.asarray(tree_flatten_vector(tree_agg)))


def test_lm_loss_is_finite_and_differentiable():
    cfg = _tinyllama_cfg()
    adapter = init_adapter(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, cfg.seq_len + 1), 0,
                              cfg.model.vocab_size)
    batch = {"images": toks, "labels": jnp.zeros((4,), jnp.int32)}
    loss, g = jax.value_and_grad(lm_loss)(adapter, batch, cfg,
                                          frozen=base_params(cfg))
    assert np.isfinite(float(loss))
    gnorm = sum(float(jnp.sum(jnp.abs(l)))
                for l in jax.tree_util.tree_leaves(g))
    assert gnorm > 0.0


# ---------------------------------------------------------------------------
# kernel dispatch at LM shapes
# ---------------------------------------------------------------------------


def test_kernel_dispatch_policy(monkeypatch):
    monkeypatch.delenv("REPRO_FORCE_PALLAS", raising=False)
    on_tpu = jax.default_backend() == "tpu"
    assert ops.kernel_dispatch(None) is on_tpu
    assert ops.kernel_dispatch(True) is True
    assert ops.kernel_dispatch(False) is False
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    assert ops.kernel_dispatch(None) is True


@pytest.mark.parametrize("arch", ["tinyllama", "mamba2-130m"])
def test_forward_kernel_route_matches_reference(arch):
    """Flash-attention / SSD kernels (interpret mode off-TPU) vs the jnp
    reference at the federated LM shapes."""
    cfg = workload_config(arch)
    params = base_params(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, cfg.seq_len), 0,
                              cfg.model.vocab_size)
    ref, _ = forward(cfg.model, params, {"tokens": toks})
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        ker, _ = forward(cfg.model, params, {"tokens": toks},
                         use_pallas=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(ker),
                               atol=2e-5, rtol=2e-5)


def test_attention_kernel_drift_at_lm_shapes():
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k1, (2, 32, 8, 16))
    k = jax.random.normal(k2, (2, 32, 2, 16))
    v = jax.random.normal(k3, (2, 32, 2, 16))
    ref = ops.attention(q, k, v, causal=True, use_pallas=False)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        ker = ops.attention(q, k, v, causal=True, use_pallas=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(ker), atol=2e-5,
                               rtol=2e-5)


def test_off_tpu_use_pallas_true_warns():
    if jax.default_backend() == "tpu":
        pytest.skip("warning is off-TPU only")
    cfg = workload_config("tinyllama")
    toks = jnp.zeros((1, cfg.seq_len), jnp.int32)
    with pytest.warns(RuntimeWarning, match="interpret mode"):
        forward(cfg.model, base_params(cfg), {"tokens": toks},
                use_pallas=True)


# ---------------------------------------------------------------------------
# end-to-end federated LM
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["tinyllama", "mamba2-130m"])
def test_lm_federated_run_traced(arch):
    exp = build_experiment(ExperimentSpec(**{**TINY_LM, "model": arch}))
    assert exp.traceable()
    hist = exp.run(rounds=2)
    assert len(hist.accuracy) == 3            # init + 2 scanned rounds
    assert all(np.isfinite(a) for a in hist.accuracy)
    assert all(t > 0 for t in hist.T_k)
    assert exp.cluster_labels is not None


@pytest.mark.slow
def test_lm_run_with_p_sharding_matches_unsharded():
    """p_shards on a single device is layout-only — numerics unchanged."""
    h0 = build_experiment(ExperimentSpec(**TINY_LM)).run(rounds=2)
    h1 = build_experiment(
        ExperimentSpec(**{**TINY_LM, "p_shards": 1})).run(rounds=2)
    assert h0.accuracy == h1.accuracy


# ---------------------------------------------------------------------------
# streaming / minibatch K-means
# ---------------------------------------------------------------------------


def _blobs(n=60, f=8, c=3, seed=0):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(c, f)) * 20.0
    labels = np.repeat(np.arange(c), n // c)
    return (centers[labels] + rng.normal(size=(n, f))).astype(np.float32), \
        labels


def test_minibatch_kmeans_single_chunk_equals_full_fit():
    x, _ = _blobs()
    key = jax.random.PRNGKey(7)
    _, full_labels, full_inertia = kmeans_fit(key, jnp.asarray(x), 3)
    _, mb_labels, mb_inertia = kmeans_fit_minibatch(
        key, lambda: iter([x]), 3)
    np.testing.assert_array_equal(np.asarray(full_labels),
                                  np.asarray(mb_labels))
    assert np.isclose(float(full_inertia), float(mb_inertia))


def test_minibatch_kmeans_multi_chunk_recovers_clusters():
    x, truth = _blobs(n=90)
    chunks = lambda: iter([x[:30], x[30:60], x[60:]])
    _, labels, _ = kmeans_fit_minibatch(jax.random.PRNGKey(0), chunks, 3)
    assert adjusted_rand_index(np.asarray(labels), truth) > 0.95


def test_minibatch_kmeans_empty_stream_raises():
    with pytest.raises(ValueError, match="empty"):
        kmeans_fit_minibatch(jax.random.PRNGKey(0), lambda: iter([]), 3)


@pytest.mark.slow
def test_cluster_minibatch_spec_matches_full_on_small_fleet():
    """cluster='minibatch' on a single-chunk fleet pins to the full fit."""
    tiny = dict(dataset="mnist", clients=8, train_samples=64,
                test_samples=16, samples_per_client=8, devices_per_round=4,
                num_clusters=2, local_iters=1, batch_size=4, rounds=1)
    e_full = build_experiment(ExperimentSpec(**tiny))
    e_mb = build_experiment(ExperimentSpec(cluster="minibatch", **tiny))
    e_full.initial_round()
    e_mb.initial_round()
    np.testing.assert_array_equal(e_full.cluster_labels, e_mb.cluster_labels)


def test_spec_rejects_bad_cluster_mode():
    with pytest.raises(ValueError, match="cluster"):
        ExperimentSpec(cluster="online")


# ---------------------------------------------------------------------------
# P-axis plane sharding specs
# ---------------------------------------------------------------------------


class FakeMesh:
    def __init__(self, shape):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)


def _leaf(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def test_plane_spec_shards_p_axis_when_divisible():
    from jax.sharding import PartitionSpec as P
    from repro.sharding.specs import plane_spec
    mesh = FakeMesh({"model": 4})
    assert plane_spec(_leaf(10, 64), mesh, 64) == P(None, "model")
    assert plane_spec(_leaf(64), mesh, 64) == P("model")
    # N-sized and scalar leaves replicate; so does a non-divisible P
    assert plane_spec(_leaf(10), mesh, 64) == P(None)
    assert plane_spec(_leaf(10, 63), mesh, 63) == P(None, None)
    # rightmost P-sized dim wins (N == P corner)
    assert plane_spec(_leaf(64, 64), mesh, 64) == P(None, "model")


def test_plane_mesh_off_and_degenerate():
    from repro.sharding.specs import plane_mesh
    assert plane_mesh(0) is None
    mesh = plane_mesh(4)            # single-device env: degenerates to 1
    assert mesh is not None
    assert mesh.shape["model"] == 1
