"""Compile rehearsals for a described TPU v5e — no chip attached.

Each case lowers and compiles a kernel or program of the main path at
real widths for a ``v5e:2x2`` topology that is described, not attached:
what Mosaic or XLA:TPU would refuse on the chip (block tiling, VMEM,
HBM fit, kernels GSPMD cannot partition) fails here. Nothing runs, so
these say nothing about results or speed.

The topology is described inside a module-scoped fixture (never at import
time: only one process may load the TPU library, and every test worker
imports this file). The persistent compilation cache is off around these
tests, because a TPU executable written to it cannot be read back here.
"""
import collections
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flat_aggregate import flat_aggregate
from repro.kernels.pairwise_l2 import pairwise_l2
from repro.kernels.ssd_scan import ssd_scan

P_MNIST = 113_744            # Table II MNIST CNN parameters
HBM_BYTES = 15.75 * 2 ** 30  # what XLA:TPU lets one v5e program use


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    # drop every trace taken with the kernel route steered on, so no later
    # test in this worker reuses one on the CPU
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        # otherwise the TPU compiler writes its logs outside the checkout
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:     # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def kernel_route(monkeypatch):
    """Steer ``ops`` onto the Pallas kernels with ``interpret=False``, as on
    a TPU backend (this process's default backend is the CPU)."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, compiled.as_text()


def _sds(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# ---------------------------------------------------------------------------
# kernels at the widths they run at
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [10, 40])
def test_flat_aggregate_compiles(one_chip, n):
    _, text = _compile(lambda x, w: flat_aggregate(x, w, interpret=False),
                       _sds((n, P_MNIST), one_chip), _sds((n,), one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m", [1, 10])     # divergence / K-means centroids
def test_pairwise_l2_compiles(one_chip, m):
    _, text = _compile(lambda x, c: pairwise_l2(x, c, interpret=False),
                       _sds((40, P_MNIST), one_chip),
                       _sds((m, P_MNIST), one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("s", [32, 2048])
def test_flash_attention_compiles_at_tinyllama_widths(one_chip, s):
    qkv = _sds((1, 32, s, 64), one_chip)            # 32 heads, head_dim 64
    _, text = _compile(lambda q, k, v: flash_attention(q, k, v,
                                                       interpret=False),
                       qkv, qkv, qkv)
    assert "tpu_custom_call" in text


def test_ssd_scan_compiles_at_mamba2_130m_widths(one_chip):
    """24 heads × head_dim 64, d_state 128, chunk 256, two chunks. Refused
    before the log-decay input moved to a [BH, S, 1] column."""
    bh, s = 24, 512
    _, text = _compile(
        lambda x, a, b, c: ssd_scan(x, a, b, c, chunk=256, interpret=False),
        _sds((bh, s, 64), one_chip), _sds((bh, s), one_chip),
        _sds((bh, s, 128), one_chip), _sds((bh, s, 128), one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kernel", ["ssd_scan", "flash_attention"])
def test_granite_eval_kernels_compile_and_keep_their_names(one_chip, kernel):
    """The kernels granite-4.0-h-micro's evaluation takes on a TPU, at its
    widths over 4 test windows of 2048 tokens: each compiles, and its call
    is named after the kernel (the ``<kernel>_roofline`` metrics find the
    calls by that name in a trace)."""
    if kernel == "ssd_scan":
        fn = functools.partial(ssd_scan, chunk=256, interpret=False)
        args = (_sds((256, 2048, 64), one_chip), _sds((256, 2048), one_chip),
                _sds((256, 2048, 128), one_chip),
                _sds((256, 2048, 128), one_chip))
    else:
        fn = functools.partial(flash_attention, causal=True, interpret=False,
                               scale=0.015625)
        args = (_sds((4, 32, 2048, 64), one_chip),) * 3
    _, text = _compile(fn, *args)
    assert f"%{kernel}." in text and "tpu_custom_call" in text


# ---------------------------------------------------------------------------
# programs
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["tinyllama", "mamba2-130m"])
def test_lora_local_update_compiles_with_kernel_route(one_chip, kernel_route,
                                                      arch):
    """``jax.grad`` of ``lm_loss`` (the LoRA local update) compiles with the
    kernel route on — it failed before, differentiating through the
    forward-only kernels — while evaluation keeps the kernels."""
    from repro.core.engine import make_local_update
    from repro.models.lm import base_params, init_adapter, lm_evaluate
    from repro.models.registry import workload_config

    cfg = workload_config(arch)
    adapter = jax.eval_shape(functools.partial(init_adapter, cfg),
                             jax.ShapeDtypeStruct((2,), jnp.uint32))
    adapter = jax.tree_util.tree_map(
        lambda x: _sds(x.shape, one_chip, x.dtype), adapter)
    frozen = jax.tree_util.tree_map(
        lambda x: _sds(x.shape, one_chip, x.dtype),
        jax.eval_shape(functools.partial(base_params, cfg)))
    windows = _sds((8, cfg.seq_len + 1), one_chip, jnp.int32)
    dialects = _sds((8,), one_chip, jnp.int32)
    key = _sds((2,), one_chip, jnp.uint32)
    update = make_local_update(cfg, 0.05, local_iters=2, batch_size=4)
    _, text = _compile(update, adapter, windows, dialects, key, frozen)
    assert "tpu_custom_call" not in text
    _, text = _compile(
        lambda a, w, d, f: lm_evaluate(a, w, d, cfg=cfg, frozen=f),
        adapter, windows, dialects, frozen)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("s", [10, 40])     # selected clients / initial round
def test_cnn_local_update_temp_stays_small(one_chip, s):
    """The vmapped local update at the paper's MNIST widths (D_n=128, L=20,
    batch 32) asks under 32 MiB of temp per client. An im2col lowering of
    the 5x5 convolutions, its patch matrix padded on the lane axis, asked
    1.18 GB at S=10 and 4.93 GB at S=40; the native one about 59 MB and
    0.59 GB."""
    from repro.configs.paper_cnn import MNIST_CNN
    from repro.core.engine import make_local_update
    from repro.models.cnn import init_cnn

    params = jax.eval_shape(functools.partial(init_cnn, MNIST_CNN),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    params = jax.tree_util.tree_map(
        lambda x: _sds(x.shape, one_chip, x.dtype), params)
    update = jax.vmap(make_local_update(MNIST_CNN, 0.05, local_iters=20,
                                        batch_size=32),
                      in_axes=(None, 0, 0, 0))
    compiled, _ = _compile(update, params,
                           _sds((s, 128, 28, 28, 1), one_chip),
                           _sds((s, 128), one_chip, jnp.int32),
                           _sds((s, 2), one_chip, jnp.uint32))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < s * 32 * 2 ** 20, temp


def _round_program_args(exp, sharding_of):
    from repro.core.wireless import fleet_arrays
    args = (exp.traced_state(), exp._images, exp._labels, exp._sizes,
            fleet_arrays(exp.fleet), exp.test_images, exp.test_labels)
    return jax.tree_util.tree_map(
        lambda x: _sds(x.shape, sharding_of(x), x.dtype), args)


def _run_rounds_for(exp, monkeypatch):
    from repro.core import engine
    # a fresh program cache: no trace taken on the CPU route is reused
    monkeypatch.setattr(engine, "_RUN_FN_CACHE", collections.OrderedDict())
    return engine.run_rounds(
        exp.engine.cfg, selector=exp.selector, allocator=exp.allocator,
        aggregator=exp.aggregator, compressor=exp.compressor,
        tctx=exp.traced_context(), feature_layer=exp.fl.feature_layer,
        rounds=1, with_init=True, channel=exp.channel)


def test_cnn_round_program_compiles_on_one_chip(one_chip, kernel_route,
                                                monkeypatch):
    """``rounds=1`` of the scanned program at the paper's MNIST widths
    (N=40, P=113,744): the Mosaic kernels are in it and it fits one v5e."""
    from repro.api import ExperimentSpec, build_experiment
    exp = build_experiment(ExperimentSpec(rounds=1))
    fn = _run_rounds_for(exp, monkeypatch)
    lowered = fn.lower(*_round_program_args(exp, lambda x: one_chip))
    text = lowered.as_text()
    for kernel in ("_flat_aggregate_kernel", "_pairwise_l2_kernel"):
        assert f'kernel_name = "{kernel}"' in text, kernel
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, used


def test_p_shards_round_program_partitions_the_kernels(topo, kernel_route,
                                                       monkeypatch):
    """``p_shards=4`` over the described 2x2 host: GSPMD cannot partition a
    Mosaic call, so the kernel seams shard_map them onto the plane's
    column shards (lowering refused before)."""
    from repro.api import ExperimentSpec, build_experiment
    from repro.sharding.specs import plane_shardings
    exp = build_experiment(ExperimentSpec(rounds=1))
    fn = _run_rounds_for(exp, monkeypatch)
    mesh = Mesh(np.asarray(topo.devices[:4]), ("model",))
    args = _round_program_args(exp, lambda x: NamedSharding(mesh, P()))
    state = exp.traced_state()
    shard = plane_shardings(state, mesh, int(state.params.shape[0]))
    args = (jax.tree_util.tree_map(lambda a, s: _sds(a.shape, s, a.dtype),
                                   args[0], shard),) + args[1:]
    with jax.set_mesh(mesh):
        text = fn.lower(*args).as_text()
    shard_cols = P_MNIST // 4 + (-(P_MNIST // 4)) % 512   # 512-col blocks
    plane_calls = [ln for ln in text.splitlines()
                   if "@tpu_custom_call" in ln
                   and f"x{shard_cols}xf32" in ln]
    names = {n for ln in plane_calls for n in
             ("_flat_aggregate_kernel", "_pairwise_l2_kernel") if n in ln}
    assert names == {"_flat_aggregate_kernel", "_pairwise_l2_kernel"}
