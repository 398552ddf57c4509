"""Partition-rule unit tests (divisibility-aware fallbacks)."""
import jax
import numpy as np
import pytest

from repro.sharding.specs import param_spec, batch_axes

jax.config.update("jax_platforms", "cpu")


class FakeMesh:
    """Just enough Mesh interface for the rule functions."""
    def __init__(self, shape):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)


MESH1 = FakeMesh({"data": 16, "model": 16})
MESH2 = FakeMesh({"pod": 2, "data": 16, "model": 16})


def leaf(*shape):
    return jax.ShapeDtypeStruct(shape, jax.numpy.bfloat16)


def test_embed_vocab_sharded():
    assert param_spec(["embed"], leaf(152064, 8192), MESH1) \
        == jax.sharding.PartitionSpec("model", None)


def test_lm_head_vocab_sharded():
    assert param_spec(["lm_head"], leaf(8192, 152064), MESH1) \
        == jax.sharding.PartitionSpec(None, "model")


def test_attention_projections():
    # [L, D, H*hd] fused projection dim sharded (works even when head
    # count isn't divisible — granite's 24 heads × 64 = 1536 % 16 == 0)
    spec = param_spec(["blocks", "attn", "wq"], leaf(32, 1536, 1536), MESH1)
    assert spec == jax.sharding.PartitionSpec(None, None, "model")
    spec = param_spec(["blocks", "attn", "wo"], leaf(32, 1536, 1536), MESH1)
    assert spec == jax.sharding.PartitionSpec(None, "model", None)


def test_moe_expert_sharding_divisible():
    # jamba: 16 experts % 16 == 0 -> expert-sharded
    spec = param_spec(["groups", "pos1", "moe", "w_gate"],
                      leaf(9, 16, 8192, 24576), MESH1)
    assert spec == jax.sharding.PartitionSpec(None, "model", None, None)


def test_moe_expert_sharding_fallback():
    # granite: 40 experts % 16 != 0 -> falls back to the FFN dim... which
    # is 512 % 16 == 0
    spec = param_spec(["blocks", "moe", "w_gate"],
                      leaf(32, 40, 1536, 512), MESH1)
    assert spec == jax.sharding.PartitionSpec(None, None, None, "model")


def test_router_replicated():
    spec = param_spec(["blocks", "moe", "router"], leaf(32, 1536, 40), MESH1)
    assert spec == jax.sharding.PartitionSpec(None, None, None)


def test_norms_replicated():
    spec = param_spec(["blocks", "ln1"], leaf(32, 8192), MESH1)
    assert spec == jax.sharding.PartitionSpec(None, None)


def test_fallback_largest_divisible():
    # unknown 2D leaf: shard the largest divisible trailing dim
    spec = param_spec(["something"], leaf(100, 4096), MESH1)
    assert spec == jax.sharding.PartitionSpec(None, "model")


def test_indivisible_everything_replicates():
    spec = param_spec(["weird"], leaf(7, 13), MESH1)
    assert spec == jax.sharding.PartitionSpec(None, None)


@pytest.mark.parametrize("mesh,batch,expect", [
    (MESH1, 256, ("data",)),
    (MESH2, 256, ("pod", "data")),
    (MESH2, 2, ("pod",)),
    (MESH1, 1, ()),
    (MESH2, 1, ()),
    (MESH1, 33, ()),                       # not divisible -> replicate
])
def test_batch_axes(mesh, batch, expect):
    assert batch_axes(mesh, batch) == expect


def test_kernel_seams_on_a_plane_mesh():
    """On a 4-device ``model`` mesh the kernel seams shard_map their Pallas
    calls (GSPMD cannot partition Mosaic kernels): results match the jnp
    references, the aggregate stays column-split, and a column count the
    axis does not divide runs whole on every device. Interpret mode on 4
    forced host devices, in a child process."""
    import os
    import subprocess
    import sys
    import textwrap

    code = textwrap.dedent("""
        import warnings
        import numpy as np
        import jax, jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.kernels import ops
        warnings.simplefilter("ignore", RuntimeWarning)
        assert len(jax.devices()) == 4, jax.devices()
        mesh = Mesh(np.asarray(jax.devices()), ("model",))
        k = jax.random.split(jax.random.PRNGKey(0), 3)
        x = jax.random.normal(k[0], (12, 1024))
        g = jax.random.normal(k[1], (1024,))
        w = jnp.abs(jax.random.normal(k[2], (12,))) + 0.1
        c = x[:3] + 0.5

        def all_ops(x, g, w, c, use_pallas):
            return (ops.flat_aggregate(x, w, use_pallas=use_pallas),
                    ops.client_divergence(x, g, use_pallas=use_pallas),
                    ops.pairwise_sq_dists(x, c, use_pallas=use_pallas),
                    ops.pairwise_sq_dists(x[:, :1001], c[:, :1001],
                                          use_pallas=use_pallas))

        want = all_ops(x, g, w, c, False)
        xs = jax.device_put(x, NamedSharding(mesh, P(None, "model")))
        with jax.set_mesh(mesh):
            got = jax.jit(lambda *a: all_ops(*a, True))(xs, g, w, c)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=2e-3)
        assert got[0].sharding.spec == P("model"), got[0].sharding
        print("SEAMS-OK")
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), "..", "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "SEAMS-OK" in out.stdout, out.stdout + "\n" + out.stderr
