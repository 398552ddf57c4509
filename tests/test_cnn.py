"""The client CNN's 5x5 VALID convolution (``models.cnn._conv``) against a
float64 NumPy direct convolution, forward and backward, and vmapped over
per-client kernels as the FL round runs it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.cnn import _conv

K = 5        # the paper's kernel size


def _direct_conv(x, w, b):
    """y[n, i, j, o] = sum over (di, dj, c) of x[n, i+di, j+dj, c] w[di, dj, c, o]."""
    H = x.shape[1] - K + 1
    W = x.shape[2] - K + 1
    y = np.zeros((x.shape[0], H, W, w.shape[-1]))
    for di in range(K):
        for dj in range(K):
            y += np.einsum("nijc,co->nijo", x[:, di:di + H, dj:dj + W, :],
                           w[di, dj])
    return y + b


def _direct_conv_grads(x, w, g):
    """Gradients of sum(conv(x, w, b) * g) with respect to w and x."""
    H, W = g.shape[1], g.shape[2]
    gw = np.zeros(w.shape)
    gx = np.zeros(x.shape)
    for di in range(K):
        for dj in range(K):
            gw[di, dj] = np.einsum("nijc,nijo->co",
                                   x[:, di:di + H, dj:dj + W, :], g)
            gx[:, di:di + H, dj:dj + W, :] += np.einsum("nijo,co->nijc", g,
                                                        w[di, dj])
    return gw, gx


def _draw(rng, *shape):
    return rng.standard_normal(shape).astype(np.float32)


# conv1 of the MNIST CNN, conv2 of the MNIST CNN, conv1 of the CIFAR-10 CNN
@pytest.mark.parametrize("cin,cout,hw", [(1, 15, 28), (15, 28, 12),
                                         (3, 15, 32)])
def test_conv_matches_direct_convolution(cin, cout, hw):
    rng = np.random.default_rng(cin * 1000 + cout)
    x = _draw(rng, 2, hw, hw, cin)
    w = _draw(rng, K, K, cin, cout) / np.float32(np.sqrt(K * K * cin))
    b = _draw(rng, cout)
    g = _draw(rng, 2, hw - K + 1, hw - K + 1, cout)
    x64, w64, b64, g64 = (a.astype(np.float64) for a in (x, w, b, g))

    with jax.default_matmul_precision("highest"):
        y = _conv(jnp.asarray(x), jnp.asarray(w), jnp.asarray(b))
        gw, gx = jax.grad(
            lambda w_, x_: jnp.sum(_conv(x_, w_, jnp.asarray(b)) * g),
            argnums=(0, 1))(jnp.asarray(w), jnp.asarray(x))

        # as the round runs it: one kernel per client, vmapped
        xs = _draw(rng, 4, 2, hw, hw, cin)
        ws = _draw(rng, 4, K, K, cin, cout) / np.float32(np.sqrt(K * K * cin))
        bs = _draw(rng, 4, cout)
        ys = jax.vmap(_conv)(jnp.asarray(xs), jnp.asarray(ws),
                             jnp.asarray(bs))
        looped = [_conv(jnp.asarray(xs[c]), jnp.asarray(ws[c]),
                        jnp.asarray(bs[c])) for c in range(4)]

    assert y.shape == (2, hw - K + 1, hw - K + 1, cout)
    np.testing.assert_allclose(np.asarray(y), _direct_conv(x64, w64, b64),
                               rtol=1e-5, atol=1e-5)
    ref_gw, ref_gx = _direct_conv_grads(x64, w64, g64)
    np.testing.assert_allclose(np.asarray(gw), ref_gw, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gx), ref_gx, rtol=1e-5, atol=1e-5)
    for c in range(4):
        np.testing.assert_allclose(np.asarray(ys[c]), np.asarray(looped[c]),
                                   rtol=1e-5, atol=1e-5)
