"""int8 gradient compression: codec bounds, error-feedback telescoping,
and convergence of EF-compressed SGD (hypothesis + numeric)."""

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.launch.mesh import make_mesh
from repro.parallel import compression as C


@given(st.lists(st.floats(-1e4, 1e4, allow_nan=False, width=32),
                min_size=1, max_size=64))
def test_quantize_bounds(vals):
    x = jnp.asarray(vals, jnp.float32)
    q, scale = C.quantize_int8(x)
    err = jnp.abs(C.dequantize_int8(q, scale) - x)
    # symmetric per-tensor int8: |err| <= scale/2 = max|x|/254
    assert float(jnp.max(err)) <= float(scale) / 2 + 1e-6


def test_error_feedback_telescopes():
    """Over T steps, sum(dequantized) + final_err == sum(grads) exactly
    (the EF invariant that makes the scheme unbiased over time)."""
    key = jax.random.key(0)
    g_sum = jnp.zeros((32,))
    q_sum = jnp.zeros((32,))
    err = jnp.zeros((32,))
    for t in range(20):
        key, k = jax.random.split(key)
        g = jax.random.normal(k, (32,)) * (10.0 ** (t % 3))
        q, scale, err = C.ef_compress(g, err)
        g_sum = g_sum + g
        q_sum = q_sum + C.dequantize_int8(q, scale)
    np.testing.assert_allclose(np.asarray(q_sum + err), np.asarray(g_sum),
                               rtol=1e-4, atol=1e-3)


def test_ef_sgd_converges_like_fp32():
    """EF-int8 SGD on a quadratic tracks full-precision SGD."""
    w_fp = jnp.array([5.0, -3.0, 2.0, -7.0])
    w_q = w_fp
    err = jnp.zeros_like(w_fp)
    lr = 0.05
    for _ in range(300):
        g_fp = 2 * w_fp
        w_fp = w_fp - lr * g_fp
        g_q = 2 * w_q
        q, scale, err = C.ef_compress(g_q, err)
        w_q = w_q - lr * C.dequantize_int8(q, scale)
    assert float(jnp.abs(w_q).max()) < 0.05
    assert float(jnp.abs(w_fp).max()) < 1e-3


def test_compressed_psum_single_device_mesh():
    """On a 1-way mesh the compressed all-reduce must be the identity
    (up to quantization handled by EF)."""
    mesh = make_mesh((1,), ("data",))
    ar = C.make_compressed_allreduce(mesh, axis="data")
    grads = {"w": jnp.array([1.0, -2.0, 3.0]), "b": jnp.array([0.5])}
    err = C.init_error_state(grads)
    out, err2 = ar(grads, err)
    # mean over 1 shard of dequant(quant(g)) == g - err2
    for k in grads:
        np.testing.assert_allclose(np.asarray(out[k] + err2[k]),
                                   np.asarray(grads[k]), rtol=1e-5,
                                   atol=1e-6)
