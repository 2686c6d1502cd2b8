"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = jax.random.PRNGKey(0)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("b,s,h,g,hd", [
    (2, 256, 4, 2, 64),
    (1, 128, 2, 2, 32),
    (2, 128, 8, 1, 16),
    (1, 512, 4, 4, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_causal(b, s, h, g, hd, dtype):
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (b, s, h, hd), dtype)
    k = jax.random.normal(ks[1], (b, s, g, hd), dtype)
    v = jax.random.normal(ks[2], (b, s, g, hd), dtype)
    o = ops.mha_flash(q, k, v, causal=True, block_q=64, block_k=64)
    rep = h // g
    qr = q.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    kr = jnp.repeat(k, rep, 2).transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    vr = jnp.repeat(v, rep, 2).transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    e = ref.attention_ref(qr, kr, vr, causal=True).reshape(b, h, s, hd).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(o, np.float32), np.asarray(e, np.float32), **_tol(dtype))


@pytest.mark.parametrize("window", [32, 64, 128])
def test_flash_attention_sliding_window(window):
    b, s, h, hd = 1, 256, 2, 32
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (b, s, h, hd))
    k = jax.random.normal(ks[1], (b, s, h, hd))
    v = jax.random.normal(ks[2], (b, s, h, hd))
    o = ops.mha_flash(q, k, v, causal=True, window=window, block_q=64, block_k=64)
    qr = q.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    kr = k.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    vr = v.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    e = ref.attention_ref(qr, kr, vr, causal=True, window=window) \
        .reshape(b, h, s, hd).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(o), np.asarray(e), rtol=2e-5, atol=2e-5)


def test_flash_attention_block_shape_invariance():
    b, s, h, hd = 1, 256, 2, 32
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (b, s, h, hd))
    k = jax.random.normal(ks[1], (b, s, h, hd))
    v = jax.random.normal(ks[2], (b, s, h, hd))
    o1 = ops.mha_flash(q, k, v, block_q=64, block_k=64)
    o2 = ops.mha_flash(q, k, v, block_q=128, block_k=32)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("b,s,h,g,hd,ds,chunk", [
    (2, 128, 4, 1, 16, 32, 64),
    (1, 256, 2, 2, 32, 16, 64),
    (2, 64, 4, 4, 8, 8, 32),
    (1, 128, 2, 1, 64, 64, 128),
])
def test_ssd_scan_vs_naive_recurrence(b, s, h, g, hd, ds, chunk):
    ks = jax.random.split(RNG, 5)
    x = jax.random.normal(ks[0], (b, s, h, hd))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)))
    bb = jax.random.normal(ks[3], (b, s, g, ds))
    cc = jax.random.normal(ks[4], (b, s, g, ds))
    y, hl = ops.ssd(x, dt, a, bb, cc, chunk=chunk)
    rep = h // g
    xf = x.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    dtf = dt.transpose(0, 2, 1).reshape(b * h, s)
    af = jnp.broadcast_to(a[None, :], (b, h)).reshape(b * h)
    bf = jnp.repeat(bb, rep, 2).transpose(0, 2, 1, 3).reshape(b * h, s, ds)
    cf = jnp.repeat(cc, rep, 2).transpose(0, 2, 1, 3).reshape(b * h, s, ds)
    ye, he = ref.ssd_ref(xf, dtf, af, bf, cf)
    scale = float(jnp.max(jnp.abs(ye))) + 1e-9
    err = float(jnp.max(jnp.abs(y - ye.reshape(b, h, s, hd).transpose(0, 2, 1, 3))))
    assert err / scale < 1e-4
    herr = float(jnp.max(jnp.abs(hl.transpose(0, 1, 3, 2).reshape(b * h, ds, hd) - he)))
    assert herr / (float(jnp.max(jnp.abs(he))) + 1e-9) < 1e-4


def test_ssd_kernel_matches_model_path():
    """Kernel vs the model's scan-over-chunks jnp implementation."""
    from repro.models.mamba import ssd_chunked
    b, s, h, hd, ds = 2, 128, 4, 16, 32
    ks = jax.random.split(RNG, 5)
    x = jax.random.normal(ks[0], (b, s, h, hd))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)))
    bb = jax.random.normal(ks[3], (b, s, 1, ds))
    cc = jax.random.normal(ks[4], (b, s, 1, ds))
    yk, hk = ops.ssd(x, dt, a, bb, cc, chunk=64)
    ym, hm = ssd_chunked(x, dt, a, bb, cc, chunk=64)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(ym), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(hk), np.asarray(hm), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("rows,d", [(128, 256), (64, 1024), (37 * 4, 512),
                                    (37, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_kernel(rows, d, dtype):
    x = (jax.random.normal(RNG, (rows, d)) * 3).astype(dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (d,)).astype(dtype) * 0.1
    o = ops.fused_rmsnorm(x, w)
    e = ref.rmsnorm_ref(x, w)
    np.testing.assert_allclose(np.asarray(o, np.float32), np.asarray(e, np.float32), **_tol(dtype))


def test_flash_matches_model_attention_path():
    """Kernel vs the model's flash_attention_ref (online-softmax jnp twin)."""
    from repro.models.attention import flash_attention_ref
    b, s, h, hd = 1, 256, 2, 32
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (b, s, h, hd))
    k = jax.random.normal(ks[1], (b, s, h, hd))
    v = jax.random.normal(ks[2], (b, s, h, hd))
    positions = jnp.arange(s, dtype=jnp.int32)
    o_model = flash_attention_ref(q, k, v, positions, kv_chunk=64)
    o_kernel = ops.mha_flash(q, k, v, causal=True, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(o_model), np.asarray(o_kernel),
                               rtol=2e-5, atol=2e-5)
