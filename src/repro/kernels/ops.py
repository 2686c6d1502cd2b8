"""jit'd public wrappers around the Pallas kernels.

These adapt model-layout tensors (B, S, H, hd / GQA groups) to kernel
layouts (heads folded into batch, padded to block multiples).  No model
calls them yet: the models run their pure-jnp paths (``models.attention``,
``models.mamba``, ``models.layers.rmsnorm``), and the kernels are checked
against those paths in ``tests/test_kernels.py`` and compiled for the TPU
in ``tests/test_tpu_compile.py``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import rmsnorm as rmsnorm_kernel
from repro.kernels.ssd_scan import ssd_scan


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k", "interpret"))
def mha_flash(q, k, v, *, causal: bool = True, window: int = 0,
              block_q: int = 128, block_k: int = 128,
              interpret: bool | None = None):
    """q: (B, S, H, hd); k/v: (B, T, G, hd) (GQA groups).  Returns (B,S,H,hd)."""
    b, s, h, hd = q.shape
    t, g = k.shape[1], k.shape[2]
    rep = h // g
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, t, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, t, hd)
    o = flash_attention(qf, kf, vf, causal=causal, window=window,
                        block_q=block_q, block_k=block_k, interpret=interpret)
    return o.reshape(b, h, s, hd).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(x, dt, a, b, c, *, chunk: int = 128, interpret: bool | None = None):
    """Model layout: x (B,S,H,hd); dt (B,S,H); a (H,); b/c (B,S,G,ds)."""
    bsz, s, h, hd = x.shape
    g, ds = b.shape[2], b.shape[3]
    rep = h // g
    if rep > 1:
        b = jnp.repeat(b, rep, axis=2)
        c = jnp.repeat(c, rep, axis=2)
    xf = x.transpose(0, 2, 1, 3).reshape(bsz * h, s, hd)
    dtf = dt.transpose(0, 2, 1).reshape(bsz * h, s)
    af = jnp.broadcast_to(a[None, :], (bsz, h)).reshape(bsz * h)
    bf = b.transpose(0, 2, 1, 3).reshape(bsz * h, s, ds)
    cf = c.transpose(0, 2, 1, 3).reshape(bsz * h, s, ds)
    y, hl = ssd_scan(xf, dtf, af, bf, cf, chunk=chunk, interpret=interpret)
    y = y.reshape(bsz, h, s, hd).transpose(0, 2, 1, 3)
    hl = hl.reshape(bsz, h, ds, hd).transpose(0, 1, 3, 2)  # (B,H,hd,ds)
    return y, hl


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def fused_rmsnorm(x, w, *, eps: float = 1e-5, interpret: bool | None = None):
    """x: (..., d) any leading shape.

    Rows are zero-padded to a multiple of 8 (the TPU's sublane tile: a
    block of fewer rows than that is refused unless it spans the whole
    array), and the block is the largest power of two up to 128 dividing
    the padded count.  Padded rows normalize to 0 and are sliced off."""
    shape = x.shape
    rows = math.prod(shape[:-1])
    d = shape[-1]
    padded = -(-rows // 8) * 8
    block = 128
    while padded % block:
        block //= 2
    x2 = x.reshape(rows, d)
    if padded != rows:
        x2 = jnp.pad(x2, ((0, padded - rows), (0, 0)))
    out = rmsnorm_kernel(x2, w, eps=eps, block_rows=block, interpret=interpret)
    return out[:rows].reshape(shape)
