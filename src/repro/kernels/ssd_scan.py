"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

Grid: (batch*heads, n_chunks) — chunks iterate sequentially carrying the
(head_dim, d_state) SSM state in VMEM scratch.  Per chunk, the intra-chunk
dual form is two MXU matmuls on (L x L) tiles plus the decay mask; the
inter-chunk recurrence is a rank-L update of the carried state.  This is
the TPU-native streaming of the SSD algorithm: O(L^2) tensors never leave
VMEM, HBM traffic is O(S * (hd + ds)) per head.

Validated in interpret mode against ``repro.kernels.ref.ssd_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, hout_ref, h_ref, *,
                chunk: int):
    ci = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0].astype(jnp.float32)      # (L, hd)
    dt = dt_ref[0].astype(jnp.float32)    # (L, 1)
    a = a_ref[0].astype(jnp.float32)      # (1, 1) decay rate for this head
    b = b_ref[0].astype(jnp.float32)      # (L, ds)
    c = c_ref[0].astype(jnp.float32)      # (L, ds)

    da = dt * a                           # (L, 1)
    l_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    m_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = l_idx >= m_idx
    # in-chunk prefix sums of da as a column and as a row, each one matmul
    # against the 0/1 lower triangle: everything stays 2-D, with no cumsum
    # or vector transpose inside the kernel
    tril = causal.astype(jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    cum_col = jax.lax.dot_general(tril, da, (((1,), (0,)), ((), ())),
                                  precision=hi,
                                  preferred_element_type=jnp.float32)  # (L, 1)
    cum_row = jax.lax.dot_general(da, tril, (((0,), (1,)), ((), ())),
                                  precision=hi,
                                  preferred_element_type=jnp.float32)  # (1, L)
    total = jnp.sum(da, axis=0, keepdims=True)                         # (1, 1)
    decay = jnp.where(causal, jnp.exp(cum_col - cum_row), 0.0)         # (L, L)

    # intra-chunk dual form: (C B^T ∘ decay) @ (x * dt)
    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    att = cb * decay
    xdt = x * dt
    y = jax.lax.dot_general(att, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk: y += C @ h_prev with in-chunk decay
    h = h_ref[...]                        # (ds, hd)
    y += jnp.exp(cum_col) * jax.lax.dot_general(
        c, h, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    # state update: h = exp(sum da) * h + B^T (x * dt * decay_to_end)
    decay_end = jnp.exp(total - cum_col)  # (L, 1)
    upd = jax.lax.dot_general(b, xdt * decay_end,
                              (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    h_ref[...] = jnp.exp(total) * h + upd

    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _emit_state():
        hout_ref[0] = h_ref[...].astype(hout_ref.dtype)


def ssd_scan(x, dt, a, b, c, *, chunk: int = 128, interpret: bool | None = None):
    """Fused SSD scan over one sequence.

    x:  (BH, S, hd)    — per-head inputs (heads folded into batch)
    dt: (BH, S)
    a:  (BH,)          — per-head decay rate (negative)
    b:  (BH, S, ds)    — already broadcast from groups to heads
    c:  (BH, S, ds)
    returns y: (BH, S, hd), final state (BH, ds, hd)
    """
    bh, s, hd = x.shape
    ds = b.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    nc = s // chunk
    grid = (bh, nc)
    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    # dt and a ride as (.., 1)-wide columns: a block's last two dims must be
    # multiples of (8, 128) or span the whole array dim, which (1, chunk)
    # over (BH, S) and (1,) over (BH,) do not
    dt = dt.reshape(bh, s, 1)
    a = a.reshape(bh, 1, 1)
    y, hlast = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, hd), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, 1), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, 1), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, chunk, ds), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, ds), lambda i, j: (i, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, hd), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, ds, hd), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, hd), x.dtype),
            jax.ShapeDtypeStruct((bh, ds, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((ds, hd), jnp.float32)],
        interpret=interpret,
    )(x, dt, a, b, c)
    return y, hlast
