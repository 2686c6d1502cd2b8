"""GQA attention: training/prefill forward + cached decode.

Long sequences use a blockwise online-softmax formulation (pure jnp; the
Pallas flash-attention kernel in ``repro.kernels`` is the TPU-optimized twin
validated against the same math).  Sliding-window layers reuse the same code
with a band mask; decode keeps either a full cache (global layers) or a
ring-buffer cache (local layers).
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.configs.base import ArchSpec
from repro.models.layers import ParamDef, apply_rope, linear
from repro.parallel.sharding import ShardingPlan

NEG_INF = -1e30


def attn_defs(spec: ArchSpec) -> dict[str, ParamDef]:
    d, h, g, hd = spec.d_model, spec.n_heads, spec.n_kv_heads, spec.resolved_head_dim
    defs = {
        "wq": ParamDef((d, h, hd), ("embed", "q_heads", "head_dim")),
        "wk": ParamDef((d, g, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamDef((d, g, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamDef((h, hd, d), ("q_heads", "head_dim", "embed")),
    }
    if spec.qkv_bias:
        defs["bq"] = ParamDef((h, hd), ("q_heads", "head_dim"), "zeros")
        defs["bk"] = ParamDef((g, hd), ("kv_heads", "head_dim"), "zeros")
        defs["bv"] = ParamDef((g, hd), ("kv_heads", "head_dim"), "zeros")
    return defs


def _project_qkv(p, x, spec: ArchSpec):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dgk->bsgk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dgk->bsgk", x, p["wv"].astype(x.dtype))
    if spec.qkv_bias:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    return q, k, v


def _repeat_kv(k, n_heads: int):
    """(B, T, G, hd) -> (B, T, H, hd) by repeating each group."""
    b, t, g, hd = k.shape
    rep = n_heads // g
    if rep == 1:
        return k
    return jnp.broadcast_to(k[:, :, :, None, :], (b, t, g, rep, hd)).reshape(b, t, n_heads, hd)


def _sdpa_block(q, k, v, mask, scale):
    """Dense softmax attention on one (query-block x full-kv) tile."""
    s = jnp.einsum("bqhk,bthk->bhqt", q, k) * scale
    s = jnp.where(mask, s.astype(jnp.float32), NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqt,bthk->bqhk", p, v)


def flash_attention_ref(q, k, v, positions, *, window: int = 0,
                        kv_chunk: int = 1024, scale: float | None = None):
    """Online-softmax attention, scanning over KV chunks (pure jnp).

    q, k, v: (B, S, H, hd) — k/v already repeated to H heads.  Peak memory is
    O(S * kv_chunk) per head instead of O(S^2).  This is also the oracle the
    Pallas flash-attention kernel is validated against.
    """
    b, s, h, hd = q.shape
    t = k.shape[1]
    scale = scale or (1.0 / math.sqrt(hd))
    ck = min(kv_chunk, t)
    assert t % ck == 0, (t, ck)
    nck = t // ck
    f32 = jnp.float32
    kc = jnp.moveaxis(k.reshape(b, nck, ck, h, hd), 1, 0)
    vc = jnp.moveaxis(v.reshape(b, nck, ck, h, hd), 1, 0)
    starts = jnp.arange(nck, dtype=jnp.int32) * ck
    qpos = positions  # (s,)

    def body(carry, xs):
        m, l, acc = carry
        ki, vi, start = xs
        kpos = start + jnp.arange(ck, dtype=jnp.int32)
        sc = jnp.einsum("bqhk,bthk->bqht", q, ki).astype(f32) * scale
        mask = kpos[None, :] <= qpos[:, None]  # (s, ck)
        if window:
            mask &= kpos[None, :] > qpos[:, None] - window
        sc = jnp.where(mask[None, :, None, :], sc, NEG_INF)
        mnew = jnp.maximum(m, sc.max(axis=-1))
        p = jnp.exp(sc - mnew[..., None])
        alpha = jnp.exp(m - mnew)
        lnew = l * alpha + p.sum(axis=-1)
        accnew = acc * alpha[..., None] + jnp.einsum(
            "bqht,bthk->bqhk", p.astype(q.dtype), vi).astype(f32)
        return (mnew, lnew, accnew), None

    m0 = jnp.full((b, s, h), NEG_INF, f32)
    l0 = jnp.zeros((b, s, h), f32)
    a0 = jnp.zeros((b, s, h, hd), f32)
    # checkpoint per KV chunk: the scan's backward otherwise stacks every
    # chunk's (B,S,H,ck) probabilities = the full S x T score matrix in f32
    body = jax.checkpoint(body, prevent_cse=False)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kc, vc, starts))
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def attention_fwd(p, x, positions, spec: ArchSpec, plan: ShardingPlan,
                  *, window: int = 0, dense_threshold: int = 2048,
                  kv_chunk: int = 1024) -> jax.Array:
    """Causal (optionally sliding-window) self-attention over a full sequence.

    Layout policy (the TP/SP adaptation of the paper's Workload knobs):
      * heads divide the 'model' axis  -> Megatron head-sharded attention
        (all-gather small GQA KV, shard all S^2 work over heads),
      * otherwise                      -> sequence-sharded attention: Q keeps
        the residual stream's seq sharding, KV is gathered, S^2 work shards
        over the query-sequence dim.  Works for any head count (gemma3's 4
        heads, qwen2's 12, granite's 24 on a 16-way axis).
    Long sequences stream KV chunks with online softmax (flash-style) so
    activation memory is O(S * kv_chunk).
    """
    b, s, d = x.shape
    h, hd = spec.n_heads, spec.resolved_head_dim
    q, k, v = _project_qkv(p, x, spec)
    q = apply_rope(q, positions, spec.rope_theta)
    k = apply_rope(k, positions, spec.rope_theta)
    # gather the (small, GQA) KV across the seq sharding FIRST, then the
    # head-repeat broadcast is purely local.  checkpoint_name lets the
    # 'save_kv' remat policy keep the gathered KV for the backward pass
    # instead of re-running the all-gather during recompute.
    k = plan.constrain(k, ("batch", None, None, None))
    v = plan.constrain(v, ("batch", None, None, None))
    k = checkpoint_name(k, "attn_kv")
    v = checkpoint_name(v, "attn_kv")
    k = _repeat_kv(k, h)
    v = _repeat_kv(v, h)
    head_sharded = plan.can_shard("q_heads", h)
    if head_sharded:
        q = plan.constrain(q, ("batch", None, "q_heads", None))
        k = plan.constrain(k, ("batch", None, "q_heads", None))
        v = plan.constrain(v, ("batch", None, "q_heads", None))
    else:
        q = plan.constrain(q, ("batch", "seq", None, None))
    scale = 1.0 / math.sqrt(hd)

    if s <= dense_threshold:
        kpos, qpos = positions, positions
        mask = kpos[None, :] <= qpos[:, None]
        if window:
            mask &= kpos[None, :] > qpos[:, None] - window
        o = _sdpa_block(q, k, v, mask[None, None], scale)
    else:
        o = flash_attention_ref(q, k, v, positions, window=window,
                                kv_chunk=kv_chunk, scale=scale)
    if head_sharded:
        # keep the output on heads: its cotangent then arrives head-sharded,
        # and the backward's seq-to-heads reshard moves dO, (B,S,H,hd), not
        # the (B,H,S,S) scores and probabilities
        o = plan.constrain(o, ("batch", None, "q_heads", None))
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype))


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def attn_cache_defs(spec: ArchSpec, batch: int, seq: int, *, window: int = 0,
                    dtype=jnp.bfloat16) -> dict[str, ParamDef]:
    g, hd = spec.n_kv_heads, spec.resolved_head_dim
    t = min(window, seq) if window else seq
    defs = {
        "k": ParamDef((batch, t, g, hd), ("batch", "kv_seq", "kv_heads", "head_dim"), "zeros"),
        "v": ParamDef((batch, t, g, hd), ("batch", "kv_seq", "kv_heads", "head_dim"), "zeros"),
    }
    if window:
        defs["kpos"] = ParamDef((t,), (None,), "zeros")  # holds pos+1 (0 = empty)
    return defs


def attn_prefill(p, x, positions, spec: ArchSpec, plan: ShardingPlan, cache,
                 *, window: int = 0):
    """Forward over the prompt, filling the cache.  Sequence length must
    equal the cache length (the dry-run prefill shape); ring-buffer caches
    keep the trailing ``window`` tokens."""
    b, s, d = x.shape
    y = attention_fwd(p, x, positions, spec, plan, window=window)
    q, k, v = _project_qkv(p, x, spec)
    k = apply_rope(k, positions, spec.rope_theta)
    t = cache["k"].shape[1]
    if window:
        # trailing `m` tokens, laid out at slot = pos % t
        m = min(s, t)
        tail_pos = positions[-m:]
        slots = tail_pos % t
        newk = jnp.zeros_like(cache["k"]).at[:, slots].set(k[:, -m:].astype(cache["k"].dtype))
        newv = jnp.zeros_like(cache["v"]).at[:, slots].set(v[:, -m:].astype(cache["v"].dtype))
        kpos = jnp.zeros_like(cache["kpos"]).at[slots].set((tail_pos + 1).astype(cache["kpos"].dtype))
        cache = {"k": newk, "v": newv, "kpos": kpos}
    else:
        assert s <= t, (s, t)
        newk = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0))
        newv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0))
        cache = {"k": newk, "v": newv}
    cache = constrain_cache(cache, plan)
    return y, cache


def constrain_cache(cache, plan: ShardingPlan):
    out = dict(cache)
    for n in ("k", "v"):
        out[n] = plan.constrain(cache[n], ("batch", "kv_seq", "kv_heads", "head_dim"))
    return out


def attn_decode(p, x, pos, spec: ArchSpec, plan: ShardingPlan, cache,
                *, window: int = 0):
    """One decode step.  x: (B, D); pos: scalar int32 (shared across batch).

    GQA is computed with grouped einsums (no head-repeat broadcast), so the
    KV cache keeps its kv_seq sharding: score/softmax reductions over the
    sharded T dim lower to all-reduces — distributed decode attention.
    """
    b, d = x.shape
    pos = jnp.asarray(pos, jnp.int32)
    h, g, hd = spec.n_heads, spec.n_kv_heads, spec.resolved_head_dim
    r = h // g
    q, k, v = _project_qkv(p, x[:, None, :], spec)  # (B,1,...)
    posv = jnp.full((1,), pos, jnp.int32)
    q = apply_rope(q, posv, spec.rope_theta)
    k = apply_rope(k, posv, spec.rope_theta)

    t = cache["k"].shape[1]
    slot = (pos % t) if window else jnp.minimum(pos, t - 1)
    newk = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
    newv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))
    if window:
        kpos = jax.lax.dynamic_update_slice(cache["kpos"], (pos + 1)[None].astype(cache["kpos"].dtype), (slot,))
        valid = (kpos > 0) & (kpos - 1 <= pos) & (kpos - 1 > pos - t)
        newc = {"k": newk, "v": newv, "kpos": kpos}
    else:
        valid = jnp.arange(t) <= pos
        newc = {"k": newk, "v": newv}
    newc = constrain_cache(newc, plan)

    qg = q[:, 0].reshape(b, g, r, hd)
    kk = newc["k"].astype(q.dtype)  # (B,T,G,hd), kv_seq-sharded
    vv = newc["v"].astype(q.dtype)
    scale = 1.0 / math.sqrt(hd)
    s = jnp.einsum("bgrk,btgk->bgrt", qg, kk) * scale  # (B,G,R,T)
    s = jnp.where(valid[None, None, None, :], s.astype(jnp.float32), NEG_INF)
    pr = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bgrt,btgk->bgrk", pr, vv).reshape(b, h, hd)
    y = jnp.einsum("bhk,hkd->bd", o, p["wo"].astype(x.dtype))
    return y, newc
