"""Decoder layers + the scan-over-repeating-blocks stack executor.

``ArchSpec.block_pattern()`` factors the layer stack into (pattern, repeats,
remainder).  Parameters (and decode caches) for the repeated pattern are
*stacked* along a leading dim and executed with ``jax.lax.scan``, keeping HLO
size O(|pattern|) — the difference between minutes and hours when compiling
for 512 devices.  Heterogeneous stacks (gemma3 local:global, jamba
mamba/attn/MoE interleave) fall out naturally: the pattern holds one params
subtree per sublayer.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchSpec, LayerDef
from repro.models import attention as attn
from repro.models import mamba as mb
from repro.models import mlp as mlpm
from repro.models import moe as moem
from repro.models.layers import ParamDef, rmsnorm, stack_defs
from repro.parallel.sharding import ShardingPlan

REMAT_POLICIES = {
    "none": None,  # no remat
    "full": jax.checkpoint_policies.nothing_saveable,
    "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    # keep the gathered KV across fwd->bwd: the backward recompute skips the
    # per-layer KV all-gather (collective-term optimization, §Perf)
    "save_kv": jax.checkpoint_policies.save_only_these_names("attn_kv"),
}


def layer_param_defs(spec: ArchSpec, ld: LayerDef) -> dict[str, Any]:
    d = spec.d_model
    defs: dict[str, Any] = {"norm1": ParamDef((d,), ("embed",), "zeros")}
    if ld.mixer == "mamba":
        defs["mixer"] = mb.mamba_defs(spec)
    else:
        defs["mixer"] = attn.attn_defs(spec)
    if ld.ffn != "none":
        defs["norm2"] = ParamDef((d,), ("embed",), "zeros")
        defs["ffn"] = moem.moe_defs(spec) if ld.ffn == "moe" else mlpm.mlp_defs(spec)
    return defs


def layer_cache_defs(spec: ArchSpec, ld: LayerDef, batch: int, seq: int,
                     dtype=jnp.bfloat16) -> dict[str, Any]:
    if ld.mixer == "mamba":
        return mb.mamba_cache_defs(spec, batch, dtype)
    window = spec.sliding_window if ld.mixer == "attn_local" else 0
    return attn.attn_cache_defs(spec, batch, seq, window=window, dtype=dtype)


def _apply_train(p, x, positions, ld: LayerDef, spec: ArchSpec, plan: ShardingPlan):
    # the named scopes label the training step's device operations (and
    # their gradients) in a profiler trace
    h = rmsnorm(x, p["norm1"], spec.norm_eps)
    if ld.mixer == "mamba":
        with jax.named_scope("repro.train.mamba"):
            y = mb.mamba_fwd(p["mixer"], h, spec, plan)
    else:
        window = spec.sliding_window if ld.mixer == "attn_local" else 0
        with jax.named_scope("repro.train.attention"):
            y = attn.attention_fwd(p["mixer"], h, positions, spec, plan, window=window)
    x = x + y
    aux = jnp.zeros((), jnp.float32)
    if ld.ffn != "none":
        h = rmsnorm(x, p["norm2"], spec.norm_eps)
        with jax.named_scope("repro.train.ffn"):
            if ld.ffn == "moe":
                y, a = moem.moe_apply(p["ffn"], h, spec, plan)
                aux = aux + a["lb_loss"]
            else:
                y = mlpm.mlp_apply(p["ffn"], h, spec, plan)
        x = x + y
    return x, aux


def _apply_prefill(p, x, positions, ld, spec, plan, cache):
    h = rmsnorm(x, p["norm1"], spec.norm_eps)
    if ld.mixer == "mamba":
        y, newc = mb.mamba_prefill(p["mixer"], h, spec, plan, cache)
    else:
        window = spec.sliding_window if ld.mixer == "attn_local" else 0
        y, newc = attn.attn_prefill(p["mixer"], h, positions, spec, plan, cache, window=window)
    x = x + y
    if ld.ffn != "none":
        h = rmsnorm(x, p["norm2"], spec.norm_eps)
        if ld.ffn == "moe":
            y, _ = moem.moe_apply(p["ffn"], h, spec, plan)
        else:
            y = mlpm.mlp_apply(p["ffn"], h, spec, plan)
        x = x + y
    return x, newc


def _apply_decode(p, x, pos, ld, spec, plan, cache):
    h = rmsnorm(x, p["norm1"], spec.norm_eps)
    if ld.mixer == "mamba":
        y, newc = mb.mamba_decode(p["mixer"], h, spec, plan, cache)
    else:
        window = spec.sliding_window if ld.mixer == "attn_local" else 0
        y, newc = attn.attn_decode(p["mixer"], h, pos, spec, plan, cache, window=window)
    x = x + y
    if ld.ffn != "none":
        h = rmsnorm(x, p["norm2"], spec.norm_eps)
        if ld.ffn == "moe":
            y, _ = moem.moe_apply(p["ffn"], h[:, None, :], spec, plan)
            y = y[:, 0, :]
        else:
            y = mlpm.mlp_apply(p["ffn"], h[:, None, :], spec, plan)[:, 0, :]
        x = x + y
    return x, newc


# ---------------------------------------------------------------------------
# stack
# ---------------------------------------------------------------------------

def stack_param_defs(spec: ArchSpec) -> dict[str, Any]:
    pattern, reps, rem = spec.block_pattern()
    blocks = {
        f"sub{j}": stack_defs(layer_param_defs(spec, ld), reps, None)
        for j, ld in enumerate(pattern)
    }
    tail = {f"tail{j}": layer_param_defs(spec, ld) for j, ld in enumerate(rem)}
    return {"blocks": blocks, "tail": tail}


def stack_cache_defs(spec: ArchSpec, batch: int, seq: int, dtype=jnp.bfloat16) -> dict[str, Any]:
    pattern, reps, rem = spec.block_pattern()
    blocks = {
        f"sub{j}": stack_defs(layer_cache_defs(spec, ld, batch, seq, dtype), reps, None)
        for j, ld in enumerate(pattern)
    }
    tail = {f"tail{j}": layer_cache_defs(spec, ld, batch, seq, dtype) for j, ld in enumerate(rem)}
    return {"blocks": blocks, "tail": tail}


def stack_train(params, x, positions, spec: ArchSpec, plan: ShardingPlan,
                remat: str = "dots"):
    pattern, reps, rem = spec.block_pattern()

    def sublayer(j, ld):
        def f(p, h):
            h, a = _apply_train(p, h, positions, ld, spec, plan)
            return plan.constrain(h, ("batch", "seq", "embed")), a
        if remat != "none":
            # checkpoint at SUBLAYER granularity: the backward pass only ever
            # holds one sublayer's recompute transients (vs. a whole
            # heterogeneous block's — 8x for jamba)
            f = jax.checkpoint(f, policy=REMAT_POLICIES[remat], prevent_cse=False)
        return f

    fns = [sublayer(j, ld) for j, ld in enumerate(pattern)]

    def block_body(carry, xs):
        h, aux = carry
        for j in range(len(pattern)):
            h, a = fns[j](xs[f"sub{j}"], h)
            aux = aux + a
        return (h, aux), None

    (x, aux), _ = jax.lax.scan(block_body, (x, jnp.zeros((), jnp.float32)),
                               params["blocks"], length=reps)
    tail_fns = [sublayer(j, ld) for j, ld in enumerate(rem)]
    for j, ld in enumerate(rem):
        x, a = tail_fns[j](params["tail"][f"tail{j}"], x)
        aux = aux + a
    return x, aux


def stack_prefill(params, x, positions, spec: ArchSpec, plan: ShardingPlan, caches):
    pattern, reps, rem = spec.block_pattern()

    def block_body(h, xs):
        ps, cs = xs
        newcs = {}
        for j, ld in enumerate(pattern):
            h, newcs[f"sub{j}"] = _apply_prefill(ps[f"sub{j}"], h, positions, ld, spec, plan, cs[f"sub{j}"])
            h = plan.constrain(h, ("batch", "seq", "embed"))
        return h, newcs

    x, new_blocks = jax.lax.scan(block_body, x, (params["blocks"], caches["blocks"]), length=reps)
    new_tail = {}
    for j, ld in enumerate(rem):
        x, new_tail[f"tail{j}"] = _apply_prefill(
            params["tail"][f"tail{j}"], x, positions, ld, spec, plan, caches["tail"][f"tail{j}"])
    return x, {"blocks": new_blocks, "tail": new_tail}


def stack_decode(params, x, pos, spec: ArchSpec, plan: ShardingPlan, caches):
    pattern, reps, rem = spec.block_pattern()

    def block_body(h, xs):
        ps, cs = xs
        newcs = {}
        for j, ld in enumerate(pattern):
            h, newcs[f"sub{j}"] = _apply_decode(ps[f"sub{j}"], h, pos, ld, spec, plan, cs[f"sub{j}"])
        return h, newcs

    x, new_blocks = jax.lax.scan(block_body, x, (params["blocks"], caches["blocks"]), length=reps)
    new_tail = {}
    for j, ld in enumerate(rem):
        x, new_tail[f"tail{j}"] = _apply_decode(
            params["tail"][f"tail{j}"], x, pos, ld, spec, plan, caches["tail"][f"tail{j}"])
    return x, {"blocks": new_blocks, "tail": new_tail}
