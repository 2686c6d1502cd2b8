"""Train-step factory: microbatched grad accumulation + AdamW + sharding.

``make_train_step`` returns pure functions suitable for jit/lower on any
mesh; everything (remat policy, microbatches, dtypes) is a RunConfig knob so
the roofline perf loop can sweep them.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchSpec
from repro.models import model as M
from repro.parallel.sharding import NULL_PLAN, ShardingPlan
from repro.train import optimizer as opt
from repro.train.loss import cross_entropy


@dataclass(frozen=True)
class RunConfig:
    compute_dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    remat: str = "dots"          # none | dots | full | save_kv
    microbatches: int = 1
    lb_weight: float = 0.01      # MoE load-balance loss weight
    loss_chunk: int = 0          # >0: chunked CE (never materialize logits)
    opt: opt.OptConfig = opt.OptConfig()

    def with_(self, **kw) -> "RunConfig":
        return dataclasses.replace(self, **kw)


BF16_RUN = RunConfig(compute_dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)


def batch_abstract(spec: ArchSpec, batch: int, seq: int, compute_dtype=jnp.bfloat16):
    if spec.frontend == "tokens":
        inp = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    else:
        inp = jax.ShapeDtypeStruct((batch, seq, spec.d_model), compute_dtype)
    return {"inputs": inp, "labels": jax.ShapeDtypeStruct((batch, seq), jnp.int32)}


def batch_axes(spec: ArchSpec):
    inp = ("batch", None) if spec.frontend == "tokens" else ("batch", None, None)
    return {"inputs": inp, "labels": ("batch", None)}


def make_loss_fn(spec: ArchSpec, plan: ShardingPlan, cfg: RunConfig):
    from repro.train.loss import chunked_cross_entropy

    def loss_fn(params, batch):
        hidden, aux = M.forward_hidden(params, batch["inputs"], spec, plan,
                                       compute_dtype=cfg.compute_dtype,
                                       remat=cfg.remat)
        with jax.named_scope("repro.train.lm_head_loss"):
            head = M.head_fn(params, spec, plan)
            if cfg.loss_chunk > 0:
                ce = chunked_cross_entropy(hidden, head, batch["labels"],
                                           chunk=cfg.loss_chunk)
            else:
                ce = cross_entropy(head(hidden), batch["labels"])
        loss = ce + cfg.lb_weight * aux
        return loss, {"ce": ce, "lb": aux}

    return loss_fn


def make_train_step(spec: ArchSpec, plan: ShardingPlan = NULL_PLAN,
                    cfg: RunConfig = RunConfig(), opt_plan: ShardingPlan | None = None):
    """opt_plan: optional sharding plan for gradients/optimizer state.  When
    weights are partially replicated (attn_dp/mamba_dp), gradients are
    reduce-SCATTERED into this fully-sharded layout per microbatch and
    parameters re-gathered once per step — ZeRO-2 semantics, instead of a
    full gradient all-reduce every microbatch."""
    loss_fn = make_loss_fn(spec, plan, cfg)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    _axes = M.param_axes(spec)

    def shard_grads(g):
        if opt_plan is None:
            return g
        return jax.tree.map(
            lambda ax, x: opt_plan.constrain(x, ax), _axes, g,
            is_leaf=lambda x: isinstance(x, tuple) and all(
                isinstance(e, (str, type(None))) for e in x))

    def train_step(state, batch):
        params = state["params"]
        if cfg.microbatches <= 1:
            (loss, metrics), grads = grad_fn(params, batch)
            grads = shard_grads(grads)
        else:
            k = cfg.microbatches
            bsz = jax.tree.leaves(batch)[0].shape[0]
            mb = bsz // k
            assert bsz % k == 0, (bsz, k)

            def mb_body(carry, i):
                acc, loss_acc = carry
                sl = jax.tree.map(lambda a: jax.lax.dynamic_slice_in_dim(a, i * mb, mb, 0), batch)
                (l, _), g = grad_fn(params, sl)
                g = shard_grads(g)
                acc = jax.tree.map(lambda a, b: a + b.astype(jnp.float32), acc, g)
                return (acc, loss_acc + l), None

            zero = shard_grads(jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params))
            (gsum, lsum), _ = jax.lax.scan(mb_body, (zero, 0.0), jnp.arange(k))
            grads = jax.tree.map(lambda g: g / k, gsum)
            loss = lsum / k
            metrics = {}
        with jax.named_scope("repro.train.optimizer"):
            new_state, om = opt.apply_updates(state, grads, cfg.opt)
        out = {"loss": loss, **{k: v for k, v in metrics.items()}, **om}
        return new_state, out

    return train_step


def init_train_state(rng, spec: ArchSpec, cfg: RunConfig = RunConfig()):
    params = M.init_params(rng, spec, jnp.float32)
    return opt.init_state(params, cfg.param_dtype)


def abstract_train_state(spec: ArchSpec, cfg: RunConfig = RunConfig()):
    return opt.abstract_state(M.abstract_params(spec), cfg.param_dtype)


def train_state_axes(spec: ArchSpec, cfg: RunConfig = RunConfig()):
    return opt.state_axes(M.param_axes(spec), cfg.param_dtype)
