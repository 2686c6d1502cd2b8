"""Batched serving engine: prefill + decode loop with static batching.

Requests are padded/batched, prompts run through ``prefill`` (which fills
the caches), then tokens decode step-by-step with greedy or temperature
sampling.  The engine is deliberately mesh-agnostic: pass a plan and jit
shardings for pod-scale serving, or nothing for CPU smoke tests.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchSpec
from repro.models import model as M
from repro.parallel.sharding import NULL_PLAN, ShardingPlan


@dataclass
class ServeStats:
    prefill_s: float = 0.0
    decode_s: float = 0.0
    tokens_out: int = 0

    @property
    def decode_tok_per_s(self) -> float:
        return self.tokens_out / self.decode_s if self.decode_s else 0.0


class Engine:
    def __init__(self, spec: ArchSpec, params, *, plan: ShardingPlan = NULL_PLAN,
                 max_len: int = 256, dtype=jnp.float32):
        self.spec = spec
        self.params = params
        self.plan = plan
        self.max_len = max_len
        self.dtype = dtype
        # the two compiled steps ``generate`` drives: prompt pass -> (last
        # logits, filled caches), and one cached token -> (logits, caches)
        self.prefill = jax.jit(
            lambda p, t, c: M.prefill(p, t, c, spec, plan, compute_dtype=dtype))
        self.decode = jax.jit(
            lambda p, c, t, pos: M.decode_step(p, c, t, pos, spec, plan,
                                               compute_dtype=dtype))

    def generate(self, prompts: np.ndarray, max_new: int = 32,
                 temperature: float = 0.0, seed: int = 0) -> tuple[np.ndarray, ServeStats]:
        """prompts: (B, S) int32 (same length; pad upstream)."""
        b, s = prompts.shape
        assert s + max_new <= self.max_len
        stats = ServeStats()
        caches = M.init_caches(self.spec, b, self.max_len, dtype=self.dtype)

        t0 = time.time()
        logits, caches = self.prefill(self.params, jnp.asarray(prompts), caches)
        logits.block_until_ready()
        stats.prefill_s = time.time() - t0

        rng = jax.random.PRNGKey(seed)
        out = np.zeros((b, max_new), np.int32)
        t0 = time.time()
        for i in range(max_new):
            if temperature > 0:
                rng, k = jax.random.split(rng)
                tok = jax.random.categorical(k, logits / temperature, axis=-1)
            else:
                tok = jnp.argmax(logits, axis=-1)
            out[:, i] = np.asarray(tok)
            logits, caches = self.decode(self.params, caches, tok.astype(jnp.int32),
                                          jnp.asarray(s + i, jnp.int32))
        jax.block_until_ready(logits)
        stats.decode_s = time.time() - t0
        stats.tokens_out = b * max_new
        return out, stats
