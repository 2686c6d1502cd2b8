"""Plain Qwen2 decoder and AdamW in float32: the training cells' reference.

Imports nothing of the program.  It follows the configuration file: a
dense decoder of ``num_hidden_layers`` layers, each RMSNorm -> grouped-query
attention with q/k/v biases and rotary positions (half-split rotation,
``rope_theta``) -> residual -> RMSNorm -> SwiGLU MLP -> residual; a final
RMSNorm and an LM head tied to the embedding; mean next-token
cross-entropy.  One departure from the published parametrisation, stated
in the configuration's ``training`` group: RMSNorm weights are stored as
offsets from 1 (scale = 1 + w), so AdamW's weight decay pulls a scale
towards 1.  AdamW as the configuration states it: global-norm clipping,
bias-corrected moments, decoupled weight decay on every leaf, linear warm-up
then cosine decay.

Matmuls run at the highest precision, or with both operands rounded to a
lower type (``operand_dtype``) for the control.  The loss is taken over
blocks of rows and each layer is rematerialised, so a whole model and its
optimizer state fit beside one step on a chip.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
LAYER = ("norm1", "wq", "bq", "wk", "bk", "wv", "bv", "wo", "norm2",
         "w_gate", "w_up", "w_down")


def dims(cfg: dict) -> tuple[int, ...]:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return (cfg["num_hidden_layers"], d, h, cfg["num_key_value_heads"],
            d // h, cfg["intermediate_size"], cfg["vocab_size"])


def shapes(cfg: dict) -> dict:
    n, d, h, g, hd, f, v = dims(cfg)
    layers = {"norm1": (n, d), "wq": (n, d, h, hd), "bq": (n, h, hd),
              "wk": (n, d, g, hd), "bk": (n, g, hd), "wv": (n, d, g, hd),
              "bv": (n, g, hd), "wo": (n, h, hd, d), "norm2": (n, d),
              "w_gate": (n, d, f), "w_up": (n, d, f), "w_down": (n, f, d)}
    return {"embed": (v, d), "final_norm": (d,), "layers": layers}


def init(key, cfg: dict, std: float = 0.02) -> dict:
    """Weights from ``key``: matrices and the embedding ~ N(0, std^2),
    biases and norm offsets 0."""
    sh = shapes(cfg)
    zero = ("bq", "bk", "bv", "norm1", "norm2")
    layers = {k: (jnp.zeros(s, F32) if k in zero else
                  std * jax.random.normal(jax.random.fold_in(key, i), s, F32))
              for i, (k, s) in enumerate(sh["layers"].items())}
    return {"embed": std * jax.random.normal(jax.random.fold_in(key, 100),
                                             sh["embed"], F32),
            "final_norm": jnp.zeros(sh["final_norm"], F32),
            "layers": layers}


def _mm(spec: str, a, b, operand_dtype):
    if operand_dtype is not None:
        a = a.astype(operand_dtype).astype(F32)
        b = b.astype(operand_dtype).astype(F32)
    return jnp.einsum(spec, a, b)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def _rope(x, theta):
    s, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(s, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(cfg, operand_dtype, x, p):
    mm = partial(_mm, operand_dtype=operand_dtype)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h_ = _rmsnorm(x, p["norm1"], eps)
    q = _rope(mm("bsd,dhk->bshk", h_, p["wq"]) + p["bq"], theta)
    k = _rope(mm("bsd,dgk->bsgk", h_, p["wk"]) + p["bk"], theta)
    v = mm("bsd,dgk->bsgk", h_, p["wv"]) + p["bv"]
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = q.shape[1]
    scores = mm("bqhk,bthk->bhqt", q, k) / math.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones((s, s), bool))
    prob = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    x = x + mm("bqhk,hkd->bqd", mm("bhqt,bthk->bqhk", prob, v), p["wo"])
    h_ = _rmsnorm(x, p["norm2"], eps)
    up = jax.nn.silu(mm("bsd,df->bsf", h_, p["w_gate"])) * mm("bsd,df->bsf", h_, p["w_up"])
    return x + mm("bsf,fd->bsd", up, p["w_down"]), None


def loss(params: dict, inputs, labels, cfg: dict, operand_dtype=None,
         rows: int = 1024):
    """Mean next-token cross-entropy."""
    x = params["embed"][inputs]
    x, _ = jax.lax.scan(jax.checkpoint(partial(_layer, cfg, operand_dtype)),
                        x, params["layers"])
    x = _rmsnorm(x, params["final_norm"], cfg["rms_norm_eps"])
    d = x.shape[-1]
    n = x.shape[0] * x.shape[1]
    rows = math.gcd(n, rows)
    xs = x.reshape(n // rows, rows, d)
    ys = labels.reshape(n // rows, rows)

    @jax.checkpoint
    def block(args):
        xb, yb = args
        logits = _mm("rd,vd->rv", xb, params["embed"], operand_dtype)
        gold = jnp.take_along_axis(logits, yb[:, None], axis=1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold)

    return jnp.sum(jax.lax.map(block, (xs, ys))) / n


def lr_at(opt: dict, step):
    """Learning rate of 1-based ``step``: linear warm-up, then cosine decay
    to ``min_lr_ratio * lr`` at ``decay_steps``."""
    step = jnp.asarray(step, F32)
    lr, warm = opt["lr"], opt["warmup_steps"]
    frac = jnp.clip((step - warm) / max(opt["decay_steps"] - warm, 1), 0.0, 1.0)
    low = opt["min_lr_ratio"] * lr
    cos = low + 0.5 * (lr - low) * (1 + jnp.cos(jnp.pi * frac))
    return jnp.where(step < warm, lr * step / max(warm, 1), cos)


def leaf_norms(tree: dict) -> dict:
    """L2 norm of every tensor of every layer, named ``layers.<i>.<name>``."""
    out = {"embed": jnp.linalg.norm(tree["embed"]),
           "final_norm": jnp.linalg.norm(tree["final_norm"])}
    for k, a in tree["layers"].items():
        per = jnp.sqrt(jnp.sum(jnp.square(a), axis=tuple(range(1, a.ndim))))
        for i in range(a.shape[0]):
            out[f"layers.{i}.{k}"] = per[i]
    return out


def to_host(norms: dict) -> dict[str, float]:
    return {k: float(v) for k, v in jax.device_get(norms).items()}


def train3(cfg: dict, opt: dict, key, batches: list[dict], *,
           operand_dtype=None) -> dict:
    """Three AdamW steps from ``init(key)`` on ``batches``: each step's loss,
    every leaf's norm of the first (clipped) gradient, and of the change of
    the weights after the three steps."""
    b1, b2, eps, wd, clip = (opt["b1"], opt["b2"], opt["eps"],
                             opt["weight_decay"], opt["grad_clip"])

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(p, m, v, t, inputs, labels):
        lval, g = jax.value_and_grad(loss)(p, inputs, labels, cfg, operand_dtype)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
        g = jax.tree.map(lambda x: x * jnp.minimum(1.0, clip / (gnorm + 1e-9)), g)
        m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
        v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
        lr = lr_at(opt, t)
        c1, c2 = 1 - b1 ** t.astype(F32), 1 - b2 ** t.astype(F32)
        p = jax.tree.map(lambda w, a, s: w - lr * ((a / c1) / (jnp.sqrt(s / c2) + eps)
                                                  + wd * w), p, m, v)
        return p, m, v, lval, leaf_norms(g)

    @jax.jit
    def change(p, key):
        return leaf_norms(jax.tree.map(jnp.subtract, p, init(key, cfg)))

    with jax.default_matmul_precision("highest"):
        p = jax.jit(partial(init, cfg=cfg))(key)
        zeros = jax.jit(lambda q: jax.tree.map(jnp.zeros_like, q))
        m, v = zeros(p), zeros(p)
        losses, grads = [], None
        for t, b in enumerate(batches, start=1):
            p, m, v, lval, gn = step(p, m, v, jnp.int32(t), b["inputs"], b["labels"])
            losses.append(float(lval))
            if grads is None:
                grads = to_host(gn)
        del m, v
        moved = to_host(change(p, key))
    return {"losses": losses, "grad": grads, "change": moved}
