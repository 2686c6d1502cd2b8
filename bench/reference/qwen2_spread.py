"""The training cells' float32 reference (``bench/reference/qwen2.py``), with
its weights, moments and batches spread over several devices, for a model
whose float32 state one chip cannot hold.

The mathematics is ``qwen2``'s, by import: the same decoder and loss, the
same initialisation, learning rate and leaf norms.  The AdamW step is
``qwen2.train3``'s, restated here only to give it shardings.  The layout is
the reference's own and none of the program's: every array lies over all the
devices along its largest dimension that they divide (replicated where none
does), and the compiler's partitioner places the rest.  Only the order of
the sums changes, so the results agree with one device to float32 rounding.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench.reference import qwen2 as ref

F32 = jnp.float32
AXIS = "all"


def spread(mesh: Mesh, shape: tuple[int, ...]) -> NamedSharding:
    """``shape`` over every device of ``mesh`` along its largest dimension
    that their number divides; replicated where none does."""
    n = mesh.devices.size
    fits = [i for i, d in enumerate(shape) if d % n == 0]
    if not fits:
        return NamedSharding(mesh, P())
    dim = max(fits, key=lambda i: shape[i])
    return NamedSharding(mesh, P(*[None] * dim, AXIS))


def train3(cfg: dict, opt: dict, key, batches: list[dict], devices, *,
           operand_dtype=None) -> dict:
    """``qwen2.train3`` with every array spread over ``devices``: each step's
    loss, every leaf's norm of the first (clipped) gradient, and of the
    change of the weights after the three steps."""
    b1, b2, eps, wd, clip = (opt["b1"], opt["b2"], opt["eps"],
                             opt["weight_decay"], opt["grad_clip"])
    mesh = Mesh(np.asarray(devices), (AXIS,))
    init = partial(ref.init, cfg=cfg)
    p_sh = jax.tree.map(lambda a: spread(mesh, a.shape), jax.eval_shape(init, key))
    b_sh = spread(mesh, batches[0]["inputs"].shape)

    @partial(jax.jit, donate_argnums=(0, 1, 2),
             in_shardings=(p_sh, p_sh, p_sh, None, b_sh, b_sh),
             out_shardings=(p_sh, p_sh, p_sh, None, None))
    def step(p, m, v, t, inputs, labels):
        lval, g = jax.value_and_grad(ref.loss)(p, inputs, labels, cfg, operand_dtype)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
        g = jax.tree.map(lambda x: x * jnp.minimum(1.0, clip / (gnorm + 1e-9)), g)
        m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
        v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
        lr = ref.lr_at(opt, t)
        c1, c2 = 1 - b1 ** t.astype(F32), 1 - b2 ** t.astype(F32)
        p = jax.tree.map(lambda w, a, s: w - lr * ((a / c1) / (jnp.sqrt(s / c2) + eps)
                                                  + wd * w), p, m, v)
        return p, m, v, lval, ref.leaf_norms(g)

    @partial(jax.jit, in_shardings=(p_sh, None))
    def change(p, key):
        return ref.leaf_norms(jax.tree.map(jnp.subtract, p, init(key)))

    with jax.default_matmul_precision("highest"):
        p = jax.jit(init, out_shardings=p_sh)(key)
        zeros = jax.jit(lambda q: jax.tree.map(jnp.zeros_like, q), out_shardings=p_sh)
        m, v = zeros(p), zeros(p)
        losses, grads = [], None
        for t, b in enumerate(batches, start=1):
            p, m, v, lval, gn = step(p, m, v, jnp.int32(t), b["inputs"], b["labels"])
            losses.append(float(lval))
            if grads is None:
                grads = ref.to_host(gn)
        del m, v
        moved = ref.to_host(change(p, key))
    return {"losses": losses, "grad": grads, "change": moved}
