"""A planted fault that only a step sharded over a mesh can have; like those
of ``bench/variants.py``, the benchmark's own runs never apply it, and it
must make ``correct`` come out false.

* ``no_exchange`` (training fault): the gradients are never exchanged
  between the ``data`` shards.  Each shard steps its own slice of the state
  with the gradient of its own rows of the batch: along the dimension a
  weight is sharded over ``data``, shard k's slice of the gradient comes
  from shard k's rows; a weight that ``data`` does not divide takes shard
  0's (each shard would step its own copy, and the first is the one read).
"""
from __future__ import annotations

import contextlib


def _data_dim(spec) -> int | None:
    """The dimension of a ``PartitionSpec`` that names ``data`` as its
    major mesh axis, if any."""
    for i, part in enumerate(spec):
        axes = part if isinstance(part, tuple) else (part,)
        if "data" in axes:
            if axes[0] != "data":
                raise ValueError(f"'data' is not the major axis of {spec}")
            return i
    return None


def _no_exchange_step(spec, plan, cfg):
    """In place of ``make_train_step``: a step that computes each ``data``
    shard's gradient on that shard's rows alone and updates each shard's
    slice of the weights with its own; the program's loss and AdamW, with
    the exchange left out (one microbatch, as the cells run it)."""
    import jax
    import jax.numpy as jnp

    from repro.models import model as M
    from repro.train import optimizer as opt
    from repro.train.train_step import make_loss_fn

    if cfg.microbatches > 1:
        raise ValueError("the fault takes the step in one microbatch")
    n = plan.axis_sizes.get("data", 1)
    dims = jax.tree.map(
        lambda ax, s: _data_dim(plan.spec(ax, s.shape)),
        M.param_axes(spec), M.abstract_params(spec),
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))
    grad_fn = jax.value_and_grad(make_loss_fn(spec, plan, cfg), has_aux=True)

    def own(dim, *grads):
        if dim is None:
            return grads[0]
        return jnp.concatenate([jnp.split(g, n, axis=dim)[k]
                                for k, g in enumerate(grads)], axis=dim)

    def faulty(state, batch):
        rows = jax.tree.leaves(batch)[0].shape[0] // n
        outs = [grad_fn(state["params"], jax.tree.map(
            lambda x: x[k * rows:(k + 1) * rows], batch)) for k in range(n)]
        grads = jax.tree.map(own, dims, *[g for _, g in outs],
                             is_leaf=lambda x: x is None or isinstance(x, int))
        new_state, om = opt.apply_updates(state, grads, cfg.opt)
        return new_state, {"loss": sum(l for (l, _), _ in outs) / n, **om}

    return faulty


@contextlib.contextmanager
def no_exchange():
    from repro.launch import train as T

    keep = T.make_train_step
    T.make_train_step = _no_exchange_step
    try:
        yield
    finally:
        T.make_train_step = keep


PATCHES = {"no_exchange": no_exchange}
