"""The model-FLOPs count against XLA's cost analysis of the program's
compiled train step, at a tiny size on a CPU, with nothing recomputed."""
import dataclasses

import pytest

from bench import flops
from bench.spec import arch_spec
from conftest import tiny_cell


@pytest.mark.parametrize("seq", [32, 128])
def test_flops_match_xla(seq):
    import jax
    import jax.numpy as jnp
    from repro.train.train_step import (RunConfig, init_train_state,
                                        make_train_step)

    cell = tiny_cell("train.8L.b4s1024")
    # one layer: XLA's cost analysis counts a loop body once
    cfg = {**cell.config, "num_hidden_layers": 1, "vocab_size": 4096,
           "hidden_size": 256, "intermediate_size": 512}
    spec = arch_spec(cfg)
    run = RunConfig(remat="none")
    batch = 4
    step = make_train_step(spec, cfg=run)
    state = jax.eval_shape(lambda: init_train_state(jax.random.PRNGKey(0), spec, run))
    data = {"inputs": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
            "labels": jax.ShapeDtypeStruct((batch, seq), jnp.int32)}
    xla = jax.jit(step).lower(state, data).compile().cost_analysis()["flops"]
    ours = flops.train_flops_per_token(cfg, seq) * batch * seq
    # XLA also counts the optimizer, norms, softmax and the loss's
    # elementwise work, a few percent at these sizes
    assert ours <= xla <= 1.08 * ours, (ours, xla, xla / ours)
