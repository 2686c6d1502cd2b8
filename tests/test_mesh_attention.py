"""The head-sharded attention's layout over a 2x2 mesh: the seq x seq work
stays on heads in the backward pass too, so no all-to-all of the compiled
step moves a (b, h, s, s) tile of scores or probabilities.

The step is the reduced qwen2-1.5b's (4 heads, which the ``model`` axis of 2
divides) with full remat, at a sequence length of 48 that no width of the
model shares: a seq x seq tile is the only tensor with both a 48 and a 24
(half the sequence, one ``model`` shard of it) among its dimensions."""
from __future__ import annotations

import json
import re

import pytest

from helpers import run_with_devices

SEQ, BATCH, HEADS = 48, 4, 4

STEP = """
import json
import jax
import jax.numpy as jnp
from repro.configs import get_arch, reduced
from repro.core.hlo_analysis import parse_hlo
from repro.launch import train as T
from repro.launch.mesh import make_mesh
from repro.train import optimizer as opt
from repro.train.train_step import RunConfig, batch_abstract

spec = reduced(get_arch("qwen2-1.5b"))
cfg = RunConfig(compute_dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                remat="full", opt=opt.OptConfig())
mesh = make_mesh((2, 2), ("data", "model"))
with jax.set_mesh(mesh):
    step, _, _ = T.build(spec, mesh, cfg,
                         batch=batch_abstract(spec, BATCH, SEQ, jnp.float32))
shapes = []
for comp in parse_hlo(step.compiled.as_text()).values():
    for ins in comp.instructions:
        if ins.opcode.startswith("all-to-all"):
            shapes.append([ins.type_str] + [comp.by_name[o].type_str
                                            for o in ins.operands if o in comp.by_name])
print(json.dumps({"heads": spec.n_heads, "shapes": shapes,
                  "all_to_all_bytes": step.collectives.collective_bytes.get("all-to-all", 0.0),
                  "counted": step.all_to_all_bytes}))
"""


@pytest.fixture(scope="module")
def mesh_step() -> dict:
    code = STEP.replace("BATCH", str(BATCH)).replace("SEQ", str(SEQ))
    out = run_with_devices(4, code, timeout=300)
    return json.loads(out.strip().splitlines()[-1])


def _dims(type_str: str) -> list[list[int]]:
    """The dimensions of each array in an HLO type, a tuple's one by one."""
    return [[int(d) for d in dims.split(",") if d]
            for dims in re.findall(r"\w+\[([\d,]*)\]", type_str)]


def test_no_all_to_all_moves_a_seq_by_seq_tile(mesh_step):
    assert mesh_step["heads"] == HEADS
    tiles = [s for s in mesh_step["shapes"]
             if any(SEQ in d and SEQ // 2 in d for t in s for d in _dims(t))]
    assert tiles == []


def test_all_to_all_bytes_stay_below_one_layers_probabilities(mesh_step):
    probs_f32 = BATCH * HEADS * SEQ * SEQ * 4      # one layer's (b, h, s, s)
    assert mesh_step["all_to_all_bytes"] < probs_f32
    assert mesh_step["counted"] == mesh_step["all_to_all_bytes"]
