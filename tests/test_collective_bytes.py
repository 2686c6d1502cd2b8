"""The training step's collective counters, ``repro.train.collective_bytes``
and ``repro.train.all_to_all_bytes``: over a 2x2 mesh each step counts what
the compiled HLO's collectives (and, of them, its all-to-alls) move,
read from the very executable the loop calls, which ``build`` compiled once;
on one device the counter reads 0 and the loop compiles as it did before."""
from __future__ import annotations

import json

import pytest

from helpers import run_with_devices

LOOP = """
import json
import jax
from repro.configs import get_arch, reduced
from repro.core.hlo_analysis import analyze_compiled_text
from repro.launch import train as T
from repro.runtime import spans

compiles = []
jax.monitoring.register_event_duration_secs_listener(
    lambda e, d, **k: compiles.append(e)
    if e == "/jax/core/compile/backend_compile_duration" else None)
built = []
keep = T.build

def build(*a, **kw):
    out = keep(*a, **kw)
    built.append((out[0], len(compiles)))
    return out

T.build = build
args = T.parse_args(["--steps", "3", "--batch", "4", "--seq", "16", "--bf16",
                     "--remat", "full", "--mesh", MESH])
run = T.train_loop(args, reduced(get_arch("qwen2-1.5b")))
step, after_build = built[0]
rows = spans.rows("repro.train.step")[-3:]
compiled = getattr(step, "compiled", None)
print(json.dumps({
    "counted": rows["repro.train.collective_bytes"].tolist(),
    "analyzed": None if compiled is None else
        analyze_compiled_text(compiled.as_text()).total_collective_bytes(),
    "a2a_counted": rows["repro.train.all_to_all_bytes"].tolist(),
    "a2a_analyzed": None if compiled is None else analyze_compiled_text(
        compiled.as_text()).collective_bytes.get("all-to-all", 0.0),
    "kinds": {} if compiled is None else dict(step.collectives.collective_counts),
    "compiles": len(compiles), "compiles_after_build": len(compiles) - after_build,
    "losses": run.losses}))
"""


def _loop(mesh: str) -> dict:
    out = run_with_devices(4, LOOP.replace("MESH", repr(mesh)), timeout=300)
    return json.loads(out.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def loops() -> dict:
    return {mesh: _loop(mesh) for mesh in ("", "2x2")}


def test_mesh_step_counts_its_compiled_collectives(loops):
    got = loops["2x2"]
    assert got["analyzed"] > 0
    assert got["counted"] == [got["analyzed"]] * 3
    # the plan's FSDP gathers and gradient reductions are all there
    assert got["kinds"]["all-gather"] > 0 and got["kinds"]["all-reduce"] > 0


def test_mesh_step_compiles_once_in_build(loops):
    mesh, one = loops["2x2"], loops[""]
    assert mesh["compiles_after_build"] == 0
    assert mesh["compiles"] == one["compiles"]


def test_one_device_counts_nothing_and_compiles_in_its_first_step(loops):
    one = loops[""]
    assert one["analyzed"] is None
    assert one["counted"] == [0.0, 0.0, 0.0]
    assert one["compiles_after_build"] >= 1      # jit compiles at the first call
    # the same model, data and seed: one device and the mesh agree
    assert one["losses"] == pytest.approx(loops["2x2"]["losses"], rel=1e-3)


def test_mesh_step_counts_its_compiled_all_to_all_bytes(loops):
    got = loops["2x2"]
    a2a = got["a2a_analyzed"]
    assert got["a2a_counted"] == [a2a] * 3
    assert 0 <= a2a <= got["analyzed"]
    assert (a2a > 0) == (got["kinds"].get("all-to-all", 0) > 0)


def test_one_device_counts_no_all_to_all_bytes(loops):
    one = loops[""]
    assert one["a2a_analyzed"] is None
    assert one["a2a_counted"] == [0.0, 0.0, 0.0]
