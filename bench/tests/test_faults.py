"""A whole run with the timed path broken underneath must come out not
correct: once for each fault a cell can have, and for the control."""
import pytest

from bench import variants
from conftest import run_cell, tiny_cell

ENGINE = ["engine.stream256.p32", "engine.train.p32"]
TRAIN = ["train.8L.b4s1024"]


@pytest.mark.parametrize("workload", ENGINE)
@pytest.mark.parametrize("variant", variants.ENGINE)
def test_engine_variant_fails(workload, variant, tmp_path):
    with variants.PATCHES[variant]():
        out = run_cell(tiny_cell(workload), tmp_path)
    assert not out.correct, out.checks


@pytest.mark.parametrize("workload", TRAIN)
@pytest.mark.parametrize("variant", variants.TRAIN)
def test_train_fault_fails(workload, variant, tmp_path):
    with variants.PATCHES[variant]():
        out = run_cell(tiny_cell(workload), tmp_path)
    assert not out.correct, out.checks


@pytest.mark.parametrize("workload", ENGINE)
def test_engine_foreign_trace_fails(workload, tmp_path):
    """A trace other than the one the traffic pins is not compared."""
    cell = tiny_cell(workload)
    cell.traffic["trace_sha256"] = "0" * 64
    out = run_cell(cell, tmp_path)
    assert not out.correct
    assert dict((n, v) for n, v, _ in out.checks)["trace_foreign"] == 1.0


@pytest.mark.parametrize("workload", TRAIN)
def test_train_control_fails(workload):
    from bench import checks

    got = checks.train_control(tiny_cell(workload))
    assert any(v > limit for _, v, limit in got), got
