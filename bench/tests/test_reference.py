"""The benchmark's frozen model of the engine agrees with the program's own
scalar path (its ``reference`` backend) to the last bit, on the traces the
cells pin, and refuses a point whose trace-shaping knobs differ."""
import pytest

from bench import compare, generate
from bench.reference import engine as ref
from conftest import tiny_cell

ENGINE = ["engine.stream256.p32", "engine.train.p32"]


def program_trace(env, point):
    """The trace the program's scalar path simulates for ``point``."""
    from repro.core.backends import get_backend

    backend, seen = get_backend("reference"), {}
    inner = backend.simulate

    def simulate(trace, *a, **k):
        seen[id(trace)] = trace
        return inner(trace, *a, **k)

    backend.simulate = simulate
    try:
        ev = env.evaluate_config(point)
    finally:
        del backend.simulate
    (trace,) = seen.values()
    return trace, ev


@pytest.mark.parametrize("workload", ENGINE)
def test_model_matches_the_scalar_path(workload):
    from bench.drivers import engine

    cell = tiny_cell(workload)
    env = engine.make_env(cell, "reference")
    points = generate.DesignPoints(cell.traffic, cell.seed).population(0)
    trace, first = program_trace(env, points[0])
    canon = ref.canonical(trace)
    assert ref.digest(canon) == cell.traffic["trace_sha256"]
    model = ref.Reference(cell.config, cell.traffic, canon)
    got = [first] + [env.evaluate_config(p) for p in points[1:]]
    worst, unequal, _ = compare.compare_points(got, [model.evaluate(p) for p in points])
    assert (worst, unequal) == (0.0, 0)


def test_model_refuses_other_pinned_knobs():
    cell = tiny_cell("engine.train.p32")
    model = ref.Reference(cell.config, cell.traffic, {"ops": [], "wave_marks": []})
    point = dict(generate.DesignPoints(cell.traffic, 1).population(0)[0], dp=32)
    with pytest.raises(ValueError, match="pinned"):
        model.evaluate(point)
