"""Each cell's driver rehearsed end to end on a CPU at a tiny size: the
harness's look for a chip is skipped, the rest of a run is driven."""
import json

import pytest

from bench import harness
from conftest import ROOT, run_cell, tiny_cell

CELLS = [w["name"] for w in harness.load_json(ROOT / "BENCHMARK.json")["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_and_is_correct(workload, tmp_path):
    cell = tiny_cell(workload)
    out = run_cell(cell, tmp_path)
    assert out.correct, out.checks
    assert out.attempted > 0 and out.failed == 0
    for m in cell.end_to_end:
        assert out.metrics[m["name"]] > 0, m["name"]
    assert out.obs["compiles_in_window"] == 0
    json.dumps(out.checks)


@pytest.mark.parametrize("workload", CELLS)
def test_same_seed_same_inputs(workload):
    from bench import generate

    cell = tiny_cell(workload, seed=2**31 + 77)
    if cell.traffic["kind"] == "design_points":
        a = generate.DesignPoints(cell.traffic, cell.seed)
        b = generate.DesignPoints(cell.traffic, cell.seed)
        assert a.population(3) == b.population(3)
        pts = [tuple(sorted(p.items())) for i in range(a.warmup)
               for p in a.warmup_population(i)]
        pts += [tuple(sorted(p.items())) for i in range(50)
                for p in a.population(i)]
        assert len(set(pts)) == len(pts)      # drawn without replacement
    else:
        t = cell.traffic
        x = generate.lm_batch(cell.seed, 5, t["batch"], t["seq"], 256)
        y = generate.lm_batch(cell.seed, 5, t["batch"], t["seq"], 256)
        z = generate.lm_batch(cell.seed, 6, t["batch"], t["seq"], 256)
        assert (x["inputs"] == y["inputs"]).all()
        assert (x["inputs"] != z["inputs"]).any()
        assert (x["labels"][:, :-1] == x["inputs"][:, 1:]).all()
