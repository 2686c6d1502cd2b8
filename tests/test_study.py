"""Study API tests: StudySpec JSON round-trip, registry-built envs
bit-identical to hand-constructed equivalents, campaign resume, shared
eval_store accounting, the heterogeneous request-length stream, and the
``repro.dse`` CLI."""
from __future__ import annotations

import json

import numpy as np
import pytest

from repro.configs import ARCHS
from repro.core.compute import SYSTEM_2_DEVICE
from repro.core.dse import run_search
from repro.core.env import CosmicEnv
from repro.core.psa import paper_psa
from repro.core.scenario import (RequestStreamScenario, TrainScenario,
                                 build_scenario, list_scenarios,
                                 scenario_psa)
from repro.core.study import AgentSpec, StudySpec, run_study
from repro.core.systems import get_system, list_systems

ARCH = "qwen2-1.5b"


def _train_spec(**over) -> StudySpec:
    kw = dict(name="t", arch=ARCH, system="system2", scenario="train",
              scenario_params={"batch": 64, "seq": 2048},
              objective="perf_per_bw", agents=("ga",), seeds=(0,),
              steps=20, batch_size=5)
    kw.update(over)
    return StudySpec(**kw)


# ---------------------------------------------------------------------------
# (a) spec: JSON round trip + spec-time validation
# ---------------------------------------------------------------------------

def test_studyspec_json_roundtrip():
    spec = _train_spec(
        scenario="request-stream",
        scenario_params={"n_requests": 16, "seq": 1024, "decode_tokens": 8,
                         "rate_rps": 4.0, "prompt_len_range": [256, 512]},
        objective="goodput",
        agents=("ga", {"kind": "bo", "steps": 10, "hyper": {"candidates": 24}}),
        seeds=[0, 1], stacks=["workload", "scenario"],
        psa_overrides={"chunks": 2})
    text = spec.to_json()
    back = StudySpec.from_json(text)
    assert back == spec
    assert back.spec_hash() == spec.spec_hash()
    # lists arriving from JSON were canonicalized to tuples
    assert back.scenario_params["prompt_len_range"] == (256, 512)
    assert back.agents[1] == AgentSpec("bo", steps=10,
                                       hyper={"candidates": 24})
    # a changed field changes the hash...
    assert _train_spec(steps=21).spec_hash() != _train_spec().spec_hash()
    # ...except workers, which only parallelizes evaluation (results are
    # bit-identical across the pool path) and must not block a resume
    assert _train_spec(workers=4).spec_hash() == _train_spec().spec_hash()


def test_studyspec_refuses_a_worker_pool_on_a_jax_backend():
    """Each pool worker would load jax and reach for the same accelerator."""
    with pytest.raises(ValueError, match="needs the reference backend"):
        _train_spec(workers=2, backend="jax")
    assert _train_spec(workers=2).workers == 2
    assert _train_spec(workers=1, backend="jax").backend == "jax"


def test_studyspec_rejects_bad_names_at_spec_time():
    with pytest.raises(ValueError, match="unknown arch"):
        _train_spec(arch="not-a-model")
    with pytest.raises(ValueError, match="unknown system"):
        _train_spec(system="system9")
    with pytest.raises(ValueError, match="unknown scenario kind"):
        _train_spec(scenario="not-a-scenario")
    with pytest.raises(ValueError, match="unknown objective"):
        _train_spec(objective="not-an-objective")
    with pytest.raises(ValueError, match="unknown agent kind"):
        _train_spec(agents=("sgd",))
    with pytest.raises(ValueError, match="unknown hyper"):
        # a typo'd hyper name must fail at spec time, not TypeError a cell
        # deep into the campaign
        _train_spec(agents=({"kind": "bo", "hyper": {"pool": 24}},))
    with pytest.raises(ValueError, match="streaming"):
        _train_spec(objective="goodput")  # train can't stream
    with pytest.raises(ValueError, match="unknown pinned parameter"):
        _train_spec(psa_overrides={"not_a_param": 3})
    with pytest.raises(ValueError, match="outside the parameter's choices"):
        _train_spec(psa_overrides={"chunks": 3})
    with pytest.raises(ValueError, match="unknown TrainScenario"):
        _train_spec(scenario_params={"batch": 64, "seq": 2048, "bogus": 1})
    with pytest.raises(ValueError, match="unknown StudySpec keys"):
        StudySpec.from_dict(dict(_train_spec().to_dict(), extra=1))


def test_registries_list_builtins():
    assert {"train", "disagg-serve", "request-stream",
            "multi-tenant"} <= set(list_scenarios())
    assert {"system1", "system2", "system3"} <= set(list_systems())
    assert get_system("system2").n_npus == 1024


# ---------------------------------------------------------------------------
# (b) registry-built env/pset bit-identical to hand-constructed equivalents
# ---------------------------------------------------------------------------

def test_spec_built_search_bit_identical_to_hand_assembled_ga50():
    """GA@50 through the Study front door == GA@50 over a hand-wired
    env/pset (the pre-study assembly), reward for reward."""
    spec = _train_spec(steps=50, batch_size=10)

    hand_ps = paper_psa(1024, max_pp=4)
    hand_env = CosmicEnv(spec=ARCHS[ARCH], n_npus=1024,
                         device=SYSTEM_2_DEVICE,
                         scenario=TrainScenario(64, 2048),
                         objective="perf_per_bw")
    want = run_search(hand_ps, hand_env, "ga", steps=50, seed=3,
                      batch_size=10)
    got = run_search(spec.build_pset(), spec.build_env(), "ga", steps=50,
                     seed=3, batch_size=10)
    assert got.best_reward == want.best_reward
    assert got.best_config == want.best_config
    assert got.reward_curve == want.reward_curve


def test_registry_scenario_reward_matches_hand_constructed_stream():
    sc_hand = RequestStreamScenario(n_requests=16, seq=1024, decode_tokens=8,
                                    rate_rps=4.0)
    sc_reg = build_scenario("request-stream",
                            {"n_requests": 16, "seq": 1024,
                             "decode_tokens": 8, "rate_rps": 4.0})
    assert sc_reg == sc_hand
    spec = _train_spec(scenario="request-stream",
                       scenario_params={"n_requests": 16, "seq": 1024,
                                        "decode_tokens": 8, "rate_rps": 4.0},
                       objective="goodput")
    env_reg = spec.build_env()
    env_hand = CosmicEnv(spec=ARCHS[ARCH], n_npus=1024,
                         device=SYSTEM_2_DEVICE, scenario=sc_hand,
                         objective="goodput")
    from repro.core.space import DesignSpace
    pset = scenario_psa(paper_psa(1024, max_pp=4), sc_hand, 1024)
    space = DesignSpace(pset)
    rng = np.random.default_rng(0)
    for _ in range(5):
        cfg = space.sample(rng)
        assert env_reg.evaluate_config(cfg).reward == \
            env_hand.evaluate_config(cfg).reward


# ---------------------------------------------------------------------------
# (c) campaign: shared store, JSONL persistence, resume
# ---------------------------------------------------------------------------

def test_shared_eval_store_across_cells():
    """Two identical GA cells in one campaign: the second re-proposes the
    exact same points (same agent seed) and must hit the shared store for
    every one of them."""
    spec = _train_spec(agents=("ga", "ga"), steps=15, batch_size=5)
    res = run_study(spec)
    first, second = res.outcomes
    assert first.result.best_reward == second.result.best_reward
    assert second.store_hits == 15           # every point was free
    assert res.store_hits + res.store_misses == 30  # per-occurrence accounting
    assert res.distinct_points == res.store_misses


def test_campaign_persists_and_resumes(tmp_path):
    out = tmp_path / "campaign.jsonl"
    spec = _train_spec(agents=("ga",), seeds=(0, 1), steps=12, batch_size=4)
    full = run_study(spec, out=out)
    assert full.cells_run == 2
    lines = [json.loads(l) for l in out.read_text().splitlines()]
    assert lines[0]["record"] == "study"
    assert lines[0]["spec_hash"] == spec.spec_hash()
    assert [l["cell_id"] for l in lines[1:]] == ["0:ga:s0", "0:ga:s1"]
    assert all(l["spec_hash"] == spec.spec_hash() for l in lines[1:])

    # chop the campaign in half: only the missing cell may run on resume
    out.write_text("\n".join(json.dumps(l) for l in lines[:2]) + "\n")
    half = run_study(spec, out=out, resume=True)
    assert half.cells_run == 1 and half.cells_skipped == 1
    assert [o.resumed for o in half.outcomes] == [True, False]
    # resumed + re-run rewards match the uninterrupted campaign bit for bit
    assert [o.result.best_reward for o in half.outcomes] == \
        [o.result.best_reward for o in full.outcomes]

    # fully complete file: nothing runs, results reconstructed from disk
    done = run_study(spec, out=out, resume=True)
    assert done.cells_run == 0 and done.cells_skipped == 2
    assert [o.result.best_reward for o in done.outcomes] == \
        [o.result.best_reward for o in full.outcomes]
    # a resumed best_config round-trips through JSON with its tuples intact
    # (hashable again — usable as a memoized env step input)
    resumed_cfg = done.best().result.best_config
    assert resumed_cfg == full.best().result.best_config
    env = spec.build_env()
    assert env.step(resumed_cfg).reward == done.best().result.best_reward


def test_resume_refuses_foreign_results_file(tmp_path):
    out = tmp_path / "campaign.jsonl"
    run_study(_train_spec(steps=8, batch_size=4), out=out)
    other = _train_spec(steps=9, batch_size=4)
    with pytest.raises(ValueError, match="refusing to resume"):
        run_study(other, out=out, resume=True)


def test_resume_needs_results_file():
    with pytest.raises(ValueError, match="results file"):
        run_study(_train_spec(), resume=True)


def test_refuses_to_overwrite_existing_results(tmp_path):
    """Re-running without --resume must never truncate a finished
    campaign's results file."""
    out = tmp_path / "campaign.jsonl"
    spec = _train_spec(steps=8, batch_size=4)
    run_study(spec, out=out)
    before = out.read_text()
    with pytest.raises(ValueError, match="already exists"):
        run_study(spec, out=out)
    assert out.read_text() == before


def test_resume_discards_truncated_final_line(tmp_path):
    """A campaign killed mid-append leaves a partial trailing record: resume
    drops it (re-running that cell) instead of crashing on it, and trims it
    so appended records don't concatenate onto the fragment."""
    out = tmp_path / "campaign.jsonl"
    spec = _train_spec(agents=("ga",), seeds=(0, 1), steps=12, batch_size=4)
    full = run_study(spec, out=out)
    lines = out.read_text().splitlines()
    out.write_text("\n".join(lines[:2]) + "\n" + lines[2][:40])  # torn write
    res = run_study(spec, out=out, resume=True)
    assert res.cells_run == 1 and res.cells_skipped == 1
    assert [o.result.best_reward for o in res.outcomes] == \
        [o.result.best_reward for o in full.outcomes]
    # the rewritten file is whole again: a second resume runs nothing
    again = run_study(spec, out=out, resume=True)
    assert again.cells_run == 0 and again.cells_skipped == 2
    # a torn line anywhere else is corruption, not a torn tail
    lines = out.read_text().splitlines()
    out.write_text("\n".join([lines[0], lines[1][:40], lines[2]]) + "\n")
    with pytest.raises(ValueError, match="not valid JSON"):
        run_study(spec, out=out, resume=True)


# ---------------------------------------------------------------------------
# (d) heterogeneous request lengths
# ---------------------------------------------------------------------------

_STREAM_CFG = dict(dp=8, sp=1, pp=1, weight_sharded=0, sched_policy="fifo",
                   coll_algo=("ring", "direct", "ring", "rhd"), chunks=2,
                   multidim_coll="baseline",
                   topology=("ring", "fc", "ring", "switch"),
                   npus_per_dim=(4, 8, 4, 8),
                   bw_per_dim=(400, 200, 150, 100), prefill_frac=0.875,
                   decode_batch=8, batch_window_ms=50.0, max_inflight=2)


def _stream_env(sc):
    return CosmicEnv(spec=ARCHS[ARCH], n_npus=1024, device=SYSTEM_2_DEVICE,
                     scenario=sc, objective="goodput")


def test_request_shapes_default_homogeneous():
    sc = RequestStreamScenario(n_requests=8, seq=1024, decode_tokens=16)
    assert sc.request_shapes() == ((1024, 16),) * 8
    assert not sc.heterogeneous()


def test_request_shapes_seeded_deterministic_and_bounded():
    sc = RequestStreamScenario(n_requests=32, seq=1024, decode_tokens=16,
                               prompt_len_range=(256, 2048),
                               decode_len_range=(4, 64), seed=5)
    shapes = sc.request_shapes()
    assert shapes == sc.request_shapes()          # memoized + deterministic
    assert sc.heterogeneous()
    assert all(256 <= p <= 2048 and 4 <= d <= 64 for p, d in shapes)
    assert len({p for p, _ in shapes}) > 1        # actually heterogeneous
    # a different seed draws different lengths
    other = RequestStreamScenario(n_requests=32, seq=1024, decode_tokens=16,
                                  prompt_len_range=(256, 2048),
                                  decode_len_range=(4, 64), seed=6)
    assert other.request_shapes() != shapes


def test_request_shapes_replayed_trace_cycles():
    sc = RequestStreamScenario(n_requests=5, seq=1024, decode_tokens=16,
                               prompt_lens=(100, 700),
                               decode_lens=(8, 2, 4))
    assert sc.request_shapes() == \
        ((100, 8), (700, 2), (100, 4), (700, 8), (100, 2))


def test_heterogeneous_lengths_change_metrics_and_stay_valid():
    homog = RequestStreamScenario(n_requests=24, seq=1024, decode_tokens=16)
    het = RequestStreamScenario(n_requests=24, seq=1024, decode_tokens=16,
                                prompt_len_range=(256, 2048),
                                decode_len_range=(4, 64))
    ev_h = _stream_env(homog).evaluate_config(_STREAM_CFG)
    ev_x = _stream_env(het).evaluate_config(_STREAM_CFG)
    assert ev_h.valid and ev_x.valid
    assert ev_x.reward != ev_h.reward
    d = ev_x.detail
    assert d["prompt_len_max"] <= 2048 and d["decode_len_max"] <= 64
    assert "prompt_len_mean" not in ev_h.detail   # only reported when het
    # shorter-than-wave-max requests finish earlier than the wave: p50 e2e
    # latency can't exceed the homogeneous-style wave completion ceiling
    assert d["latency_p99_ms"] > 0


def test_heterogeneous_range_validation():
    sc = RequestStreamScenario(n_requests=4, prompt_len_range=(0, 8))
    with pytest.raises(ValueError, match="prompt"):
        sc.request_shapes()
    sc = RequestStreamScenario(n_requests=4, decode_len_range=(9, 3))
    with pytest.raises(ValueError, match="decode"):
        sc.request_shapes()


def test_heterogeneous_params_via_study_spec():
    spec = _train_spec(
        scenario="request-stream", objective="goodput",
        scenario_params={"n_requests": 12, "seq": 1024, "decode_tokens": 8,
                         "rate_rps": 4.0, "prompt_len_range": [128, 512],
                         "decode_lens": [4, 8]})
    sc = spec.build_scenario()
    assert sc.prompt_len_range == (128, 512)
    assert sc.decode_lens == (4, 8)
    assert sc.heterogeneous()


# ---------------------------------------------------------------------------
# (e) the CLI
# ---------------------------------------------------------------------------

def test_cli_run_and_resume(tmp_path, capsys):
    from repro.dse import main

    spec_path = tmp_path / "smoke.json"
    out_path = tmp_path / "smoke.results.jsonl"
    _train_spec(steps=8, batch_size=4).to_json(spec_path)

    assert main(["run", str(spec_path), "--out", str(out_path)]) == 0
    assert "cells_run=1" in capsys.readouterr().out
    assert out_path.exists()

    assert main(["run", str(spec_path), "--out", str(out_path),
                 "--resume"]) == 0
    assert "cells_run=0 cells_skipped=1" in capsys.readouterr().out

    for cmd in ("list-scenarios", "list-systems", "list-objectives",
                "list-backends"):
        assert main([cmd]) == 0
    listed = capsys.readouterr().out
    assert "request-stream" in listed and "system2" in listed \
        and "goodput" in listed and "reference" in listed


# ---------------------------------------------------------------------------
# (f) simulation-backend selection on the spec
# ---------------------------------------------------------------------------

def test_spec_backend_field_roundtrip_and_validation():
    spec = _train_spec(backend="reference")
    assert StudySpec.from_json(spec.to_json()) == spec
    # the backend changes results (within tolerance), so it changes the hash
    assert _train_spec(backend="jax").spec_hash() != spec.spec_hash()
    # ...but the default backend hashes as if the field didn't exist, so
    # campaigns recorded before PR 5 stay resumable
    import hashlib

    d = spec.to_dict()
    for k in ("workers", "eval_store_path", "backend"):
        del d[k]
    pre_pr5 = hashlib.sha256(json.dumps(
        d, sort_keys=True, separators=(",", ":")).encode()).hexdigest()[:16]
    assert spec.spec_hash() == pre_pr5
    with pytest.raises(ValueError, match="unknown simulation backend"):
        _train_spec(backend="not-a-backend")
    env = spec.build_env()
    assert env.backend == "reference"
    # old spec JSONs (no backend key) load with the default
    d = spec.to_dict()
    del d["backend"]
    assert StudySpec.from_dict(d).backend == "reference"


def test_cli_backend_override(tmp_path, capsys):
    pytest.importorskip("jax")
    from repro.dse import main

    spec_path = tmp_path / "s.json"
    _train_spec(steps=6, batch_size=3).to_json(spec_path)
    assert main(["run", str(spec_path), "--backend", "jax",
                 "--out", str(tmp_path / "r.jsonl")]) == 0
    assert "backend=jax" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# (g) cross-campaign persistent eval store
# ---------------------------------------------------------------------------

def test_persistent_eval_store_reused_across_campaigns(tmp_path):
    store_path = tmp_path / "evals.jsonl"
    spec = _train_spec(steps=10, batch_size=5,
                       eval_store_path=str(store_path))
    # eval_store_path is hash-exempt (reuse never changes results)
    assert spec.spec_hash() == _train_spec(steps=10,
                                           batch_size=5).spec_hash()

    first = run_study(spec, out=tmp_path / "r1.jsonl")
    assert first.store_preloaded == 0
    assert first.store_persisted == first.distinct_points > 0
    assert store_path.exists()

    second = run_study(spec, out=tmp_path / "r2.jsonl")
    assert second.store_preloaded == first.store_persisted
    assert second.store_misses == 0          # every point came from disk
    assert second.store_hit_rate == 1.0
    assert second.store_persisted == 0       # nothing new to write back
    # and the campaign's results are identical to the fresh one's
    assert [o.result.best_reward for o in second.outcomes] == \
        [o.result.best_reward for o in first.outcomes]
    assert [o.result.reward_curve for o in second.outcomes] == \
        [o.result.reward_curve for o in first.outcomes]


def test_persistent_eval_store_isolates_incompatible_studies(tmp_path):
    """Entries are stamped with the evaluation signature: a study over a
    different (arch/objective/...) must not preload another's results."""
    store_path = tmp_path / "evals.jsonl"
    spec_a = _train_spec(steps=6, batch_size=3,
                         eval_store_path=str(store_path))
    run_study(spec_a, out=tmp_path / "a.jsonl")

    spec_b = _train_spec(steps=6, batch_size=3, objective="latency",
                         eval_store_path=str(store_path))
    assert spec_b.eval_signature() != spec_a.eval_signature()
    res_b = run_study(spec_b, out=tmp_path / "b.jsonl")
    assert res_b.store_preloaded == 0
    assert res_b.store_persisted > 0

    # ...while a search-shape change (steps/agents) still shares entries
    spec_c = _train_spec(steps=4, batch_size=2, agents=("rw",),
                         eval_store_path=str(store_path))
    assert spec_c.eval_signature() == spec_a.eval_signature()
    assert run_study(spec_c, out=tmp_path / "c.jsonl").store_preloaded > 0


def test_persistent_eval_store_survives_torn_tail(tmp_path):
    store_path = tmp_path / "evals.jsonl"
    spec = _train_spec(steps=6, batch_size=3,
                       eval_store_path=str(store_path))
    run_study(spec, out=tmp_path / "a.jsonl")
    with store_path.open("a") as f:
        f.write('{"sig": "torn')  # killed mid-append
    res = run_study(spec, out=tmp_path / "b.jsonl")
    assert res.store_preloaded > 0 and res.store_misses == 0


# ---------------------------------------------------------------------------
# (h) the results-comparison CLI
# ---------------------------------------------------------------------------

def test_cli_compare_results_files(tmp_path, capsys):
    from repro.dse import main

    spec_path = tmp_path / "s.json"
    _train_spec(steps=8, batch_size=4, agents=("ga", "rw")).to_json(spec_path)
    a, b = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    assert main(["run", str(spec_path), "--out", str(a), "--quiet"]) == 0
    assert main(["run", str(spec_path), "--out", str(b), "--quiet"]) == 0
    capsys.readouterr()

    assert main(["compare", str(a), str(b)]) == 0
    got = capsys.readouterr()
    assert "0:ga:s0" in got.out and "1:rw:s0" in got.out
    assert "winner: tie" in got.out          # identical campaigns
    assert "warning" not in got.err          # same spec hash

    # a different study into b -> hash-mismatch warning + a winner
    b2 = tmp_path / "b2.jsonl"
    spec2 = tmp_path / "s2.json"
    _train_spec(steps=12, batch_size=4, agents=("ga", "rw"),
                seeds=(1,)).to_json(spec2)
    assert main(["run", str(spec2), "--out", str(b2), "--quiet"]) == 0
    capsys.readouterr()
    assert main(["compare", str(a), str(b2)]) == 0
    got = capsys.readouterr()
    assert "spec hashes differ" in got.err
    assert "winner:" in got.out

    assert main(["compare", str(a), str(tmp_path / "missing.jsonl")]) == 2


# ---------------------------------------------------------------------------
# (h) CLI robustness: empty/torn JSONL, resume on an empty file, lint/analyze
# ---------------------------------------------------------------------------

def test_cli_compare_empty_and_torn_files_exit_2(tmp_path, capsys):
    from repro.dse import main

    spec_path = tmp_path / "s.json"
    _train_spec(steps=4, batch_size=2).to_json(spec_path)
    good = tmp_path / "good.jsonl"
    assert main(["run", str(spec_path), "--out", str(good), "--quiet"]) == 0
    capsys.readouterr()

    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    assert main(["compare", str(good), str(empty)]) == 2
    assert "error:" in capsys.readouterr().err

    # header written, then killed mid-first-cell: lenient reader drops the
    # torn tail, no cells remain -> clean exit 2, no traceback
    torn = tmp_path / "torn.jsonl"
    torn.write_text(good.read_text().split("\n")[0] + "\n"
                    + '{"record": "cell", "cell_id": "0:ga:s0", "res')
    assert main(["compare", str(good), str(torn)]) == 2
    assert "no cell records" in capsys.readouterr().err

    assert main(["analyze", str(empty)]) == 2
    assert "error:" in capsys.readouterr().err


def test_cli_resume_on_empty_results_file_writes_header(tmp_path, capsys):
    from repro.dse import main

    spec_path = tmp_path / "s.json"
    _train_spec(steps=4, batch_size=2).to_json(spec_path)
    out = tmp_path / "r.jsonl"
    out.write_text("")      # e.g. `touch`ed by a scheduler before the run
    assert main(["run", str(spec_path), "--out", str(out), "--resume",
                 "--quiet"]) == 0
    capsys.readouterr()
    first = json.loads(out.read_text().splitlines()[0])
    assert first["record"] == "study"       # header present, not cells-only
    # and the file now resumes cleanly
    assert main(["run", str(spec_path), "--out", str(out), "--resume",
                 "--quiet"]) == 0
    assert "cells_run=0 cells_skipped=1" in capsys.readouterr().out


def test_cli_lint_and_analyze(tmp_path, capsys):
    from repro.dse import main

    spec_path = tmp_path / "s.json"
    _train_spec(steps=4, batch_size=2).to_json(spec_path)
    assert main(["lint", str(spec_path)]) == 0
    out = capsys.readouterr().out
    assert "ok" in out and "cells=1" in out

    # unknown scenario param -> spec doesn't build -> exit 2
    bad = tmp_path / "bad.json"
    d = _train_spec().to_dict()
    d["scenario_params"]["batcj"] = 64
    bad.write_text(json.dumps(d))
    assert main(["lint", str(bad)]) == 2
    assert "batcj" in capsys.readouterr().err

    # unsatisfiable pins -> lint reports, exit 1
    unsat = tmp_path / "unsat.json"
    d2 = _train_spec(psa_overrides={"dp": 1024, "sp": 1024}).to_dict()
    unsat.write_text(json.dumps(d2))
    assert main(["lint", str(unsat)]) == 1
    got = capsys.readouterr()
    assert "constraint-unsat" in got.out

    # analyze: bottleneck-attribution table over a finished campaign
    res = tmp_path / "r.jsonl"
    assert main(["run", str(spec_path), "--out", str(res), "--quiet"]) == 0
    capsys.readouterr()
    assert main(["analyze", str(res)]) == 0
    table = capsys.readouterr().out
    assert "cp%" in table and "0:ga:s0" in table and "bound" in table
