"""Static verification layer: the trace/plan verifier never fires on any
golden scenario trace (zero false positives), catches the whole seeded
defect corpus (zero false negatives), critical-path/slack invariants hold
against simulated makespans, and the PsA lint finds unsatisfiable
constraint sets and dead knobs."""
from __future__ import annotations

import dataclasses
import json
import time
from dataclasses import dataclass

import numpy as np
import pytest

from repro.configs import ARCHS
from repro.core.analysis import (AnalysisReport, PlanVerificationError,
                                 aggregate_summaries, analyze_job,
                                 critical_path, lint_pset, lint_study,
                                 preflight, verify_plan, verify_trace)
from repro.core.backends.base import SimJob, run_sim_job
from repro.core.compute import SYSTEM_2_DEVICE
from repro.core.env import CosmicEnv
from repro.core.psa import Constraint, Parameter, ParameterSet, paper_psa
from repro.core.scenario import (DisaggServeScenario, MultiTenantScenario,
                                 RequestStreamScenario, Tenant,
                                 TrainScenario, register_scenario,
                                 scenario_psa, SCENARIO_REGISTRY)
from repro.core.simulator import SCHED_POLICIES, _sim_plan, plan_durations, \
    simulate
from repro.core.space import DesignSpace
from repro.core.study import StudySpec, run_study
from repro.core.workload import Op, Parallelism, Trace

ARCH = ARCHS["qwen2-1.5b"]


def _env(scenario):
    return CosmicEnv(spec=ARCH, n_npus=1024, device=SYSTEM_2_DEVICE,
                     scenario=scenario)


def _tenants():
    return (Tenant("t0", ARCH, 64, 512, "train", slo_ms=5e5),
            Tenant("t1", ARCH, 16, 512, "serve", slo_ms=5e4))


SCENARIOS = {
    "train": lambda: TrainScenario(64, 512),
    "disagg": lambda: DisaggServeScenario(batch=16, seq=512),
    "request-stream": lambda: RequestStreamScenario(
        n_requests=8, seq=256, decode_tokens=8, rate_rps=8.0, seed=0),
    "multi-tenant": lambda: MultiTenantScenario(tenants=_tenants()),
}


def _jobs(sc, policy, n=3, seed=7):
    """(config, SimJob) pairs for n sampled design points under one sched
    policy; gated-invalid points are skipped (sampling continues until n
    survivors or the try budget runs out)."""
    env = _env(sc)
    pset = scenario_psa(paper_psa(1024), sc, 1024).pin(
        {"sched_policy": policy})
    space = DesignSpace(pset)
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n * 12):
        if len(out) == n:
            break
        cfg = space.sample(rng)
        job = sc.sim_job(env.context(cfg))
        if isinstance(job, SimJob):
            out.append((cfg, job))
    return out


# ---------------------------------------------------------------------------
# (a) zero false positives: the verifier never fires on golden traces
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", list(SCENARIOS))
@pytest.mark.parametrize("policy", SCHED_POLICIES)
def test_verifier_clean_on_all_scenario_families(kind, policy,
                                                 clear_dse_caches):
    jobs = _jobs(SCENARIOS[kind](), policy)
    assert jobs, "every probe gated invalid — widen the sample"
    for cfg, job in jobs:
        for c in job.calls:
            rep = verify_trace(c.trace, c.cfg, c.par, c.pools)
            assert rep.issues == (), \
                f"false positive on {kind}/{policy}:\n{rep.format()}"


# ---------------------------------------------------------------------------
# (b) critical-path/slack invariants vs simulated makespans
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", list(SCENARIOS))
def test_critical_path_invariants(kind, clear_dse_caches):
    checked = 0
    for cfg, job in _jobs(SCENARIOS[kind](), "fifo", n=2):
        for c in job.calls:
            res = simulate(c.trace, c.cfg, c.par, pools=c.pools)
            plan, dur = plan_durations(c.trace, c.cfg, c.par, c.pools)
            cp = critical_path(plan, dur)
            tol = max(cp.length_us, 1.0) * 1e-9
            # the dependency chain is a lower bound on any schedule
            assert cp.length_us <= res.makespan_us + tol
            # so is each unit-capacity resource's total demand
            assert cp.resource_lb_us <= res.makespan_us + tol
            # the reported path is a dependency chain of zero-slack ops
            for u in cp.path:
                assert cp.slack_us[u] <= tol
            for prev, u in zip(cp.path, cp.path[1:]):
                assert prev in c.trace.ops[u].deps
            # the per-category breakdown is a partition of the path
            assert sum(cp.breakdown_us.values()) == \
                pytest.approx(cp.length_us, rel=1e-9)
            assert cp.n_critical >= len(cp.path) > 0
            s = cp.summary(makespan_us=res.makespan_us)
            assert 0.0 < s["cp_frac_of_makespan"] <= 1.0 + 1e-9
            assert sum(s["breakdown_frac"].values()) == pytest.approx(1.0)
            checked += 1
    assert checked


def test_simulate_analyze_flag_attaches_summary(clear_dse_caches):
    sc = TrainScenario(64, 512)
    (cfg, job), = _jobs(sc, "fifo", n=1)
    c = job.calls[0]
    res = simulate(c.trace, c.cfg, c.par, pools=c.pools, analyze=True)
    assert res.analysis is not None
    assert res.analysis["makespan_us"] == res.makespan_us
    assert res.analysis["critical_path_us"] <= res.makespan_us * (1 + 1e-9)
    plain = simulate(c.trace, c.cfg, c.par, pools=c.pools)
    assert plain.analysis is None
    assert plain.makespan_us == res.makespan_us

    ev, summaries = analyze_job(job)
    assert len(summaries) == len(job.calls)
    agg = aggregate_summaries(summaries)
    assert agg["calls"] == len(job.calls)
    assert sum(agg["breakdown_frac"].values()) == pytest.approx(1.0)
    assert aggregate_summaries([]) is None


# ---------------------------------------------------------------------------
# (c) zero false negatives: the seeded defect corpus
# ---------------------------------------------------------------------------

def _comp(uid, deps=()):
    return Op(uid=uid, name=f"op{uid}", kind="comp", deps=tuple(deps),
              flops=1e9, bytes=1e6)


def test_defect_dep_cycle():
    rep = verify_trace(Trace(ops=[_comp(0, (1,)), _comp(1, (0,))], meta={}))
    assert [i.code for i in rep.errors] == ["dep-cycle"]
    with pytest.raises(PlanVerificationError) as ei:
        rep.raise_if_issues()
    assert ei.value.report is rep
    assert "dep-cycle" in str(ei.value)


def test_defect_self_dependency():
    rep = verify_trace(Trace(ops=[_comp(0, (0,))], meta={}))
    assert any(i.code == "dep-cycle" for i in rep.errors)


def test_defect_forward_dag_is_not_flagged():
    # forward (but acyclic) deps force the Kahn fallback — must stay clean
    ops = [Op(uid=0, name="a", kind="comp", deps=(1,), flops=1e9, bytes=1e6),
           Op(uid=1, name="b", kind="comp", deps=(), flops=1e9, bytes=1e6)]
    assert verify_trace(Trace(ops=ops, meta={})).issues == ()


@pytest.mark.parametrize("bad_dep", [5, -3])
def test_defect_dangling_dep(bad_dep):
    rep = verify_trace(Trace(ops=[_comp(0, (bad_dep,))], meta={}))
    assert any(i.code == "dangling-dep" for i in rep.errors)


def test_defect_non_dense_uids():
    ops = [Op(uid=3, name="a", kind="comp", deps=(), flops=1e9, bytes=1e6)]
    rep = verify_trace(Trace(ops=ops, meta={}))
    assert any(i.code == "bad-uid" for i in rep.errors)


def test_defect_dangling_resource():
    plan = _sim_plan(Trace(ops=[_comp(0), _comp(1, (0,))], meta={}))
    bad = dataclasses.replace(plan, res_of=[0, 99], pack_memo={})
    rep = verify_plan(bad)
    assert any(i.code == "dangling-resource" and i.op == 1
               and i.resource == 99 for i in rep.errors)


def test_defect_bad_costs_and_repeat():
    ops = [Op(uid=0, name="a", kind="comp", deps=(), flops=float("nan"),
              bytes=1e6)]
    rep = verify_trace(Trace(ops=ops, meta={}))
    assert any(i.code == "bad-cost" for i in rep.errors)

    ops = [Op(uid=0, name="c", kind="coll", deps=(), coll="allreduce",
              size_bytes=1e6, group="dp", repeat=0)]
    rep = verify_trace(Trace(ops=ops, meta={}))
    assert any(i.code == "bad-repeat" for i in rep.errors)

    ops = [Op(uid=0, name="d", kind="delay", deps=(), delay_us=-5.0)]
    rep = verify_trace(Trace(ops=ops, meta={}))
    assert any(i.code == "bad-delay" for i in rep.errors)


def test_defect_oversubscribed_pool(clear_dse_caches):
    """A pool whose placement demands more NPUs than its network provides
    is flagged with the offending pool and an op scheduled onto it."""
    sc = RequestStreamScenario(n_requests=4, seq=256, decode_tokens=8,
                               rate_rps=8.0, seed=0)
    (cfg, job), = _jobs(sc, "fifo", n=1)
    c = job.calls[0]
    bad_pools = dict(c.pools)
    pool_id, entry = next(iter(bad_pools.items()))
    par0, net0 = entry[0], entry[1]
    over = dataclasses.replace(par0, n_npus=net0.n_npus * 4,
                               dp=net0.n_npus * 4)
    bad_pools[pool_id] = (over,) + tuple(entry[1:])
    rep = verify_trace(c.trace, c.cfg, c.par, bad_pools)
    assert any(i.code == "pool-capacity" and i.pool == pool_id
               and i.op is not None for i in rep.errors)
    # the structural memo must not have absorbed the contextual issue
    del c.trace._verify_report
    assert verify_trace(c.trace, c.cfg, c.par, c.pools).issues == ()


def test_unmapped_pool_is_a_warning(clear_dse_caches):
    sc = DisaggServeScenario(batch=16, seq=512)
    jobs = _jobs(sc, "fifo", n=3)
    assert jobs
    cfg, job = jobs[0]
    c = next(c for c in job.calls if c.pools and len(c.pools) > 1)
    keep = next(iter(c.pools))
    rep = verify_trace(c.trace, c.cfg, c.par, {keep: c.pools[keep]})
    assert rep.ok                       # warnings don't fail a run
    assert any(i.code == "pool-unmapped" and i.severity == "warning"
               for i in rep.warnings)
    del c.trace._verify_report


def test_simulate_and_run_sim_job_verify_flag():
    trace = Trace(ops=[_comp(0, (1,)), _comp(1, (0,))], meta={})
    calls_seen = []
    job = SimJob(calls=(), finalize=lambda rs: calls_seen.append(rs))
    run_sim_job(job, verify=True)       # empty job: nothing to verify
    assert calls_seen == [[]]
    ok = Trace(ops=[_comp(0), _comp(1, (0,))], meta={})
    plan = _sim_plan(ok)
    from repro.core.simulator import SystemConfig
    from repro.core.topology import system_2
    cfg = SystemConfig(network=system_2(), device=SYSTEM_2_DEVICE,
                       coll_algo=("ring",) * 4)
    par = Parallelism(1024, 1, 1, 1)
    with pytest.raises(PlanVerificationError):
        simulate(trace, cfg, par, verify=True)
    res = simulate(ok, cfg, par, verify=True)
    assert res.makespan_us > 0


# ---------------------------------------------------------------------------
# (d) PsA lint: satisfiability + dead knobs
# ---------------------------------------------------------------------------

def test_lint_unsatisfiable_constraint_pair():
    pset = ParameterSet(
        [Parameter("dp", "workload", (1, 2, 4, 8, 16, 32, 64, 128, 256,
                                      512, 1024)),
         Parameter("pp", "workload", (1, 2, 4))],
        [Constraint("product_eq", ("dp", "pp"), 1024),
         Constraint("product_le", ("dp", "pp"), 512)], name="unsat-pair")
    rep = lint_pset(pset)
    assert not rep.ok
    assert any(i.code == "constraint-unsat" and "pair" in i.message
               for i in rep.errors)


def test_lint_oversubscribed_sum_budget():
    pset = ParameterSet(
        [Parameter("t0_npus", "scenario", (256, 512)),
         Parameter("t1_npus", "scenario", (512, 1024))],
        [Constraint("sum_le", ("t0_npus", "t1_npus"), 512)], name="oversub")
    rep = lint_pset(pset)
    assert any(i.code == "constraint-unsat" and "oversubscribed" in i.message
               for i in rep.errors)


def test_lint_unreachable_product_target():
    pset = ParameterSet(
        [Parameter("npus_per_dim", "network", (4, 8), ndim=2)],
        [Constraint("product_eq", ("npus_per_dim",), 100)], name="unreach")
    assert any(i.code == "constraint-unsat"
               for i in lint_pset(pset).errors)


def test_lint_sampling_probe_catches_pinned_unsat():
    # analytically fine, but the pinned value makes sampling infeasible
    pset = ParameterSet(
        [Parameter("dp", "workload", (1, 2, 4)),
         Parameter("pp", "workload", (1, 2, 4))],
        [Constraint("product_eq", ("dp", "pp"), 16)],
        fixed={"dp": 1, "pp": 1}, name="pinned-unsat")
    rep = lint_pset(pset)
    assert any(i.code == "constraint-unsat" for i in rep.errors)


def test_lint_clean_paper_psa_and_dead_knob(clear_dse_caches):
    sc = TrainScenario(64, 512)
    env = _env(sc)
    pset = scenario_psa(paper_psa(1024), sc, 1024)
    assert lint_pset(pset, env=env).ok
    ghost = pset.extend([Parameter("phantom_knob", "scenario", (1, 2, 3))])
    rep = lint_pset(ghost, env=env)
    assert [i.constraint for i in rep.issues
            if i.code == "dead-knob"] == ["phantom_knob"]


def test_searched_params_and_violation_rates():
    pset = ParameterSet(
        [Parameter("a", "workload", (1, 2)),
         Parameter("b", "workload", (1,)),          # single choice: inert
         Parameter("c", "workload", (1, 2, 4))],
        [Constraint("product_le", ("a", "c"), 1)],
        fixed={"c": 1}, name="sp")
    assert [p.name for p in pset.searched_params()] == ["a"]
    rates = DesignSpace(pset).constraint_violation_rates(
        np.random.default_rng(0), tries=64)
    # a=2 violates product_le 1 in half the raw decodes
    assert 0.2 < rates["product(a, c) <= 1"] < 0.8


# ---------------------------------------------------------------------------
# (e) run_study preflight + lint_study end to end
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class _DefectiveScenario:
    """Every design point yields a trace with a dependency cycle."""
    name: str = "defective-cyclic"

    def psa_params(self):
        return []

    def psa_constraints(self, n_npus):
        return []

    def traces(self, ctx):
        return {}

    def sim_job(self, ctx):
        trace = Trace(ops=[_comp(0, (1,)), _comp(1, (0,))], meta={})
        from repro.core.backends.base import SimCall
        call = SimCall(trace, ctx.sys_cfg, ctx.parallelism())
        return SimJob((call,), lambda rs: None)


@pytest.fixture()
def defective_scenario_kind():
    kind = "defective-cyclic-test"
    register_scenario(kind, lambda **p: _DefectiveScenario(),
                      replace_existing=True)
    yield kind
    SCENARIO_REGISTRY.pop(kind, None)


def _spec(scenario_kind, **over):
    d = dict(name="t", arch="qwen2-1.5b", system="system2",
             scenario=scenario_kind, agents=[{"kind": "rw"}], steps=5,
             batch_size=2, seeds=[0])
    d.update(over)
    return StudySpec.from_dict(d)


def test_run_study_preflight_fails_fast(defective_scenario_kind, tmp_path,
                                        clear_dse_caches):
    spec = _spec(defective_scenario_kind)
    with pytest.raises(PlanVerificationError) as ei:
        run_study(spec, out=tmp_path / "r.jsonl")
    assert any(i.code == "dep-cycle" for i in ei.value.report.errors)


def test_cli_run_exits_2_on_defective_plan(defective_scenario_kind,
                                           tmp_path, capsys,
                                           clear_dse_caches):
    from repro.dse import main
    spec_path = tmp_path / "bad.json"
    spec_path.write_text(json.dumps(_spec(defective_scenario_kind)
                                    .to_dict()))
    rc = main(["run", str(spec_path), "--out", str(tmp_path / "r.jsonl"),
               "--quiet"])
    assert rc == 2
    err = capsys.readouterr().err
    assert "static verification failed" in err and "dep-cycle" in err


def test_lint_study_flags_defective_scenario(defective_scenario_kind,
                                             clear_dse_caches):
    rep = lint_study(_spec(defective_scenario_kind))
    assert isinstance(rep, AnalysisReport) and not rep.ok
    assert any(i.code == "dep-cycle" for i in rep.errors)


def test_lint_study_clean_and_cost_fields(clear_dse_caches):
    spec = _spec("train", scenario_params={"batch": 64, "seq": 512},
                 agents=[{"kind": "rw"}, {"kind": "ga", "steps": 9}])
    rep = lint_study(spec)
    assert rep.ok, rep.format()
    assert rep.info["cells"] == 2
    assert rep.info["evaluations_max"] == 5 + 9
    assert rep.info["trace_ops"] > 0
    assert float(rep.info["cardinality"]) > 1


def test_preflight_clean_and_gated(clear_dse_caches):
    sc = TrainScenario(64, 512)
    env = _env(sc)
    pset = scenario_psa(paper_psa(1024), sc, 1024)
    rep = preflight(env, pset, seed=0)
    assert rep is not None and rep.ok


# ---------------------------------------------------------------------------
# (f) overhead: verification must be a rounding error next to simulation
# ---------------------------------------------------------------------------

def test_verify_overhead_is_small(clear_dse_caches):
    """Steady-state verification (structural verdict re-derived, plan-level
    array conversions amortized like the plan itself) must stay well under
    the 5%% acceptance bound — asserted leniently here at 25%% because CI
    boxes are noisy and this trace is far smaller than the ~26k-op
    acceptance trace (fixed costs loom larger); the benchmark row
    (``benchmarks.run --only backends``) measures the real ratio."""
    sc = RequestStreamScenario(n_requests=32, seq=512, decode_tokens=16,
                               rate_rps=16.0, seed=0)
    (cfg, job), = _jobs(sc, "fifo", n=1)
    c = job.calls[0]
    simulate(c.trace, c.cfg, c.par, pools=c.pools)   # build + warm the plan
    verify_trace(c.trace, c.cfg, c.par, c.pools)     # amortized conversions
    sim_t = min(_timed(lambda: simulate(c.trace, c.cfg, c.par,
                                        pools=c.pools)) for _ in range(3))

    def cold_verify():
        if hasattr(c.trace, "_verify_report"):
            del c.trace._verify_report
        verify_trace(c.trace, c.cfg, c.par, c.pools)

    ver_t = min(_timed(cold_verify) for _ in range(5))
    assert ver_t < 0.25 * sim_t, \
        f"verify {ver_t * 1e3:.2f}ms vs simulate {sim_t * 1e3:.2f}ms"


def _timed(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0
