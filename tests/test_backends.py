"""Simulation-backend API tests: registry plumbing, ReferenceBackend
bit-identity against pre-refactor golden values, SystemConfig validation,
the SimJob batch driver, and (jax-guarded) JaxBackend parity across every
scenario trace family and both scheduling policies."""
from __future__ import annotations

import numpy as np
import pytest

from repro.configs import ARCHS
from repro.core.backends import (SimCall, SimJob, backend_available,
                                 get_backend, list_backends,
                                 register_backend, run_sim_job, run_sim_jobs)
from repro.core.compute import SYSTEM_2_DEVICE
from repro.core.env import CosmicEnv
from repro.core.psa import paper_psa
from repro.core.scenario import (DisaggServeScenario, MultiTenantScenario,
                                 RequestStreamScenario, Tenant,
                                 TrainScenario, scenario_psa)
from repro.core.simulator import SystemConfig, simulate
from repro.core.space import DesignSpace
from repro.core.systems import system_env
from repro.core.topology import system_2
from repro.core.workload import Parallelism, generate_trace


def _sys(policy: str = "fifo") -> SystemConfig:
    return SystemConfig(network=system_2(), device=SYSTEM_2_DEVICE,
                        coll_algo=("ring", "direct", "ring", "rhd"),
                        chunks=2, sched_policy=policy)


BASE_CFG = dict(dp=8, sp=1, pp=1, weight_sharded=0, sched_policy="fifo",
                coll_algo=("ring", "direct", "ring", "rhd"), chunks=2,
                multidim_coll="baseline",
                topology=("ring", "fc", "ring", "switch"),
                npus_per_dim=(4, 8, 4, 8), bw_per_dim=(400, 200, 150, 100))


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_lists_builtins_and_rejects_unknown():
    assert {"reference", "jax"} <= set(list_backends())
    assert get_backend("reference").name == "reference"
    assert get_backend(None).name == "reference"  # the default
    # an instance passes through untouched
    be = get_backend("reference")
    assert get_backend(be) is be
    with pytest.raises(ValueError, match="unknown simulation backend"):
        get_backend("not-a-backend")
    with pytest.raises(ValueError, match="already registered"):
        register_backend("reference", lambda: None)
    assert backend_available("reference")
    assert not backend_available("not-a-backend")


def test_env_and_simulate_reject_unknown_backend():
    with pytest.raises(ValueError, match="unknown simulation backend"):
        CosmicEnv(spec=ARCHS["qwen2-1.5b"], n_npus=1024,
                  device=SYSTEM_2_DEVICE, scenario=TrainScenario(64, 2048),
                  backend="not-a-backend")
    par = Parallelism(64, dp=64, sp=1, pp=1)
    tr = generate_trace(ARCHS["qwen2-1.5b"], par, batch=64, seq=128)
    with pytest.raises(ValueError, match="unknown simulation backend"):
        simulate(tr, _sys(), par, backend="not-a-backend")


# ---------------------------------------------------------------------------
# SystemConfig validation (pinned): a typo'd sched_policy used to silently
# schedule as FIFO
# ---------------------------------------------------------------------------

def test_sched_policy_validated_at_construction():
    for ok in ("fifo", "lifo"):
        assert _sys(ok).sched_policy == ok
    for bad in ("lifoo", "FIFO", "", "random"):
        with pytest.raises(ValueError, match="unknown sched_policy"):
            _sys(bad)


# ---------------------------------------------------------------------------
# ReferenceBackend bit-identity: golden makespans captured from the
# pre-backend simulate() (PR-4 tree), exact to the last ulp
# ---------------------------------------------------------------------------

def test_reference_backend_matches_pre_refactor_golden_values():
    cases = [
        ("gpt3-13b", Parallelism(1024, 64, 4, 1, True), 1024, "train",
         16271035.786701888, 16185591.472128013, 85444.3145738747),
        ("gpt3-175b", Parallelism(1024, 32, 8, 1, True), 1024, "train",
         217819100.03438663, 216970720.1298433, 848379.9045433402),
        ("gpt3-13b", Parallelism(1024, 64, 4, 1), 64, "decode",
         137863.06259999986, 137621.4177999999, 241.64479999995092),
        # dp-grad-overlap-heavy shape (the sched-policy stress case)
        ("gpt3-175b", Parallelism(1024, 64, 1, 1, True), 1024, "train",
         218434834.8352596, None, 1452035.1098963022),
    ]
    for arch, par, batch, mode, makespan, compute, exposed in cases:
        tr = generate_trace(ARCHS[arch], par, batch=batch, seq=2048,
                            mode=mode)
        for policy in ("fifo", "lifo"):
            res = simulate(tr, _sys(policy), par)
            assert res.makespan_us == makespan, (arch, mode, policy)
            assert res.exposed_comm_us == exposed, (arch, mode, policy)
            if compute is not None:
                assert res.compute_busy_us == compute, (arch, mode, policy)


def test_scenario_golden_values_via_reference_backend():
    """Multi-pool + delay-op traces: disagg and request-stream evaluations
    pinned against pre-refactor values (xfer, gates, releases, repeats)."""
    disagg = system_env("qwen2-1.5b", "system2",
                        scenario=DisaggServeScenario(64, 2048, 16),
                        objective="latency")
    ev = disagg.evaluate_config(dict(BASE_CFG, prefill_frac=0.5,
                                     decode_batch=4))
    assert ev.latency_ms == 235.54705323946763
    assert ev.reward == 0.004245436256777772

    stream = system_env(
        "qwen2-1.5b", "system2",
        scenario=RequestStreamScenario(n_requests=32, seq=1024,
                                       decode_tokens=16, rate_rps=16.0,
                                       seed=3),
        objective="goodput")
    ev = stream.evaluate_config(dict(BASE_CFG, prefill_frac=0.5,
                                     decode_batch=4, batch_window_ms=50.0,
                                     max_inflight=2))
    assert ev.latency_ms == 74.93265646512177
    assert ev.reward == 18.606955522152628


def test_simulate_is_a_thin_delegate():
    """Module-level simulate() == ReferenceBackend.simulate, field for
    field, including the opt-in recording flags."""
    par = Parallelism(1024, 64, 4, 1, True)
    tr = generate_trace(ARCHS["gpt3-13b"], par, batch=1024, seq=2048)
    via_delegate = simulate(tr, _sys(), par, record_per_op=True)
    direct = get_backend("reference").simulate(tr, _sys(), par,
                                               record_per_op=True)
    assert via_delegate == direct
    assert via_delegate.per_op_us and via_delegate.op_finish_us


# ---------------------------------------------------------------------------
# SimJob driver: grouped batch execution == per-job execution
# ---------------------------------------------------------------------------

def test_run_sim_jobs_groups_by_trace_and_matches_serial():
    env = system_env("qwen2-1.5b", "system2", batch=64, seq=2048)
    cfgs = [dict(BASE_CFG, chunks=c) for c in (2, 4, 8)]
    jobs = [env.scenario.sim_job(env.context(c)) for c in cfgs]
    assert all(isinstance(j, SimJob) for j in jobs)
    batched = run_sim_jobs(jobs, "reference")
    serial = [env.evaluate_config(c) for c in cfgs]
    assert [b.reward for b in batched] == [s.reward for s in serial]
    assert [b.latency_ms for b in batched] == [s.latency_ms for s in serial]
    # terminal evaluations (gated-invalid points) pass through in order
    bad = dict(BASE_CFG, dp=512, sp=4, pp=4)  # dp*sp*pp > n_npus
    mixed = [env.scenario.sim_job(env.context(c)) for c in (cfgs[0], bad)]
    out = run_sim_jobs(mixed, "reference")
    assert out[0].valid and not out[1].valid


def test_run_sim_job_passes_evaluations_through():
    from repro.core.rewards import Evaluation

    ev = Evaluation(0.0, float("inf"), False, {"why": "gated"})
    assert run_sim_job(ev, "reference") is ev


# ---------------------------------------------------------------------------
# JaxBackend parity (guarded like hypothesis: the jax extra is optional)
# ---------------------------------------------------------------------------

jax = pytest.importorskip("jax")

RTOL = 1e-9


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-12)


def test_jax_parity_train_trace_both_policies():
    jb = get_backend("jax")
    for arch, par in (("gpt3-13b", Parallelism(1024, 64, 4, 1, True)),
                      ("gpt3-175b", Parallelism(1024, 64, 1, 1, True))):
        tr = generate_trace(ARCHS[arch], par, batch=1024, seq=2048)
        for policy in ("fifo", "lifo"):
            ref = simulate(tr, _sys(policy), par)
            got = jb.simulate(tr, _sys(policy), par)
            assert _rel(got.makespan_us, ref.makespan_us) < RTOL
            assert _rel(got.compute_busy_us, ref.compute_busy_us) < RTOL
            for k, v in ref.comm_busy_us.items():
                assert _rel(got.comm_busy_us[k], v) < RTOL


@pytest.mark.parametrize("policy", ["fifo", "lifo"])
def test_jax_parity_all_scenarios(policy):
    """Env-level parity on all four scenario families — rewards and
    latencies agree between the jax sweep and the reference event loop."""
    scenarios = [
        ("train", None, {}),
        ("disagg", DisaggServeScenario(64, 2048, 16),
         dict(prefill_frac=0.5, decode_batch=4)),
        ("stream", RequestStreamScenario(n_requests=24, seq=1024,
                                         decode_tokens=16, rate_rps=16.0,
                                         seed=3),
         dict(prefill_frac=0.5, decode_batch=4, batch_window_ms=50.0,
              max_inflight=2)),
        ("tenants", MultiTenantScenario(tenants=(
            Tenant("a", ARCHS["gpt3-13b"], 512, 2048, "train", slo_ms=5e5),
            Tenant("b", ARCHS["qwen2-1.5b"], 64, 2048, "serve",
                   slo_ms=5e4))),
         dict(tenant_npus=(512, 256))),
    ]
    for name, sc, extra in scenarios:
        kw = dict(scenario=sc) if sc is not None else dict(batch=64)
        obj = "goodput" if name == "stream" else "perf_per_bw"
        env_ref = system_env("qwen2-1.5b", "system2", objective=obj, **kw)
        env_jax = system_env("qwen2-1.5b", "system2", objective=obj,
                             backend="jax", **kw)
        cfg = dict(BASE_CFG, sched_policy=policy, **extra)
        ref = env_ref.evaluate_config(cfg)
        got = env_jax.evaluate_config(cfg)
        assert ref.valid and got.valid, name
        assert _rel(got.latency_ms, ref.latency_ms) < RTOL, name
        assert _rel(got.reward, ref.reward) < RTOL, name


def test_jax_parity_seeded_design_space_sweep():
    """Random full-stack design points: jax and reference agree on every
    valid point (and on which points gate invalid)."""
    env_ref = system_env("gpt3-13b", "system2")
    env_jax = system_env("gpt3-13b", "system2", backend="jax")
    space = DesignSpace(paper_psa(1024, max_pp=4))
    rng = np.random.default_rng(7)
    checked = 0
    for _ in range(12):
        cfg = space.sample(rng)
        ref = env_ref.evaluate_config(cfg)
        got = env_jax.evaluate_config(cfg)
        assert got.valid == ref.valid
        if ref.valid:
            checked += 1
            assert _rel(got.latency_ms, ref.latency_ms) < RTOL
    assert checked >= 3  # the sweep actually exercised valid points


def test_jax_batch_is_bit_identical_to_jax_single():
    """simulate_batch over a population == simulate per point (the same
    compiled sweep runs either way)."""
    jb = get_backend("jax")
    par = Parallelism(1024, 64, 4, 1, True)
    tr = generate_trace(ARCHS["qwen2-1.5b"], par, batch=1024, seq=2048)
    cfgs = [SystemConfig(network=system_2(), device=SYSTEM_2_DEVICE,
                         coll_algo=("ring", "direct", "ring", "rhd"),
                         chunks=c, sched_policy=p)
            for c, p in ((2, "fifo"), (8, "lifo"), (16, "fifo"))]
    batch = jb.simulate_batch(tr, [SimCall(tr, c, par) for c in cfgs])
    for cfg, got in zip(cfgs, batch):
        one = jb.simulate(tr, cfg, par)
        assert got.makespan_us == one.makespan_us
        assert got.comm_busy_us == one.comm_busy_us


def test_jax_step_batch_routes_through_simulate_batch():
    """The env's vectorized path (dedupe -> sim_job -> grouped
    simulate_batch) returns exactly what serial jax evaluation returns,
    in input order, with history recorded once per occurrence."""
    sc = RequestStreamScenario(n_requests=24, seq=1024, decode_tokens=16,
                               rate_rps=16.0, seed=3)
    env = system_env("qwen2-1.5b", "system2", scenario=sc,
                     objective="goodput", backend="jax")
    base = dict(BASE_CFG, prefill_frac=0.5, decode_batch=4,
                batch_window_ms=50.0, max_inflight=2)
    cfgs = [dict(base, chunks=c) for c in (2, 4, 8, 4)]  # one duplicate
    out = env.step_batch(cfgs)
    assert len(out) == 4 and len(env.history) == 4
    assert out[1].reward == out[3].reward  # dedupe returned the memo entry
    serial = [env.evaluate_config(c) for c in cfgs]
    assert [o.reward for o in out] == [s.reward for s in serial]


def test_backends_do_not_cross_hit_a_shared_eval_store():
    """The env signature includes the backend, so reference and jax envs
    sharing one eval_store keep separate entries."""
    store: dict = {}
    kw = dict(batch=64, seq=2048, eval_store=store)
    env_ref = system_env("qwen2-1.5b", "system2", **kw)
    env_jax = system_env("qwen2-1.5b", "system2", backend="jax", **kw)
    env_ref.step(dict(BASE_CFG))
    env_jax.step(dict(BASE_CFG))
    assert env_ref.store_misses == 1 and env_ref.store_hits == 0
    assert env_jax.store_misses == 1 and env_jax.store_hits == 0
    assert len(store) == 2


# ---------------------------------------------------------------------------
# what the fused call copies back: the makespan, the busy time and the
# finish times of the wave-marked uids, unless every op is asked for
# ---------------------------------------------------------------------------

WAVE_SCENARIOS = {
    "stream": (RequestStreamScenario(n_requests=24, seq=1024,
                                     decode_tokens=16, rate_rps=16.0, seed=3),
               "goodput", dict(prefill_frac=0.5, decode_batch=4,
                               batch_window_ms=50.0, max_inflight=2)),
    "disagg": (DisaggServeScenario(64, 2048, 16), "latency",
               dict(prefill_frac=0.5, decode_batch=4)),
}


def _wave_calls(name: str) -> list[SimCall]:
    """One population's calls over a scenario's shared wave trace."""
    sc, obj, extra = WAVE_SCENARIOS[name]
    env = system_env("qwen2-1.5b", "system2", scenario=sc, objective=obj,
                     backend="jax")
    cfgs = [dict(BASE_CFG, chunks=c, sched_policy=p, **extra)
            for c in (2, 8) for p in ("fifo", "lifo")]
    calls = [c for cfg in cfgs
             for c in env.scenario.sim_job(env.context(cfg)).calls]
    assert all(c.trace is calls[0].trace and c.record_finish for c in calls)
    return calls


@pytest.mark.parametrize("name", sorted(WAVE_SCENARIOS))
def test_jax_matches_reference_on_wave_traces(name):
    """Makespan and wave times bit for bit, busy to f64 rounding: the
    device reductions against the reference event loop."""
    from repro.core.scenario import _wave_times_ms

    calls = _wave_calls(name)
    tr = calls[0].trace
    jaxed = get_backend("jax").simulate_batch(tr, calls)
    ref = get_backend("reference").simulate_batch(tr, calls)
    for got, want in zip(jaxed, ref):
        assert got.makespan_us == want.makespan_us
        assert _wave_times_ms(tr, got) == _wave_times_ms(tr, want)
        assert _rel(got.compute_busy_us, want.compute_busy_us) < RTOL
        assert got.comm_busy_us.keys() == want.comm_busy_us.keys()
        for k, v in want.comm_busy_us.items():
            assert _rel(got.comm_busy_us[k], v) < RTOL


def test_jax_record_finish_holds_only_the_marked_uids():
    from repro.core.workload import wave_mark_uids

    calls = _wave_calls("stream")
    tr = calls[0].trace
    got = get_backend("jax").simulate_batch(tr, calls)[0].op_finish_us
    want = get_backend("reference").simulate_batch(tr, calls)[0].op_finish_us
    marked = wave_mark_uids(tr).tolist()
    assert 0 < len(marked) < len(tr.ops) // 4
    assert list(got) == marked and len(got) == len(marked)
    assert all(got[u] == want[u] for u in marked)
    assert got.take(marked[::-1]).tolist() == [want[u] for u in marked[::-1]]
    unmarked = next(u for u in range(len(tr.ops)) if u not in set(marked))
    for uid in (unmarked, -1, len(tr.ops)):
        assert uid not in got
        with pytest.raises(KeyError):
            got[uid]
    with pytest.raises(KeyError):
        got.take([marked[0], unmarked])


@pytest.mark.parametrize("ask", ["record_per_op", "finish_without_wave_marks"])
def test_jax_full_matrices_where_every_op_is_asked_for(ask):
    """A call that asks for every op, or finish times on a trace that marks
    no waves, still gets each op's duration and finish time."""
    from dataclasses import replace

    from repro.core.workload import Trace

    calls = _wave_calls("stream")
    tr = calls[0].trace
    if ask == "record_per_op":
        calls = [replace(calls[0], record_per_op=True)] + calls[1:]
    else:
        tr = Trace(tr.ops, meta={k: v for k, v in tr.meta.items()
                                 if k != "wave_marks"})
        calls = [replace(c, trace=tr) for c in calls]
    got = get_backend("jax").simulate_batch(tr, calls)
    want = get_backend("reference").simulate_batch(tr, calls)
    n = len(tr.ops)
    assert len(got[0].op_finish_us) == n == len(want[0].op_finish_us)
    assert all(got[0].op_finish_us[u] == want[0].op_finish_us[u]
               for u in range(0, n, 97))
    if ask == "record_per_op":
        assert len(got[0].per_op_us) == n
        assert all(_rel(got[0].per_op_us[u], want[0].per_op_us[u]) < RTOL
                   for u in range(n))
    assert [g.makespan_us for g in got] == [w.makespan_us for w in want]


def test_jax_copy_back_bytes_of_a_stream_batch():
    from dataclasses import replace

    from repro.core.simulator import plan_duration_tables
    from repro.core.workload import wave_mark_uids
    from repro.runtime import spans

    calls = _wave_calls("stream")
    tr = calls[0].trace
    n_res = len(plan_duration_tables(tr, calls[:1])[0].res_names)
    gen = "repro.engine.generation"
    with spans.unit(gen):
        get_backend("jax").simulate_batch(tr, calls)
    copied = spans.rows(gen)[-1]["repro.engine.copy_back_bytes"]
    assert 0 < copied <= (len(wave_mark_uids(tr)) + 1 + n_res) * len(calls) * 8
    # a member that asks for every op brings the full matrices back
    with spans.unit(gen):
        get_backend("jax").simulate_batch(
            tr, [replace(calls[0], record_per_op=True)] + calls[1:])
    assert spans.rows(gen)[-1]["repro.engine.copy_back_bytes"] \
        >= len(tr.ops) * len(calls) * 8
