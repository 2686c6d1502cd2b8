"""Scenario layer: TrainScenario bit-identity with the pre-scenario engine,
disaggregated-serving degeneracy and multi-pool simulation, request-stream
serving with queueing, multi-tenant partition safety, the PR-3 modeling
fixes (per-physical-dim collective algorithms, PP remainder layers), and
batched/process-pool evaluation per scenario type."""
from __future__ import annotations

import dataclasses
import math

import numpy as np
import pytest

from repro.configs import ARCHS
from repro.core.compute import SYSTEM_2_DEVICE
from repro.core.env import CosmicEnv
from repro.core.psa import paper_psa
from repro.core.rewards import evaluate
from repro.core.scenario import (DisaggServeScenario, MultiTenantScenario,
                                 RequestStreamScenario, Scenario, Tenant,
                                 TrainScenario, scenario_psa)
from repro.core.simulator import SystemConfig, simulate
from repro.core.space import DesignSpace
from repro.core.topology import partition_cluster, sub_network, system_2
from repro.core.workload import (Op, Parallelism, Trace, TraceBuilder,
                                 compose_phases, generate_trace)

SPEC = ARCHS["gpt3-13b"]


def _env(scenario=None, *, batch=1024, seq=2048, mode="train",
         decode_tokens=64, **kw):
    if scenario is None:
        scenario = TrainScenario(batch, seq, mode, decode_tokens)
    return CosmicEnv(spec=SPEC, n_npus=1024, device=SYSTEM_2_DEVICE,
                     scenario=scenario, **kw)


def _disagg_scenario(**kw):
    kw.setdefault("batch", 64)
    kw.setdefault("seq", 2048)
    return DisaggServeScenario(**kw)


def _tenants():
    return (Tenant("train-13b", SPEC, 512, 2048, "train", slo_ms=5e5,
                   weight=2.0),
            Tenant("serve-1.5b", ARCHS["qwen2-1.5b"], 64, 2048, "serve",
                   slo_ms=5e4, device_name="system3-h100"))


def _sample_configs(pset, n, seed=0):
    space = DesignSpace(pset)
    rng = np.random.default_rng(seed)
    return [space.sample(rng) for _ in range(n)]


# ---------------------------------------------------------------------------
# (a) TrainScenario == pre-refactor engine, bit for bit
# ---------------------------------------------------------------------------

def _pre_refactor_evaluate(env: CosmicEnv, config: dict):
    """The seed repo's CosmicEnv.evaluate_config, verbatim: build the
    parallelization + network + system stacks and call rewards.evaluate."""
    from repro.core.topology import build_network

    par = Parallelism(env.n_npus, config["dp"], config["sp"], config["pp"],
                      bool(config["weight_sharded"]))
    net = build_network(config["topology"], config["npus_per_dim"],
                        config["bw_per_dim"])
    sys_cfg = SystemConfig(network=net, device=env.device,
                           coll_algo=tuple(config["coll_algo"]),
                           chunks=int(config["chunks"]),
                           sched_policy=config["sched_policy"],
                           multidim_coll=config["multidim_coll"])
    sc = env.scenario
    return evaluate(env.spec, par, sys_cfg, batch=sc.batch, seq=sc.seq,
                    mode=sc.mode, objective=env.objective,
                    capacity_gb=env.capacity_gb)


# rewards/latencies recorded by running THIS sweep (gpt3-13b, system2,
# paper_psa(1024), rng seed 7) on the pre-scenario engine at commit 9d735d8
# (PR 1) — golden values, independent of the current code.  Entry 7 was
# re-pinned after the PR-3 collective-algorithm attribution fix: its config
# puts DP on outer network dims whose per-dim algorithms the pre-fix
# simulator mis-resolved from position 0 (was 3.057855450484146e-08 /
# 22553.557703103957); the other seven are bit-identical to PR 1.
_PR1_GOLDEN = [
    (5.606140838198029e-08, 16215.985047354485, True),
    (4.152428749523412e-08, 16608.477656128038, True),
    (0.0, float("inf"), False),
    (0.0, float("inf"), False),
    (7.517698102017199e-08, 20464.530940735993, True),
    (0.0, float("inf"), False),
    (0.0, float("inf"), False),
    (3.057920050568171e-08, 22553.081247979546, True),
]


def test_train_scenario_bit_identical_to_pre_refactor(clear_dse_caches):
    env = _env()
    for i, cfg in enumerate(_sample_configs(paper_psa(1024), 25, seed=7)):
        got = env.step(cfg)
        if i < len(_PR1_GOLDEN):
            assert (got.reward, got.latency_ms, got.valid) == _PR1_GOLDEN[i]
        want = _pre_refactor_evaluate(env, cfg)
        assert (got.reward, got.latency_ms, got.valid) == \
            (want.reward, want.latency_ms, want.valid)


def test_decode_tokens_threads_through_serve_path(clear_dse_caches):
    cfgs = _sample_configs(paper_psa(1024), 12, seed=3)
    short = _env(mode="serve", batch=64, decode_tokens=8)
    long = _env(mode="serve", batch=64, decode_tokens=256)
    pairs = [(short.step(c), long.step(c)) for c in cfgs]
    valid = [(a, b) for a, b in pairs if a.valid]
    assert valid, "no valid serve configs sampled"
    for a, b in valid:
        assert b.latency_ms > a.latency_ms
        dec = a.detail["decode_ms"]
        assert b.latency_ms - a.latency_ms == pytest.approx(248 * dec)


# ---------------------------------------------------------------------------
# (b) DisaggServeScenario: monolithic degeneracy + multi-pool simulation
# ---------------------------------------------------------------------------

def test_disagg_full_pool_degenerates_to_monolithic(clear_dse_caches):
    sc = _disagg_scenario()
    mono = TrainScenario(sc.batch, sc.seq, "serve", sc.decode_tokens)
    env_d, env_m = _env(sc), _env(mono)
    found = 0
    for cfg in _sample_configs(scenario_psa(paper_psa(1024), sc, 1024), 20,
                               seed=1):
        cfg = dict(cfg, prefill_frac=1.0)
        a = env_d.evaluate_config(cfg)
        b = env_m.evaluate_config(cfg)
        assert (a.reward, a.latency_ms, a.valid) == \
            (b.reward, b.latency_ms, b.valid)
        found += a.valid
    assert found, "no valid monolithic configs sampled"


def test_disagg_pools_are_simulated_separately(clear_dse_caches):
    sc = _disagg_scenario()
    env = _env(sc)
    for cfg in _sample_configs(scenario_psa(paper_psa(1024), sc, 1024), 30,
                               seed=2):
        cfg = dict(cfg, prefill_frac=0.5)
        ev = env.evaluate_config(cfg)
        if not ev.valid:
            continue
        assert ev.detail["prefill_npus"] == 512
        assert ev.detail["decode_npus"] <= 512
        assert ev.detail["p50_token_latency_ms"] > 0
        traces = sc.traces(env.context(cfg))
        combined = traces["combined"]
        assert {op.pool for op in combined.ops} == {0, 1}
        assert any(op.group == "xfer" for op in combined.ops)
        return
    pytest.fail("no valid disagg config sampled")


def test_multi_pool_simulator_xfer_and_streams(clear_dse_caches):
    par_a = Parallelism(512, dp=8, sp=1, pp=1)
    par_b = Parallelism(512, dp=4, sp=1, pp=1)
    pre = generate_trace(SPEC, par_a, batch=64, seq=2048, mode="prefill")
    dec = generate_trace(SPEC, par_b, batch=64, seq=2048, mode="decode")
    tr = compose_phases([(pre, 0), (dec, 1)], transfers=[1e9])
    cfg = SystemConfig(network=system_2(), device=SYSTEM_2_DEVICE,
                       coll_algo=("ring",) * 4, chunks=2)
    res = simulate(tr, cfg, par_a, pools={0: par_a, 1: par_b})
    assert set(res.pool_compute_us) == {0, 1}
    assert all(v > 0 for v in res.pool_compute_us.values())
    assert res.comm_busy_us.get("xfer", 0) > 0
    # the phases are dependency-chained: the makespan covers both pools
    assert res.makespan_us >= max(res.pool_compute_us.values())
    # per-op recording is opt-in
    assert res.per_op_us == {}
    rec = simulate(tr, cfg, par_a, pools={0: par_a, 1: par_b},
                   record_per_op=True)
    assert len(rec.per_op_us) == len(tr.ops)


def test_decode_latency_does_not_get_free_pp_speedup(clear_dse_caches):
    cfg = SystemConfig(network=system_2(), device=SYSTEM_2_DEVICE,
                       coll_algo=("ring",) * 4, chunks=2)
    lat = {}
    for pp in (1, 4):
        par = Parallelism(1024, dp=16, sp=1, pp=pp)
        tr = generate_trace(SPEC, par, batch=64, seq=2048, mode="decode")
        lat[pp] = simulate(tr, cfg, par).latency_ms
    # the token still traverses every layer, plus cross-stage hops
    assert lat[4] >= lat[1]


# ---------------------------------------------------------------------------
# (c) PR-3 modeling fixes: per-physical-dim collective algorithms, PP
#     remainder layers, simulator repeat/delay op semantics
# ---------------------------------------------------------------------------

# system2 under tp=4, dp=64: TP occupies physical dim 0, DP carves dims
# 1-3 — the regression config for the per-dim algorithm attribution fix
_ALGO_PAR = Parallelism(1024, dp=64, sp=1, pp=1)


def _dp_only_trace() -> Trace:
    tb = TraceBuilder()
    u = tb.comp("x", 1e9, 1e6, [])
    tb.coll("dp.ar", "all_reduce", 1e9, "dp", [u])
    return Trace(tb.ops)


def _algo_makespan(coll_algo: tuple) -> float:
    cfg = SystemConfig(network=system_2(), device=SYSTEM_2_DEVICE,
                       coll_algo=coll_algo, chunks=2)
    return simulate(_dp_only_trace(), cfg, _ALGO_PAR).makespan_us


def test_coll_algo_follows_physical_dims(clear_dse_caches):
    """Pinned regression for the `_group_net` attribution fix: a DP
    collective riding physical dims 1-3 must be priced with THOSE dims'
    algorithms.  Pre-fix, `coll_algo[:3]` was sliced from position 0, so
    the outermost slot was dead for DP and the TP dim's slot leaked in —
    exactly the opposite of both assertions below."""
    base = _algo_makespan(("ring", "ring", "ring", "ring"))
    outer = _algo_makespan(("ring", "ring", "ring", "dbt"))
    inner = _algo_makespan(("dbt", "ring", "ring", "ring"))
    # changing only the outermost (DP-occupied) dim's algorithm moves time
    assert outer != base
    # changing only the TP dim's algorithm leaves the DP collective alone
    assert inner == base
    # pinned post-fix values (SYSTEM_2_DEVICE, system_2 fabric)
    assert base == pytest.approx(9420.035714285714, rel=1e-9)
    assert outer == pytest.approx(9416.035714285714, rel=1e-9)


def test_pp_stage_models_remainder_layers(clear_dse_caches):
    """34 layers @ pp=4 must model a 9-layer (ceil) stage, not 8 (floor):
    the largest stage's compute, so PP never under-counts FLOPs."""
    spec = dataclasses.replace(SPEC, n_layers=34)
    # same (dp, sp, tp=16) in both, so per-layer op costs are identical and
    # only the stage slicing differs
    par4 = Parallelism(1024, dp=16, sp=1, pp=4)
    par1 = Parallelism(256, dp=16, sp=1, pp=1)
    tr4 = generate_trace(spec, par4, batch=64, seq=2048, mode="train")
    tr1 = generate_trace(spec, par1, batch=64, seq=2048, mode="train")
    n_stage = sum(op.name.endswith(".mixer.fwd") for op in tr4.ops)
    assert n_stage == math.ceil(34 / 4) == 9
    # de-bubbled stage compute x pp covers every layer (34 identical
    # layers: 9 * 4 = 36 modeled layer-slots >= 34, never fewer)
    f4 = sum(op.flops for op in tr4.ops
             if op.name.endswith(".mixer.fwd")) / tr4.meta["bubble"]
    f1 = sum(op.flops for op in tr1.ops if op.name.endswith(".mixer.fwd"))
    assert f4 * 4 >= f1
    assert f4 * 4 == pytest.approx(f1 * 36 / 34)


def test_simulator_repeat_and_delay_ops(clear_dse_caches):
    """`repeat` condenses k back-to-back executions into one op (k x the
    single duration); `delay` ops shift their dependents' start without
    occupying compute or comm resources."""
    one = Trace([Op(0, "c", "comp", [], flops=1e12, bytes=1e9)])
    rep = Trace([Op(0, "c", "comp", [], flops=1e12, bytes=1e9, repeat=5)])
    cfg = SystemConfig(network=system_2(), device=SYSTEM_2_DEVICE,
                       coll_algo=("ring",) * 4, chunks=2)
    par = Parallelism(1024, dp=64, sp=1, pp=1)
    t1 = simulate(one, cfg, par).makespan_us
    t5 = simulate(rep, cfg, par).makespan_us
    assert t5 == pytest.approx(5 * t1)

    delayed = Trace([Op(0, "rel", "delay", [], delay_us=1234.5),
                     Op(1, "c", "comp", [0], flops=1e12, bytes=1e9)])
    res = simulate(delayed, cfg, par, record_per_op=True)
    assert res.makespan_us == pytest.approx(1234.5 + t1)
    assert res.op_finish_us[0] == pytest.approx(1234.5)
    assert res.comm_busy_us == {}          # the timer is not communication
    assert res.compute_busy_us == pytest.approx(t1)


# ---------------------------------------------------------------------------
# (d) RequestStreamScenario: queueing, pipelined multi-wave traces,
#     streaming rewards
# ---------------------------------------------------------------------------

def _stream_scenario(**kw):
    kw.setdefault("n_requests", 16)
    kw.setdefault("seq", 2048)
    kw.setdefault("decode_tokens", 8)
    kw.setdefault("rate_rps", 16.0)
    kw.setdefault("max_batch", 8)
    return RequestStreamScenario(**kw)


# a known-valid design point on system2's stacks (prefill pool 896 NPUs)
_STREAM_CFG = dict(dp=8, sp=1, pp=1, weight_sharded=0, sched_policy="fifo",
                   coll_algo=("ring", "direct", "ring", "rhd"), chunks=2,
                   multidim_coll="baseline",
                   topology=("ring", "fc", "ring", "switch"),
                   npus_per_dim=(4, 8, 4, 8), bw_per_dim=(400, 200, 150, 100),
                   prefill_frac=0.875, decode_batch=4,
                   batch_window_ms=200.0, max_inflight=2)


def test_request_stream_wave_formation_golden():
    """Deterministic queueing golden: replayed 10ms inter-arrival gaps,
    max_batch=3 — waves close on fill or on window expiry."""
    sc = RequestStreamScenario(n_requests=6, arrival_gaps_ms=(10.0,),
                               max_batch=3)
    assert sc.arrivals_ms() == (10.0, 20.0, 30.0, 40.0, 50.0, 60.0)
    # wide window: waves fill to max_batch and release at the filling arrival
    assert sc.form_waves(100.0) == (((0, 1, 2), 30.0), ((3, 4, 5), 60.0))
    # 15ms window: pairs release at open+15
    assert sc.form_waves(15.0) == (((0, 1), 25.0), ((2, 3), 45.0),
                                   ((4, 5), 65.0))
    # no batching window: every request is its own wave, released on arrival
    assert sc.form_waves(0.0) == tuple(
        ((i,), 10.0 * (i + 1)) for i in range(6))


def test_request_stream_deterministic(clear_dse_caches):
    """Same scenario fields + config -> bit-identical Evaluation across
    fresh scenario/env instances (the Poisson arrivals are seeded)."""
    a = _env(_stream_scenario(), objective="goodput").evaluate_config(_STREAM_CFG)
    b = _env(_stream_scenario(), objective="goodput").evaluate_config(_STREAM_CFG)
    assert a.valid and b.valid
    assert (a.reward, a.latency_ms) == (b.reward, b.latency_ms)
    assert a.detail == b.detail


def test_request_stream_trace_is_pipelined_multiwave(clear_dse_caches):
    sc = _stream_scenario()
    env = _env(sc, objective="goodput")
    tr = sc.traces(env.context(_STREAM_CFG))["stream"]
    marks = tr.meta["wave_marks"]
    assert len(marks) >= 2                      # an actual request stream
    assert {op.pool for op in tr.ops} == {0, 1}  # both pools populated
    # one release delay and one KV xfer per admitted wave
    assert sum(op.kind == "delay" for op in tr.ops) == len(marks)
    assert sum(op.group == "xfer" for op in tr.ops) == len(marks)
    ev = env.evaluate_config(_STREAM_CFG)
    assert ev.valid
    d = ev.detail
    assert d["waves"] == len(marks)
    assert sum(d["wave_sizes"]) == sc.n_requests
    assert 0 < d["ttft_p50_ms"] <= d["ttft_p99_ms"]
    assert 0 < d["tpot_p50_ms"] <= d["tpot_p99_ms"]
    assert d["latency_p99_ms"] >= d["ttft_p99_ms"]


def test_request_stream_slo_gates_goodput(clear_dse_caches):
    """Goodput counts only requests meeting BOTH SLOs; impossible SLOs
    zero it while the latency percentiles are unchanged."""
    loose = _env(_stream_scenario(), objective="goodput") \
        .evaluate_config(_STREAM_CFG)
    tight = _env(_stream_scenario(ttft_slo_ms=1e-3, tpot_slo_ms=1e-3),
                 objective="goodput").evaluate_config(_STREAM_CFG)
    assert loose.valid and tight.valid
    assert loose.detail["goodput_rps"] > 0 and loose.reward > 0
    assert tight.detail["goodput_rps"] == 0 and tight.reward == 0
    assert tight.detail["ttft_p99_ms"] == loose.detail["ttft_p99_ms"]


def test_request_stream_batching_window_trades_ttft(clear_dse_caches):
    """A wider admission window queues requests longer: p50 TTFT must not
    shrink when the only change is a bigger batch_window_ms."""
    env = _env(_stream_scenario(), objective="goodput")
    narrow = env.evaluate_config(dict(_STREAM_CFG, batch_window_ms=0.0))
    wide = env.evaluate_config(dict(_STREAM_CFG, batch_window_ms=1000.0))
    assert narrow.valid and wide.valid
    assert wide.detail["waves"] <= narrow.detail["waves"]
    assert wide.detail["ttft_p50_ms"] >= narrow.detail["ttft_p50_ms"]


def test_request_stream_waves_respect_decode_capacity(clear_dse_caches):
    """An admitted wave never exceeds the decode pool's resident capacity
    (replicas * decode_batch), even when the scenario's max_batch is
    larger — otherwise the simulated decode would hold more requests than
    the memory gate checked."""
    sc = RequestStreamScenario(n_requests=48, seq=2048, decode_tokens=4,
                               rate_rps=1000.0, max_batch=32)
    env = CosmicEnv(spec=ARCHS["qwen2-1.5b"], n_npus=64,
                    device=SYSTEM_2_DEVICE, scenario=sc, objective="goodput")
    # n_dec = 8, decode_batch=2 -> replicas=8 (tp=1), capacity 16 < 32
    cfg = dict(_STREAM_CFG, dp=2, decode_batch=2, batch_window_ms=1000.0,
               max_inflight=2)
    ev = env.evaluate_config(cfg)
    assert ev.valid
    assert ev.detail["decode_replicas"] * 2 == 16
    assert max(ev.detail["wave_sizes"]) <= 16
    assert sum(ev.detail["wave_sizes"]) == sc.n_requests


def test_goodput_objective_requires_streaming_scenario():
    """Construction-time gate: streaming objectives need a scenario that
    resolves per-request metrics — not a KeyError deep inside a search."""
    _env(_stream_scenario(), objective="goodput")  # fine
    with pytest.raises(ValueError, match="streaming"):
        _env(TrainScenario(64, 2048, "serve"), objective="goodput")
    with pytest.raises(ValueError, match="streaming"):
        _env(objective="goodput")  # the default TrainScenario
    with pytest.raises(ValueError, match="unknown objective"):
        _env(objective="not-an-objective")


def test_env_needs_a_scenario():
    with pytest.raises(TypeError, match="scenario"):
        CosmicEnv(spec=SPEC, n_npus=1024, device=SYSTEM_2_DEVICE)


@pytest.mark.parametrize("seq", [2048, None])
def test_system_env_without_a_scenario_builds_a_train_scenario(
        seq, clear_dse_caches):
    """``system_env``'s batch/seq fallback is the same env as one handed
    the ``TrainScenario`` outright: same store signature, same rewards."""
    from repro.core.systems import system_env

    built = system_env("gpt3-13b", "system2", batch=64, seq=seq)
    given = _env(TrainScenario(64, seq or SPEC.max_seq))
    assert built._store_sig() == given._store_sig()
    cfgs = _sample_configs(paper_psa(1024), 8, seed=5)
    got, want = built.step_batch(cfgs), given.step_batch(cfgs)
    assert [(e.reward, e.latency_ms, e.valid) for e in got] == \
        [(e.reward, e.latency_ms, e.valid) for e in want]


def test_pipelined_multiwave_beats_analytic_composition(clear_dse_caches):
    """The acceptance point: on a multi-wave load the pipelined multi-wave
    disagg trace (wave k+1 prefill overlapping wave k decode) must beat
    the analytic single-wave composition."""
    spec = ARCHS["qwen2-1.5b"]
    cfg = dict(_STREAM_CFG, decode_batch=2)
    for k in ("batch_window_ms", "max_inflight"):
        cfg.pop(k)
    evs = {}
    for pipelined in (True, False):
        sc = DisaggServeScenario(512, 2048, 64, pipelined=pipelined)
        env = CosmicEnv(spec=spec, n_npus=1024, device=SYSTEM_2_DEVICE,
                        scenario=sc, objective="latency")
        evs[pipelined] = env.evaluate_config(cfg)
    assert evs[True].valid and evs[False].valid
    assert evs[True].detail["waves"] >= 2
    assert evs[True].latency_ms < evs[False].latency_ms


# ---------------------------------------------------------------------------
# (e) MultiTenantScenario: disjoint partitions, invalid gates to 0
# ---------------------------------------------------------------------------

def test_multi_tenant_partitions_disjoint_and_gated(clear_dse_caches):
    sc = MultiTenantScenario(tenants=_tenants())
    env = _env(sc)
    pset = scenario_psa(paper_psa(1024), sc, 1024)
    n_valid = 0
    for cfg in _sample_configs(pset, 15, seed=5):
        ev = env.evaluate_config(cfg)
        assert sum(cfg["tenant_npus"]) <= 1024  # sampler respects sum_le
        if not ev.valid:
            assert ev.reward == 0.0
            continue
        n_valid += 1
        ranges = [tuple(t["range"]) for t in ev.detail["tenants"].values()]
        for i, (lo_i, hi_i) in enumerate(ranges):
            for lo_j, hi_j in ranges[i + 1:]:
                assert hi_i <= lo_j or hi_j <= lo_i, \
                    f"partitions share NPUs: {ranges}"
        assert 0.0 <= ev.reward <= 1.0
    assert n_valid, "no valid multi-tenant configs sampled"
    # oversubscription gates to reward 0 even if a repaired config slips past
    base = _sample_configs(pset, 1, seed=6)[0]
    over = dict(base, tenant_npus=(1024, 1024))
    ev = env.evaluate_config(over)
    assert not ev.valid and ev.reward == 0.0


def test_partition_cluster_heterogeneous_devices():
    from repro.core.compute import SYSTEM_3_DEVICE

    net = system_2()
    cluster = partition_cluster(net, (512, 256),
                                (SYSTEM_2_DEVICE, SYSTEM_3_DEVICE))
    a, b = cluster.partitions
    assert a.npu_range() == (0, 512) and b.npu_range() == (512, 768)
    assert b.device.name == "system3-h100"
    assert sub_network(net, 512).n_npus == 512
    with pytest.raises(ValueError):
        partition_cluster(net, (1024, 512), (SYSTEM_2_DEVICE,) * 2)


# ---------------------------------------------------------------------------
# (f) step_batch + process pool works with every scenario type
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("make_scenario", [
    lambda: TrainScenario(1024, 2048),
    lambda: _disagg_scenario(),
    lambda: _stream_scenario(),
    lambda: MultiTenantScenario(tenants=_tenants()),
], ids=["train", "disagg", "request-stream", "multi-tenant"])
def test_step_batch_and_pool_per_scenario(make_scenario, clear_dse_caches):
    sc = make_scenario()
    assert isinstance(sc, Scenario)  # structural protocol check
    pset = scenario_psa(paper_psa(1024), sc, 1024)
    cfgs = _sample_configs(pset, 6, seed=11)
    serial_env = _env(make_scenario())
    serial = [serial_env.step(c) for c in cfgs]
    with _env(make_scenario()) as pool_env:
        pooled = pool_env.step_batch(cfgs, workers=2)
    for a, b in zip(pooled, serial):
        assert (a.reward, a.latency_ms, a.valid) == \
            (b.reward, b.latency_ms, b.valid)
    assert [r.config for r in pool_env.history] == cfgs


def test_sum_le_repair_respects_fixed_slots():
    from repro.core.psa import Constraint, Parameter, ParameterSet

    pset = ParameterSet(
        [Parameter("a", "scenario", (128, 256, 512, 1024)),
         Parameter("b", "scenario", (128, 256, 512, 1024))],
        [Constraint("sum_le", ("a", "b"), 1024)],
        fixed={"a": 768})
    space = DesignSpace(pset)
    rng = np.random.default_rng(0)
    for _ in range(10):
        cfg = space.sample(rng)
        assert cfg["a"] == 768 and cfg["a"] + cfg["b"] <= 1024


# ---------------------------------------------------------------------------
# shared cross-search eval store
# ---------------------------------------------------------------------------

def test_shared_eval_store_dedupes_across_envs(clear_dse_caches):
    store: dict = {}
    cfgs = _sample_configs(paper_psa(1024), 5, seed=13)
    env_a = _env(eval_store=store)
    first = env_a.step_batch(cfgs)
    assert env_a.store_misses == len(store) > 0
    env_b = _env(eval_store=store)
    second = env_b.step_batch(cfgs)
    assert env_b.store_misses == 0
    assert env_b.store_hits == len({tuple(sorted(c.items())) for c in cfgs})
    for a, b in zip(first, second):
        assert a is b  # the stored Evaluation instance is shared
    # a different env signature must not collide in the same store
    env_c = _env(eval_store=store, batch=512)
    env_c.step(cfgs[0])
    assert env_c.store_hits == 0 and env_c.store_misses == 1


# ---------------------------------------------------------------------------
# scenario registry rejects unknown / typo'd parameter keys
# ---------------------------------------------------------------------------

def test_build_scenario_rejects_unknown_params():
    from repro.core.scenario import build_scenario

    with pytest.raises(ValueError) as ei:
        build_scenario("request-stream", {"n_requests": 8, "rate_rsp": 9.0})
    # the error names the typo AND the valid keys
    assert "rate_rsp" in str(ei.value) and "rate_rps" in str(ei.value)


def test_build_multi_tenant_rejects_unknown_tenant_keys():
    from repro.core.scenario import build_scenario

    with pytest.raises(ValueError) as ei:
        build_scenario("multi-tenant", {"tenants": [
            {"name": "t0", "arch": "qwen2-1.5b", "batch": 64, "seq": 512,
             "slo": 100.0}]})       # typo: the field is slo_ms
    msg = str(ei.value)
    assert "'slo'" in msg and "slo_ms" in msg and "t0" in msg


def test_build_multi_tenant_still_accepts_known_keys():
    from repro.core.scenario import build_scenario

    sc = build_scenario("multi-tenant", {"tenants": [
        {"name": "t0", "arch": "qwen2-1.5b", "batch": 64, "seq": 512,
         "phase": "serve", "slo_ms": 100.0, "decode_tokens": 8}]})
    assert sc.tenants[0].slo_ms == 100.0
