"""Batched DSE evaluation engine: batched-vs-sequential search equivalence,
bit-identical memoization layers, and process-pool consistency."""
from __future__ import annotations


import numpy as np
import pytest

from repro.configs import ARCHS
from repro.core import cache
from repro.core.agents import make_agent
from repro.core.collectives import (_multidim_collective_time_impl,
                                    multidim_collective_time_us)
from repro.core.compute import SYSTEM_2_DEVICE
from repro.core.dse import run_search
from repro.core.env import CosmicEnv
from repro.core.psa import paper_psa
from repro.core.scenario import TrainScenario
from repro.core.space import DesignSpace
from repro.core.topology import build_network, system_2
from repro.core.workload import Parallelism, _generate_trace_impl, generate_trace


def _env():
    return CosmicEnv(spec=ARCHS["gpt3-13b"], n_npus=1024, device=SYSTEM_2_DEVICE,
                     scenario=TrainScenario(1024, 2048))


def _sequential_reference(kind: str, steps: int, seed: int):
    """The seed repo's propose/step/observe loop, via the scalar agent API."""
    space = DesignSpace(paper_psa(1024))
    agent = make_agent(kind, space, seed=seed)
    env = _env()
    best, best_step = -np.inf, 0
    curve = []
    for i in range(steps):
        cfg = agent.propose()
        ev = env.step(cfg)
        agent.observe(cfg, ev.reward)
        if ev.reward > best:
            best, best_step = ev.reward, i
        curve.append(best)
    return best, best_step, curve


@pytest.mark.parametrize("kind", ["ga", "rw", "aco", "bo"])
def test_batched_driver_batch1_equals_sequential(kind, clear_dse_caches):
    """batch_size=1 must reproduce the sequential loop exactly: same RNG
    stream, same rewards, same convergence bookkeeping."""
    steps = 40 if kind != "bo" else 24
    best, best_step, curve = _sequential_reference(kind, steps, seed=0)
    res = run_search(paper_psa(1024), _env(), kind, steps=steps, seed=0,
                     batch_size=1)
    assert res.best_reward == best
    assert res.steps_to_peak == best_step
    assert res.reward_curve == curve


def test_random_walk_any_batch_matches_sequential(clear_dse_caches):
    """RW proposals are history-free, so the batched search coincides with
    the sequential one at EVERY step for any batch size."""
    steps = 48
    best, best_step, curve = _sequential_reference("rw", steps, seed=3)
    res = run_search(paper_psa(1024), _env(), "rw", steps=steps, seed=3,
                     batch_size=8)
    assert res.best_reward == best
    assert res.steps_to_peak == best_step
    assert res.reward_curve == curve


def test_ga_generation_batch_reaches_valid_optimum(clear_dse_caches):
    """Whole-generation GA is a different (but valid) trajectory: it must
    still find a positive-reward design and keep its bookkeeping coherent."""
    res = run_search(paper_psa(1024), _env(), "ga", steps=64, seed=0,
                     batch_size=16)
    assert res.steps == 64 and len(res.reward_curve) == 64
    assert res.best_reward > 0 and res.best_config is not None
    assert res.reward_curve[res.steps_to_peak] == res.best_reward


def test_trace_cache_bit_identical_and_interned(clear_dse_caches):
    spec = ARCHS["gpt3-13b"]
    par = Parallelism(1024, dp=8, sp=2, pp=2, weight_sharded=True)
    for mode in ("train", "inference", "decode"):
        cached = generate_trace(spec, par, batch=512, seq=2048, mode=mode)
        raw = _generate_trace_impl(spec, par, 512, 2048, mode, None)
        assert cached.meta == raw.meta
        assert len(cached.ops) == len(raw.ops)
        for a, b in zip(cached.ops, raw.ops):
            assert (a.uid, a.name, a.kind, a.deps) == (b.uid, b.name, b.kind, b.deps)
            assert (a.flops, a.bytes) == (b.flops, b.bytes)
            assert (a.coll, a.size_bytes, a.group) == (b.coll, b.size_bytes, b.group)
        # repeated design points return the interned trace: near-free
        assert generate_trace(spec, par, batch=512, seq=2048, mode=mode) is cached


def test_collective_cache_bit_identical(clear_dse_caches):
    net = system_2()
    small = build_network(("ring", "fc"), (4, 8), (200.0, 100.0))
    for n, algos in ((net, ("ring", "direct", "rhd", "dbt")),
                     (small, ("dbt", "direct"))):
        for kind in ("all_reduce", "all_gather", "reduce_scatter", "all_to_all"):
            for mode in ("baseline", "blueconnect"):
                for chunks in (1, 4):
                    got = multidim_collective_time_us(kind, 3.7e8, n, algos,
                                                      chunks=chunks, mode=mode)
                    want = _multidim_collective_time_impl(kind, 3.7e8, n,
                                                          tuple(algos), chunks,
                                                          mode, None)
                    assert got == want


def test_disabling_caches_matches_cached_results(clear_dse_caches):
    env_c, env_u = _env(), _env()
    space = DesignSpace(paper_psa(1024))
    cfgs = [space.sample(np.random.default_rng(7)) for _ in range(6)]
    cached = [env_c.step(c) for c in cfgs]
    cache.set_caches_enabled(False)
    try:
        uncached = [env_u.step(c) for c in cfgs]
    finally:
        cache.set_caches_enabled(True)
    for a, b in zip(cached, uncached):
        assert (a.reward, a.latency_ms, a.valid) == (b.reward, b.latency_ms, b.valid)


def test_eval_memo_dedupes_repeated_points(clear_dse_caches):
    env = _env()
    space = DesignSpace(paper_psa(1024))
    cfg = space.sample(np.random.default_rng(1))
    first = env.step(cfg)
    again = env.step(dict(cfg))  # equal-valued copy must hit the memo
    assert again is first
    assert len(env.history) == 2 and env.history[1].reward == first.reward


def test_step_batch_process_pool_matches_serial(clear_dse_caches):
    space = DesignSpace(paper_psa(1024))
    rng = np.random.default_rng(11)
    cfgs = [space.sample(rng) for _ in range(8)]
    serial_env = _env()
    serial = [serial_env.step(c) for c in cfgs]
    with _env() as pool_env:
        pooled = pool_env.step_batch(cfgs, workers=2)
    assert len(pooled) == len(serial)
    for a, b in zip(pooled, serial):
        assert (a.reward, a.latency_ms, a.valid) == (b.reward, b.latency_ms, b.valid)
    # history recorded in input order
    assert [r.config for r in pool_env.history] == cfgs


def test_jax_backend_never_starts_a_worker(clear_dse_caches):
    """One process per accelerator: a jax-backend env asked for workers
    evaluates in its own process and never creates the pool."""
    space = DesignSpace(paper_psa(1024))
    rng = np.random.default_rng(5)
    cfgs = [space.sample(rng) for _ in range(4)]
    serial = [_env().evaluate_config(c) for c in cfgs]
    with CosmicEnv(spec=ARCHS["gpt3-13b"], n_npus=1024,
                   device=SYSTEM_2_DEVICE,
                   scenario=TrainScenario(1024, 2048),
                   backend="jax") as env:
        got = env.step_batch(cfgs, workers=2)
        assert env._executor is None
    for a, b in zip(got, serial):
        assert a.valid == b.valid
        if b.valid:
            assert abs(a.latency_ms - b.latency_ms) <= 1e-9 * b.latency_ms


@pytest.mark.slow
def test_batched_engine_throughput(clear_dse_caches):
    """Caching + batching must beat the uncached sequential loop (the seed
    loop proxy) on the acceptance workload.  The in-process floor is
    conservative (the uncached engine is itself ~2x faster than the seed);
    see ROADMAP.md for the measured 3x-vs-seed numbers at 500 steps."""
    import time

    ratios = []
    for _ in range(3):  # shared-CPU noise: pass if any attempt clears the bar
        try:
            cache.set_caches_enabled(False)
            t0 = time.time()
            run_search(paper_psa(1024), _env(), "ga", steps=500, seed=0)
            t_seq = time.time() - t0
            ref = run_search(paper_psa(1024), _env(), "ga", steps=500, seed=0,
                             batch_size=32)
        finally:
            cache.set_caches_enabled(True)
        cache.clear_all_caches()
        t0 = time.time()
        bat = run_search(paper_psa(1024), _env(), "ga", steps=500, seed=0,
                         batch_size=32)
        t_bat = time.time() - t0
        # caching only changes speed: the batched trajectory is bit-identical
        assert bat.reward_curve == ref.reward_curve
        ratios.append(t_seq / t_bat)
        if ratios[-1] > 1.2:
            break
    assert max(ratios) > 1.2, \
        f"batched only x{max(ratios):.2f} over uncached across {len(ratios)} attempts"
