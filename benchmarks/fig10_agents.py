"""Fig. 9/10: agent comparison — RW/GA/ACO/BO on full-stack GPT3-175B DSE:
convergence speed (steps to peak), final reward, and distinctness of the
discovered configurations.  The whole comparison is ONE declarative study
(four agents, one seed, shared eval_store): the campaign runs the batched
engine in its sequential mode (batch_size=1: per-point feedback, like the
paper's Fig. 10, so steps_to_peak is comparable across agents) but still
rides the trace/collective caches; the throughput row measures the
population path (batch 32) against the uncached sequential loop (the seed
baseline)."""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import STEPS, emit, make_env, make_pset
from repro.core import cache
from repro.core.dse import run_search
from repro.core.study import StudySpec, run_study

AGENTS = ("rw", "ga", "aco", "bo")


def dse_throughput(steps: int = 500, arch: str = "gpt3-13b") -> tuple[float, float]:
    """(uncached sequential, batched+cached) points/sec on one GA search —
    the acceptance measurement for the batched engine (uncached sequential
    is the in-process proxy for the seed evaluation loop)."""
    was_enabled = cache.caches_enabled()
    try:
        cache.set_caches_enabled(False)
        t0 = time.time()
        run_search(make_pset("system2"), make_env(arch, "system2"), "ga",
                   steps=steps, seed=0)
        seq = steps / (time.time() - t0)
        cache.set_caches_enabled(True)
        cache.clear_all_caches()
        t0 = time.time()
        run_search(make_pset("system2"), make_env(arch, "system2"), "ga",
                   steps=steps, seed=0, batch_size=32)
        batched = steps / (time.time() - t0)
    finally:
        cache.set_caches_enabled(was_enabled)
    return seq, batched


BACKEND_ROW_ORDER = ("reference", "jax")


def backend_population(points: int = 32, n_requests: int = 256, seed: int = 0):
    """The backend-throughput workload: ``n_requests`` Poisson requests of
    qwen2-1.5b through disaggregated pools (256 give a ~26k-op pipelined
    multi-wave trace) and a seeded ``points``-member population of
    collective/network stacks.  The trace-shaping knobs are pinned, so the
    whole population shares ONE scheduling plan.  Returns (scenario, cfgs)."""
    from repro.core.scenario import RequestStreamScenario

    scenario = RequestStreamScenario(n_requests=n_requests, seq=2048,
                                     decode_tokens=64, rate_rps=32.0,
                                     seed=seed)
    pinned = dict(dp=8, sp=1, pp=1, weight_sharded=0,
                  topology=("ring", "fc", "ring", "switch"),
                  npus_per_dim=(4, 8, 4, 8),
                  prefill_frac=0.5, decode_batch=8, batch_window_ms=50.0,
                  max_inflight=2)
    rng = np.random.default_rng(seed)
    algos = ("ring", "direct", "rhd", "dbt")
    cfgs = []
    for _ in range(points):
        cfgs.append(dict(
            pinned,
            coll_algo=tuple(rng.choice(algos) for _ in range(4)),
            chunks=int(rng.choice((2, 4, 8, 16))),
            sched_policy=str(rng.choice(("fifo", "lifo"))),
            multidim_coll=str(rng.choice(("baseline", "blueconnect"))),
            bw_per_dim=tuple(int(b) for b in
                             rng.choice(range(50, 501, 50), size=4))))
    return scenario, cfgs


def backend_throughput(points: int = 32, n_requests: int = 256,
                       repeats: int = 3) -> "list[dict] | None":
    """Points/sec per simulation backend (reference / jax) evaluating one
    agent population of collective/network stacks over a LARGE pipelined
    request-stream trace (``backend_population``) — the acceptance
    measurement for the backend API and the fused-evaluation path.  Both
    rows run through ``CosmicEnv.step_batch`` (the batched engine); the
    ``jax`` row swaps the per-point heapq event loop for one
    shared-plan ``simulate_batch`` call that prices all durations and
    sweeps the schedule together.  Each row carries the backend's pack vs
    compiled-call wall split (``last_timings``) so the bottleneck claim
    stays measurable.  None when jax is unavailable."""
    from repro.core.backends import backend_available, get_backend

    if not backend_available("jax"):
        return None
    scenario, cfgs = backend_population(points, n_requests)
    rows = []
    for backend in BACKEND_ROW_ORDER:
        env = make_env("qwen2-1.5b", "system2", scenario=scenario,
                       objective="goodput", backend=backend)
        # warm trace caches + compile the sweep at the population shape
        env.step_batch(cfgs)
        best = float("inf")
        for _ in range(1 if backend == "reference" else repeats):
            env.clear_memo()
            t0 = time.time()
            env.step_batch(cfgs)
            best = min(best, time.time() - t0)
        timings = getattr(get_backend(backend), "last_timings", {})
        rows.append({
            "backend": backend, "points": points, "n_requests": n_requests,
            "pts_per_s": len(cfgs) / best, "ms_per_gen": best * 1e3,
            "durations_ms": timings.get("durations_s", float("nan")) * 1e3,
            "sweep_ms": timings.get("sweep_s", float("nan")) * 1e3,
        })
    return rows


def verify_overhead_rows(n_requests: int = 256) -> list[tuple]:
    """Static-verification cost vs a reference-backend evaluation on the
    acceptance trace: the ISSUE-8 bound is overhead < 5%.  ``verify_ms``
    re-derives the structural verdict + contextual checks each rep (the
    per-evaluation steady state — the plan-level array views, built once
    with the plan, stay amortized exactly like the plan itself);
    ``memo_us`` is the memoized-report path every later evaluation of the
    same trace pays.  Works without jax (reference backend only)."""
    from repro.core.analysis import verify_trace
    from repro.core.scenario import RequestStreamScenario
    from repro.core.simulator import simulate

    scenario = RequestStreamScenario(n_requests=n_requests, seq=2048,
                                     decode_tokens=64, rate_rps=32.0, seed=0)
    env = make_env("qwen2-1.5b", "system2", scenario=scenario,
                   objective="goodput", backend="reference")
    cfg = dict(dp=8, sp=1, pp=1, weight_sharded=0,
               topology=("ring", "fc", "ring", "switch"),
               npus_per_dim=(4, 8, 4, 8), bw_per_dim=(100, 200, 300, 400),
               coll_algo=("ring", "direct", "rhd", "dbt"), chunks=4,
               sched_policy="fifo", multidim_coll="baseline",
               prefill_frac=0.5, decode_batch=8, batch_window_ms=50.0,
               max_inflight=2)
    job = env.scenario.sim_job(env.context(cfg))
    call = job.calls[0]
    simulate(call.trace, call.cfg, call.par, pools=call.pools)  # warm plan
    verify_trace(call.trace, call.cfg, call.par, call.pools)
    sim_s = float("inf")
    for _ in range(3):
        t0 = time.time()
        simulate(call.trace, call.cfg, call.par, pools=call.pools)
        sim_s = min(sim_s, time.time() - t0)
    ver_s = float("inf")
    for _ in range(5):
        if hasattr(call.trace, "_verify_report"):
            del call.trace._verify_report
        t0 = time.time()
        verify_trace(call.trace, call.cfg, call.par, call.pools)
        ver_s = min(ver_s, time.time() - t0)
    t0 = time.time()
    for _ in range(100):
        verify_trace(call.trace, call.cfg, call.par, call.pools)
    memo_us = (time.time() - t0) / 100 * 1e6
    return [("verify_overhead", ver_s * 1e6,
             f"verify_ms={ver_s * 1e3:.3f} simulate_ms={sim_s * 1e3:.2f} "
             f"overhead=x{ver_s / max(sim_s, 1e-12):.4f} "
             f"memo_us={memo_us:.1f} n_ops={len(call.trace.ops)}")]


def backend_rows(points: int = 32, n_requests: int = 256) -> list[tuple]:
    """The ``backend_throughput`` measurement as emit()-able benchmark rows
    (one per backend plus a speedup summary) — also the payload of the
    ``BENCH_backends.json`` perf-trajectory artifact.  The static-analysis
    overhead row rides along (it needs only the reference backend, so it
    emits even where jax is unavailable)."""
    bt = backend_throughput(points=points, n_requests=n_requests)
    if bt is None:
        return [("backend_throughput", 0.0, "jax_unavailable"),
                *verify_overhead_rows(n_requests=n_requests)]
    rows = []
    for r in bt:
        rows.append((f"backend_throughput[{r['backend']}]", 0.0,
                     f"pts_per_s={r['pts_per_s']:.1f} "
                     f"ms_per_gen={r['ms_per_gen']:.1f} "
                     f"durations_ms={r['durations_ms']:.1f} "
                     f"sweep_ms={r['sweep_ms']:.1f} "
                     f"points={r['points']} n_requests={r['n_requests']}"))
    by = {r["backend"]: r["pts_per_s"] for r in bt}
    rows.append(("backend_throughput", 0.0,
                 f"ref_pts_per_s={by['reference']:.1f} "
                 f"fused_pts_per_s={by['jax']:.1f} "
                 f"fused_vs_ref=x{by['jax'] / max(by['reference'], 1e-9):.2f}"))
    rows.extend(verify_overhead_rows(n_requests=n_requests))
    return rows


def agents_study(steps: int) -> StudySpec:
    """All four agents over the same space as one campaign — any design
    point one agent visited is free for the rest (shared eval store).
    BO's cubic GP cost caps its per-cell budget."""
    return StudySpec(
        name="fig10-agents", arch="gpt3-175b", system="system2",
        scenario="train", objective="perf_per_bw",
        agents=tuple({"kind": a, "steps": min(steps, 200)} if a == "bo"
                     else a for a in AGENTS),
        seeds=(0,), steps=steps, batch_size=1)


def run(steps: int | None = None) -> list[tuple]:
    steps = steps or max(STEPS, 300)
    rows = []
    study = run_study(agents_study(steps))
    for cell in study.outcomes:
        res = cell.result
        rows.append((f"fig10_{cell.agent}", res.wall_s * 1e6 / res.steps,
                     f"best={res.best_reward:.3e} steps_to_peak={res.steps_to_peak} "
                     f"invalid_rate={res.invalid_rate:.2f} "
                     f"points_per_s={res.points_per_s:.0f}"))
    lookups = study.store_hits + study.store_misses
    rows.append(("fig10_eval_store", 0.0,
                 f"hits={study.store_hits} misses={study.store_misses} "
                 f"hit_rate={study.store_hits / max(lookups, 1):.2f} "
                 f"distinct_points={study.distinct_points}"))
    # Fig 9: distinct high-performing configs across agents
    cfgs = [tuple(sorted((k, str(v)) for k, v in o.result.best_config.items()))
            for o in study.outcomes if o.result.best_config]
    rows.append(("fig9_distinct_optima", 0.0,
                 f"distinct={len(set(cfgs))}_of_{len(cfgs)}"))
    seq, batched = dse_throughput(steps=steps)  # 500 via BENCH_STEPS=500
    rows.append(("dse_throughput", 0.0,
                 f"seq_pts_per_s={seq:.0f} batched_pts_per_s={batched:.0f} "
                 f"speedup=x{batched / max(seq, 1e-9):.2f}"))
    rows.extend(backend_rows())
    return rows


if __name__ == "__main__":
    emit(run())
