"""Vectorized-evaluation tests: the array collective evaluator against the
scalar oracle over the full model grid, the batched whole-population
duration pass against the scalar per-call pass (bit-identical), the
sub-network-carving memoization, the device busy sums' row table, and the
jax backend against the reference event loop."""
from __future__ import annotations

import numpy as np
import pytest

from repro.configs import ARCHS
from repro.core import cache
from repro.core.backends.base import SimCall
from repro.core.collectives import (ALGO_IDS, ALGOS, COLL_KIND_IDS,
                                    COLL_KINDS, TOPO_KIND_IDS,
                                    collective_time_us, collective_time_vec,
                                    multidim_collective_time_us,
                                    multidim_collective_time_vec)
from repro.core.compute import SYSTEM_2_DEVICE
from repro.core.scenario import RequestStreamScenario
from repro.core.simulator import (SystemConfig, _group_net_cached,
                                  _pool_group_dims_cached, group_dims,
                                  plan_duration_tables, plan_durations,
                                  plan_durations_batch, pool_group_dims,
                                  _sim_plan)
from repro.core.systems import system_env
from repro.core.topology import (TOPO_KINDS, Network, TopoDim, carve_dims,
                                 system_2)
from repro.core.workload import Parallelism, generate_trace

RTOL = 1e-9


def _rel(a, b):
    return np.abs(a - b) / np.maximum(np.abs(b), 1e-12)


# ---------------------------------------------------------------------------
# single-dim evaluator: full kind x algo x topo x chunks grid, random dims
# ---------------------------------------------------------------------------

def test_collective_time_vec_full_grid_parity():
    rng = np.random.default_rng(0)
    scalar, kind_id, size, n, bw, lat, topo_id, algo_id, chunks = \
        [], [], [], [], [], [], [], [], []
    for kind in COLL_KINDS:
        for algo in ALGOS:
            for topo in TOPO_KINDS:
                for c in (1, 2, 7, 16):
                    for _ in range(3):
                        npus = int(rng.choice((2, 3, 4, 5, 7, 8, 16, 27, 64)))
                        b = float(rng.uniform(10.0, 900.0))
                        l = float(rng.uniform(0.05, 2.0))
                        sz = float(rng.uniform(1.0, 1e9))
                        dim = TopoDim(topo, npus, b, l)
                        scalar.append(collective_time_us(kind, sz, dim,
                                                         algo, c))
                        kind_id.append(COLL_KIND_IDS[kind])
                        size.append(sz)
                        n.append(npus)
                        bw.append(b)
                        lat.append(l)
                        topo_id.append(TOPO_KIND_IDS[topo])
                        algo_id.append(ALGO_IDS[algo])
                        chunks.append(c)
    got = collective_time_vec(np.array(kind_id), np.array(size), np.array(n),
                              np.array(bw), np.array(lat), np.array(topo_id),
                              np.array(algo_id), np.array(chunks))
    assert got.shape == (len(scalar),)
    assert np.all(_rel(got, np.array(scalar)) < RTOL)


def test_collective_time_vec_degenerate_entries_are_exact_zero():
    """npus <= 1 (padded slots) and size <= 0 price to exactly 0.0 — the
    padding contract the packed class tables rely on."""
    got = collective_time_vec(
        np.array([0, 1, 2]), np.array([1e6, 0.0, 1e6]),
        np.array([1.0, 8.0, 1.0]), np.array([100.0] * 3),
        np.array([0.5] * 3), np.array([0, 1, 2]), np.array([0, 1, 2]),
        np.array([2, 2, 2]))
    assert np.array_equal(got, np.zeros(3))


# ---------------------------------------------------------------------------
# multi-dim evaluator: random fabrics, both modes, partial carves,
# residual virtual dims
# ---------------------------------------------------------------------------

def _pack_dims(carved, coll_algo):
    """Pad one carved-dims row the way ``_pack_class_tables`` does,
    resolving per-dim algorithms against source physical dims."""
    D = max(len(carved), 1)
    npus = np.ones(D)
    bw = np.ones(D)
    lat = np.zeros(D)
    topo = np.zeros(D, dtype=np.int32)
    algo = np.zeros(D, dtype=np.int32)
    for j, (src, d) in enumerate(carved):
        npus[j] = d.npus
        bw[j] = d.bw
        lat[j] = d.latency_us
        topo[j] = TOPO_KIND_IDS[d.kind]
        algo[j] = ALGO_IDS[coll_algo[src]]
    return npus, bw, lat, topo, algo


def test_integer_bit_length_equals_frexp_exponent():
    """The jnp ceil-log2 path counts bits in int32 (the TPU cannot lower
    ``frexp`` on f64); it must equal numpy's frexp exponent everywhere."""
    import jax.numpy as jnp

    from repro.core.collectives import _bit_length_i32

    n = np.arange(1, 2 ** 20 + 1, dtype=np.int64)
    _, want = np.frexp(n.astype(np.float64))
    got = np.asarray(_bit_length_i32(jnp.asarray(n, dtype=jnp.int32)))
    np.testing.assert_array_equal(got, want)


def test_multidim_vec_parity_random_sweep():
    """Randomized full sweep vs the scalar oracle: every collective kind,
    per-dim algo mix, both decomposition modes, chunk grid, over random
    carvings (gcd-partial dims AND residual virtual dims)."""
    import math

    rng = np.random.default_rng(7)
    rows, scalars = [], []
    n_residual = n_partial = 0
    for trial in range(200):
        ndim = int(rng.integers(2, 5))
        kinds = [str(rng.choice(TOPO_KINDS)) for _ in range(ndim)]
        npus = [int(rng.choice((2, 4, 8))) for _ in range(ndim)]
        bws = [float(rng.uniform(25.0, 900.0)) for _ in range(ndim)]
        lats = [float(rng.uniform(0.1, 1.5)) for _ in range(ndim)]
        net = Network(tuple(TopoDim(k, n, b, l)
                            for k, n, b, l in zip(kinds, npus, bws, lats)))
        coll_algo = tuple(str(rng.choice(ALGOS)) for _ in range(ndim))
        # group sizes with non-power-of-two factors exercise the residual
        # virtual dim (a factor no physical dim covers) and partial carves
        need = int(rng.choice((2, 3, 4, 6, 8, 12, 24, 48, 96)))
        carved = carve_dims(net.dims, [d.npus for d in net.dims], need)
        if not carved:
            continue
        rem = need
        for i in range(ndim):  # residual factor no physical dim covers?
            if rem <= 1:
                break
            g = math.gcd(rem, npus[i])
            rem //= g
        n_residual += rem > 1
        n_partial += any(d.npus < net.dims[src].npus for src, d in carved)
        kind = str(rng.choice(COLL_KINDS))
        chunks = int(rng.choice((1, 2, 4, 16)))
        mode = str(rng.choice(("baseline", "blueconnect")))
        size = float(rng.uniform(1e3, 1e9))
        sub = Network(tuple(d for _, d in carved))
        algos = tuple(coll_algo[src] for src, _ in carved)
        scalars.append(multidim_collective_time_us(kind, size, sub, algos,
                                                   chunks=chunks, mode=mode))
        rows.append((_pack_dims(carved, coll_algo), kind, size, chunks, mode))
    assert len(rows) >= 150
    # the sweep must actually exercise both carving edge cases
    assert n_residual >= 10 and n_partial >= 10
    D = max(len(r[0][0]) for r in rows)
    P = len(rows)
    npus = np.ones((P, D))
    bw = np.ones((P, D))
    lat = np.zeros((P, D))
    topo = np.zeros((P, D), dtype=np.int32)
    algo = np.zeros((P, D), dtype=np.int32)
    kind_id = np.zeros(P, dtype=np.int32)
    size = np.zeros(P)
    chunks = np.zeros(P)
    blue = np.zeros(P, dtype=bool)
    for i, ((n_, b_, l_, t_, a_), kind, sz, c, mode) in enumerate(rows):
        w = len(n_)
        npus[i, :w], bw[i, :w], lat[i, :w] = n_, b_, l_
        topo[i, :w], algo[i, :w] = t_, a_
        kind_id[i] = COLL_KIND_IDS[kind]
        size[i] = sz
        chunks[i] = c
        blue[i] = mode == "blueconnect"
    got = multidim_collective_time_vec(kind_id, size, npus, bw, lat, topo,
                                       algo, chunks, blue)
    assert np.all(_rel(got, np.array(scalars)) < RTOL)


def test_multidim_vec_residual_virtual_dim_and_single_dim():
    """Pinned structural cases: a residual factor becomes a virtual dim at
    the outermost tier (and is priced, not free); a single active dim
    bypasses the cross-dim pipelining entirely."""
    net = Network((TopoDim("ring", 4, 200.0, 0.5),
                   TopoDim("switch", 8, 50.0, 1.0)))
    carved = carve_dims(net.dims, [4, 8], 96)  # 96 = 4*8*3 -> residual 3
    assert [d.npus for _, d in carved] == [4, 8, 3]
    assert carved[-1] == (1, TopoDim("switch", 3, 50.0, 1.0))
    coll_algo = ("ring", "rhd")
    for kind in COLL_KINDS:
        for mode in ("baseline", "blueconnect"):
            sub = Network(tuple(d for _, d in carved))
            algos = tuple(coll_algo[src] for src, _ in carved)
            want = multidim_collective_time_us(kind, 1e7, sub, algos,
                                               chunks=4, mode=mode)
            n_, b_, l_, t_, a_ = _pack_dims(carved, coll_algo)
            got = multidim_collective_time_vec(
                np.array([COLL_KIND_IDS[kind]]), np.array([1e7]),
                n_[None], b_[None], l_[None], t_[None], a_[None],
                np.array([4.0]), np.array([mode == "blueconnect"]))
            assert float(_rel(got[0], want)) < RTOL, (kind, mode)
            assert want > 0.0
    # one active dim (others padded): == the bare single-dim collective
    one = _pack_dims(carved[:1], coll_algo)
    pad = [np.concatenate([x, np.ones(2) if x.dtype == np.float64 and i < 2
                           else np.zeros(2, x.dtype)])
           for i, x in enumerate(one)]
    got = multidim_collective_time_vec(
        np.array([COLL_KIND_IDS["all_gather"]]), np.array([1e7]),
        pad[0][None], pad[1][None], pad[2][None],
        pad[3][None].astype(np.int32), pad[4][None].astype(np.int32),
        np.array([4.0]), np.array([False]))
    want = collective_time_us("all_gather", 1e7, carved[0][1], "ring", 4)
    assert float(_rel(got[0], want)) < RTOL


# ---------------------------------------------------------------------------
# batched duration pass == scalar per-call pass, bit for bit
# ---------------------------------------------------------------------------

def _cfgs_population():
    """A population varying every duration-relevant knob (algos, chunks,
    decomposition mode, policy)."""
    out = []
    for algos, chunks, mode, policy in (
            (("ring", "direct", "ring", "rhd"), 2, "baseline", "fifo"),
            (("dbt", "rhd", "direct", "ring"), 8, "blueconnect", "lifo"),
            (("direct", "direct", "dbt", "dbt"), 1, "baseline", "lifo"),
            (("rhd", "ring", "rhd", "ring"), 16, "blueconnect", "fifo")):
        out.append(SystemConfig(network=system_2(), device=SYSTEM_2_DEVICE,
                                coll_algo=algos, chunks=chunks,
                                multidim_coll=mode, sched_policy=policy))
    return out


def test_plan_durations_batch_bit_identical_train_trace():
    par = Parallelism(1024, 64, 4, 1, True)
    tr = generate_trace(ARCHS["qwen2-1.5b"], par, batch=256, seq=1024)
    calls = [SimCall(tr, cfg, par) for cfg in _cfgs_population()]
    plan, dur = plan_durations_batch(tr, calls)
    assert dur.shape == (len(calls), plan.n_ops)
    for k, call in enumerate(calls):
        _, want = plan_durations(tr, call.cfg, call.par, call.pools)
        assert np.array_equal(dur[k], want), k  # bit-identical, not approx


def test_plan_durations_batch_bit_identical_stream_trace_with_xfer():
    """The multi-pool pipelined request-stream trace: delay ops, partial
    pool carvings, and cross-pool transfer classes all ride the batched
    pass bit-identically."""
    sc = RequestStreamScenario(n_requests=16, seq=512, decode_tokens=8,
                               rate_rps=16.0, seed=3)
    env = system_env("qwen2-1.5b", "system2", scenario=sc,
                     objective="goodput")
    base = dict(dp=8, sp=1, pp=1, weight_sharded=0, sched_policy="fifo",
                coll_algo=("ring", "direct", "ring", "rhd"), chunks=2,
                multidim_coll="baseline",
                topology=("ring", "fc", "ring", "switch"),
                npus_per_dim=(4, 8, 4, 8), bw_per_dim=(400, 200, 150, 100),
                prefill_frac=0.5, decode_batch=4, batch_window_ms=50.0,
                max_inflight=2)
    jobs = [env.scenario.sim_job(env.context(dict(base, chunks=c,
                                                  multidim_coll=m)))
            for c, m in ((2, "baseline"), (8, "blueconnect"),
                         (16, "baseline"))]
    calls = [c for j in jobs for c in j.calls]
    tr = calls[0].trace
    assert all(c.trace is tr for c in calls)  # one shared plan
    assert any(c.pools for c in calls)
    plan, dur = plan_durations_batch(tr, calls)
    # the coverage this test exists for: transfer classes and delay ops
    assert any(group == "xfer" for _p, group, _c, _s in plan.coll_shapes)
    assert plan.delay_ops
    for k, call in enumerate(calls):
        _, want = plan_durations(tr, call.cfg, call.par, call.pools)
        assert np.array_equal(dur[k], want), k


# ---------------------------------------------------------------------------
# sub-network carving memoization
# ---------------------------------------------------------------------------

def test_carving_caches_hit_across_population_and_batches():
    """A population re-pricing one fabric resolves the carving once:
    ``group_dims`` / ``_group_net_cached`` / ``_pool_group_dims_cached``
    all hit, and the per-plan pack memo shares the class tables between
    calls that differ only in chunks/mode/policy."""
    assert cache.caches_enabled()
    par = Parallelism(1024, 64, 4, 1, True)
    # clear FIRST: generate_trace memoizes, and the plan (piggybacked on
    # the trace) would carry pack tables resolved by earlier tests
    cache.clear_all_caches()
    tr = generate_trace(ARCHS["qwen2-1.5b"], par, batch=256, seq=1024)
    plan = _sim_plan(tr)
    cfgs = _cfgs_population()
    h0 = (group_dims.cache_info().hits,
          _group_net_cached.cache_info().hits,
          _pool_group_dims_cached.cache_info().hits)
    pool_group_dims(plan, cfgs[0], par, None)
    pool_group_dims(plan, cfgs[0], par, None)  # same key -> pure hit
    assert _pool_group_dims_cached.cache_info().hits == h0[2] + 1
    assert group_dims.cache_info().misses >= 1
    # the group -> dims carve itself memoizes on the frozen (net, par) key
    group_dims(cfgs[0].network, par)
    assert group_dims.cache_info().hits > h0[0]
    # the whole population shares one fabric: every member's carve resolves
    # from cache (the outer pool-entries layer, plus the per-group algo
    # resolution shared by the many duration classes of each member)
    calls = [SimCall(tr, cfg, par) for cfg in cfgs]
    h1 = _pool_group_dims_cached.cache_info().hits
    plan_duration_tables(tr, calls)
    assert _pool_group_dims_cached.cache_info().hits >= h1 + len(calls)
    assert _group_net_cached.cache_info().hits > h0[1]
    # per-plan pack memo: identical (network, coll_algo, pools) keys share
    # ONE packed table object across differing chunks/mode/policy
    same_carve = [SimCall(tr, SystemConfig(network=system_2(),
                                           device=SYSTEM_2_DEVICE,
                                           coll_algo=("ring",) * 4,
                                           chunks=c, sched_policy=p), par)
                  for c, p in ((1, "fifo"), (4, "lifo"), (16, "fifo"))]
    from repro.core.simulator import _pack_class_tables
    packs = [_pack_class_tables(plan, c.cfg, c.par, c.pools)
             for c in same_carve]
    assert packs[0] is packs[1] is packs[2]


# ---------------------------------------------------------------------------
# the jax backend against the reference loop (jax-guarded, like
# test_backends)
# ---------------------------------------------------------------------------

jax = pytest.importorskip("jax")

from repro.core.backends import get_backend, list_backends  # noqa: E402


def test_backend_registry_is_reference_and_jax():
    assert set(list_backends()) == {"reference", "jax"}
    assert get_backend("jax").name == "jax"
    with pytest.raises(ValueError, match="unknown simulation backend"):
        get_backend("jax-unfused")


def test_busy_chunks_hold_each_op_once_in_uid_order():
    """The device busy sums' row table: every op in exactly one row of its
    own resource, rows grouped by resource, uid order within a resource,
    so the row sums scattered per resource give the per-resource busy
    time."""
    from repro.core.backends.jax_backend import BUSY_CHUNK, _busy_chunks

    par = Parallelism(1024, 64, 4, 1, True)
    tr = generate_trace(ARCHS["qwen2-1.5b"], par, batch=256, seq=1024)
    plan, dur = plan_durations(tr, _cfgs_population()[0], par)
    chunks, chunk_res = _busy_chunks(plan)
    n = plan.n_ops
    res_of = np.asarray(plan.res_of)
    assert chunks.shape[1] == BUSY_CHUNK
    assert np.all(np.diff(chunk_res) >= 0)
    held = chunks[chunks < n]
    assert sorted(held.tolist()) == list(range(n))
    for row, r in zip(chunks, chunk_res):
        ops = row[row < n]
        assert np.all(res_of[ops] == r) and np.all(np.diff(ops) > 0)
    part = np.append(dur, 0.0)[chunks].sum(axis=1)
    busy = np.bincount(chunk_res, weights=part, minlength=len(plan.res_names))
    want = np.bincount(res_of, weights=dur, minlength=len(plan.res_names))
    assert np.all(np.abs(busy - want) <= RTOL * np.maximum(np.abs(want), 1e-12))


def test_fused_matches_unfused_and_single():
    """The jax backend (durations priced inside the compiled sweep) agrees
    with the reference event loop to float64 tolerance, and its batch ==
    its own single."""
    par = Parallelism(1024, 64, 4, 1, True)
    tr = generate_trace(ARCHS["qwen2-1.5b"], par, batch=256, seq=1024)
    calls = [SimCall(tr, cfg, par) for cfg in _cfgs_population()]
    jb = get_backend("jax")
    got = jb.simulate_batch(tr, calls)
    ref = get_backend("reference").simulate_batch(tr, calls)
    for k, call in enumerate(calls):
        rel = _rel(got[k].makespan_us, ref[k].makespan_us)
        assert float(rel) < RTOL, k
        one = jb.simulate(tr, call.cfg, call.par)
        assert got[k].makespan_us == one.makespan_us
        assert got[k].comm_busy_us == one.comm_busy_us
        assert got[k].comm_busy_us.keys() == ref[k].comm_busy_us.keys()
        for res, busy in ref[k].comm_busy_us.items():
            assert float(_rel(got[k].comm_busy_us[res], busy)) < RTOL
    # the timing split is populated (the benchmark reads it)
    assert set(jb.last_timings) == {"durations_s", "sweep_s"}
