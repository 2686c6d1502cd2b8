"""The scheduling sweep's Pallas kernel (``jax_backend._sweep_pairs``) on
the CPU, in interpret mode: finish times carried as float32 (hi, lo) pairs
against the float64 loop (``_sweep_population``), and the choice between
the two paths.  On a TPU the kernel is what runs; the CPU keeps the loop."""
from __future__ import annotations

import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.configs import ARCHS  # noqa: E402
from repro.core.backends import SimCall, get_backend  # noqa: E402
from repro.core.backends import jax_backend as jb  # noqa: E402
from repro.core.compute import SYSTEM_2_DEVICE  # noqa: E402
from repro.core.scenario import (DisaggServeScenario,  # noqa: E402
                                 RequestStreamScenario)
from repro.core.simulator import (SystemConfig,  # noqa: E402
                                  batch_op_durations, plan_duration_tables)
from repro.core.systems import system_env  # noqa: E402
from repro.core.topology import build_network  # noqa: E402
from repro.core.workload import (Parallelism, Trace, TraceBuilder,  # noqa: E402
                                 generate_trace)

# the pairs carry ~48 bits: the largest relative deviation of a finish time
# from the float64 loop over the ~26k-op stream trace is 7.2e-13
RTOL = 1e-12

BASE_CFG = dict(dp=8, sp=1, pp=1, weight_sharded=0,
                coll_algo=("ring", "direct", "ring", "rhd"),
                multidim_coll="baseline",
                topology=("ring", "fc", "ring", "switch"),
                npus_per_dim=(4, 8, 4, 8))
SERVE = dict(prefill_frac=0.5, decode_batch=4)
SCENARIOS = {
    "stream": (RequestStreamScenario(n_requests=24, seq=1024,
                                     decode_tokens=16, rate_rps=16.0, seed=3),
               "goodput", dict(SERVE, batch_window_ms=50.0, max_inflight=2)),
    "disagg": (DisaggServeScenario(64, 2048, 16), "latency", SERVE),
}
TRAIN_PAR = Parallelism(1024, 64, 4, 1, True)
BWS = [(400, 200, 150, 100), (100, 300, 250, 50), (500, 50, 450, 200),
       (250, 250, 250, 250)]


def _sys(chunks: int, bw: tuple, policy: str = "fifo") -> SystemConfig:
    return SystemConfig(network=build_network(BASE_CFG["topology"],
                                              BASE_CFG["npus_per_dim"], bw),
                        device=SYSTEM_2_DEVICE,
                        coll_algo=BASE_CFG["coll_algo"], chunks=chunks,
                        sched_policy=policy)


def _calls(name: str, policy: str, p: int) -> list[SimCall]:
    """``p`` calls over one trace of the family ``name``: its members vary
    chunks and bandwidths under one scheduling policy."""
    knobs = [(c, bw) for bw in BWS for c in (2, 4, 8, 16)] * 2
    if name == "train":
        tr = generate_trace(ARCHS["gpt3-13b"], TRAIN_PAR, batch=1024, seq=2048)
        return [SimCall(tr, _sys(c, bw, policy), TRAIN_PAR)
                for c, bw in knobs[:p]]
    sc, obj, extra = SCENARIOS[name]
    env = system_env("qwen2-1.5b", "system2", scenario=sc, objective=obj,
                     backend="jax")
    cfgs = [dict(BASE_CFG, chunks=c, bw_per_dim=bw, sched_policy=policy,
                 **extra) for c, bw in knobs[:p]]
    calls = [c for cfg in cfgs
             for c in env.scenario.sim_job(env.context(cfg)).calls][:p]
    assert len(calls) == p and all(c.trace is calls[0].trace for c in calls)
    return calls


def _both(dur: np.ndarray, parents: np.ndarray,
          layout: jb.SweepLayout | None = None):
    """The float64 loop's and the kernel's finish times, (n_ops + 1, P)."""
    layout = layout or jb.sweep_layout(len(parents), parents.shape[1],
                                       jb._sweep_window(parents))
    with jax.enable_x64(True):
        d = jnp.asarray(dur, jnp.float64)
        want = jb._sweep_population(d, jnp.asarray(parents))
        got = jb._sweep_pairs(d, jnp.asarray(jb._ring_parents(parents, layout)),
                              layout, interpret=True)
        return np.asarray(got), np.asarray(want)


def _assert_close(got: np.ndarray, want: np.ndarray) -> None:
    assert got.shape == want.shape and got.dtype == np.float64
    assert np.all(np.abs(got - want) <= RTOL * np.abs(want))


@pytest.mark.parametrize("p", [1, 5, 32])
@pytest.mark.parametrize("policy", ["fifo", "lifo"])
@pytest.mark.parametrize("name", ["stream", "disagg", "train"])
def test_sweep_kernel_matches_the_loop(name, policy, p):
    calls = _calls(name, policy, p)
    tr = calls[0].trace
    plan, tables = plan_duration_tables(tr, calls)
    parents = jb._plan_parents(tr, plan)
    dur = batch_op_durations(plan, tables, xp=np, op_major=True)
    assert dur.shape == (len(tr.ops), p)
    got, want = _both(dur, parents)
    _assert_close(got, want)
    assert (want[-1] == 0).all() and (got[-1] == 0).all()


def _far_trace(n: int = 2000, far: int = 701) -> Trace:
    """A hand-built topologically ordered trace: a compute chain with a
    collective every seventh op, each collective joined back by an op
    ``far`` uids later, and every 50th op with no parent at all."""
    tb = TraceBuilder()
    for i in range(n):
        if i % 7 == 3:
            tb.coll(f"c{i}", "all_reduce", 1e6 * (1 + i % 5), "dp",
                    [i - 1] if i % 50 else [])
        else:
            deps = [i - far] if i >= far and (i - far) % 7 == 3 else []
            tb.comp(f"x{i}", 1e9 * (1 + i % 11), 1e6, deps)
    return Trace(tb.ops)


def test_sweep_kernel_window_carries_across_blocks():
    """Parents up to 701 ops back, beyond one block of the default size:
    the layout widens the block to cover them, the ring carries each
    block's rows into the next, and the padding parent reads 0."""
    tr = _far_trace()
    cfgs = [_sys(c, bw) for c, bw in zip((2, 4, 8, 16, 2), BWS + BWS[:1])]
    plan, tables = plan_duration_tables(
        tr, [SimCall(tr, c, TRAIN_PAR) for c in cfgs])
    parents = jb._plan_parents(tr, plan)
    window = jb._sweep_window(parents)
    layout = jb.sweep_layout(plan.n_ops, parents.shape[1], window)
    assert window == 701 > jb.SWEEP_BLOCK
    assert layout.block >= window and layout.block % 8 == 0
    assert -(-plan.n_ops // layout.block) >= 2
    assert (parents == plan.n_ops).any()
    got, want = _both(batch_op_durations(plan, tables, xp=np, op_major=True),
                      parents, layout)
    _assert_close(got, want)


def test_sweep_kernel_takes_the_later_lo_where_hi_ties():
    """Durations that round to the same float32 and differ below it: the
    max compares lo where hi ties, whichever order the parents come in."""
    tiny, small = 2.0 ** -30, 2.0 ** -35
    assert np.float32(1 + tiny) == np.float32(1 - tiny) == 1
    n = 6
    parents = np.array([[n, n], [n, n], [n, n], [0, 1], [1, 0], [2, 0]],
                       dtype=np.int32)
    dur = np.array([1 + tiny, 1 + small, 1 - tiny, 0.5, 0.5, 0.25])[:, None]
    got, want = _both(dur, parents)
    assert want[3:6, 0].tolist() == [1.5 + tiny, 1.5 + tiny, 1.25 + tiny]
    assert (got == want).all()


def test_pair_add_keeps_the_error_of_both_halves():
    """The double-float add folds in the two-sums of both halves: where the
    hi parts cancel, the lo parts' rounding error survives, and every sum
    lies within 2**-46 of the exact one."""
    from fractions import Fraction

    rng = np.random.default_rng(7)
    x = rng.uniform(0.5, 2.0, 2000) * 2.0 ** rng.integers(-8, 8, 2000)
    y = np.where(np.arange(2000) % 2 == 0, -x, rng.uniform(0.5, 2.0, 2000))
    y = y + x * rng.uniform(-2.0 ** -26, 2.0 ** -26, 2000)

    def pair(v):
        hi = v.astype(np.float32)
        return hi, (v - hi).astype(np.float32)

    (ahi, alo), (bhi, blo) = pair(x), pair(y)
    shi, slo = jb._pair_add(ahi, alo, bhi, blo)
    assert shi.dtype == slo.dtype == np.float32
    for parts, got in zip(zip(ahi, alo, bhi, blo), zip(shi, slo)):
        exact = sum(map(Fraction, map(float, parts)))
        err = abs(sum(map(Fraction, map(float, got))) - exact)
        assert err <= abs(exact) * Fraction(2) ** -46


def test_path_choice_from_platform_window_and_population():
    stream = jb.sweep_layout(25_872, 3, 233)
    assert stream == jb.SweepLayout(block=512, stride=4, depth=3)
    assert jb.sweep_layout(201, 2, 3) == jb.SweepLayout(512, 2, 2)
    assert jb.sweep_kernel_fits("tpu", stream, 32)
    # the CPU keeps the float64 loop, whatever the plan
    assert not jb.sweep_kernel_fits("cpu", stream, 32)
    assert not jb.sweep_kernel_fits("gpu", stream, 32)
    # a window, or a population, past the kernel's memory keeps the loop
    assert not jb.sweep_kernel_fits("tpu", jb.sweep_layout(100_000, 3, 20_000), 32)
    assert not jb.sweep_kernel_fits("tpu", jb.sweep_layout(100_000, 16, 512), 32)
    assert not jb.sweep_kernel_fits("tpu", stream, 4096)


def _counts(backend, tr, calls):
    from repro.runtime import spans

    gen = "repro.engine.generation"
    with spans.unit(gen):
        out = backend.simulate_batch(tr, calls)
    row = spans.rows(gen)[-1]
    return out, row["repro.engine.sweep_kernel_ops"], row["repro.engine.sweep_ops"]


def test_cpu_runs_the_loop_and_counts_no_kernel_ops():
    calls = _calls("stream", "fifo", 5)
    tr = calls[0].trace
    _, kernel_ops, swept = _counts(get_backend("jax"), tr, calls)
    assert (kernel_ops, swept) == (0, len(tr.ops))


def test_simulate_batch_through_the_kernel(monkeypatch):
    """The whole fused call with the kernel path taken, the kernel in
    interpret mode: the same results as the reference loop to the pairs'
    precision, and the counter holds every op."""
    monkeypatch.setattr(jb, "sweep_kernel_fits", lambda *a: True)
    monkeypatch.setattr(jb, "_sweep_pairs",
                        functools.partial(jb._sweep_pairs, interpret=True))
    calls = _calls("stream", "lifo", 5)
    tr = Trace(calls[0].trace.ops, meta=dict(calls[0].trace.meta))
    calls = [SimCall(tr, c.cfg, c.par, pools=c.pools,
                     record_finish=c.record_finish) for c in calls]
    got, kernel_ops, swept = _counts(jb.JaxBackend(), tr, calls)
    want = get_backend("reference").simulate_batch(tr, calls)
    assert kernel_ops == swept == len(tr.ops)
    for g, w in zip(got, want):
        assert abs(g.makespan_us - w.makespan_us) <= RTOL * w.makespan_us
        assert len(g.op_finish_us) > 0
        for uid in list(g.op_finish_us)[::7]:
            assert (abs(g.op_finish_us[uid] - w.op_finish_us[uid])
                    <= RTOL * w.op_finish_us[uid])
