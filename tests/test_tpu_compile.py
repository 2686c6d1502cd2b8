"""Compile the accelerator paths for a described TPU v5e chip.

Nothing runs here: XLA's TPU compiler lowers each program for a v5e that is
described, not attached, so a kernel block shape or an f64 rewrite the chip
refuses fails this file instead of a chip run.  The topology is described
inside module-scoped fixtures, never at import, so every test worker
collects the same tests and only the worker running this file loads the
TPU compiler.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a TPU executable cannot be read back without a chip: keep these
    # compiles out of any persistent cache the environment configured
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_qwen2_width(one_chip):
    """qwen2-1.5b heads: hd 128 at S 2048 (12 query heads folded)."""
    from repro.kernels.flash_attention import flash_attention

    q = _sds((12, 2048, 128), jnp.bfloat16, one_chip)
    fn = jax.jit(lambda q, k, v: flash_attention(q, k, v, interpret=False))
    _assert_kernel(fn.lower(q, q, q).compile())


def test_rmsnorm_qwen2_width(one_chip):
    from repro.kernels.rmsnorm import rmsnorm

    x = _sds((8192, 1536), jnp.bfloat16, one_chip)
    w = _sds((1536,), jnp.bfloat16, one_chip)
    fn = jax.jit(lambda x, w: rmsnorm(x, w, interpret=False))
    _assert_kernel(fn.lower(x, w).compile())


def test_fused_rmsnorm_rows_not_multiple_of_8(one_chip):
    from repro.kernels import ops

    x = _sds((3, 333, 1536), jnp.bfloat16, one_chip)   # 999 rows
    w = _sds((1536,), jnp.bfloat16, one_chip)
    _assert_kernel(ops.fused_rmsnorm.lower(x, w, interpret=False).compile())


def test_ssd_scan_mamba2_130m_width(one_chip):
    """mamba2-130m: 24 heads x hd 64, state 128, S 2048."""
    from repro.kernels.ssd_scan import ssd_scan

    bh, s, hd, ds = 24, 2048, 64, 128
    x = _sds((bh, s, hd), jnp.float32, one_chip)
    dt = _sds((bh, s), jnp.float32, one_chip)
    a = _sds((bh,), jnp.float32, one_chip)
    b = _sds((bh, s, ds), jnp.float32, one_chip)
    fn = jax.jit(lambda x, dt, a, b, c: ssd_scan(x, dt, a, b, c,
                                                 interpret=False))
    _assert_kernel(fn.lower(x, dt, a, b, b).compile())


def _stream_plan():
    """A 48-request stream trace's plan and a 32-point population's
    duration tables."""
    from repro.core.scenario import RequestStreamScenario
    from repro.core.simulator import plan_duration_tables
    from repro.core.systems import system_env

    sc = RequestStreamScenario(n_requests=48, seq=2048, decode_tokens=64,
                               rate_rps=32.0, seed=0)
    env = system_env("qwen2-1.5b", "system2", scenario=sc,
                     objective="goodput", backend="jax")
    rng = np.random.default_rng(0)
    algos = ("ring", "direct", "rhd", "dbt")
    cfgs = [dict(dp=8, sp=1, pp=1, weight_sharded=0,
                 topology=("ring", "fc", "ring", "switch"),
                 npus_per_dim=(4, 8, 4, 8), prefill_frac=0.5, decode_batch=8,
                 batch_window_ms=50.0, max_inflight=2,
                 coll_algo=tuple(rng.choice(algos) for _ in range(4)),
                 chunks=int(rng.choice((2, 4, 8, 16))),
                 sched_policy=str(rng.choice(("fifo", "lifo"))),
                 multidim_coll=str(rng.choice(("baseline", "blueconnect"))),
                 bw_per_dim=tuple(int(b) for b in
                                  rng.choice(range(50, 501, 50), size=4)))
            for _ in range(32)]
    calls = [c for cfg in cfgs
             for c in env.scenario.sim_job(env.context(cfg)).calls]
    trace = calls[0].trace
    assert all(c.trace is trace for c in calls)
    return trace, *plan_duration_tables(trace, calls)


def _compile_fused(one_chip, sweep):
    from repro.core.backends.jax_backend import (_busy_chunks, _fused_eval,
                                                 _plan_parents, _ring_parents)
    from repro.core.workload import wave_mark_uids

    trace, plan, tables = _stream_plan()
    parents = _plan_parents(trace, plan)
    with jax.enable_x64(True):
        tabs = {k: _sds(np.shape(v), np.asarray(v).dtype, one_chip)
                for k, v in tables.items()}
        statics = (parents if sweep is None else _ring_parents(parents, sweep),
                   *_busy_chunks(plan), wave_mark_uids(trace).astype(np.int32))
        return _fused_eval(plan, False, sweep).lower(
            tabs, *(_sds(a.shape, a.dtype, one_chip) for a in statics)).compile()


def test_fused_eval_request_stream_x64(one_chip):
    """The fused jax backend's whole compiled call (f64 collective and
    roofline pricing, the scheduling sweep, and the makespan, busy and
    marked finish rows reduced on the device) on a 48-request stream plan
    with a 32-point population."""
    compiled = _compile_fused(one_chip, None)
    assert compiled.memory_analysis() is not None


def test_fused_eval_request_stream_sweep_kernel(one_chip):
    """The same call with the sweep as the Pallas kernel, as a TPU runs it."""
    from repro.core.backends.jax_backend import sweep_kernel_fits, sweep_layout

    layout = sweep_layout(4096, 3, 233)
    assert sweep_kernel_fits("tpu", layout, 32)
    _assert_kernel(_compile_fused(one_chip, layout))


def test_sweep_kernel_stream_cell_width(one_chip):
    """The kernel alone at the stream cell's size: 25,872 ops, 32 members,
    parents up to 233 ops back."""
    from repro.core.backends.jax_backend import _sweep_pairs, sweep_layout

    layout = sweep_layout(25_872, 3, 233)
    n_pad = -(-25_872 // layout.block) * layout.block
    with jax.enable_x64(True):
        dur = _sds((25_872, 32), jnp.float64, one_chip)
        ring = _sds((n_pad * layout.stride,), jnp.int32, one_chip)
        fn = jax.jit(lambda d, r: _sweep_pairs(d, r, layout))
        compiled = fn.lower(dur, ring).compile()
    _assert_kernel(compiled)
    assert compiled.memory_analysis() is not None
