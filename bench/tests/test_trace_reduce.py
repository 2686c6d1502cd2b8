"""The trace reduction on a small hand-made trace."""
from types import SimpleNamespace as NS

import pytest

from bench import trace_reduce


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def plane(name, lines):
    return NS(name=name, lines=[NS(name=k, events=v) for k, v in lines.items()])


def test_busy_idle_collectives_and_gaps():
    host = plane("/host:CPU", {"python": [
        ev("bench.step", 1_000, 99_000),          # the traced tail: 1e3..1e5 ns
        ev("PjitFunction(step)", 40_000, 15_000),
    ]})
    dev = plane("/device:TPU:0", {
        "XLA Ops": [ev("fusion.1", 0, 11_000),          # clipped to 1e3
                    ev("fusion.2", 5_000, 10_000),       # overlaps: union
                    ev("all-reduce.3", 20_000, 20_000),
                    ev("fusion.4", 52_000, 5_000),       # 12 us gap before
                    ev("fusion.5", 57_003, 1_000)],     # 3 ns seam
        "XLA Modules": [ev("jit_step", 0, 200_000)],    # ignored
    })
    other = plane("/device:TPU:1", {"XLA Ops": [ev("fusion.9", 0, 100_000)]})
    r = trace_reduce.reduce_profile([host, dev, other], units=2)
    assert r.window_s == pytest.approx(99_000e-9)
    busy0 = (15_000 - 1_000) + 20_000 + 5_000 + 1_000
    assert r.busy_s == pytest.approx((busy0 + 99_000) / 2 * 1e-9)
    assert r.collective_s == pytest.approx(20_000 / 2 * 1e-9)
    gaps = dict(r.idle_gaps)
    assert gaps["PjitFunction(step)"] == pytest.approx(12_000 / 2 * 1e-9)
    assert gaps["bench.step"] == pytest.approx((100_000 - 58_003) / 2 * 1e-9)
    assert gaps["seams between ops (<10 us)"] == pytest.approx((5_000 + 3) / 2 * 1e-9)
    assert r.top_ops[0][0] == "fusion.9"
    assert 0 < r.idle_share < 1


def test_no_device_or_no_marks_is_an_error():
    host = plane("/host:CPU", {"python": [ev("bench.step", 0, 10)]})
    with pytest.raises(ValueError):
        trace_reduce.reduce_profile([host])


def test_recorded_chip_trace():
    """A 10-generation tail of ``engine.train.p32`` traced on one TPU v5e:
    the reduction gives what that run printed."""
    import gzip
    from pathlib import Path

    from jax.profiler import ProfileData

    raw = gzip.decompress((Path(__file__).parent / "data"
                           / "engine_train_p32_tail.xplane.pb.gz").read_bytes())
    r = trace_reduce.reduce_profile(ProfileData.from_serialized_xspace(raw).planes,
                                    units=10)
    assert r.devices == 1
    assert r.window_s == pytest.approx(0.096177128, rel=1e-6)
    assert r.busy_s == pytest.approx(0.003572257, rel=1e-6)
    assert r.top_ops[0][0] == "%while.58"
    assert r.idle_gaps[0][0] == "bench.generation"
    assert r.collective_s == 0.0
