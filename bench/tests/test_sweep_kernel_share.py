"""The reader of the program's sweep counters, ``sweep_kernel_share.engine``,
over a tiny stream cell's window on the CPU (where the float64 loop runs),
and where the program lacks what it reads."""
import sys
from types import SimpleNamespace as NS

import pytest

from bench import harness
from conftest import run_cell

NAME = "sweep_kernel_share.engine"
KERNEL, SWEPT = "repro.engine.sweep_kernel_ops", "repro.engine.sweep_ops"


def _read(out, tail=0):
    ctx = {"obs": out.obs, "metrics": out.metrics, "trace": NS(units=tail)}
    return harness.load_reader(NAME).read(ctx)


def test_sweep_share_reads_the_loop_on_the_cpu(tiny, tmp_path):
    from repro.runtime import spans

    out = run_cell(tiny("engine.stream256.p32"), tmp_path)
    rows = spans.RECORDER.window("repro.engine.generation", out.obs["generations"])
    assert (rows[SWEPT] == 462).all() and (rows[KERNEL] == 0).all()
    assert _read(out) == 0.0
    # more units than the ring holds after the tail: nothing is read
    assert _read(out, tail=10 ** 6) is None


def test_sweep_share_of_a_window_the_kernel_swept(monkeypatch):
    from repro.runtime import spans

    rec = spans.Recorder()
    monkeypatch.setattr(spans, "RECORDER", rec)
    for kernel in (100, 100, 0):
        with rec.unit("repro.engine.generation"):
            rec.count(SWEPT, 100)
            rec.count(KERNEL, kernel)
    assert _read(NS(obs={"generations": 3}, metrics={})) == pytest.approx(2 / 3)
    assert _read(NS(obs={"generations": 2}, metrics={}), tail=1) == 1.0


def test_sweep_share_reads_nothing_without_the_recorder(monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.runtime.spans", None)
    assert _read(NS(obs={"generations": 5}, metrics={})) is None


@pytest.mark.parametrize("counter", [KERNEL, SWEPT])
def test_sweep_share_reads_nothing_without_its_counters(monkeypatch, counter):
    from repro.runtime import spans

    monkeypatch.delitem(spans.COUNTERS, counter)
    assert _read(NS(obs={"generations": 1}, metrics={})) is None
