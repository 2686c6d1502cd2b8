"""The reader of the program's copy-back counter, ``copy_back_mb.engine``,
over a tiny stream cell's window, and where the program lacks what it reads."""
import sys
from types import SimpleNamespace as NS

import pytest

from bench import harness
from conftest import run_cell

NAME = "copy_back_mb.engine"
COUNTER = "repro.engine.copy_back_bytes"


def _read(out, tail=0):
    ctx = {"obs": out.obs, "metrics": out.metrics, "trace": NS(units=tail)}
    return harness.load_reader(NAME).read(ctx)


def test_copy_back_reader_reads_the_counter(tiny, tmp_path):
    from repro.runtime import spans

    out = run_cell(tiny("engine.stream256.p32"), tmp_path)
    got = _read(out)
    rows = spans.RECORDER.window("repro.engine.generation", out.obs["generations"])
    assert got == pytest.approx(rows[COUNTER].mean() / 1e6, rel=1e-12)
    assert 0 < got < 0.1
    # more units than the ring holds after the tail: nothing is read
    assert _read(out, tail=10 ** 6) is None


def test_copy_back_reader_reads_nothing_without_the_recorder(monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.runtime.spans", None)
    assert _read(NS(obs={"generations": 5}, metrics={})) is None


def test_copy_back_reader_reads_nothing_without_its_counter(monkeypatch):
    from repro.runtime import spans

    monkeypatch.delitem(spans.COUNTERS, COUNTER)
    assert _read(NS(obs={"generations": 1}, metrics={})) is None
