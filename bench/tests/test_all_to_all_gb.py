"""The reader of the program's all-to-all counter, ``all_to_all_gb.train``,
over a tiny training cell's window and over counted rows, and where the
program lacks what it reads."""
import sys
from types import SimpleNamespace as NS

import pytest

from bench import harness
from conftest import run_cell

NAME = "all_to_all_gb.train"
COUNTER = "repro.train.all_to_all_bytes"


def _read(obs, tail=0):
    ctx = {"obs": obs, "metrics": {}, "trace": NS(units=tail)}
    return harness.load_reader(NAME).read(ctx)


def test_reader_reads_zero_from_a_one_device_loop(tiny, tmp_path):
    # one device: the loop's jitted step counts no collectives, and the
    # reader finds the counter there, at 0
    out = run_cell(tiny("train.8L.b4s1024"), tmp_path)
    assert _read(out.obs) == 0.0


def test_reader_reads_the_counter_per_window_step(monkeypatch):
    from repro.runtime import spans

    rec = spans.Recorder(capacity=4)
    for n in (1.0, 2.0, 3e9, 5e9, 7.0):      # warm-up, 2 window steps, tail
        with rec.unit("repro.train.step"):
            rec.count(COUNTER, n)
    monkeypatch.setattr(spans, "RECORDER", rec)
    assert _read({"steps": 2}, tail=1) == pytest.approx(4.0)
    # the ring keeps 4 rows: a window and tail of 5 are not all there
    assert _read({"steps": 2}, tail=3) is None
    assert _read({"steps": 9}, tail=1) is None


def test_reader_reads_nothing_without_the_recorder(monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.runtime.spans", None)
    assert _read({"steps": 1}) is None


def test_reader_reads_nothing_without_its_counter(monkeypatch):
    from repro.runtime import spans

    monkeypatch.delitem(spans.COUNTERS, COUNTER)
    assert _read({"steps": 1}) is None
