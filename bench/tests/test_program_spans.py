"""The program's own spans as the benchmark reads them: idle gaps named by
them in a trace, and the per-layer readers over a tiny cell's window."""
import sys
from types import SimpleNamespace as NS

import pytest

from bench import harness, trace_reduce
from conftest import run_cell
from test_trace_reduce import ev, plane

ENGINE = ["jobs_ms.engine", "dispatch_ms.engine", "device_wait_ms.engine",
          "copy_back_ms.engine", "assemble_ms.engine", "finalize_ms.engine"]
TRAIN = ["host_gap_ms.train", "loss_sync_ms.train"]


def test_gaps_under_program_spans_are_named_by_them():
    host = plane("/host:CPU", {"python": [
        ev("bench.generation", 0, 100_000),            # the traced tail
        ev("repro.engine.generation", 1_000, 79_000),
        ev("repro.engine.dispatch", 20_000, 10_000),
        ev("repro.engine.copy_back", 40_000, 30_000),
    ]})
    dev = plane("/device:TPU:0", {"XLA Ops": [
        ev("fusion.1", 0, 5_000), ev("fusion.2", 15_000, 5_000),
        ev("while.3", 30_000, 10_000), ev("fusion.4", 70_000, 15_000)]})
    gaps = dict(trace_reduce.reduce_profile([host, dev], units=1).idle_gaps)
    assert gaps == pytest.approx({"repro.engine.generation": 10_000e-9,
                                  "repro.engine.dispatch": 10_000e-9,
                                  "repro.engine.copy_back": 30_000e-9,
                                  "bench.generation": 15_000e-9})


def test_program_spans_are_not_tail_marks():
    host = plane("/host:CPU", {"python": [
        ev("repro.engine.generation", 0, 500_000),
        ev("bench.generation", 100_000, 100_000),
    ]})
    dev = plane("/device:TPU:0", {"XLA Ops": [ev("fusion.1", 0, 500_000)]})
    assert trace_reduce.reduce_profile([host, dev]).window_s == pytest.approx(100_000e-9)
    only_program = plane("/host:CPU", {"python": [ev("repro.train.step", 0, 10)]})
    with pytest.raises(ValueError):
        trace_reduce.reduce_profile([only_program, dev])


def _read(names, out, tail=0):
    ctx = {"obs": out.obs, "metrics": out.metrics, "trace": NS(units=tail)}
    return {n: harness.load_reader(n).read(ctx) for n in names}


def test_engine_readers_split_the_device_call(tiny, tmp_path):
    out = run_cell(tiny("engine.train.p32"), tmp_path)
    got = _read(ENGINE + ["device_call_ms.engine", "gen_p95_ms.engine"], out)
    assert all(got[n] > 0 for n in ENGINE), got
    call = got["dispatch_ms.engine"] + got["device_wait_ms.engine"] \
        + got["copy_back_ms.engine"]
    assert call == pytest.approx(got["device_call_ms.engine"], rel=1e-9)
    mean_wall_ms = sum(out.obs["gen_wall_s"]) / out.obs["generations"] * 1e3
    assert sum(got[n] for n in ENGINE) < mean_wall_ms
    # more units than the ring holds after the tail: nothing is read
    assert _read(ENGINE, out, tail=10 ** 6) == {n: None for n in ENGINE}


def test_train_readers(tiny, tmp_path):
    out = run_cell(tiny("train.8L.b4s1024"), tmp_path)
    got = _read(TRAIN, out)
    assert all(got[n] > 0 for n in TRAIN), got
    assert sum(got.values()) < out.obs["window_s"] / out.obs["steps"] * 1e3 * 1.5


def test_readers_read_nothing_from_a_program_without_the_recorder(monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.runtime.spans", None)
    out = NS(obs={"generations": 5, "steps": 5}, metrics={})
    assert _read(ENGINE + TRAIN, out) == {n: None for n in ENGINE + TRAIN}
