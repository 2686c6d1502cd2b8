"""The span-and-counter recorder (``repro.runtime.spans``): rows, self
times, the ring's bound, window slicing, and the rows one engine
generation and a short training loop leave."""
from __future__ import annotations

import os
import subprocess
import sys

import numpy as np
import pytest

from repro.runtime import spans

GEN = "repro.engine.generation"
ENGINE_SPANS = ["repro.engine.jobs", "repro.engine.pack",
                "repro.engine.dispatch", "repro.engine.device_wait",
                "repro.engine.copy_back", "repro.engine.assemble",
                "repro.engine.finalize"]
STEP_SPANS = ["repro.train.input", "repro.train.put", "repro.train.dispatch",
              "repro.train.loss_sync", "repro.train.bookkeeping"]


def _numbered(rec: spans.Recorder, n: int) -> None:
    """``n`` engine units whose ``points`` counter reads 0 .. n-1."""
    for i in range(n):
        with rec.unit(GEN):
            rec.count("repro.engine.points", i)


def test_nesting_self_time_and_one_row_per_unit():
    rec = spans.Recorder(capacity=8)
    with rec.unit(GEN) as u:
        with rec.span("repro.engine.pack") as pack:
            sum(range(1000))
        with rec.span("repro.engine.pack"):
            sum(range(1000))
        with rec.span("repro.engine.finalize"):
            pass
        rec.count("repro.engine.points", 32)
        rec.count("repro.engine.evaluated", 30)
        rec.count("repro.engine.evaluated", 1)
    rows = rec.rows(GEN)
    assert len(rows) == 1 and u.ordinal == 0
    row = rows[0]
    assert row[GEN] == u.seconds > 0
    assert row["repro.engine.pack"] > pack.seconds > 0      # both packs add up
    assert row["repro.engine.points"] == 32
    assert row["repro.engine.evaluated"] == 31
    own = spans.self_seconds(rows, GEN)[0]
    assert own == pytest.approx(
        row[GEN] - sum(row[n] for n in ENGINE_SPANS), abs=1e-15)
    assert 0 <= own < row[GEN]
    # a leaf's self time is its duration
    assert (spans.self_seconds(rows, "repro.engine.pack")
            == rows["repro.engine.pack"]).all()


def test_self_seconds_subtracts_children():
    rec = spans.Recorder(capacity=4)
    row = [0.0] * len(rec.rows(GEN).dtype.names)
    names = rec.rows(GEN).dtype.names
    row[names.index(GEN)] = 10.0
    row[names.index("repro.engine.pack")] = 3.0
    row[names.index("repro.engine.dispatch")] = 2.5
    rec.keep(GEN, row)
    assert spans.self_seconds(rec.rows(GEN), GEN).tolist() == [4.5]


def test_ring_keeps_the_newest_rows_oldest_first():
    rec = spans.Recorder(capacity=4)
    _numbered(rec, 6)
    rows = rec.rows(GEN)
    assert len(rows) == 4 and rec.written(GEN) == 6
    assert rows["repro.engine.points"].tolist() == [2, 3, 4, 5]
    with rec.unit(GEN) as u:
        pass
    assert u.ordinal == 6
    assert rec.rows(GEN)["repro.engine.points"].tolist() == [3, 4, 5, 0]


def test_window_slicing_and_too_few_rows():
    rec = spans.Recorder(capacity=16)
    _numbered(rec, 10)
    w = rec.window(GEN, 3, tail=2)
    assert w["repro.engine.points"].tolist() == [5, 6, 7]
    assert rec.window(GEN, 8, tail=2) is not None
    assert rec.window(GEN, 9, tail=2) is None      # would take a warm-up row
    assert rec.window(GEN, 0) is None and rec.window(GEN, None) is None
    assert rec.window("repro.train.step", 1) is None
    ms = rec.window_mean_ms(["repro.engine.points"], 3, tail=2)
    assert ms == pytest.approx(6 * 1e3)
    assert rec.window_mean_ms(["repro.engine.points"], 11) is None


def test_recording_off_dropped_and_failed_units_leave_no_row():
    rec = spans.Recorder(capacity=4)
    with rec.recording(False):
        with rec.unit(GEN):
            with rec.span("repro.engine.pack") as pack:
                pass
    assert pack.seconds > 0 and rec.on
    with rec.unit(GEN) as u:
        u.drop()
    with pytest.raises(RuntimeError):
        with rec.unit(GEN):
            raise RuntimeError("fails inside the unit")
    assert len(rec.rows(GEN)) == 0 and rec.open is None


def test_spans_outside_their_unit_only_time():
    rec = spans.Recorder(capacity=4)
    with rec.span("repro.engine.pack") as pack:      # no unit open
        rec.count("repro.engine.points", 5)
    assert pack.seconds > 0
    with rec.unit("repro.train.step"):
        with rec.span("repro.engine.pack"):          # another kind's span
            pass
        rec.count("repro.engine.points", 5)
    assert len(rec.rows(GEN)) == 0
    (row,) = rec.rows("repro.train.step")
    assert all(row[n] == 0 for n in STEP_SPANS)
    with pytest.raises(KeyError):
        rec.span("repro.engine.undeclared")
    with pytest.raises(ValueError):
        rec.unit("repro.engine.pack")


def test_every_name_is_declared_outside_the_benchmarks_marks():
    names = list(spans.SPANS) + list(spans.COUNTERS)
    assert all(n.startswith(("repro.engine.", "repro.train.")) for n in names)
    assert not any(n.startswith("bench.") for n in names)
    assert set(spans.KINDS) == {GEN, "repro.train.step"}
    assert set(spans.COUNTERS.values()) <= set(spans.KINDS)


def test_the_engine_imports_no_jax_for_the_recorder():
    code = "import sys, repro.core.env; print('jax' in sys.modules)"
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(sys.path))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, check=True, env=env)
    assert out.stdout.strip() == "False"


BASE_CFG = dict(dp=8, sp=1, pp=1, weight_sharded=0, sched_policy="fifo",
                coll_algo=("ring", "direct", "ring", "rhd"), chunks=2,
                multidim_coll="baseline",
                topology=("ring", "fc", "ring", "switch"),
                npus_per_dim=(4, 8, 4, 8), bw_per_dim=(400, 200, 150, 100))


def test_one_generation_on_the_jax_backend_is_one_row(clear_dse_caches):
    pytest.importorskip("jax")
    from repro.core.backends import get_backend
    from repro.core.systems import system_env

    env = system_env("qwen2-1.5b", "system2", batch=64, seq=2048,
                     backend="jax")
    before = spans.RECORDER.written(GEN)
    env.step_batch([dict(BASE_CFG, chunks=c) for c in (2, 4, 8, 4)])
    assert spans.RECORDER.written(GEN) == before + 1
    row = spans.rows(GEN)[-1]
    assert row["repro.engine.points"] == 4
    assert row["repro.engine.evaluated"] == 3          # one duplicate
    assert all(row[n] > 0 for n in ENGINE_SPANS), row
    assert sum(row[n] for n in ENGINE_SPANS) <= row[GEN]
    # one shared trace, so one simulate_batch: its timings are the row's
    lt = get_backend("jax").last_timings
    assert lt["durations_s"] == row["repro.engine.pack"]
    assert lt["sweep_s"] == (row["repro.engine.dispatch"]
                             + row["repro.engine.device_wait"]
                             + row["repro.engine.copy_back"])


def test_a_three_step_train_loop_leaves_three_step_rows(tmp_path):
    pytest.importorskip("jax")
    from repro.configs import get_arch, reduced
    from repro.launch.train import parse_args, train_loop

    args = parse_args(["--steps", "3", "--batch", "2", "--seq", "16",
                       "--ckpt-dir", str(tmp_path), "--ckpt-every", "2"])
    before = spans.RECORDER.written("repro.train.step")
    run = train_loop(args, reduced(get_arch("qwen2-1.5b")))
    assert run.final == 3 and len(run.losses) == 3
    assert np.isfinite(run.losses).all()
    assert spans.RECORDER.written("repro.train.step") == before + 3
    rows = spans.rows("repro.train.step")[-3:]
    for n in STEP_SPANS:
        assert (rows[n] > 0).all(), n
    own = spans.self_seconds(rows, "repro.train.step")
    assert (own >= 0).all() and (own < rows["repro.train.step"]).all()
