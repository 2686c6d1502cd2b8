"""The four-chip training cell rehearsed on four CPU devices at a tiny size
(2 layers, ``conftest.TINY_MODEL``'s widths, batch 4 x 16): the whole
driver comes out correct, every planted fault makes it false, the spread
reference equals the one-device reference to float32 rounding, and the two
collective readers read what is there and nothing where it is not.

Each run on four devices is a subprocess: the device count is fixed when
JAX starts."""
import json
import os
import subprocess
import sys
import textwrap
from types import SimpleNamespace as NS

import pytest

from bench import harness
from conftest import ROOT

CELL = "train.28L.mesh2x2"
SEED = 2**31 + 77
# The cell's change limit (1e-2) is set for the published widths, where the
# worst leaf's change gap reads under 1e-3.  At the tiny widths a key bias,
# whose gradient is round-off (the softmax is blind to a shift of every key)
# and which AdamW scales to a whole step, weighs more against the median
# leaf: sound runs read 1.2e-2 .. 1.9e-2 (CPU, three seeds), the no-exchange
# fault 6.4e-2, the half batch 3.5e-2, a frozen state 1.  The tiny runs take
# the 8-layer cell's limit; every fault still fails (the half batch on the
# loss and the gradient).
TINY_LIMITS = {"CHANGE_GAP": 5e-2}

RUN = """
import json, sys, time
from pathlib import Path
from bench import harness, variants, variants_mesh
from bench.drivers import train, train_mesh
from conftest import TINY_MODEL, run_cell

cell = harness.find_cell({cell!r}, {seed}, 0.3, False)
cell.config = {{**cell.config, **TINY_MODEL}}
cell.traffic = {{**cell.traffic, "batch": 4, "seq": 16, "trace_steps": 1}}
patches = {{**variants.PATCHES, **variants_mesh.PATCHES}}
with train.patched(train_mesh, LIMITS={{**train_mesh.LIMITS, **{limits!r}}}), \\
        patches[{variant!r}]() if {variant!r} else __import__("contextlib").nullcontext():
    out = run_cell(cell, Path({tmp!r}))
print(json.dumps({{"correct": out.correct, "checks": out.checks,
                  "metrics": out.metrics, "obs": out.obs,
                  "device": out.device}}))
"""


def on_four_devices(code: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT),
                                           str(ROOT / "bench" / "tests")]))
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def run_tiny(variant: str, tmp_path) -> dict:
    return on_four_devices(RUN.format(cell=CELL, seed=SEED, variant=variant,
                                      limits=TINY_LIMITS, tmp=str(tmp_path)))


def test_driver_is_correct_over_a_2x2_mesh(tmp_path):
    got = run_tiny("", tmp_path)
    assert got["correct"], got["checks"]
    assert got["device"]["count"] == 4
    assert got["metrics"]["train_tokens_per_s"] > 0 and got["metrics"]["setup_s"] > 0
    obs = got["obs"]
    assert obs["compiles_in_window"] == 0
    assert obs["collective_bytes_per_step"] > 0
    assert obs["collective_bytes_per_step"] == pytest.approx(
        sum(obs["collective_bytes_by_kind"].values()))
    assert len(obs["memory_peak_bytes_per_device"]) == 4


@pytest.mark.parametrize("variant", ["no_exchange", "half_batch", "frozen"])
def test_fault_fails(variant, tmp_path):
    got = run_tiny(variant, tmp_path)
    assert not got["correct"], got["checks"]


def test_control_fails():
    got = on_four_devices(f"""
        import json, jax
        from bench import checks_mesh, harness
        from conftest import TINY_MODEL
        cell = harness.find_cell({CELL!r}, {SEED}, 0.3, False)
        cell.config = {{**cell.config, **TINY_MODEL}}
        cell.traffic = {{**cell.traffic, "batch": 4, "seq": 16}}
        print(json.dumps(checks_mesh.control(cell, jax.devices())))
        """)
    assert any(v > limit for _, v, limit in got), got


# float32 rounding, with the sums split over four devices in another order:
# losses within 8 float32 ulps; a leaf's first-gradient norm within 1e-5 of
# the median leaf; after three AdamW steps, whose update divides by the
# square root of the second moment, the key biases (whose gradient is
# round-off, the softmax being blind to a shift of every key) move by
# rounding that AdamW scales to a whole step: 1e-4
SPREAD_LOSS, SPREAD_GRAD, SPREAD_CHANGE = 1e-6, 1e-5, 1e-4


def test_spread_reference_equals_one_device():
    got = on_four_devices(f"""
        import json, jax
        from bench import compare, generate, harness
        from bench.reference import qwen2, qwen2_spread
        from conftest import TINY_MODEL
        cell = harness.find_cell({CELL!r}, {SEED}, 0.3, False)
        cfg = {{**cell.config, **TINY_MODEL}}
        opt = cfg["training"]["optimizer"]
        key = jax.random.PRNGKey(harness.seed32({SEED}))
        batches = [generate.lm_batch({SEED}, i, 4, 16, cfg["vocab_size"])
                   for i in range(3)]
        one = qwen2.train3(cfg, opt, key, batches)
        four = qwen2_spread.train3(cfg, opt, key, batches, jax.devices())
        print(json.dumps({{
            "loss": compare.loss_gap(four["losses"], one["losses"]),
            "grad": compare.leaf_gap(four["grad"], one["grad"])[0],
            "change": compare.leaf_gap(four["change"], one["change"])[0],
            "devices": len(jax.devices())}}))
        """)
    assert got["devices"] == 4
    assert got["loss"] <= SPREAD_LOSS
    assert got["grad"] <= SPREAD_GRAD
    assert got["change"] <= SPREAD_CHANGE


def test_spread_lays_each_array_over_its_largest_divisible_dimension():
    from jax.sharding import Mesh, PartitionSpec as P

    from bench.reference import qwen2_spread
    import jax
    import numpy as np

    mesh = Mesh(np.array(jax.devices()[:1] * 4), ("all",))
    assert qwen2_spread.spread(mesh, (28, 1536, 12, 128)).spec == P(None, "all")
    assert qwen2_spread.spread(mesh, (151936, 1536)).spec == P("all")
    assert qwen2_spread.spread(mesh, (28, 2, 128)).spec == P(None, None, "all")
    assert qwen2_spread.spread(mesh, (3, 5)).spec == P()


# the readers of the two collective metrics

def _read(name, obs=None, trace=None):
    ctx = {"obs": obs or {}, "metrics": {}, "trace": trace}
    return harness.load_reader(name).read(ctx)


def test_collective_ms_reads_the_tail_per_step():
    trace = NS(collective_s=0.03, units=5)
    assert _read("collective_ms.train", trace=trace) == pytest.approx(6.0)
    assert _read("collective_ms.train", trace=None) is None
    assert _read("collective_ms.train", trace=NS(collective_s=0.0, units=0)) is None


def test_collective_gb_reads_the_counter_per_window_step(monkeypatch):
    from repro.runtime import spans

    rec = spans.Recorder(capacity=16)
    for n in (1.0, 3e9, 5e9, 7.0):                  # warm-up, 2 window steps, tail
        with rec.unit("repro.train.step"):
            rec.count("repro.train.collective_bytes", n)
    monkeypatch.setattr(spans, "RECORDER", rec)
    got = _read("collective_gb.train", obs={"steps": 2}, trace=NS(units=1))
    assert got == pytest.approx(4.0)
    assert _read("collective_gb.train", obs={"steps": 9}, trace=NS(units=1)) is None


def test_collective_gb_reads_nothing_without_the_recorder_or_counter(monkeypatch):
    from repro.runtime import spans

    monkeypatch.delitem(spans.COUNTERS, "repro.train.collective_bytes")
    assert _read("collective_gb.train", obs={"steps": 1}) is None
    monkeypatch.setitem(sys.modules, "repro.runtime.spans", None)
    assert _read("collective_gb.train", obs={"steps": 1}) is None
