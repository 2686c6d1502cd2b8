"""Plumbing shared by every cell: the cell's files found by name, the chip,
the compile cache, host spans, compile events, the profiler window and the
result line.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``.  Its
configuration file names the driver (``bench/drivers/<driver>.py``), its
traffic is ``bench/traffic/<traffic>.json`` and each per-layer metric is read
by ``bench/metrics/<metric>.py``.  Adding a configuration, a mix or a metric
adds files and entries; nothing here changes.
"""
from __future__ import annotations

import contextlib
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoChip(RuntimeError):
    """JAX finds no accelerator of a known kind, or fewer chips than the
    cell asks for: the run reports nothing."""


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def import_program() -> None:
    """Put the program (``src/``) and the benchmark package on the path."""
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)


@dataclass
class Cell:
    name: str
    workload: dict
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    seed: int
    seconds: float
    trace: bool

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])


def _applies(metric: dict, cell: str, reported: set[str] | None = None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return reported is None or metric["moves"] in reported


def find_cell(name: str, seed: int, seconds: float, trace: bool,
              bench_file: Path = ROOT / "BENCHMARK.json") -> Cell:
    spec = load_json(bench_file)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(ROOT / configs[w["config"]]["file"])
    traffic = load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in spec["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"] if _applies(m, name, reported)]
    return Cell(name, w, config, traffic, e2e, per_layer, seed, seconds, trace)


def seed32(seed: int) -> int:
    """A non-negative 31-bit seed for APIs that take an int32, drawn from
    any whole ``seed``, which may be wider than 32 bits."""
    import numpy as np

    return int(np.random.SeedSequence(seed).generate_state(1)[0] & 0x7FFFFFFF)


# ---------------------------------------------------------------------------
# the chip
# ---------------------------------------------------------------------------

def accelerator(chips: int) -> tuple[list, dict]:
    """The cell's devices and the peaks of their kind.  Refuses a CPU, a
    device kind that the peak table lacks, and too few chips."""
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise NoChip("JAX finds no accelerator (platform 'cpu')")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX finds "
                     f"{len(devices)}")
    peaks = load_json(BENCH / "peaks.json")
    kind = devices[0].device_kind
    if kind not in peaks:
        raise NoChip(f"device kind {kind!r} is not in bench/peaks.json")
    return devices[:chips], peaks[kind]


def device_info(devices) -> dict:
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}


def use_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (``JAX_COMPILATION_CACHE_DIR`` wins), caching every program,
    so that only a cell's first run in a checkout compiles."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


# ---------------------------------------------------------------------------
# host spans, compile events, the profiler
# ---------------------------------------------------------------------------

class CompileLog:
    """Every compile-side duration JAX reports (tracing, lowering, backend
    compile or cache fetch), stamped with the host clock."""

    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        self.events: list[tuple[float, str, float]] = []
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_: Any) -> None:
        if event.startswith("/jax/core/compile/"):
            self.events.append((time.perf_counter(), event, duration))

    def seconds_between(self, t0: float, t1: float) -> float:
        return sum(d for t, e, d in self.events if t0 <= t <= t1)

    def compiles_between(self, t0: float, t1: float) -> int:
        return sum(1 for t, e, _ in self.events
                   if e == self.BACKEND and t0 <= t <= t1)


class GcLog:
    """Every pause of Python's garbage collector: (start on the host clock,
    generation collected, seconds)."""

    def __init__(self) -> None:
        self.events: list[tuple[float, int, float]] = []
        self._t0 = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        now = time.perf_counter()
        if phase == "start":
            self._t0 = now
        else:
            self.events.append((self._t0, int(info["generation"]), now - self._t0))

    def between(self, t0: float, t1: float, generation: int | None = None):
        return [(t, g, d) for t, g, d in self.events if t0 <= t <= t1
                and (generation is None or g == generation)]

    def seconds_between(self, t0: float, t1: float) -> float:
        return sum(d for _, _, d in self.between(t0, t1))


class Tools:
    """What a driver gets from the harness: the clock the run started on,
    compile events, pauses of the garbage collector, and the profiler for
    the traced tail."""

    def __init__(self, t_start: float, trace: bool, trace_dir: Path) -> None:
        self.t_start = t_start
        self.trace = trace
        self.trace_dir = trace_dir
        self.compiles = CompileLog()
        self.gc = GcLog()
        self.tail_units = 0

    def span(self, name: str):
        """A host span in the profiler's trace (only while tracing)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax.profiler

        return jax.profiler.TraceAnnotation(name)

    def start_tail(self) -> None:
        import jax.profiler

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # host spans only, no per-call tracing
        jax.profiler.start_trace(str(self.trace_dir), profiler_options=opts)

    def stop_tail(self, units: int) -> None:
        import jax.profiler

        jax.profiler.stop_trace()
        self.tail_units = units


@dataclass
class Outcome:
    """What a driver hands back.  ``metrics`` holds the end-to-end values;
    ``obs`` what the per-layer readers read; ``checks`` the compared
    numbers as (name, value, limit), each passing when value <= limit."""
    attempted: int
    failed: int
    metrics: dict[str, float]
    obs: dict[str, Any]
    checks: list[tuple[str, float, float]]
    device: dict
    notes: dict[str, Any] = field(default_factory=dict)

    @property
    def correct(self) -> bool:
        return self.failed == 0 and all(v <= lim for _, v, lim in self.checks)


def load_driver(name: str):
    return importlib.import_module(f"bench.drivers.{name}")


def load_reader(metric: str):
    """``bench/metrics/<metric>.py`` (metric names may hold dots)."""
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
