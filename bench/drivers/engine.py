"""Engine cells: a random-sampling agent's generations through
``CosmicEnv.step_batch`` on the fused ``jax`` backend.

Set-up builds the scenario, the env and the populations from the seed, and
runs the warm-up generations (trace generation, planning, compilation).
The window then runs whole generations until the deadline passes; the one
that crosses it counts.  ``points_per_s`` is all points over all window
time.  Each generation's wall time and the garbage collector's pauses in
it are kept for the per-layer readers, with the set-up split by phase.

The harness wraps the backend's ``simulate_batch`` to add up, per
generation, the program's own ``last_timings`` split (table packing, and
the compiled call less any compilation inside it).

``correct``: a sample of the window's points, drawn from the seed, is
evaluated again by the benchmark's own model of the engine
(``bench/reference/engine.py``: pricing, scheduling, finalize and reward in
float64 on the host, importing nothing of the program) and every statistic
is compared.  The trace the window simulated must be the one the traffic
pins by its SHA-256.
"""
from __future__ import annotations

import contextlib
import time
from typing import Any

import numpy as np

from bench import compare, generate, harness
from bench.spec import arch_spec

# Limit on the largest relative deviation of any simulated float statistic
# from the benchmark's float64 model.  The chip emulates float64 with pairs of
# float32, so sound runs deviate by up to 2.7e-11; the x64-off control
# (float32 pricing and sweep) by 1.7e-4 and more (readings in PERF.md).
STAT_RTOL = 1e-7


def scenario(traffic: dict):
    from repro.core.scenario import RequestStreamScenario, TrainScenario

    p = dict(traffic["scenario"])
    kind = p.pop("type")
    if kind == "request_stream":
        return RequestStreamScenario(**p)
    if kind == "train":
        return TrainScenario(**p)
    raise ValueError(f"unknown scenario type {kind!r}")


def make_env(cell: harness.Cell, backend: str):
    from repro.core.systems import get_system, system_env

    system = cell.config["system"]
    if get_system(system["name"]).n_npus != system["n_npus"]:
        raise ValueError(f"{system['name']} is not the {system['n_npus']}-NPU "
                         f"system the configuration states")
    return system_env(arch_spec(cell.config), system["name"],
                      scenario=scenario(cell.traffic),
                      objective=cell.traffic["objective"], backend=backend,
                      capacity_gb=system["device"]["capacity_gb"])


class Timings:
    """Per-generation sums of the backend's ``last_timings`` split, with the
    compile seconds inside the compiled call kept apart, and the traces the
    backend was handed."""

    def __init__(self, backend, compiles: harness.CompileLog) -> None:
        self.backend, self.compiles = backend, compiles
        self.pack = self.call = 0.0
        self.traces: dict[int, Any] = {}

    @contextlib.contextmanager
    def wrapped(self):
        inner = self.backend.simulate_batch

        def simulate_batch(trace, calls):
            t0 = time.perf_counter()
            out = inner(trace, calls)
            t1 = time.perf_counter()
            lt = self.backend.last_timings
            c = self.compiles.seconds_between(t0, t1)
            self.traces.setdefault(id(trace), trace)
            self.pack += lt["durations_s"]
            self.call += lt["sweep_s"] - c
            return out

        self.backend.simulate_batch = simulate_batch
        try:
            yield self
        finally:
            del self.backend.simulate_batch

    def take(self) -> tuple[float, float]:
        out = (self.pack, self.call)
        self.pack = self.call = 0.0
        return out

    def take_traces(self) -> list:
        out = list(self.traces.values())
        self.traces = {}
        return out


def run(cell: harness.Cell, tools: harness.Tools, devices) -> harness.Outcome:
    from repro.core.backends import get_backend

    from bench.reference import engine as ref

    cfg = cell.config
    marks = [("driver", time.perf_counter())]
    env = make_env(cell, cfg["backend"])
    backend = get_backend(cfg["backend"])
    points = generate.DesignPoints(cell.traffic, cell.seed)
    timings = Timings(backend, tools.compiles)
    gens: list[dict[str, Any]] = []
    marks.append(("env_and_points", time.perf_counter()))
    with timings.wrapped():
        for i in range(points.warmup):
            env.step_batch(points.warmup_population(i))
            marks.append((f"warmup_generation_{i}", time.perf_counter()))
        timings.take()
        timings.take_traces()

        k = 0
        t_start = time.perf_counter()
        deadline = t_start + cell.seconds
        while True:
            pop = points.population(k)
            t0 = time.perf_counter()
            evs = env.step_batch(pop)
            t1 = time.perf_counter()
            pack, call = timings.take()
            gens.append({"pop": pop, "evs": evs, "wall": t1 - t0,
                         "pack": pack, "call": call,
                         "gc": tools.gc.seconds_between(t0, t1)})
            k += 1
            if t1 >= deadline:
                break
        t_end = time.perf_counter()
        traces = timings.take_traces()

        if cell.trace:
            n = int(cell.traffic["trace_generations"])
            tools.start_tail()
            for j in range(n):
                with tools.span("bench.generation"):
                    env.step_batch(points.population(k + j))
            tools.stop_tail(n)
    device = harness.device_info(devices)

    window = t_end - t_start
    walls = np.array([g["wall"] for g in gens])
    n_points = sum(len(g["pop"]) for g in gens)
    metrics = {"setup_s": t_start - tools.t_start,
               "points_per_s": n_points / window}
    obs = {"generations": len(gens), "window_s": window,
           "gen_wall_s": walls.tolist(),
           "pack_s": [g["pack"] for g in gens],
           "call_s": [g["call"] for g in gens],
           "gc_s": [g["gc"] for g in gens],
           "compiles_in_window": tools.compiles.compiles_between(t_start, t_end)}
    # where set-up went: seconds from the previous mark to each mark
    stamps = [("process_to_driver", marks[0][1] - tools.t_start)]
    stamps += [(name, t - prev) for (_, prev), (name, t) in zip(marks, marks[1:])]
    full = tools.gc.between(t_start, t_end, 2)
    slow = sorted(range(len(gens)), key=lambda i: -gens[i]["wall"])[:10]

    # correctness: the window's trace against its pinned digest, then a
    # seeded sample of the window's points on the benchmark's own model
    canons = [ref.canonical(t) for t in traces]
    digests = sorted({ref.digest(c) for c in canons})
    foreign = float(digests != [cell.traffic["trace_sha256"]])
    flat = [(p, e) for g in gens for p, e in zip(g["pop"], g["evs"])]
    rng = np.random.default_rng([cell.seed, 1])
    pick = rng.choice(len(flat), size=min(int(cell.traffic["check_points"]),
                                          len(flat)), replace=False)
    worst, unequal, where = float("inf"), len(pick), "the trace"
    if not foreign:
        model = ref.Reference(cfg, cell.traffic, canons[0])
        got = [flat[i][1] for i in pick]
        want = [model.evaluate(flat[i][0]) for i in pick]
        worst, unequal, where = compare.compare_points(got, want)
    checks = [("trace_foreign", foreign, 0.0),
              ("stat_rel_dev", worst, STAT_RTOL),
              ("points_unequal", float(unequal), 0.0)]
    return harness.Outcome(
        attempted=n_points, failed=unequal, metrics=metrics, obs=obs,
        checks=checks, device=device,
        notes={"setup_split_s": stamps,
               "setup_compile_s": tools.compiles.seconds_between(tools.t_start, t_start),
               "gc_full_in_window": [(round(t - t_start, 3), d) for t, _, d in full],
               "gc_s_in_window": tools.gc.seconds_between(t_start, t_end),
               "slowest_generations": [(i, gens[i]["wall"], gens[i]["gc"]) for i in slow],
               "checked_points": len(pick), "worst_stat": where,
               "trace_sha256": digests,
               "compiles_in_window": obs["compiles_in_window"],
               "valid_points": sum(bool(e.valid) for _, e in flat)})
