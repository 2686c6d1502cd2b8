"""From a profiler trace to the numbers the per-layer readers need.

The traced tail is marked on the host by the benchmark's own spans (names
starting ``bench.``): it runs from the first such span's start to the last
one's end.  On each device plane (``/device:<KIND>:<n>``) the operations
are the events of its ``XLA Ops`` line; busy time is the union of their
intervals inside the tail, collective time the sum of the durations of the
collective operations (all-reduce, all-gather, reduce-scatter,
collective-permute, all-to-all).  An idle gap of a device is named by what
the host was doing at its midpoint: the shortest host event that spans it.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:[A-Za-z_]+:\d+$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all", re.IGNORECASE)
MARK = "bench."
# gaps shorter than this are the seams between one operation and the next
# inside a program, not time the host held the device back
SEAM_NS = 10_000.0


@dataclass
class Reduced:
    window_s: float
    busy_s: float                  # mean over devices
    collective_s: float            # mean over devices
    devices: int
    units: int                     # generations or steps in the tail
    top_ops: list = field(default_factory=list)     # [name, seconds/device]
    idle_gaps: list = field(default_factory=list)   # [host activity, seconds/device]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops[:10],
                "idle_gaps": self.idle_gaps[:10]}


def union(starts: np.ndarray, ends: np.ndarray) -> np.ndarray:
    """Disjoint, sorted (k, 2) intervals covering the given ones."""
    if len(starts) == 0:
        return np.zeros((0, 2))
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > reach[:-1]
    first = np.flatnonzero(new)
    last = np.r_[first[1:] - 1, len(s) - 1]
    return np.stack([s[first], reach[last]], axis=1)


def _events(line):
    # a device op's event is named by its HLO text; keep the instruction
    # name (``%fusion.3``, ``%all-reduce.1``), which XLA takes from the opcode
    for ev in line.events:
        yield (ev.name.split(" = ", 1)[0], float(ev.start_ns),
               float(ev.start_ns + ev.duration_ns))


def reduce_profile(planes, units: int = 0) -> Reduced:
    """``planes``: iterable of objects with ``name`` and ``lines`` (each
    line with ``name`` and ``events``), as ``jax.profiler.ProfileData``
    gives them."""
    host, devices = [], []
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(_events(line))
        elif DEVICE_PLANE.match(plane.name):
            ops = [ev for line in plane.lines if line.name == OPS_LINE
                   for ev in _events(line)]
            devices.append(ops)
    marks = [(s, e) for n, s, e in host if n.startswith(MARK)]
    if not marks or not devices:
        raise ValueError("the trace holds no benchmark spans or no device")
    t0 = min(s for s, _ in marks)
    t1 = max(e for _, e in marks)
    host_arr = sorted(((e - s, s, e, n) for n, s, e in host),
                      key=lambda x: x[0])

    def activity(t: float) -> str:
        for _, s, e, n in host_arr:          # shortest first
            if s <= t <= e:
                return n
        return "host: no span"

    busy, coll = [], []
    op_time: dict[str, float] = defaultdict(float)
    gap_time: dict[str, float] = defaultdict(float)
    for ops in devices:
        if ops:
            names = [n for n, _, _ in ops]
            s = np.clip(np.array([x[1] for x in ops]), t0, t1)
            e = np.clip(np.array([x[2] for x in ops]), t0, t1)
        else:
            names, s, e = [], np.zeros(0), np.zeros(0)
        keep = e > s
        merged = union(s[keep], e[keep])
        busy.append(float((merged[:, 1] - merged[:, 0]).sum()))
        coll.append(float(sum(b - a for n, a, b, k in zip(names, s, e, keep)
                              if k and COLLECTIVE.search(n))))
        for n, a, b, k in zip(names, s, e, keep):
            if k:
                op_time[n] += b - a
        edges = np.r_[t0, merged.ravel(), t1].reshape(-1, 2)
        for a, b in edges:
            if b - a >= SEAM_NS:
                gap_time[activity((a + b) / 2)] += b - a
            elif b > a:
                gap_time["seams between ops (<10 us)"] += b - a
    nd = len(devices)
    ns = 1e-9

    def ranked(d: dict) -> list:
        return [[k, v * ns / nd] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return Reduced(window_s=(t1 - t0) * ns, busy_s=float(np.mean(busy)) * ns,
                   collective_s=float(np.mean(coll)) * ns, devices=nd,
                   units=units, top_ops=ranked(op_time),
                   idle_gaps=ranked(gap_time))


def reduce_dir(trace_dir: Path, n_devices: int, units: int = 0) -> Reduced:
    """Reduce the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    red = reduce_profile(ProfileData.from_file(str(files[-1])).planes, units)
    if red.devices < n_devices:
        raise ValueError(f"the trace shows {red.devices} devices, the cell "
                         f"uses {n_devices}")
    return red
