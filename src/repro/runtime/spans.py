"""Spans and counters of the program's own work, on the profiler's clock.

A *unit* is one piece of work the program repeats: an engine generation
(``CosmicEnv.step_batch``) or a training step (``launch/train.train_loop``).
A *span* is a named part of a unit.  Each opens a
``jax.profiler.TraceAnnotation`` of its name, so that under a profiler it
lands on the host plane of the same trace as the device's operations, and
adds its ``time.perf_counter`` duration to the open unit's row.  A unit's
annotation carries its ordinal (``unit=<n>``), which identifies every span
nested in it.  ``count`` adds to a counter in the same row.

Every name is declared once, in ``SPANS`` and ``COUNTERS``.  A span or
counter outside a unit of its kind only annotates.  Rows are kept per kind
in a preallocated numpy ring of ``CAPACITY`` rows, never as per-unit
objects, so the recorder keeps nothing that the garbage collector walks.

    with spans.unit("repro.engine.generation"):
        with spans.span("repro.engine.pack") as pack:
            ...
        pack.seconds
    spans.rows("repro.engine.generation")["repro.engine.pack"]

The recorder serves one thread: units and spans are opened by the thread
that runs the work.
"""
from __future__ import annotations

import contextlib
import sys
from time import perf_counter
from typing import Iterator, Sequence

import numpy as np

# every span, by its parent; a span without one is a unit (a row's kind).
# A span's self time is its duration less its children's.
SPANS: dict[str, str | None] = {
    "repro.engine.generation": None,
    "repro.engine.jobs": "repro.engine.generation",
    "repro.engine.pack": "repro.engine.generation",
    "repro.engine.dispatch": "repro.engine.generation",
    "repro.engine.device_wait": "repro.engine.generation",
    "repro.engine.copy_back": "repro.engine.generation",
    "repro.engine.assemble": "repro.engine.generation",
    "repro.engine.finalize": "repro.engine.generation",
    "repro.train.step": None,
    "repro.train.input": "repro.train.step",
    "repro.train.put": "repro.train.step",
    "repro.train.dispatch": "repro.train.step",
    "repro.train.loss_sync": "repro.train.step",
    "repro.train.bookkeeping": "repro.train.step",
}
# every counter, by the unit whose row holds it
COUNTERS: dict[str, str] = {
    "repro.engine.points": "repro.engine.generation",   # points handed in
    "repro.engine.evaluated": "repro.engine.generation",  # evaluation memo misses
    "repro.engine.copy_back_bytes": "repro.engine.generation",  # device to host
    # bytes the compiled step's collectives move (a step over a mesh)
    "repro.train.collective_bytes": "repro.train.step",
    # of those, the all-to-alls' bytes
    "repro.train.all_to_all_bytes": "repro.train.step",
}
CAPACITY = 65_536


def _kind(name: str) -> str:
    while SPANS[name] is not None:
        name = SPANS[name]
    return name


KINDS = tuple(n for n, p in SPANS.items() if p is None)
CHILDREN = {n: tuple(c for c, p in SPANS.items() if p == n) for n in SPANS}
# each kind's row: its own wall, then its spans and its counters
_FIELDS = {k: [n for n in SPANS if _kind(n) == k]
           + [c for c, u in COUNTERS.items() if u == k] for k in KINDS}
_DTYPE = {k: np.dtype([(f, np.float64) for f in fs]) for k, fs in _FIELDS.items()}
# name -> (kind, column)
_COLUMN = {f: (k, i) for k, fs in _FIELDS.items() for i, f in enumerate(fs)}

_TraceAnnotation = None


def _annotation(name: str, **stats):
    """The profiler's annotation of ``name``, or None while JAX is not
    loaded (then no profiler runs, and the engine's numpy-only paths stay
    free of JAX)."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        if "jax" not in sys.modules:
            return None
        from jax.profiler import TraceAnnotation as _TraceAnnotation
    return _TraceAnnotation(name, **stats)


class Span:
    """One timed, annotated part of a unit; ``seconds`` holds its duration
    once it has closed."""

    __slots__ = ("name", "seconds", "_rec", "_kind", "_col", "_ann", "_t0")

    def __init__(self, rec: "Recorder", name: str) -> None:
        self._kind, self._col = _COLUMN[name]
        self.name, self.seconds, self._rec = name, 0.0, rec

    def __enter__(self) -> "Span":
        self._ann = ann = _annotation(self.name)
        if ann is not None:
            ann.__enter__()
        self._t0 = perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        unit = self._rec.open
        if unit is not None and unit.kind == self._kind:
            unit.row[self._col] += self.seconds


class Unit:
    """One unit of work: a span whose row the recorder keeps when it closes
    without an exception, unless ``drop`` was called."""

    __slots__ = ("kind", "ordinal", "row", "seconds", "_rec", "_ann", "_t0",
                 "_outer", "_dropped")

    def __init__(self, rec: "Recorder", kind: str) -> None:
        if SPANS[kind] is not None:
            raise ValueError(f"{kind!r} is a span of {_kind(kind)!r}, not a unit")
        self.kind, self.seconds, self._rec = kind, 0.0, rec

    def __enter__(self) -> "Unit":
        rec = self._rec
        self.ordinal = rec.written(self.kind)
        self.row = [0.0] * len(_FIELDS[self.kind])
        self._dropped = False
        self._outer, rec.open = rec.open, self
        self._ann = ann = _annotation(self.kind, unit=self.ordinal)
        if ann is not None:
            ann.__enter__()
        self._t0 = perf_counter()
        return self

    def elapsed(self) -> float:
        """Seconds since the unit opened."""
        return perf_counter() - self._t0

    def drop(self) -> None:
        """Keep no row for this unit (it turned out to hold no work)."""
        self._dropped = True

    def __exit__(self, exc_type, *exc) -> None:
        self.seconds = perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(exc_type, *exc)
        rec = self._rec
        rec.open = self._outer
        if exc_type is None and not self._dropped and rec.on:
            self.row[0] = self.seconds
            rec.keep(self.kind, self.row)


class Recorder:
    """Rows of the units of each kind, in a bounded ring."""

    def __init__(self, capacity: int = CAPACITY) -> None:
        self.capacity = capacity
        self.on = True
        self.open: Unit | None = None       # the innermost open unit
        self._rings: dict[str, np.ndarray] = {}
        self._written: dict[str, int] = {}

    def span(self, name: str) -> Span:
        return Span(self, name)

    def unit(self, kind: str) -> Unit:
        return Unit(self, kind)

    def count(self, name: str, n: float = 1) -> None:
        kind, col = _COLUMN[name]
        unit = self.open
        if unit is not None and unit.kind == kind:
            unit.row[col] += n

    @contextlib.contextmanager
    def recording(self, on: bool) -> Iterator[None]:
        """Keep rows (or not) inside the block; spans still annotate and
        time."""
        was, self.on = self.on, on
        try:
            yield
        finally:
            self.on = was

    def written(self, kind: str) -> int:
        """Rows of ``kind`` kept so far, those the ring has dropped included."""
        return self._written.get(kind, 0)

    def keep(self, kind: str, row: list[float]) -> None:
        ring = self._rings.get(kind)
        if ring is None:
            ring = self._rings[kind] = np.zeros(self.capacity, _DTYPE[kind])
        n = self._written.get(kind, 0)
        ring[n % self.capacity] = tuple(row)
        self._written[kind] = n + 1

    def rows(self, kind: str) -> np.ndarray:
        """The kept rows of ``kind``, oldest first: a structured array with
        one field per name of the kind (the unit's own is its wall)."""
        ring, n = self._rings.get(kind), self.written(kind)
        if ring is None:
            return np.zeros(0, _DTYPE[kind])
        if n <= self.capacity:
            return ring[:n].copy()
        i = n % self.capacity
        return np.concatenate([ring[i:], ring[:i]])

    def window(self, kind: str, units: int | None, tail: int = 0) -> np.ndarray | None:
        """The ``units`` rows before the last ``tail``; None when the ring
        holds fewer than ``units + tail`` rows, so that a reader can never
        take the wrong units."""
        rows = self.rows(kind)
        if not units or len(rows) < units + tail:
            return None
        return rows[len(rows) - tail - units:len(rows) - tail]

    def window_mean_ms(self, names: Sequence[str], units: int | None,
                       tail: int = 0, own: bool = False) -> float | None:
        """Mean over the window's units (see ``window``) of the summed
        durations of ``names`` (their self times with ``own``), in ms."""
        rows = self.window(_COLUMN[names[0]][0], units, tail)
        if rows is None:
            return None
        pick = self_seconds if own else (lambda r, n: r[n])
        return float(np.mean(sum(pick(rows, n) for n in names))) * 1e3


def self_seconds(rows: np.ndarray, name: str) -> np.ndarray:
    """``name``'s duration less its children's, row by row."""
    out = rows[name].copy()
    for child in CHILDREN[name]:
        out -= rows[child]
    return out


RECORDER = Recorder()
span = RECORDER.span
unit = RECORDER.unit
count = RECORDER.count
recording = RECORDER.recording
rows = RECORDER.rows
window_mean_ms = RECORDER.window_mean_ms
