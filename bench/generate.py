"""The one traffic generator: reads a traffic file's parameters and the
seed, and makes the cell's inputs.

Two kinds of traffic exist today:

* ``design_points``: the populations a random-sampling search agent hands
  the DSE engine.  The trace-shaping knobs are pinned (so a whole
  population shares one scheduling plan) and every other knob is drawn
  from its list of choices.  Copied from ``backend_population`` in
  ``benchmarks/fig10_agents.py`` and generalised to any pinned set, with
  two changes: points are drawn without replacement over the whole run
  (a memo hit would shrink a population to a new shape, which compiles),
  and the draws are made in bulk in set-up.
* ``lm_tokens``: uniform random token ids over the model's vocabulary, one
  batch per step, every row different, from ``(seed, step)``.
"""
from __future__ import annotations

from typing import Any

import numpy as np


class DesignPoints:
    """Seeded populations of distinct design points.

    ``choices`` maps a knob to ``{"values": [...], "per_dim": n}``; a knob
    with ``per_dim`` takes a tuple of ``n`` independent draws.  The first
    ``warmup`` populations are for set-up; ``population(i)`` is the i-th
    after them."""

    def __init__(self, traffic: dict, seed: int) -> None:
        self.pinned = {k: tuple(v) if isinstance(v, list) else v
                       for k, v in traffic["pinned"].items()}
        self.size = int(traffic["population"])
        self.warmup = int(traffic["warmup_generations"])
        n = self.size * (self.warmup + int(traffic["max_generations"]))
        rng = np.random.default_rng(seed)
        self.knobs: list[tuple[str, list, int]] = []
        cols = []
        for knob, c in traffic["choices"].items():
            values, per_dim = list(c["values"]), int(c.get("per_dim", 0))
            self.knobs.append((knob, values, per_dim))
            cols.append(rng.integers(0, len(values), size=(n, max(per_dim, 1))))
        idx = np.concatenate(cols, axis=1)
        radix = [len(v) for _, v, d in self.knobs for _ in range(max(d, 1))]
        key = idx @ np.cumprod([1] + radix[:-1], dtype=np.int64)
        # without replacement: keep each point's first draw, in draw order
        _, first = np.unique(key, return_index=True)
        self.idx = idx[np.sort(first)]
        self.capacity = len(self.idx) // self.size - self.warmup

    def _point(self, row: np.ndarray) -> dict[str, Any]:
        cfg = dict(self.pinned)
        j = 0
        for knob, values, per_dim in self.knobs:
            if per_dim:
                cfg[knob] = tuple(values[int(i)] for i in row[j:j + per_dim])
                j += per_dim
            else:
                cfg[knob] = values[int(row[j])]
                j += 1
        return cfg

    def _block(self, k: int) -> list[dict[str, Any]]:
        if k >= self.warmup + self.capacity:
            raise RuntimeError(f"traffic exhausted after {k} populations; "
                               f"raise max_generations")
        rows = self.idx[k * self.size:(k + 1) * self.size]
        return [self._point(r) for r in rows]

    def warmup_population(self, i: int) -> list[dict[str, Any]]:
        return self._block(i)

    def population(self, i: int) -> list[dict[str, Any]]:
        return self._block(self.warmup + i)


def lm_batch(seed: int, step: int, batch: int, seq: int,
             vocab: int) -> dict[str, np.ndarray]:
    """One training batch: ``batch`` rows of ``seq + 1`` uniform token ids,
    split into inputs and next-token labels."""
    rng = np.random.default_rng([seed, step])
    toks = rng.integers(0, vocab, size=(batch, seq + 1), dtype=np.int32)
    return {"inputs": toks[:, :-1], "labels": toks[:, 1:]}
