"""The comparisons that decide ``correct``.

Engine: every simulated statistic of a design point (validity, reward,
latency and every value of ``detail``, nested groups included) against
the reference.  Floats are compared by relative deviation; everything else
must be equal.

Training: per leaf (one tensor of one layer), the gap between the
program's norm and the reference's, measured against the reference's norm
of that leaf or of the median leaf, whichever is larger.
"""
from __future__ import annotations

from typing import Any

import numpy as np


def flat_stats(ev) -> dict[str, Any]:
    out: dict[str, Any] = {"valid": bool(ev.valid), "reward": ev.reward,
                           "latency_ms": ev.latency_ms}

    def walk(prefix: str, v: Any) -> None:
        if isinstance(v, dict):
            for k, x in v.items():
                walk(f"{prefix}.{k}", x)
        elif isinstance(v, (list, tuple)):
            for i, x in enumerate(v):
                walk(f"{prefix}[{i}]", x)
        else:
            out[prefix] = v

    for k, v in ev.detail.items():
        walk(k, v)
    return out


def rel_dev(got: float, want: float) -> float:
    """Relative deviation; equal values (infinities included) give 0, a
    non-zero value against an exact zero gives inf."""
    if got == want:
        return 0.0
    if want == 0.0 or not np.isfinite(want) or not np.isfinite(got):
        return float("inf")
    return abs(got - want) / abs(want)


def compare_points(got: list, want: list) -> tuple[float, int, str]:
    """(largest relative deviation of any float statistic, number of points
    with any other statistic unequal, the statistic that deviated most)."""
    worst, worst_key, unequal = 0.0, "", 0
    for g, w in zip(got, want):
        gs, ws = flat_stats(g), flat_stats(w)
        bad = gs.keys() != ws.keys()
        for k in ws.keys() & gs.keys():
            a, b = gs[k], ws[k]
            if isinstance(b, float) and not isinstance(b, bool):
                d = rel_dev(float(a), b)
                if d > worst:
                    worst, worst_key = d, k
            elif a != b:
                bad = True
        unequal += bad
    return worst, unequal, worst_key


def leaf_gap(got: dict[str, float], want: dict[str, float],
             skip: set[str] = frozenset()) -> tuple[float, str]:
    """Worst leaf's |norm_got - norm_want| / max(norm_want, median norm)."""
    floor = float(np.median([want[k] for k in want if k not in skip]))
    worst, where = 0.0, ""
    for k, w in want.items():
        if k in skip:
            continue
        d = abs(got[k] - w) / max(w, floor)
        if d > worst:
            worst, where = d, k
    return worst, where


def loss_gap(got: list[float], want: list[float]) -> float:
    return max(abs(g - w) / abs(w) for g, w in zip(got, want))
