"""Python's garbage collector (the interpreter under the engine: the env's
evaluation memo and the per-point packing memo grow through a campaign, and
every full collection walks them): pause seconds inside each window
generation, from ``gc.callbacks``; mean ms per window generation."""
import numpy as np


def read(ctx):
    gc_s = ctx["obs"].get("gc_s")
    return float(np.mean(gc_s)) * 1e3 if gc_s else None
