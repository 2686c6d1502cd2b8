"""Table packing (``core/simulator.plan_duration_tables``): the backend's own
``last_timings["durations_s"]``, summed over each generation's
``simulate_batch`` calls by the harness; mean ms per window generation."""
import numpy as np


def read(ctx):
    pack = ctx["obs"].get("pack_s")
    return float(np.mean(pack)) * 1e3 if pack else None
