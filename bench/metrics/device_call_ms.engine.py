"""Device pricing + sweep (``core/backends/jax_backend._fused_eval``): the
backend's ``last_timings["sweep_s"]`` (host clock around the compiled call
and its copy back) less the compile seconds JAX reported inside it; summed
per generation, mean ms per window generation."""
import numpy as np


def read(ctx):
    call = ctx["obs"].get("call_s")
    return float(np.mean(call)) * 1e3 if call else None
