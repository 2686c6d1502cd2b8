"""Host assembly, scenario finalize and rewards (``jax_backend.simulate_batch``
after the call, ``core/scenario``, ``core/rewards``): generation wall time
less packing and the device call; mean ms per window generation."""
import numpy as np


def read(ctx):
    obs = ctx["obs"]
    if not obs.get("gen_wall_s"):
        return None
    rest = (np.array(obs["gen_wall_s"]) - np.array(obs["pack_s"])
            - np.array(obs["call_s"]))
    return float(np.mean(rest)) * 1e3
