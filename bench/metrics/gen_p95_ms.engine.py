"""A whole generation (``CosmicEnv.step_batch``, the population handed in
until its rewards are back): the 95th percentile of the wall time of every
generation of the window, in ms.  The engine runs as a closed loop at full
speed, so its rate is the end-to-end metric and this tail stands beside it."""
import numpy as np


def read(ctx):
    walls = ctx["obs"].get("gen_wall_s")
    return float(np.percentile(walls, 95)) * 1e3 if walls else None
