"""Copying the results to the host (``jax_backend.simulate_batch``: the two
``np.asarray`` and their transposes): the program's span
``repro.engine.copy_back``, mean ms per window generation.  None where the
program has no recorder."""


def read(ctx):
    try:
        from repro.runtime.spans import window_mean_ms
    except ImportError:
        return None
    return window_mean_ms(["repro.engine.copy_back"], ctx["obs"].get("generations"),
                          getattr(ctx["trace"], "units", 0))
