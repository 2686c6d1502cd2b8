"""Waiting for the device (``jax_backend.simulate_batch``:
``block_until_ready`` on the durations and finish times): the program's
span ``repro.engine.device_wait``, mean ms per window generation.  None
where the program has no recorder."""


def read(ctx):
    try:
        from repro.runtime.spans import window_mean_ms
    except ImportError:
        return None
    return window_mean_ms(["repro.engine.device_wait"], ctx["obs"].get("generations"),
                          getattr(ctx["trace"], "units", 0))
