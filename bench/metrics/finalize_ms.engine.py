"""Scenario finalize and rewards (``core/backends/base.run_sim_jobs``: every
job's ``finalize``): the program's span ``repro.engine.finalize``, mean ms
per window generation.  None where the program has no recorder."""


def read(ctx):
    try:
        from repro.runtime.spans import window_mean_ms
    except ImportError:
        return None
    return window_mean_ms(["repro.engine.finalize"], ctx["obs"].get("generations"),
                          getattr(ctx["trace"], "units", 0))
