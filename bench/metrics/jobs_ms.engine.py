"""Building every point's simulator job (``core/env.CosmicEnv._eval_many``:
the point's context, parallelism and memory gate, its trace looked up or
generated): the program's span ``repro.engine.jobs``, mean ms per window
generation.  None where the program has no recorder."""


def read(ctx):
    try:
        from repro.runtime.spans import window_mean_ms
    except ImportError:
        return None
    return window_mean_ms(["repro.engine.jobs"], ctx["obs"].get("generations"),
                          getattr(ctx["trace"], "units", 0))
