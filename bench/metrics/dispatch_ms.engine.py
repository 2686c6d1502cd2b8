"""Dispatching the compiled pricing + sweep (``jax_backend.simulate_batch``:
``_fused_eval(plan)(...)`` returning): the program's span
``repro.engine.dispatch``, mean ms per window generation.  None where the
program has no recorder."""


def read(ctx):
    try:
        from repro.runtime.spans import window_mean_ms
    except ImportError:
        return None
    return window_mean_ms(["repro.engine.dispatch"], ctx["obs"].get("generations"),
                          getattr(ctx["trace"], "units", 0))
