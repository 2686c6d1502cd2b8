"""Host assembly (``jax_backend.simulate_batch``: busy accounting and the
``build_sim_result`` loop): the program's span ``repro.engine.assemble``,
mean ms per window generation.  None where the program has no recorder."""


def read(ctx):
    try:
        from repro.runtime.spans import window_mean_ms
    except ImportError:
        return None
    return window_mean_ms(["repro.engine.assemble"], ctx["obs"].get("generations"),
                          getattr(ctx["trace"], "units", 0))
