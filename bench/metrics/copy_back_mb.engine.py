"""What the compiled call copies back to the host (``jax_backend.simulate_batch``):
the program's counter ``repro.engine.copy_back_bytes``, mean MB (1e6 bytes)
per window generation.  None where the program has no recorder or no such
counter."""


def read(ctx):
    try:
        from repro.runtime.spans import COUNTERS, RECORDER
    except ImportError:
        return None
    name = "repro.engine.copy_back_bytes"
    if name not in COUNTERS:
        return None
    rows = RECORDER.window(COUNTERS[name], ctx["obs"].get("generations"),
                           getattr(ctx["trace"], "units", 0))
    if rows is None:
        return None
    return float(rows[name].mean()) / 1e6
