"""The share of the scheduling sweep that runs as the Pallas kernel
(``jax_backend.simulate_batch``): the window mean of the program's counter
``repro.engine.sweep_kernel_ops`` over that of ``repro.engine.sweep_ops``
(the plan's ops, once a call), a fraction from 0 (the float64 loop) to 1.
None where the program has no recorder or no such counters, or the window
swept nothing."""


def read(ctx):
    try:
        from repro.runtime.spans import COUNTERS, RECORDER
    except ImportError:
        return None
    names = ("repro.engine.sweep_kernel_ops", "repro.engine.sweep_ops")
    if any(n not in COUNTERS for n in names):
        return None
    rows = RECORDER.window(COUNTERS[names[0]], ctx["obs"].get("generations"),
                           getattr(ctx["trace"], "units", 0))
    if rows is None:
        return None
    swept = float(rows[names[1]].mean())
    return float(rows[names[0]].mean()) / swept if swept else None
