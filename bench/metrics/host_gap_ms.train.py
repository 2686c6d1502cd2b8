"""The training loop's host work around the step (``launch/train.train_loop``:
taking the next batch, ``device_put``, the jitted step returning, and the
straggler monitor, heartbeat, checkpoint and log): the self times of the
program's spans ``repro.train.input``, ``repro.train.put``,
``repro.train.dispatch`` and ``repro.train.bookkeeping``, summed, mean ms
per window step.  None where the program has no recorder."""

SPANS = ["repro.train.input", "repro.train.put", "repro.train.dispatch",
         "repro.train.bookkeeping"]


def read(ctx):
    try:
        from repro.runtime.spans import window_mean_ms
    except ImportError:
        return None
    return window_mean_ms(SPANS, ctx["obs"].get("steps"),
                          getattr(ctx["trace"], "units", 0), own=True)
