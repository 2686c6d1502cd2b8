"""The per-step loss read-back (``launch/train.train_loop``:
``float(metrics["loss"])``, which waits for the step on the device): the
program's span ``repro.train.loss_sync``, mean ms per window step.  None
where the program has no recorder."""


def read(ctx):
    try:
        from repro.runtime.spans import window_mean_ms
    except ImportError:
        return None
    return window_mean_ms(["repro.train.loss_sync"], ctx["obs"].get("steps"),
                          getattr(ctx["trace"], "units", 0))
