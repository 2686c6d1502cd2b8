"""What the step's all-to-alls move (``launch/train.CompiledStep``: the
compiled HLO's all-to-all output bytes on one chip, through the layer scan):
the program's counter ``repro.train.all_to_all_bytes``, mean GB (1e9 bytes)
per window step.  None where the program has no recorder or no such
counter."""


def read(ctx):
    try:
        from repro.runtime.spans import COUNTERS, RECORDER
    except ImportError:
        return None
    name = "repro.train.all_to_all_bytes"
    if name not in COUNTERS:
        return None
    rows = RECORDER.window(COUNTERS[name], ctx["obs"].get("steps"),
                           getattr(ctx["trace"], "units", 0))
    if rows is None:
        return None
    return float(rows[name].mean()) / 1e9
