"""Device idle share over the traced tail of engine generations: one less
the union of the device's operation intervals over the tail's length, in %,
averaged over the cell's chips."""


def read(ctx):
    trace = ctx["trace"]
    return None if trace is None else trace.idle_share * 100.0
