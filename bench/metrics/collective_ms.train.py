"""Collectives across chips (the all-reduce, all-gather, reduce-scatter,
collective-permute and all-to-all operations the partitioner puts into the
step over a mesh): their device time over the traced tail
(``bench/trace_reduce``'s ``collective_s``, averaged over the cell's chips),
mean ms per tail step.  None without a trace or its steps."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None or not getattr(trace, "units", 0):
        return None
    return trace.collective_s / trace.units * 1e3
