"""A span's self time: the summed duration of the program's spans of one
name minus the part of each that its other spans cover (``obs/trace.py``
``X`` spans of the pass, recorded while the harness had its span tracer
on).  What is left is time inside the span that no span names.  Children
may overlap or nest: the covered part is the union of their intervals,
clipped to the span."""
from .. import trace_reduce
from .common import normalise, passes_of


def read(source, ctx):
    passes = passes_of(source, ctx)
    total, found = 0.0, False
    for p in passes:
        for lo, hi, name in p["spans"]:
            if name != source["key"]:
                continue
            found = True
            inside = [(s, e) for s, e, other in p["spans"]
                      if (s, e, other) != (lo, hi, name)]
            covered = trace_reduce.union(inside, lo, hi)
            total += (hi - lo) - sum(e - s for s, e in covered)
    if not found:
        return None
    return normalise(total, source, passes)
