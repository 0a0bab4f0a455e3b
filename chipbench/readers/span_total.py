"""Summed duration of the program's spans of one name (``obs/trace.py``,
recorded while the harness had its span tracer on)."""
from .common import normalise, passes_of


def read(source, ctx):
    passes = passes_of(source, ctx)
    durs = [e - s for p in passes for s, e, name in p["spans"]
            if name == source["key"]]
    if not durs:
        return None
    return normalise(sum(durs), source, passes)
