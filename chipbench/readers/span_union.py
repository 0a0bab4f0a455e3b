"""Seconds covered by the program's spans whose name matches ``match``, a
regular expression over the names of the pass's ``X`` spans
(``obs/trace.py``, recorded while the harness had its span tracer on): the
union of their intervals, so that a span nested inside another that also
matches (a program built inside its parent's build phase) is not counted
twice.  A program that records no such span gives nothing to read."""
import re

from .. import trace_reduce
from .common import normalise, passes_of


def read(source, ctx):
    rx = re.compile(source["match"])
    passes = passes_of(source, ctx)
    total, found = 0.0, False
    for p in passes:
        hit = [(s, e) for s, e, name in p["spans"] if rx.search(name)]
        found = found or bool(hit)
        total += sum(e - s for s, e in trace_reduce.union(hit))
    if not found:
        return None
    return normalise(total, source, passes)
