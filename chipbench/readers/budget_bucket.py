"""Seconds the program's BudgetAccountant put in named buckets of its
chunks (host clock around blocking work).  ``keys`` are summed;
``per: hit_chunk`` divides by the chunks in which any of them is present."""
from .common import chunks_of, normalise, passes_of


def read(source, ctx):
    passes = passes_of(source, ctx)
    total, nhit = 0.0, 0
    for c in chunks_of(passes):
        vals = [c["buckets"][k] for k in source["keys"] if k in c["buckets"]]
        if vals:
            nhit += 1
            total += sum(vals)
    if not nhit:
        return None
    return normalise(total, source, passes, nhit)
