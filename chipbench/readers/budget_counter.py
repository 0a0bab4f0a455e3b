"""Per-chunk counters of the budget footer (dispatches, readbacks, ...),
``keys`` summed."""
from .common import chunks_of, normalise, passes_of


def read(source, ctx):
    passes = passes_of(source, ctx)
    chunks = chunks_of(passes)
    if not chunks:
        return None
    total = sum(c["counters"].get(k, 0) for c in chunks
                for k in source["keys"])
    return normalise(float(total), source, passes)
