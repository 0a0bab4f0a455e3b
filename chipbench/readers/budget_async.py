"""Seconds of work the program overlapped onto other threads
(``BUDGET_JSON.async_s``: read_decode, persist)."""
from .common import normalise, passes_of


def read(source, ctx):
    passes = passes_of(source, ctx)
    vals = [(p["budget"] or {}).get("async_s", {}).get(source["key"])
            for p in passes]
    if any(v is None for v in vals) or not vals:
        return None
    return normalise(sum(vals), source, passes)
