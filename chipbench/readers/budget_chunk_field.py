"""Mean of one field of the budget footer's per-chunk records
(``BUDGET_JSON.per_chunk[].<key>``) over the chunks that carry it; a
program whose records lack the field gives nothing to read."""
from .common import chunks_of, passes_of


def read(source, ctx):
    vals = [c[source["key"]] for c in chunks_of(passes_of(source, ctx))
            if source["key"] in c]
    if not vals:
        return None
    return sum(vals) / len(vals) * source.get("scale", 1.0)
