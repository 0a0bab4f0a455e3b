"""What a call of the entry point costs outside its chunks: pass wall
minus the sum of its chunk walls (side-car load, plan, reader, sift)."""
from .common import normalise, passes_of


def read(source, ctx):
    passes = passes_of(source, ctx)
    if not passes or any(not p["budget"] for p in passes):
        return None
    total = sum(p["wall_s"] - sum(c["wall_s"]
                                  for c in p["budget"]["per_chunk"])
                for p in passes)
    return normalise(total, dict(source, per="pass"), passes)
