"""A pass's first chunk wall minus the median of its other chunks', mean
over passes: the re-trace and the read that nothing overlaps."""
import statistics

from .common import passes_of


def read(source, ctx):
    extras = []
    for p in passes_of(source, ctx):
        walls = [c["wall_s"] for c in (p["budget"] or {}).get("per_chunk", [])]
        if len(walls) >= 2:
            extras.append(walls[0] - statistics.median(walls[1:]))
    if not extras:
        return None
    return statistics.fmean(extras) * source.get("scale", 1.0)
