"""Delta of a counter of the program's metrics registry over the passes
read (``obs/metrics.py`` REGISTRY totals, taken by the harness around each
pass)."""
from .common import normalise, passes_of


def read(source, ctx):
    passes = passes_of(source, ctx)
    # a counter the program never touched is not in its registry: 0
    vals = [p["registry_delta"].get(source["key"], 0) for p in passes]
    if not vals:
        return None
    return normalise(float(sum(vals)), source, passes)
