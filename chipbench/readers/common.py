"""Shared helpers of the readers: which passes a metric reads and how a
total is normalised."""


def passes_of(source, ctx):
    return [ctx["cold"]] if source.get("pass") == "cold" else ctx["passes"]


def chunks_of(passes):
    return [c for p in passes for c in (p["budget"] or {}).get("per_chunk", [])]


def normalise(total, source, passes, nhit=None):
    per = source.get("per", "chunk")
    if per == "total":
        n = 1
    elif per == "pass":
        n = len(passes)
    elif per == "hit_chunk":
        n = nhit
    else:
        n = len(chunks_of(passes))
    if not n:
        return None
    return total / n * source.get("scale", 1.0)
