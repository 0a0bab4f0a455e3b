"""Wall of the entry point's call itself (host clock around
``PUsearchfrb``'s ``main()``), mean over the passes read: with
``pass: cold`` the first search of a file never seen before."""
from .common import normalise, passes_of


def read(source, ctx):
    passes = passes_of(source, ctx)
    if not passes:
        return None
    return normalise(sum(p["wall_s"] for p in passes),
                     dict(source, per="pass"), passes)
