"""Device seconds of the operations whose name matches ``match``, a
regular expression over the trace's ``<program>/<operation>`` names
(``trace_reduce.py``): what the device itself spent on a layer, to put
beside the host bucket that waits for it."""
from .. import trace_reduce
from .common import normalise, passes_of


def read(source, ctx):
    tr = ctx.get("trace")
    if not tr or not tr.get("op_seconds"):
        return None
    seconds, names = trace_reduce.kernel_seconds(tr["op_seconds"],
                                                 source["match"])
    if not names:
        return None
    return normalise(seconds, source, passes_of(source, ctx))
