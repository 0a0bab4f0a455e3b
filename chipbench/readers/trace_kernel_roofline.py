"""A kernel's share of its roofline: the least time the chip could take
for the calls made (the ``counts`` function from the cell's shapes, peaks
from ``peaks.json``) over the kernel's summed device time in the trace.
``counts`` names a function of ``kernel_counts.py``, or
``<module>:<function>`` of another module of ``chipbench/``, so that a
configuration can bring its kernel's counts as a file of its own.  The
kernel's operations are found by ``match``, a regular expression over the
trace's operation names; ``calls_per`` says how many calls the stretch made
(one per chunk)."""
import importlib

from .. import kernel_counts, trace_reduce
from .common import chunks_of


def read(source, ctx):
    tr = ctx.get("trace")
    if not tr or not tr.get("op_seconds"):
        return None
    seconds, names = trace_reduce.kernel_seconds(tr["op_seconds"],
                                                 source["match"])
    if not seconds:
        return None
    module, _, function = source["counts"].rpartition(":")
    counts_of = getattr(importlib.import_module("chipbench." + module)
                        if module else kernel_counts, function)
    counts = counts_of(**ctx["shapes"])
    least, roof = kernel_counts.roofline_seconds(counts, ctx["peaks"])
    calls = len(chunks_of(ctx["passes"]))
    ctx["notes"].append(
        f"{source['counts']}: {calls} calls, least {least * 1e3:.3f} ms "
        f"each ({roof} roof), device time {seconds:.4f} s over "
        f"{len(names)} operation names")
    return 100.0 * least * calls / seconds
