"""A kernel's share of its roofline where the work follows a count the
program keeps: the least time the chip could take for the rows the passes
read asked for (the ``counts`` function, ``<module>:<function>`` of
``chipbench/``, from the cell's shapes and ``rows=`` the delta of the
registry counter ``rows_key`` over those passes; peaks from
``peaks.json``) over the kernel's summed device time in the trace.  One
call's worth of bytes for each pass that asked for any row
(``trace_kernel_roofline.py`` counts one call a chunk).  A program
without the counter, or a stretch in which no row was asked for or no
operation matched, gives nothing to read."""
import importlib

from .. import kernel_counts, trace_reduce
from .common import passes_of


def read(source, ctx):
    tr = ctx.get("trace")
    if not tr or not tr.get("op_seconds"):
        return None
    seconds, names = trace_reduce.kernel_seconds(tr["op_seconds"],
                                                 source["match"])
    rows = [p["registry_delta"].get(source["rows_key"], 0)
            for p in passes_of(source, ctx)]
    if not seconds or not any(rows):
        return None
    module, _, function = source["counts"].rpartition(":")
    counts_of = getattr(importlib.import_module("chipbench." + module),
                        function)
    least = sum(kernel_counts.roofline_seconds(
        counts_of(**ctx["shapes"], rows=int(r)), ctx["peaks"])[0]
        for r in rows if r)
    ctx["notes"].append(
        f"{source['counts']}: rows a pass {[int(r) for r in rows]}, least "
        f"{least * 1e3:.3f} ms in all (memory roof), device time "
        f"{seconds:.4f} s over {len(names)} operation names")
    return 100.0 * least / seconds
