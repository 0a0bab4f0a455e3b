"""Share of the traced stretch in which no operation ran on the device:
1 - union of the device-op intervals / stretch (``trace_reduce.py``)."""


def read(source, ctx):
    tr = ctx.get("trace")
    if not tr or not tr["window_s"] or not tr["busy_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
