"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals / window), averaged over the
cell's chips, in %."""
from chipbench import trace as tr


def read(ctx):
    if ctx.trace is None or ctx.trace.window() is None:
        return None
    ops = ctx.trace.device_ops()
    if not ops:
        return None
    lo, hi = ctx.trace.window()
    busy = [tr.busy_ns(ev, lo, hi) / (hi - lo) for ev in ops.values()]
    return 100.0 * (1.0 - sum(busy) / len(busy))
