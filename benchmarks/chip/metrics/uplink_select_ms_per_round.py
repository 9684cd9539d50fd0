"""Device milliseconds per round of the threshold select of the global
top-k uplink (the Pallas kernel ``threshold_select_3d``), over the traced
window, on the chip that spends the most on it.

A time, not a roofline share: XLA places the select's (n, d_pad) plane in
the chip's on-core memory (layout ``S(1)``), where it moves its 9 MB per
round at 1.7 TB/s, above the HBM peak of ``peaks.json``, and no published
bandwidth of that memory is at hand to bound it."""
import re

from chipbench import trace as tr

#: the kernel's name in a recorded trace
SELECT_OPS = re.compile(r"threshold_select")


def read(ctx):
    if ctx.trace is None or ctx.trace.window() is None or not ctx.rounds:
        return None
    ops = ctx.trace.device_ops()
    if not ops:
        return None
    lo, hi = ctx.trace.window()
    ns = max(tr.op_time_ns(ev, SELECT_OPS, lo, hi) for ev in ops.values())
    return ns * 1e-6 / ctx.rounds if ns > 0 else None
