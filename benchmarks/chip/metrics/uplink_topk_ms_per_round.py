"""Device milliseconds per round of the operations that find the k-th
largest magnitude for the global top-k uplink (``lax.top_k``, which the
TPU lowers to a ``sort`` of each client's plane), over the traced window,
on the chip that spends the most on them."""
import re

from chipbench import trace as tr

#: names of the device ops that implement lax.top_k on a TPU v5e (a sort
#: of (10, 112512) values with their indices, seen in a recorded trace)
TOPK_OPS = re.compile(r"(sort|top-?k)\b", re.IGNORECASE)


def read(ctx):
    if ctx.trace is None or ctx.trace.window() is None or not ctx.rounds:
        return None
    ops = ctx.trace.device_ops()
    if not ops:
        return None
    lo, hi = ctx.trace.window()
    ns = max(tr.op_time_ns(ev, TOPK_OPS, lo, hi) for ev in ops.values())
    return ns * 1e-6 / ctx.rounds if ns > 0 else None
