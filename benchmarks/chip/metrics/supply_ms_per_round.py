"""Host milliseconds per round spent in the batch supplier: the harness's
``bench/supply`` spans (every call into the supplier) inside the traced
window, on the profiler's clock, over the rounds of the window."""


def read(ctx):
    if ctx.trace is None or ctx.trace.window() is None or not ctx.rounds:
        return None
    lo, hi = ctx.trace.window()
    ns = sum(min(s + d, hi) - max(s, lo) for _, s, d in
             ctx.trace.spans("bench/supply") if s < hi and s + d > lo)
    return ns * 1e-6 / ctx.rounds
