"""The whole step's share of the chips' bf16 peak: the forward and
backward FLOPs the model requires per sample (counted from its shapes by
the reference's ``flops_per_sample``, recomputation not counted) times
samples per second, over chips x peak (``peaks.json``), in %."""


def read(ctx):
    peak = ctx.peaks.get("bf16_flops")
    if not peak or ctx.window_s <= 0:
        return None
    rate = ctx.samples / ctx.window_s
    return 100.0 * ctx.flops_per_sample * rate / (ctx.chips * peak)
