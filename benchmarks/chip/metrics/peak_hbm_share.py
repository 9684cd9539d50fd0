"""Peak device memory in use after the window over the chip's limit
(``memory_stats()``), on the fullest chip, in %."""


def read(ctx):
    m = ctx.memory
    if not m.get("limit_bytes"):
        return None
    return 100.0 * m["peak_bytes"] / m["limit_bytes"]
