"""Seconds from the process's start to the window's: imports, data,
weights, building the engine, compiling (or loading from the cache) and
the three steps the comparison reads (host clock)."""


def read(ctx):
    return ctx.setup_s
