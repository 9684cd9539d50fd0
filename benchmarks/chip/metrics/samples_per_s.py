"""Client samples consumed by local gradient steps over the whole window:
rounds completed x clients x tau x batch, over the time from the window's
start to the end of its last chunk (host clock)."""


def read(ctx):
    return ctx.samples / ctx.window_s if ctx.window_s > 0 else None
