"""XLA compilations inside the measured window, counted from JAX's
``/jax/core/compile/backend_compile_duration`` events.  Should be 0."""


def read(ctx):
    return float(ctx.compiles_in_window)
