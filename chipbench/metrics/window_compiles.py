"""window_compiles: backend compiles inside the window, counted by the
harness's ``jax.monitoring`` listener. Every shape should be warm, so
this should read 0."""


def read(ctx):
    return ctx.compiles
