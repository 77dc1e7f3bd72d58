"""JAX's backend compiles and compile-cache loads counted (by
``jax.monitoring``) between the window's start and its end. Set-up warms
every shape, so this should read 0."""


def read(ctx):
    return float(ctx.compiles)
