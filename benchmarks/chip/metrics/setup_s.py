"""Set-up time: process start to the window's start (JAX start, planes from
the seed, resident planes on the device, the cell's shapes compiled or
loaded from the compile cache)."""


def read(ctx):
    return ctx.setup_s
