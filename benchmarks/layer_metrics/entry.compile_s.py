"""Seconds spent obtaining executables (compiling, or loading them from the
persistent cache) during set-up, by JAX's monitoring events."""


def read(ctx):
    return ctx["counters"]["compile_s"]
