"""Executables obtained inside the measured window (expect 0)."""


def read(ctx):
    return ctx["counters"]["inwindow_compiles"]
