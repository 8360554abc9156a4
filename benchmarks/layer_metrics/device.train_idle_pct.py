"""1 - union of device operations over the traced window, the device that
idles most."""
import readers


def read(ctx):
    return readers.idle_pct(ctx)
