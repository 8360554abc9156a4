"""Median time between consecutive steps finishing, by the host's clock on
``block_until_ready`` (the host keeps two steps in flight, so in steady
state this is the device's time per step)."""
import numpy as np


def read(ctx):
    done = np.asarray(ctx["done_at"])
    if len(done) < 3:
        return None
    return float(np.median(np.diff(done)) * 1e3)
