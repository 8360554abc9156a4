"""Share of the traced window in which a collective runs on a device and no
other operation does (the worst device)."""
import readers
import tracing


def read(ctx):
    w = readers.window_ns(ctx)
    if w is None:
        return None
    share = tracing.exposed_collective_share(ctx["trace"], *w)
    return None if share is None else 100.0 * share
