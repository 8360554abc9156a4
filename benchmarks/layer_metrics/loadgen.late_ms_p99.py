"""How late the generator ran: submitted minus due, 99th percentile over
the window's requests (it can only submit between ``step()`` calls)."""
import readers


def read(ctx):
    b = ctx["book"]
    return readers.percentile(((b.submitted[r] - b.due[r]) * 1e3
                               for r in ctx["window_requests"]), 99)
