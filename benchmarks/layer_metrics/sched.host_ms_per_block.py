"""The scheduler's own host time per decode block: ``timing_stats()``
host_plan + dispatch + host_parse over the window's dispatches.  ``fetch``
is left out: it is the host waiting for the block's results, that is the
device's time (with chaining, nearly a whole block), not the host's work."""


def read(ctx):
    c = ctx["counters"]
    n = c.get("decode_dispatches", 0)
    if not n:
        return None
    host = sum(c.get(f"span.{p}.s", 0.0)
               for p in ("host_plan", "dispatch", "host_parse"))
    return 1e3 * host / n
