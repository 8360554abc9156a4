"""K/V bytes of the context the server held live, averaged over the window:
each request's prompt and tokens so far, from its first token to its last,
by the benchmark's per-request clock (the family's
``work.kv_bytes_per_token`` a position).  ``device.serve_hbm_peak_gb`` beside it counts the whole page
pool, which is reserved whether or not the traffic fills it."""


def read(ctx):
    held = ctx["counters"].get("window_live_token_s")
    if not held or not ctx["window_s"]:
        return None
    per_token = ctx["work"].kv_bytes_per_token(ctx["config"])
    return (held / ctx["window_s"]) * per_token / 1e9
