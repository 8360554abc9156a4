"""Paged decode attention: K/V bytes of the live context of the window's
decode steps over the HBM bandwidth (memory-bound), over the kernel's time."""
import readers
import work


def read(ctx):
    secs = readers.kernel_seconds(ctx)
    ctx_tokens = ctx["counters"]["window_decode_context_tokens"]
    if not secs or not ctx_tokens:
        return None
    nbytes = work.decode_attn_bytes(ctx["config"], ctx_tokens)
    return readers.share_pct(nbytes / ctx["peak"]["hbm_bytes_per_s"], secs)
