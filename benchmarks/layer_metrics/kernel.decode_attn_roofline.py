"""Paged decode attention: what the family's ``work.kernels["decode_attn"]``
counts for the live context of the window's decode steps (K/V bytes over the
HBM bandwidth: memory-bound) over the kernel's time."""
import readers


def read(ctx):
    return readers.kernel_roofline_pct(
        ctx, "decode_attn", 1, readers.kernel_seconds(ctx),
        context_tokens=ctx["counters"]["window_decode_context_tokens"])
