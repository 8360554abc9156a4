"""FLOPs the window's decode steps require (2 per parameter per token they
emit or teacher-force, plus attention over the live context) over the
device time of the decode programs x the bf16 peak."""
import readers


def read(ctx):
    c = ctx["counters"]
    runs = readers.program_runs(ctx, with_kernels=True)
    if not runs:
        return None
    need = ctx["work"].decode_flops(
        ctx["config"],
        c["window_decode_tokens"] + c.get("inblock_prefill_steps", 0.0),
        c["window_decode_context_tokens"])
    secs = 1e-9 * sum(d for _, d in runs)
    return readers.share_pct(need / ctx["peak"]["bf16_flops_per_s"], secs)
