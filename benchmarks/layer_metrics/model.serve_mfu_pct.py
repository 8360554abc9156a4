"""The whole serving step's share of the chip's bf16 peak: FLOPs of every
prompt and output token processed in the window over the window x peak."""
import readers


def read(ctx):
    c, b = ctx["counters"], ctx["book"]
    prompts = [len(b.req[r].prompt) for r, t in b.first.items()
               if ctx["t_open"] <= t < ctx["t_close"]]
    need = (sum(ctx["work"].prompt_flops(ctx["config"], n) for n in prompts)
            + ctx["work"].decode_flops(ctx["config"], c["window_decode_tokens"],
                                c["window_decode_context_tokens"]))
    have = ctx["window_s"] * ctx["peak"]["bf16_flops_per_s"]
    return readers.share_pct(need, have)
