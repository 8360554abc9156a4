"""The whole step's share of the chips' bf16 peak: tokens/s times the FLOPs
a training token requires (recomputation not counted) over chips x peak."""
import readers


def read(ctx):
    if not ctx["steps"]:
        return None
    need = (ctx["steps"] * ctx["tokens_per_step"]
            * ctx["work"].train_flops_per_token(ctx["config"], ctx["seq"]))
    have = ctx["window_s"] * ctx["chips"] * ctx["peak"]["bf16_flops_per_s"]
    return readers.share_pct(need, have)
