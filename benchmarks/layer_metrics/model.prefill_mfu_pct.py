"""FLOPs the window's batch prefills require (causal forward over each
admitted prompt, less what was teacher-forced inside decode blocks) over
the device time of the prefill programs x the bf16 peak."""
import readers


def read(ctx):
    c, b = ctx["counters"], ctx["book"]
    runs = readers.program_runs(ctx, name=r"prefill")
    prompts = [len(b.req[r].prompt) for r, t in b.first.items()
               if ctx["t_open"] <= t < ctx["t_close"]]
    if not runs or not prompts:
        return None
    batch_share = max(0.0, 1.0 - c.get("inblock_prefill_steps", 0.0)
                      / max(sum(prompts), 1))
    need = batch_share * sum(ctx["work"].prompt_flops(ctx["config"], n)
                             for n in prompts)
    secs = 1e-9 * sum(d for _, d in runs)
    return readers.share_pct(need / ctx["peak"]["bf16_flops_per_s"], secs)
