"""Flash attention forward + backward: the least time the chip could take
for the attention of the steps in the traced window (max of FLOPs / peak and
bytes / bandwidth, from shapes; compute-bound at these shapes) over the
kernels' time on the first device."""
import readers
import work


def read(ctx):
    secs = readers.kernel_seconds(ctx)
    steps = len(readers.program_runs(ctx, with_kernels=True))
    if not secs or not steps:
        return None
    w = work.flash_attn_work(ctx["config"], ctx["rows"] // ctx["chips"],
                             ctx["seq"])
    least = work.roofline_seconds(w["flops"], w["bytes"], ctx["peak"])
    return readers.share_pct(steps * least["seconds"], secs)
