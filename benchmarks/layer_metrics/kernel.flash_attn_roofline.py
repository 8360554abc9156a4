"""Flash attention forward + backward: the least time the chip could take
for the attention of the steps in the traced window (max of FLOPs / peak and
bytes / bandwidth, from shapes, as the family's ``work.kernels["flash_attn"]``
counts them; compute-bound at these shapes) over the kernels' time on the
first device."""
import readers


def read(ctx):
    steps = len(readers.program_runs(ctx, with_kernels=True))
    return readers.kernel_roofline_pct(
        ctx, "flash_attn", steps, readers.kernel_seconds(ctx),
        rows=ctx["rows"] // ctx["chips"], seq=ctx["seq"])
