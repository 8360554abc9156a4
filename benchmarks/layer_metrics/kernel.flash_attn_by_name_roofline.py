"""``kernel.flash_attn_roofline`` for a step that holds grouped products
too: the least time for the family's ``work.kernels["flash_attn"]`` (global
and windowed layers counted apart) over the time of the Pallas kernels that
are no grouped product (``routed_ops.is_flash``) on the first device."""
import readers
import routed_ops


def read(ctx):
    steps = len(readers.program_runs(ctx, with_kernels=True))
    return readers.kernel_roofline_pct(
        ctx, "flash_attn", steps,
        routed_ops.seconds(ctx, routed_ops.is_flash),
        rows=ctx["rows"] // ctx["chips"], seq=ctx["seq"])
