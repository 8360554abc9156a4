"""The windowed layers' flash attention, forward + backward: the least time
the chip could take for the family's ``work.kernels["flash_attn_window"]``
(the windowed layers' pairs at their own head count, counted from the
mathematics: a query's mean of keys inside the window, not the blocks a
kernel visits) over the self time, on the first device, of the Pallas kernels
that are no grouped product and whose operands carry the windowed layers'
head count (``work.window_kernel_operand``: where the kinds differ in head
count, the trace's names tell their kernels apart by it).  None for a family
without either, or whose kinds have one head count."""
import readers
import routed_ops


def read(ctx):
    operand = getattr(ctx["work"], "window_kernel_operand", None)
    if operand is None:
        return None
    rows, seq = ctx["rows"] // ctx["chips"], ctx["seq"]
    shape = operand(ctx["config"], rows, seq)
    if shape is None:
        return None
    steps = len(readers.program_runs(ctx, with_kernels=True))
    return readers.kernel_roofline_pct(
        ctx, "flash_attn_window", steps,
        routed_ops.seconds(ctx, lambda n: routed_ops.is_flash(n)
                           and shape in n),
        rows=rows, seq=seq)
