"""The linear layers' delta rule, forward + backward: the least time the
chip could take for the family's ``work.kernels["gated_delta"]`` (FLOPs from
the mathematics of the chunked form at chunks of 64, bytes of q, k, v, g,
beta in, o out and the state once a chunk) over the self time, on the first
device, of the operations whose result shape is one of the delta rule's
(``work.gated_delta_operands``: the delta rule runs as XLA operations, so
their shapes, not a kernel's name, tell them).  None for a family without
either."""
import readers
import routed_ops


def read(ctx):
    operands = getattr(ctx["work"], "gated_delta_operands", None)
    if operands is None:
        return None
    rows, seq = ctx["rows"] // ctx["chips"], ctx["seq"]
    runs = operands(ctx["config"], rows, seq)["delta_rule"]
    steps = len(readers.program_runs(ctx, with_kernels=True))
    return readers.kernel_roofline_pct(
        ctx, "gated_delta", steps,
        routed_ops.seconds(ctx, lambda n: any(
            r in n.partition(" = ")[2] for r in runs)),
        rows=rows, seq=seq)
