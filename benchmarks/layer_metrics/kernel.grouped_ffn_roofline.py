"""The held experts' gate, up and down products, forward + backward: the
least time the chip could take for them over the steps in the traced window
(the family's ``work.kernels["grouped_ffn"]`` under even routing: 3 products
x 3 passes x 2 x rows x d x f a layer, compute-bound) over the time of the
operations named ``ragged-dot`` on the first device."""
import readers
import routed_ops


def read(ctx):
    steps = len(readers.program_runs(ctx, with_kernels=True))
    return readers.kernel_roofline_pct(
        ctx, "grouped_ffn", steps,
        routed_ops.seconds(ctx, routed_ops.is_grouped),
        tokens=ctx["tokens_per_step"] // ctx["chips"])
