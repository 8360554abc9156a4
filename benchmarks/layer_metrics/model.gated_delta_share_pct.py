"""Share of the first device's busy time in the traced window that the
linear layers' delta rule and causal convolution take, forward and
backward: the operations whose result shape is one of theirs
(``work.gated_delta_operands``).  None for a family without it."""
import routed_ops


def read(ctx):
    operands = getattr(ctx["work"], "gated_delta_operands", None)
    if operands is None or not ctx.get("reduced"):
        return None
    found = operands(ctx["config"], ctx["rows"] // ctx["chips"], ctx["seq"])
    runs = found["delta_rule"] + found["conv"]
    mine = routed_ops.seconds(ctx, lambda n: any(
        r in n.partition(" = ")[2] for r in runs))
    busy = ctx["reduced"]["busy_s_per_device"]
    if not mine or not busy or not busy[0]:
        return None
    return 100.0 * mine / busy[0]
