"""How much of the linear layers' delta rule runs inside its own kernels:
the self time, on the first device inside the traced window, of the Pallas
kernels whose own name starts with ``gated_delta`` (``kernel_names``: the
forward ``gated_delta_fwd`` and the backward ``gated_delta_bwd``) over the
self time of every operation that ``kernel.gated_delta_roofline`` counts
(a result shape with one of ``work.gated_delta_operands``' runs: the
kernels and the XLA operations left around them).  None for a family
without the delta rule, and where no kernel carries the name (a program
whose delta rule runs as XLA operations alone)."""
import kernel_names
import routed_ops


def read(ctx):
    operands = getattr(ctx["work"], "gated_delta_operands", None)
    if operands is None:
        return None
    mine = kernel_names.seconds(ctx, "gated_delta")
    if not mine:
        return None
    runs = operands(ctx["config"], ctx["rows"] // ctx["chips"],
                    ctx["seq"])["delta_rule"]
    rule = routed_ops.seconds(ctx, lambda n: any(
        r in n.partition(" = ")[2] for r in runs))
    return 100.0 * mine / rule if rule else None
