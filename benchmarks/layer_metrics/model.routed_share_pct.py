"""Share of the first device's busy time in the traced window that the
routed layer takes: the sort of the picks, the gathers of one row a pick,
the grouped products, the masks and the weighted combine
(``routed_ops.is_routed``).  None for a family without
``work.routed_rows``."""
import routed_ops


def read(ctx):
    if not hasattr(ctx["work"], "routed_rows") or not ctx.get("reduced"):
        return None
    dims = routed_ops.routed_dims(ctx)
    routed = routed_ops.seconds(ctx, lambda n: routed_ops.is_routed(n, *dims))
    busy = ctx["reduced"]["busy_s_per_device"]
    if not routed or not busy or not busy[0]:
        return None
    return 100.0 * routed / busy[0]
