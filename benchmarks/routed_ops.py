"""Telling a routed step's operations apart in the device trace, for the
readers of a family whose step holds more than one kind of kernel.

XLA lowers ``lax.ragged_dot`` on the TPU to Mosaic kernels of its own, so in
the trace a grouped product is a ``custom-call`` marked `` pallas`` like the
flash-attention kernels (``readers.kernel_seconds`` sums both).  It keeps its
HLO name, ``%ragged-dot...``, while a ``pallas_call`` is named after the
jitted function it was traced in (``jvp__``, ``transpose_jvp___``); and every
operation of the routed layer's dispatch and combine (the sort of the picks,
the row gathers, the masks, the weighting and the sum over a token's picks)
has the step's number of picks, tokens x experts per token, among its
dimensions (``[49152,2560]``) or those two side by side (``[8192,6,2560]``),
which no other operation of the step has.  Seen on the chip's trace of
``smallthinker_train_8k`` and pinned on a cut of it
(``tests/test_routed_ops.py``).
"""

from __future__ import annotations

import re

import readers
import tracing


def is_grouped(name: str) -> bool:
    """A grouped product (or the metadata kernel before it)."""
    return "ragged-dot" in name


def is_flash(name: str) -> bool:
    """A Pallas kernel that is no grouped product."""
    return tracing.is_kernel(name) and not is_grouped(name)


def is_routed(name: str, tokens: int, k: int, buffer: int = 0) -> bool:
    """An operation of the routed layer: a grouped product, or one whose
    result has ``tokens * k`` (or the row buffer's length) among its
    dimensions, or ``tokens, k`` side by side."""
    shape = name.partition(" = ")[2]
    return is_grouped(name) or re.search(
        rf"[\[,]({tokens * k}|{buffer or tokens * k}|{tokens},{k})[,\]]",
        shape) is not None


def seconds(ctx, keep) -> float:
    """Self time, on the first device inside the traced window, of the
    operations whose name ``keep`` accepts."""
    w = readers.window_ns(ctx)
    if w is None:
        return 0.0
    return sum(s for n, s in tracing.self_times(readers.first_plane_ops(ctx),
                                                *w)
               if keep(n) and not tracing.is_container(n))


def routed_dims(ctx) -> tuple[int, int, int]:
    """A chip's tokens a step, the experts a token picks, and the rows of
    the routed layer's buffer."""
    tokens = ctx["tokens_per_step"] // ctx["chips"]
    rows = ctx["work"].routed_rows(ctx["config"], tokens)
    return tokens, int(rows["picks"]) // tokens, int(rows["buffer"])
