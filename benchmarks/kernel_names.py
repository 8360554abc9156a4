"""The program's Pallas kernels by their own names, read off the device trace.

Every kernel of the program passes a name to ``pallas_call`` (``flash_fwd``,
``flash_bwd_window``, ``decode_attn``, ...).  It reaches the trace as the
kernel's HLO instruction name, wrapped in the transformations the kernel was
traced under: in a training step the flash forward is
``%jvp_flash_fwd_.3`` (``jvp(flash_fwd)``) and its backward
``%transpose_jvp_flash_bwd__.5``; under ``checkpoint`` or in a loop's body
the same kernels are ``%flash_fwd.1``.  ``own_name`` takes the wrappers off.

A program whose kernels carry no name (every one from before they were
named: ``%jvp__``, ``%transpose_jvp___``) has none of these names, and a
reader of one reads None there.  Imports nothing of the program.
"""

from __future__ import annotations

import re

import readers
import routed_ops
import tracing

# every forward flash kernel's name starts with the one, every backward
# one's with the other (``_window``, ``_dq`` and ``_dkv`` follow)
FLASH_FWD = "flash_fwd"
FLASH_BWD = "flash_bwd"

# what the name stack of ``jvp(...)``, ``transpose(...)`` and ``vmap(...)``
# leaves in front of a name once the compiler has made it an identifier
_WRAPPERS = re.compile(r"^(?:(?:jvp|transpose|vmap)_)+")


def own_name(op: str) -> str:
    """The name a Pallas kernel was given, from its trace event
    (``%transpose_jvp_flash_bwd__.5 = ... custom-call pallas`` ->
    ``flash_bwd``); '' for an unnamed kernel and for anything else."""
    if not tracing.is_kernel(op):
        return ""
    name = tracing.op_key(op).split(" ")[0]
    wrapped = _WRAPPERS.match(name)
    return name[wrapped.end():].rstrip("_") if wrapped else name


def seconds(ctx, prefix: str) -> float:
    """Self time, on the first device inside the traced window, of the
    Pallas kernels whose own name starts with ``prefix``."""
    return routed_ops.seconds(ctx, lambda n: own_name(n).startswith(prefix))


def flash_roofline(ctx, prefix: str, share: float):
    """``share`` of the least time the family's ``work.kernels
    ["flash_attn"]`` gives the steps of the traced window, over the time of
    the kernels named ``prefix``; None where no kernel carries the name."""
    steps = len(readers.program_runs(ctx, with_kernels=True))
    return readers.kernel_roofline_pct(
        ctx, "flash_attn", steps * share, seconds(ctx, prefix),
        rows=ctx["rows"] // ctx["chips"], seq=ctx["seq"])
