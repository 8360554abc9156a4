"""What several metric readers share.  A reader is ``read(ctx) -> number or
None``: None when it finds nothing to read (the harness then leaves the
metric out of the line); never 0 for a share of a roofline or of a peak.

``ctx`` (built by ``run_train`` / ``run_serve`` and ``run.execute``):
``kind``, ``cell``, ``config``, ``mix``, ``chips``, ``peak`` (the chip's
peaks), ``work`` (the family's counting functions: the operations and bytes
the model's algorithm needs; a reader reaches the model through it alone),
``setup_s``, ``window_s``, ``counters`` (the program's counters and
host spans as window deltas, the compile clock, and the work the window
required), ``memory_peak_bytes``, ``trace`` and ``reduced`` (``--trace 1``
only: the plain trace and its busy/idle reduction with the window's ``t0``,
``t1`` in ns); for ``train`` also ``steps``, ``tokens_per_step``, ``rows``,
``seq``, ``done_at``; for ``serve`` also ``book`` (the per-request clock),
``window_requests``, ``t_open``, ``t_close``.
"""

from __future__ import annotations

import re

import numpy as np

import tracing
import work


def window_ns(ctx):
    r = ctx.get("reduced")
    return (r["t0"], r["t1"]) if r else None


def first_plane_ops(ctx):
    planes = tracing.device_planes(ctx["trace"]) if ctx.get("trace") else []
    return tracing.line_events(planes[0], tracing.OPS_LINE) if planes else []


def kernel_seconds(ctx) -> float:
    """Self time of the Pallas kernels on the first device inside the
    traced window."""
    w = window_ns(ctx)
    if w is None:
        return 0.0
    return sum(s for n, s in tracing.self_times(first_plane_ops(ctx), *w)
               if tracing.is_kernel(n))


def kernel_count(ctx) -> int:
    w = window_ns(ctx)
    if w is None:
        return 0
    return sum(1 for n, s, d in first_plane_ops(ctx)
               if tracing.is_kernel(n) and s >= w[0] and s + d <= w[1])


def program_runs(ctx, with_kernels: bool | None = None, name=None) -> list:
    """[start_ns, dur_ns] of the first device's program runs wholly inside
    the traced window; ``with_kernels`` keeps those that do (or do not)
    hold a Pallas kernel, ``name`` those whose name it matches."""
    w = window_ns(ctx)
    if w is None:
        return []
    mods = tracing.module_runs(
        ctx["trace"], *w,
        match=None if name is None else lambda n: re.search(name, n))
    if with_kernels is not None:
        starts = np.sort(np.asarray([s for n, s, d in first_plane_ops(ctx)
                                     if tracing.is_kernel(n)]))

        def holds(m):
            i = np.searchsorted(starts, m[1])
            return i < len(starts) and starts[i] < m[1] + m[2]

        mods = [m for m in mods if holds(m) == with_kernels]
    return [[s, d] for _, s, d in mods]


def percentile(values, q):
    v = np.asarray(list(values), np.float64)
    return float(np.percentile(v, q)) if len(v) else None


def ttfts_ms(ctx) -> list:
    """First token handed out minus the moment the request was due, for
    every request due in the window that got one."""
    b = ctx["book"]
    return [(b.first[r] - b.due[r]) * 1e3 for r in ctx["window_requests"]
            if r in b.first]


def tpots_ms(ctx) -> list:
    """(last token - first token) / (output tokens - 1) for every request
    that finished inside the window."""
    b = ctx["book"]
    return [(b.last[r] - b.first[r]) / (b.count[r] - 1) * 1e3
            for r in b.first
            if b.finished(r) and b.count[r] > 1
            and ctx["t_open"] <= b.last[r] < ctx["t_close"]]


def idle_pct(ctx):
    r = ctx.get("reduced")
    if not r or r["idle_share_max"] is None:
        return None
    return 100.0 * r["idle_share_max"]


def share_pct(required_s: float, measured_s: float):
    """A share of a roofline or of a peak, or None where either is nought."""
    if not required_s or not measured_s or measured_s <= 0:
        return None
    return 100.0 * required_s / measured_s


def kernel_roofline_pct(ctx, kernel: str, calls: float, secs: float,
                        **numbers):
    """``<kernel>_roofline``: the least time the chip could take for
    ``calls`` times what the family's ``work.kernels[kernel]`` counts at
    ``numbers`` (the larger of FLOPs over the peak and bytes over the
    bandwidth) over the ``secs`` the kernel took.  None where the family has
    no such kernel or nothing was measured."""
    count = ctx["work"].kernels.get(kernel)
    if count is None or not secs or not calls:
        return None
    need = count(ctx["config"], **numbers)
    least = work.roofline_seconds(need["flops"], need["bytes"], ctx["peak"])
    return share_pct(calls * least["seconds"], secs)
