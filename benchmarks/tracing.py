"""From a profiler trace to numbers: the reduction every PR's per-layer
metrics are read through, so that no PR that claims a gain can change it.

``capture()`` wraps a window in the JAX profiler (host spans on, Python
tracer off) and reads the ``.xplane.pb`` back with ``jax.profiler.
ProfileData`` into a plain structure::

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, dur_ns], ...]}]}]}

Everything below works on that structure alone, so the tests run it on a
small recorded trace (``tests/recorded_trace.json``) with no chip.

- Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line is the
  core's stream of operations and ``XLA Modules`` one event per program run.
- ``busy`` is the measure of the union of the operations' intervals inside
  the window, ``idle`` the rest; gaps are named by the benchmark's own host
  span (``bench:<name>`` annotations, written by ``span()``) that covers
  each gap's middle.
- XLA names an operation by its whole HLO line (``%fusion.12 = bf16[64,128]
  {1,0:T(8,128)} fusion(...operands...), kind=...``, hundreds of characters,
  a million of them in a serving trace); ``short_name`` keeps ``%name =
  shape opcode`` and marks a Pallas kernel (``custom_call_target=
  "tpu_custom_call"``) with `` pallas`` at the end.  ``op_key`` drops the
  serial number and the layout, so ``fusion.123`` and ``fusion.7`` of one
  shape are one row of the breakdown.
"""

from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
import tempfile

SPAN_PREFIX = "bench:"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")


def span(name: str):
    """A host span of the benchmark's own, on the trace's clock."""
    import jax

    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


@contextlib.contextmanager
def capture(out: dict):
    """Trace the body; afterwards ``out["trace"]`` is the plain structure.
    The trace goes under ``TMPDIR`` and is removed once read."""
    import jax

    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        try:
            files = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                           "*.xplane.pb"))
            out["trace"] = read_xplane(files[0]) if files else {"planes": []}
        finally:
            shutil.rmtree(tmp, ignore_errors=True)


def short_name(full: str) -> str:
    """``%name = shape opcode`` of an HLO line, `` pallas`` appended for a
    Pallas kernel; anything that is no HLO line comes back whole."""
    m = re.match(r"^(%[\w\-\.]+) = ", full)
    if not m:
        return full
    rest, depth, i = full[m.end():], 0, 0
    while i < len(rest):          # the shape may be a tuple with spaces
        ch = rest[i]
        depth += ch in "([{"
        depth -= ch in ")]}"
        if ch == " " and depth == 0:
            break
        i += 1
    shape = re.sub(r"\{[^{}]*\}", "", rest[:i]).replace(" ", "")  # no layouts
    opcode = re.match(r"[\w\-]+", rest[i + 1:])
    out = f"{m.group(1)} = {shape} {opcode.group(0) if opcode else ''}"
    if 'custom_call_target="tpu_custom_call"' in full:
        out += " pallas"
    return out


def read_xplane(path: str) -> dict:
    """The device planes' op and module lines and every host line that
    holds one of the benchmark's spans (only those spans are kept)."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        is_dev = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if is_dev:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                ev = [[short_name(e.name), float(e.start_ns),
                       float(e.duration_ns)] for e in line.events]
            else:
                ev = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in line.events
                      if e.name.startswith(SPAN_PREFIX)]
            if ev:
                lines.append({"name": line.name, "events": ev})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


# -- the reduction ----------------------------------------------------------

def device_planes(trace: dict) -> list[dict]:
    return sorted((p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])),
                  key=lambda p: int(DEVICE_PLANE.match(p["name"]).group(1)))


def line_events(plane: dict, line: str) -> list:
    for ln in plane["lines"]:
        if ln["name"] == line:
            return ln["events"]
    return []


def host_spans(trace: dict) -> list:
    """[name without prefix, start_ns, dur_ns] of the benchmark's spans."""
    out = []
    for p in trace["planes"]:
        if DEVICE_PLANE.match(p["name"]):
            continue
        for ln in p["lines"]:
            out += [[n[len(SPAN_PREFIX):], s, d] for n, s, d in ln["events"]
                    if n.startswith(SPAN_PREFIX)]
    return sorted(out, key=lambda e: e[1])


def window_of(trace: dict, span_name: str = "window"):
    """(t0, t1) in ns of the ``bench:window`` span; without one, the
    extent of the device operations."""
    for n, s, d in host_spans(trace):
        if n == span_name:
            return s, s + d
    ev = [e for p in device_planes(trace) for e in line_events(p, OPS_LINE)]
    if not ev:
        return 0.0, 0.0
    return min(e[1] for e in ev), max(e[1] + e[2] for e in ev)


def union(intervals) -> list:
    """Sorted disjoint intervals covering the same points."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(events, t0: float, t1: float) -> list:
    """(start, end) of events, cut to the window."""
    return [(max(s, t0), min(s + d, t1)) for _, s, d in events
            if s + d > t0 and s < t1]


def measure(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: list, b: list) -> list:
    """Points of the disjoint sorted intervals a that are not in b."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def op_key(name: str) -> str:
    """Kind and shape of an operation without its serial number:
    ``%fusion.123 = bf16[64,128] fusion`` -> ``fusion bf16[64,128]``."""
    m = re.match(r"^%?([A-Za-z_\-][\w\-]*?)(?:\.\d+)*(?: = (\S+))?(?: .*)?$",
                 name)
    if not m:
        return name
    return m.group(1) + (" " + m.group(2) if m.group(2) else "")


def is_kernel(name: str) -> bool:
    """A Pallas kernel, as ``short_name`` marks it."""
    return name.endswith(" pallas")


def busy_and_idle(trace: dict, t0: float, t1: float) -> dict:
    """Per device plane: busy seconds (union of operations) in the window;
    and their mean, the contract's ``busy_s``."""
    per = []
    for p in device_planes(trace):
        per.append(measure(union(clip(line_events(p, OPS_LINE), t0, t1)))
                   / 1e9)
    window = (t1 - t0) / 1e9
    return {"window_s": window, "busy_s_per_device": per,
            "busy_s": sum(per) / len(per) if per else 0.0,
            "idle_share_max": (1.0 - min(per) / window
                               if per and window > 0 else None)}


def op_totals(trace: dict, t0: float, t1: float, match=None) -> dict:
    """op_key -> self seconds inside the window, averaged over device
    planes (a ``while`` and the operations of its body are told apart by
    ``self_times``, so a loop's time is counted once)."""
    planes = device_planes(trace)
    out: dict = {}
    for p in planes:
        for name, secs in self_times(line_events(p, OPS_LINE), t0, t1):
            if match is not None and not match(name):
                continue
            k = op_key(name)
            out[k] = out.get(k, 0.0) + secs / len(planes)
    return out


def self_times(events, t0: float, t1: float):
    """(name, self seconds) per event: its time in the window minus the
    time of events nested inside it (a ``while`` holds its body's ops)."""
    ev = sorted(((s, s + d, n) for n, s, d in events
                 if s + d > t0 and s < t1), key=lambda x: (x[0], -x[1]))
    out, stack = [], []   # stack of [end, name, self_ns]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, name, self_ns = stack.pop()
            out.append((name, max(self_ns, 0.0) / 1e9))

    for s, e, n in ev:
        close(s)
        cs, ce = max(s, t0), min(e, t1)
        if stack:
            stack[-1][2] -= ce - cs
        stack.append([e, n, ce - cs])
    close(float("inf"))
    return out


def idle_gaps(trace: dict, t0: float, t1: float, top: int = 10) -> list:
    """[[host span name, idle seconds], ...]: the first device's idle time
    in the window by the innermost benchmark span covering each gap's
    middle (``_no_span_`` where none does), largest first."""
    planes = device_planes(trace)
    if not planes:
        return []
    busy = union(clip(line_events(planes[0], OPS_LINE), t0, t1))
    gaps = subtract([[t0, t1]], busy)
    spans = [s for s in host_spans(trace) if s[0] != "window"]
    by: dict = {}
    for s, e in gaps:
        mid = (s + e) / 2
        cover = [sp for sp in spans if sp[1] <= mid < sp[1] + sp[2]]
        name = min(cover, key=lambda sp: sp[2])[0] if cover else "_no_span_"
        by[name] = by.get(name, 0.0) + (e - s) / 1e9
    return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])][:top]


def exposed_collective_share(trace: dict, t0: float, t1: float):
    """Share of the window in which a collective runs on a device and no
    other operation does, the worst device's; None without collectives."""
    shares = []
    for p in device_planes(trace):
        ev = line_events(p, OPS_LINE)
        coll = union(clip([e for e in ev if COLLECTIVE.search(e[0])], t0, t1))
        if not coll:
            continue
        rest = union(clip([e for e in ev if not COLLECTIVE.search(e[0])
                           and not is_container(e[0])], t0, t1))
        shares.append(measure(subtract(coll, rest)) / (t1 - t0))
    return max(shares) if shares else None


def is_container(name: str) -> bool:
    """Operations that only hold others (their time is their body's)."""
    return bool(re.match(r"^%?(while|conditional|call)\b", name))


def module_runs(trace: dict, t0: float, t1: float, match=None) -> list:
    """[name, start_ns, dur_ns] of the first device's program runs that lie
    wholly inside the window and whose name ``match`` accepts."""
    planes = device_planes(trace)
    if not planes:
        return []
    return [[n, s, d] for n, s, d in line_events(planes[0], MODULES_LINE)
            if s >= t0 and s + d <= t1 and (match is None or match(n))]


def breakdown(trace: dict, t0: float, t1: float) -> dict:
    ops = sorted(op_totals(trace, t0, t1,
                           match=lambda n: not is_container(n)).items(),
                 key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": idle_gaps(trace, t0, t1)}
