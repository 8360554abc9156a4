#!/usr/bin/env python3
"""Rehearse a cell without the chip, before any chip time is spent on it.

    python3 benchmarks/rehearse.py --workload <name> [--trace 1] [--seconds 3]
    python3 benchmarks/rehearse.py --compile            # every cell's kernels

The first form runs the cell end to end on the CPU at a tiny size: the same
``run.execute`` the chip runs, entered past the look for the chip, with the
cell's configuration, traffic and deployment shrunk by ``shrink`` (widths,
lengths and counts only, the configuration's to its family's ``tiny``; every
code path, the reference and the check stay).
A four-chip cell runs on four virtual CPU devices.  Times it prints are the
CPU's and mean nothing.

The second form compiles, for a described v5e that is not attached, the
Pallas kernels of every cell in ``BENCHMARK.json`` at the cell's real shapes,
as the cell's family lists them (``program.kernel_compiles``: for the dense
family flash attention forward and backward for ``train`` mixes, paged decode
attention for ``serve`` mixes): what the chip's compiler would refuse (VMEM,
tiling) shows here.  ``tests/test_rehearsal.py`` keeps both under pytest.
"""

from __future__ import annotations

import argparse
import copy
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

def shrink(cell: dict) -> dict:
    """The cell at a size the CPU can run in seconds: the configuration at
    its family's ``tiny`` sizes."""
    family = cell["family"]     # modules: shared, not copied
    cell = copy.deepcopy({k: v for k, v in cell.items() if k != "family"})
    cell["family"] = family
    cell["config_file"].update(family.weights.tiny)
    mix = cell["mix"]
    mix["trace_seconds"] = 2.0
    if mix["kind"] == "train":
        mix.update(seq_len=128, rows_per_chip=2, warm_steps=3)
    else:
        mix.update(ramp_seconds=1.5, check_tokens=40,
                   warm_buckets=[16, 64], warm_widths=[1, 2, 4],
                   prompt_len=_scaled(mix["prompt_len"], 1 / 32),
                   output_len=_scaled(mix["output_len"], 1 / 16))
        arr = mix["arrivals"]
        arr.update({k: v for k, v in {"depth": 8, "segment_requests": 8,
                                      "stagger": 4,
                                      "segment_seconds": 1.0}.items()
                    if k in arr})
        cell["deployment"].update(slots=4, max_len=1024, pool_pages=9,
                                  prompt_buckets=[16, 64],
                                  dtype="float32")
    return cell


def _scaled(dist: dict, f: float) -> dict:
    out = dict(dist)
    for k in ("median", "min", "max", "mean", "value"):
        if k in out:
            out[k] = max(2.0, out[k] * f)
    return out


def rehearse(workload: str, seed: int, seconds: float, trace: bool) -> dict:
    import jax

    import harness
    import run
    import work

    cell = shrink(harness.find_cell(workload))
    chips = int(cell["chips"])
    devices = jax.devices("cpu")
    if len(devices) < chips:
        raise SystemExit(
            f"{workload} needs {chips} devices: set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={chips}")
    # the CPU is in no table of peaks: shares of a peak printed by a
    # rehearsal are against the v5e's row and mean nothing
    peaks, v5e = work.peaks, work.peaks("TPU v5 lite")
    work.peaks = lambda kind: v5e
    try:
        return run.execute(cell, devices[:chips], seed, seconds, trace,
                           time.perf_counter(), harness.CompileClock())
    finally:
        work.peaks = peaks


def describe_v5e():
    """A v5e 2x2 that is described and not attached (call from a test's
    fixture or a script's main, never at import)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


def compile_cell_kernels(cell: dict, topo) -> dict:
    """Compile the cell's Pallas kernels at its real shapes for one
    described chip; returns {kernel: seconds it took to compile}."""
    import jax
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(topo.devices[0])
    took = {}
    for name, (fn, shapes) in cell["family"].program.kernel_compiles(
            cell).items():
        args = [jax.ShapeDtypeStruct(dims, dtype, sharding=one)
                for dims, dtype in shapes]
        t0 = time.perf_counter()
        jax.jit(fn).lower(*args).compile()
        took[name] = time.perf_counter() - t0
    return took


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=(1 << 31) + 17)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--compile", action="store_true")
    args = ap.parse_args(argv)
    import harness

    if args.compile:
        import jax
        jax.config.update("jax_enable_compilation_cache", False)
        topo = describe_v5e()
        bench = harness.load_benchmark(left_out=True)
        for w in bench["workloads"]:
            print(w["name"], compile_cell_kernels(
                harness.find_cell(w["name"], bench), topo), flush=True)
        return 0
    done = rehearse(args.workload, args.seed, args.seconds, bool(args.trace))
    harness.emit(done["result"], done["checks"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
