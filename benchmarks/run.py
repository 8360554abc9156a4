#!/usr/bin/env python3
"""Run one cell of the benchmark once, in one process.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Looks for the chip (and fails without one: never a CPU fallback), makes the
weights on the device from the seed, warms the cell's own shapes, measures
for ``--seconds``, checks what the timed path produced against the plain
reference, and prints one JSON object as the last line of standard output.
With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window's first ``trace_seconds`` (the traffic file's), which is then the
whole measured window.

The harness knows two kinds of run, ``train`` and ``serve``, from the
traffic file, and reaches the model through the configuration's family
(``benchmarks/families/``); nothing in it names a cell.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is counted from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))


def execute(cell: dict, devices, seed: int, seconds: float, trace: bool,
            t_start: float, clock, dump_trace: str | None = None) -> dict:
    """Everything after the look for the chip: run the cell and build the
    result line.  (The tests enter here with the CPU's devices.)"""
    import harness
    import tracing

    kind = cell["mix"]["kind"]
    runner = __import__("run_" + kind)
    out = runner.run(cell, devices, seed, seconds, trace, t_start, clock)
    ctx = out["ctx"]
    reduced = None
    if trace:
        if dump_trace:
            import gzip
            import json
            with gzip.open(dump_trace, "wt") as f:
                json.dump(ctx["trace"], f)
        t0, t1 = tracing.window_of(ctx["trace"])
        reduced = tracing.busy_and_idle(ctx["trace"], t0, t1)
        reduced["t0"], reduced["t1"] = t0, t1
        ctx["reduced"] = reduced
        metrics = harness.read_metrics("layer_metrics", cell["per_layer"],
                                       ctx)
    else:
        metrics = harness.read_metrics("end_to_end", cell["end_to_end"], ctx)
    result = {
        "correct": bool(out["correct"]), "attempted": int(out["attempted"]),
        "failed": int(out["failed"]), "metrics": metrics,
        "device": harness.device_block(devices, out["memory_peak"], reduced),
    }
    if trace:
        result["breakdown"] = tracing.breakdown(ctx["trace"], reduced["t0"],
                                                reduced["t1"])
    return {"result": result, "checks": out["checks"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-trace", default=None,
                    help="with --trace 1, also write the plain trace here "
                         "(gzipped JSON): what tests/recorded_trace.json "
                         "was cut from")
    args = ap.parse_args(argv)

    import harness

    cell = harness.find_cell(args.workload)
    devices = harness.require_devices(int(cell["chips"]))
    harness.enable_compile_cache()
    clock = harness.CompileClock()
    done = execute(cell, devices, args.seed, args.seconds, bool(args.trace),
                   T_START, clock, args.dump_trace)
    harness.emit(done["result"], done["checks"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
