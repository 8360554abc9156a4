#!/usr/bin/env python3
"""Find an open-loop cell's knee once, on the chip: the highest rate the
server sustains without a growing backlog.

    python3 benchmarks/sweep.py --workload lm_serve_chat --rates 4,5,6,7,8 \
        --seconds 30 --out chiprun_out/sweep.json

One process and one warm server; each rate gets the mix's own ramp, a window
of ``--seconds``, and a full drain.  The knee is the highest rate at which
nothing waits at the close (``waiting_at_close``: requests due in the window
whose first token had not been handed out when it closed; the tail of the
second half, ``ttft_p90_second_half``, says the same) and tokens/s has not
fallen.  The cell's rate is then fixed in its traffic file at four fifths of
it (the mix says what was measured under its ``rate`` key); the benchmark
itself never searches.
"""

from __future__ import annotations

import argparse
import copy
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=(1 << 31) + 555)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    import jax.numpy as jnp

    import harness
    import program
    import readers
    import run_serve
    import traffic
    import weights

    cell = harness.find_cell(args.workload)
    devices = harness.require_devices(int(cell["chips"]))
    harness.enable_compile_cache()
    clock = harness.CompileClock()
    cfg, dep = cell["config_file"], cell["deployment"]
    params = weights.make_params(cell["family"], args.seed, cfg,
                                 jnp.dtype(dep["dtype"]))
    cb = program.build_server(cell, params, args.seed)
    run_serve.warm(cb, cell["mix"], cfg["vocab_size"])
    rows = []
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = copy.deepcopy(cell["mix"])
        mix["arrivals"]["rate_per_s"] = rate
        book = run_serve.Book()
        edge = run_serve.drive(cb, traffic.Requests(mix, args.seed,
                                                    cfg["vocab_size"]),
                               mix, args.seconds, book, clock, None)
        gc.unfreeze()
        t_open, t_close = edge["t_open"], edge["t_close"]
        ctx = {"book": book, "t_open": t_open, "t_close": t_close,
               "window_requests": [r for r, d in book.due.items()
                                   if t_open <= d < t_close]}
        ttft = readers.ttfts_ms(ctx)
        half = t_open + (t_close - t_open) / 2
        late = [(book.first[r] - book.due[r]) * 1e3
                for r in ctx["window_requests"]
                if r in book.first and book.due[r] >= half]
        row = {"rate": rate, "requests": len(ctx["window_requests"]),
               "ttft_p50": readers.percentile(ttft, 50),
               "ttft_p90": readers.percentile(ttft, 90),
               "ttft_p90_second_half": readers.percentile(late, 90),
               "tpot_p90": readers.percentile(readers.tpots_ms(ctx), 90),
               "tokens_per_s": book.window_tokens / (t_close - t_open),
               # the drive returns once every request due in the window has
               # its first token, so the server's own queue is empty by then
               "waiting_at_close": sum(
                   1 for r in ctx["window_requests"]
                   if book.first.get(r, t_close) >= t_close),
               "live_at_close": sum(o is not None for o in cb.occupant)}
        harness.log(json.dumps(row))
        rows.append(row)
        while cb.pending():
            cb.step()
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
