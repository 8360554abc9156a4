"""One-command per-phase decode/serving attribution (ISSUE 2 satellite).

Answers "where does a serving millisecond go?" without a TPU: runs the
standard ragged serving workload through ``ContinuousBatcher`` twice —
overlapped dispatch ON and OFF — and prints each run's per-phase wall
clock from the batcher's ``utils.tracing.PhaseTimer`` (host planning,
dispatch enqueue, the blocking result fetch, host parse, admission
prefill), plus a paired-window static-decode measurement using the same
hardened methodology as ``bench.py::bench_decode`` (difference of a long
and a short window, each ended by a one-element fetch, median of reps).

Runs anywhere JAX runs:

    JAX_PLATFORMS=cpu python scripts/profile_decode.py

On CPU the dispatch phase absorbs device compute (execution is eager
enough that enqueue blocks), so the split to read is fetch + host_* vs
dispatch; on TPU, fetch is the wait for the block's results, which the
overlapped pipeline hides under device compute.  Output is one JSON
object.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from bench_serving import warm_clone  # scripts/ is sys.path[0] when run

from distributed_pytorch_tpu import generate as gen
from distributed_pytorch_tpu.models import transformer as tfm
from distributed_pytorch_tpu.serve import ContinuousBatcher


def serving_phases(params, cfg, *, overlap: bool, requests: int = 6,
                   slots: int = 2, seed: int = 0, cold=None) -> dict:
    """One timed serving pass.  ``cold``: a batcher that already ran the
    workload — its compiled fns are shared (bench_serving.warm_clone) so
    the timed wall and the per-phase attribution measure EXECUTION, not
    tracing/compilation (both variants share one program set)."""
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, (int(rng.integers(8, 25)),))
               .astype(np.int32) for _ in range(requests)]
    budgets = [int(rng.integers(16, 49)) for _ in range(requests)]

    def make():
        return ContinuousBatcher(params, cfg, slots=slots, max_len=256,
                                 temperature=0.0, prompt_buckets=(32,),
                                 steps_per_sync=8, overlap=overlap)

    cb = make() if cold is None else warm_clone(cold, make)
    rids = [cb.submit(p, max_new=b) for p, b in zip(prompts, budgets)]
    t0 = time.perf_counter()
    while cb.pending():
        cb.step()
    wall = time.perf_counter() - t0
    tokens = sum(len(cb.result(r)) - len(p) for r, p in zip(rids, prompts))
    phases = {k: round(v["total_s"], 4) for k, v in cb.timing_stats().items()
              if isinstance(v, dict)}
    return {"overlap": overlap, "wall_s": round(wall, 3),
            "tokens": tokens,
            "ms_per_token": round(wall / tokens * 1e3, 3),
            "chained_dispatches": cb.stats["chained_dispatches"],
            "decode_dispatches": cb.stats["decode_dispatches"],
            "phase_total_s": phases,
            "unattributed_s": round(
                wall - cb.timing_stats().get("_total_s", 0.0), 4)}, cb


def decode_paired(params, cfg, *, long_new: int = 96, base: int = 32,
                  reps: int = 3) -> dict:
    """bench.py::bench_decode's paired-window methodology at test scale."""
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16))
                         .astype(np.int32))

    def run(n):
        out = gen.generate(params, prompt, jax.random.key(1), cfg=cfg,
                           max_new=n, temperature=0.0)
        return gen.force_fetch_last(out)

    run(base)
    run(long_new)  # compile + warm
    ds = []
    for _ in range(reps):
        t0 = time.perf_counter()
        run(base)
        tb = time.perf_counter() - t0
        t0 = time.perf_counter()
        run(long_new)
        tl = time.perf_counter() - t0
        ds.append((tl - tb) / (long_new - base) * 1e3)
    ds.sort()
    return {"windows": (long_new, base), "reps": reps,
            "ms_per_token_p50": round(ds[len(ds) // 2], 4),
            "spread": round((ds[-1] - ds[0]) / max(ds[len(ds) // 2], 1e-9),
                            3)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=2)
    args = ap.parse_args()

    cfg = tfm.TransformerConfig(vocab_size=256, d_model=128, n_layers=2,
                                n_heads=4, head_dim=32, n_kv_heads=2,
                                d_ff=256)
    params = tfm.init(jax.random.key(0), cfg)

    # cold pass: compiles every program both variants then SHARE (the
    # timed passes clone its compiled fns — bench_serving.warm_clone)
    _, cold = serving_phases(params, cfg, overlap=True,
                             requests=args.requests, slots=args.slots)
    on, _ = serving_phases(params, cfg, overlap=True, cold=cold,
                           requests=args.requests, slots=args.slots)
    off, _ = serving_phases(params, cfg, overlap=False, cold=cold,
                            requests=args.requests, slots=args.slots)
    print(json.dumps({
        "serving": [on, off],
        "static_decode": decode_paired(params, cfg),
    }, indent=2))


if __name__ == "__main__":
    main()
