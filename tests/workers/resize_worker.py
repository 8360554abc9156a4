"""Worker for the GANG-LEVEL elastic-resize test (test_elastic.py):
kill -> shrink -> resume resharded -> rejoin -> grow back.

Gang model (the repo's CPU-simulation idiom): each member is a single-process jax worker that
builds its mesh over ``WORLD_SIZE`` local fake devices — the exact mesh
shape, batch split, and checkpoint LAYOUT a real WORLD_SIZE-member gang
produces — and trains the canonical global batch.  A correctly
synchronized DP gang holds bitwise-identical replicas after every sync;
redundant full-batch compute gives the same invariant without the
collectives, so the loss trajectory IS the real gang's trajectory and
members differ only in which output files they own.

Everything the elastic machinery must prove is therefore real:
- the mesh genuinely resizes with the gang (dp=W, ZeRO-3 when W > 1),
  so every resume after a resize is a REAL cross-topology reshard
  through ``ShardedCheckpointer.load_resharded``;
- data comes through ``ElasticSampler`` re-keyed per
  (generation, world_size): the global order is world-size-independent,
  so no example is dropped or double-counted across resizes;
- heartbeats + the drain sync point (parallel/elastic.py): on SIGTERM
  the worker exits the step loop at a step boundary, rank 0 flushes the
  checkpoint, and everyone leaves with ``ELASTIC_DRAIN_EXIT_CODE``;
- faults come ONLY from the chaos harness's env plan (``FAULT_PLAN``,
  generation- and rank-gated): the test arms a crash on gang rank 1 in
  generation 0; later generations run clean ("the lost worker
  returns").

Per generation, rank 0 dumps the loss trajectory (float64-exact) plus
(start, world) to ``TEST_OUT_DIR/losses_gen<G>.npz`` — the test pins
the post-shrink trajectory BITWISE against a fresh gang launched at the
small size from the same checkpoint, and the merged per-step losses
against an uninterrupted full-size run.
"""

import os
import sys

_DEV_PER_PROC = int(os.environ.get("TEST_DEVICES_PER_PROC", "2"))

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + f" --xla_force_host_platform_device_count={_DEV_PER_PROC}").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
from _cache import enable_compile_cache  # noqa: E402 (same dir)

enable_compile_cache()

import time  # noqa: E402

import numpy as np  # noqa: E402

from distributed_pytorch_tpu.data.sampler import ElasticSampler  # noqa: E402
from distributed_pytorch_tpu.lm import (  # noqa: E402
    IGNORE, LMTrainConfig, LMTrainer)
from distributed_pytorch_tpu.models import transformer as tfm  # noqa: E402
from distributed_pytorch_tpu.parallel import elastic as el  # noqa: E402
from distributed_pytorch_tpu.utils import telemetry  # noqa: E402
from distributed_pytorch_tpu.utils.checkpoint import (  # noqa: E402
    ShardedCheckpointer)

VOCAB, SEQ, GLOBAL_BATCH, DATASET = 64, 32, 4, 64


def _example(idx: int) -> np.ndarray:
    """Deterministic per-INDEX example: the sampler decides who consumes
    it; the content never depends on the topology."""
    rng = np.random.default_rng(5_000 + int(idx))
    return rng.integers(0, VOCAB, (SEQ,)).astype(np.int32)


def _batch(sampler: ElasticSampler, step: int):
    """The CANONICAL global batch for this step (world-size-independent
    order; the dp mesh splits its rows exactly as a real gang splits
    them over members)."""
    tokens = np.stack([_example(i) for i in sampler.global_indices(step)])
    targets = np.roll(tokens, -1, 1).astype(np.int32)
    targets[:, -1] = IGNORE
    return tokens, targets


def main() -> int:
    # install the drain handler FIRST: a SIGTERM during compile must be
    # honored at the first sync point, not kill us mid-build
    guard = el.DrainGuard().install()
    steps = int(os.environ["TEST_STEPS"])
    ckpt_every = int(os.environ.get("TEST_CKPT_EVERY", "1"))
    step_sleep = float(os.environ.get("TEST_STEP_SLEEP", "0"))
    gen = int(os.environ.get("RESTART_ATTEMPT", "0"))
    rank = int(os.environ.get("RANK", "0"))
    world = int(os.environ.get("WORLD_SIZE", "1"))
    out_dir = os.environ["TEST_OUT_DIR"]
    ckpt_dir = os.environ["TEST_CKPT_DIR"]
    assert world <= _DEV_PER_PROC, (world, _DEV_PER_PROC)

    ectx = el.ElasticContext.from_env()
    hb = (el.Heartbeat(ectx.run_dir, rank, gen)
          if ectx is not None else None)
    # unified telemetry (round 13): on when the agent/test exported
    # TELEMETRY_DIR — train-step spans/gauges and checkpoint IO then
    # land on the same generation-tagged timeline as the agent's gang
    # events (every record is written through per-record atomic appends,
    # so the drain path's os._exit loses nothing)
    telemetry.maybe_enable()

    model = tfm.TransformerConfig(vocab_size=VOCAB, d_model=32, n_layers=1,
                                  n_heads=2, head_dim=16, d_ff=64)
    # the member-count mesh: ZeRO-3 whenever the world allows, so every
    # resize moves REAL shards through load_resharded
    cfg = LMTrainConfig(model=model, dp=world, fsdp=world > 1,
                        compute_dtype=None)
    tr = LMTrainer(cfg)
    start = tr.maybe_restore(ckpt_dir)  # sharded -> load_resharded
    if gen > 0:
        assert start > 0, "resized gang found no checkpoint to resume"
    print(f"worker rank={rank} gen={gen} world={world} "
          f"start_step={start}", flush=True)

    sampler = ElasticSampler(DATASET, GLOBAL_BATCH, seed=7)
    sampler.set_generation(gen, world, rank)  # membership re-key
    ck = ShardedCheckpointer(ckpt_dir, keep=100)  # the test reads history

    def save(step_no: int) -> None:
        # rank 0 owns the files (members are bitwise replicas; two
        # writers racing the same proc0.npz would corrupt it)
        if rank == 0:
            ck.save({"params": tr.params, "opt": tr.opt_state}, step_no,
                    meta={"world": world, "gen": gen})

    losses: list[float] = []

    def dump_losses() -> None:
        if rank != 0:
            return
        path = os.path.join(out_dir, f"losses_gen{gen}.npz")
        tmp = path + ".tmp.npz"
        np.savez(tmp, start=start, world=world,
                 losses=np.asarray(losses, np.float64))
        os.replace(tmp, path)

    for step in range(start, steps):
        if step_sleep:
            time.sleep(step_sleep)  # keeps the agent's poll ahead of us
        if hb is not None:
            hb.beat(step)
        if guard.sync():
            print(f"worker rank={rank} gen={gen} DRAIN at step {step}",
                  flush=True)
            tel = telemetry.active()
            if tel is not None:
                tel.event("worker_drain", phase="gang", step=step)
            el.drain_exit(lambda: save(step))
        loss = float(tr.train_step(*_batch(sampler, step)))
        assert np.isfinite(loss), (step, loss)
        losses.append(loss)
        dump_losses()
        if (step + 1) % ckpt_every == 0:
            save(step + 1)

    # gather the (possibly ZeRO-3-sharded) params to full for the final
    # comparison dump
    from jax.sharding import NamedSharding, PartitionSpec as P
    gather = jax.jit(lambda x: x,
                     out_shardings=NamedSharding(tr.mesh, P()))
    flat = np.concatenate([np.asarray(gather(leaf)).ravel()
                           for leaf in jax.tree.leaves(tr.params)])
    if rank == 0:
        np.save(os.path.join(out_dir, f"final_gen{gen}.npy"), flat)
    print(f"worker rank={rank} gen={gen} OK final", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
