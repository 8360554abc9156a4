"""Worker for the LM END-TO-END elastic recovery test (test_launch.py):
the LMTrainer analog of elastic_worker.py, covering the state where LM
resume bugs would actually live — AdamW moments, ZeRO-3 (fsdp) params
sharded ACROSS the process boundary, and the data-position carry.

Each gang process trains TEST_STEPS deterministic steps (data seeded by
the step index, so a restarted gang replays the same batches),
checkpointing every TEST_CKPT_EVERY steps with the data position in
``extra_meta``.  On the FIRST attempt (RESTART_ATTEMPT=0) with
TEST_KILL_AT_STEP set, rank 0 hard-exits after completing that step —
strictly after a checkpoint landed and with further un-checkpointed
steps executed.  A correct recovery detects the death, tears the gang
down, relaunches, restores the SHARDED params + Adam state + position,
and replays the lost steps to a final state trajectory-equal to an
uninterrupted run.  Final params are all-gathered to full and dumped
per attempt for the test's bitwise comparison.
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

import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from distributed_pytorch_tpu.lm import (  # noqa: E402
    IGNORE, LMTrainConfig, LMTrainer)
from distributed_pytorch_tpu.models import transformer as tfm  # noqa: E402
from distributed_pytorch_tpu.parallel import init as dist_init  # noqa: E402


def _batch(step: int, rank: int, rows: int, seq: int):
    """Deterministic per-(step, rank) host-local batch share: a
    restarted gang regenerates the exact global batches the crashed one
    saw (the in-test stand-in for the CLI's corpus-position carry, whose
    value rides the checkpoint meta below)."""
    rng = np.random.default_rng(9_000 + 31 * step + rank)
    tokens = rng.integers(0, 128, (rows, seq)).astype(np.int32)
    targets = np.roll(tokens, -1, 1).astype(np.int32)
    targets[:, -1] = IGNORE
    return tokens, targets


def main() -> int:
    steps = int(os.environ["TEST_STEPS"])
    ckpt_every = int(os.environ.get("TEST_CKPT_EVERY", "2"))
    kill_at = int(os.environ.get("TEST_KILL_AT_STEP", "-1"))
    attempt = int(os.environ.get("RESTART_ATTEMPT", "0"))

    dist_init.init_from_env(timeout_s=120)
    rank, world = dist_init.process_info()
    assert world == 2, world

    model = tfm.TransformerConfig(vocab_size=128, d_model=64, n_layers=2,
                                  n_heads=2, head_dim=32, d_ff=128)
    # dp=2 x sp=2 over 2 procs x 2 devices with ZeRO-3: the fsdp-sharded
    # params/Adam state live SPLIT across the process boundary, so
    # restore must reassemble exactly the sharded layout it saved
    cfg = LMTrainConfig(model=model, dp=2, sp=2, fsdp=True,
                        compute_dtype=None)
    tr = LMTrainer(cfg)
    start = tr.maybe_restore(os.environ["TEST_CKPT_DIR"])
    if attempt > 0:
        assert start > 0, "restarted gang found no checkpoint to resume"
        # the data-position carry came back through the meta
        assert tr.restored_meta.get("next_step") == start, tr.restored_meta
    print(f"lm worker rank={rank} attempt={attempt} start_step={start}",
          flush=True)

    for step in range(start, steps):
        tokens, targets = _batch(step, rank, rows=2, seq=64)
        loss = float(tr.train_step(tokens, targets))
        assert np.isfinite(loss), (step, loss)
        if (step + 1) % ckpt_every == 0:
            tr.save_checkpoint(os.environ["TEST_CKPT_DIR"],
                               extra_meta={"next_step": step + 1})
            tr.flush_checkpoints()
        if attempt == 0 and step + 1 == kill_at and rank == 0:
            print(f"lm worker rank=0 KILLING at step {step + 1}",
                  flush=True)
            os._exit(17)  # hard crash: no teardown, no final checkpoint

    # all-gather the ZeRO-3 shards to full values for the bitwise dump
    rep = NamedSharding(tr.mesh, P())
    gather = jax.jit(lambda x: x, out_shardings=rep)
    flat = np.concatenate([np.asarray(gather(leaf)).ravel()
                           for leaf in jax.tree.leaves(tr.params)])
    if rank == 0:
        out = os.path.join(os.environ["TEST_OUT_DIR"],
                           f"final_attempt{attempt}.npy")
        np.save(out, flat)
    print(f"lm worker rank={rank} OK final", flush=True)
    dist_init.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
