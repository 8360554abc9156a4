"""Worker for the END-TO-END elastic recovery test (test_launch.py):
checkpointed training that survives a mid-run worker crash.

Each gang process trains TEST_STEPS deterministic steps (data seeded by
the step index, so a restarted gang replays the same batches),
checkpointing every TEST_CKPT_EVERY steps.  On the FIRST attempt
(RESTART_ATTEMPT=0) with TEST_KILL_AT_STEP set, rank 0 hard-exits after
completing that step — strictly after a checkpoint landed and with
further un-checkpointed steps executed, so a correct recovery must (a)
detect the death and tear the gang down (reference contrast:
main_all_reduce.py:96 timeout=None hangs forever), (b) relaunch, (c)
resume from the checkpoint, and (d) replay the lost steps to a final
state trajectory-equal to an uninterrupted run.  The final parameters
are dumped per attempt for the test to compare bitwise.
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

from distributed_pytorch_tpu.parallel import init as dist_init  # noqa: E402
from distributed_pytorch_tpu.parallel.mesh import make_mesh  # noqa: E402
from distributed_pytorch_tpu.train import TrainConfig, Trainer  # noqa: E402
from distributed_pytorch_tpu.utils.checkpoint import Checkpointer  # noqa: E402


def _batch(step: int, rank: int, local: int):
    """Deterministic per-step data: a restarted gang regenerates the
    exact batches the crashed one saw."""
    rng = np.random.default_rng(7_000 + 31 * step + rank)
    images = rng.integers(0, 256, (local, 32, 32, 3)).astype(np.uint8)
    labels = rng.integers(0, 10, local).astype(np.int32)
    return images, labels


def main() -> int:
    steps = int(os.environ["TEST_STEPS"])
    ckpt_every = int(os.environ.get("TEST_CKPT_EVERY", "2"))
    kill_at = int(os.environ.get("TEST_KILL_AT_STEP", "-1"))
    attempt = int(os.environ.get("RESTART_ATTEMPT", "0"))

    dist_init.init_from_env(timeout_s=120)
    rank, world = dist_init.process_info()

    cfg = TrainConfig(model="TINY", strategy="ddp", batch_size=4, lr=1e-2)
    trainer = Trainer(cfg, mesh=make_mesh())
    ckpt = Checkpointer(os.environ["TEST_CKPT_DIR"])
    start = ckpt.maybe_restore(trainer)
    if attempt > 0:
        # the relaunch must actually RESUME (checkpoint from attempt 0)
        assert start > 0, "restarted gang found no checkpoint to resume"
    print(f"worker rank={rank} attempt={attempt} start_step={start}",
          flush=True)

    local = _DEV_PER_PROC * cfg.batch_size
    for step in range(start, steps):
        images, labels = _batch(step, rank, local)
        loss = float(trainer.train_step(images, labels))
        assert np.isfinite(loss), (step, loss)
        if (step + 1) % ckpt_every == 0:
            # every process joins the save (the state fetch is a
            # collective); rank 0 writes the file
            ckpt.save(trainer, step + 1)
        if attempt == 0 and step + 1 == kill_at and rank == 0:
            print(f"worker rank=0 KILLING at step {step + 1}", flush=True)
            os._exit(17)  # hard crash: no teardown, no final checkpoint

    trainer.check_consistency()
    if rank == 0:
        flat = np.concatenate([np.asarray(x).ravel()
                               for x in jax.tree.leaves(trainer.params)])
        out = os.path.join(os.environ["TEST_OUT_DIR"],
                           f"final_attempt{attempt}.npy")
        np.save(out, flat)
    print(f"worker rank={rank} OK final", flush=True)
    dist_init.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
