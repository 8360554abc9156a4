"""Worker for the GANG-LEVEL fault-injection test (test_faults.py):
checkpointed single-process training whose faults come ONLY from the
chaos harness's env plan (``FAULT_PLAN``) — no test-specific kill logic.

Generation 0 runs with the injected plan live (utils/faults.py gates
plans by ``RESTART_ATTEMPT``), e.g. a crash fault that hard-exits with
``FAULT_EXIT_CODE`` mid-run; the launcher classifies that exit as
injected and relaunches.  Generation 1 sees the same env var but the
plan is gen-gated off, so the worker resumes from the checkpoint and
must finish with parameters bitwise-equal to an uninterrupted run (the
test compares the dumped finals).
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
from _cache import enable_compile_cache  # noqa: E402 (same dir)

enable_compile_cache()

import numpy as np  # noqa: E402

from distributed_pytorch_tpu.train import TrainConfig, Trainer  # noqa: E402
from distributed_pytorch_tpu.utils.checkpoint import Checkpointer  # noqa: E402


def _batch(step: int, n: int):
    rng = np.random.default_rng(9_000 + 31 * step)
    images = rng.integers(0, 256, (n, 32, 32, 3)).astype(np.uint8)
    labels = rng.integers(0, 10, n).astype(np.int32)
    return images, labels


def main() -> int:
    steps = int(os.environ["TEST_STEPS"])
    ckpt_every = int(os.environ.get("TEST_CKPT_EVERY", "2"))
    attempt = int(os.environ.get("RESTART_ATTEMPT", "0"))

    cfg = TrainConfig(model="TINY", strategy="none", batch_size=4, lr=1e-2)
    trainer = Trainer(cfg)
    ckpt = Checkpointer(os.environ["TEST_CKPT_DIR"])
    start = ckpt.maybe_restore(trainer)
    if attempt > 0:
        assert start > 0, "restarted worker found no checkpoint to resume"
    print(f"fault_worker attempt={attempt} start_step={start}", flush=True)

    for step in range(start, steps):
        # train_step's chaos hooks fire the env plan (crash at its step
        # in generation 0; quiet in generation 1)
        loss = float(trainer.train_step(*_batch(step, cfg.batch_size)))
        assert np.isfinite(loss), (step, loss)
        if (step + 1) % ckpt_every == 0:
            ckpt.save(trainer, step + 1)

    flat = np.concatenate([np.asarray(x).ravel()
                           for x in jax.tree.leaves(trainer.params)])
    np.save(os.path.join(os.environ["TEST_OUT_DIR"],
                         f"final_attempt{attempt}.npy"), flat)
    print(f"fault_worker attempt={attempt} OK final", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
