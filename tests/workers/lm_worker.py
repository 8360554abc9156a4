"""Worker for the 2-process LM-training integration test.

Each process gets 2 fake CPU devices; the gang trains a transformer over a
real 2-process / 4-device (data x seq) mesh — jax.distributed rendezvous,
cross-process ring-attention collectives, the multi-host global-batch
assembly path in LMTrainer.train_step (make_array_from_process_local_data),
and a multi-host checkpoint save/flush.
"""

import os
import sys

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=2").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
from _cache import enable_compile_cache  # noqa: E402 (same dir)

enable_compile_cache()

import numpy as np  # noqa: E402

from distributed_pytorch_tpu.lm import (  # noqa: E402
    IGNORE, LMTrainConfig, LMTrainer)
from distributed_pytorch_tpu.models import transformer as tfm  # noqa: E402
from distributed_pytorch_tpu.parallel import init as dist_init  # noqa: E402


def main() -> int:
    dist_init.init_from_env(timeout_s=120)
    rank, world = dist_init.process_info()
    assert world == 2, world
    assert len(jax.devices()) == 4

    model = tfm.TransformerConfig(vocab_size=128, d_model=64, n_layers=2,
                                  n_heads=2, head_dim=32, d_ff=128)
    # sp=4 over 4 devices spanning both processes: the mesh is built over
    # jax.devices() in process-contiguous order, so the SEQ axis crosses
    # the process boundary between devices 1 and 2 — the ring attention's
    # ppermute hops genuinely travel between processes (dp=1: the
    # cross-process DP-gradient path is covered by ddp_worker.py).
    cfg = LMTrainConfig(model=model, dp=1, sp=4, compute_dtype=None)
    tr = LMTrainer(cfg)

    rng = np.random.default_rng(0)  # same data on every process: each
    # passes its host-local share of the (2, 128) global batch — with the
    # SEQ axis spanning processes, the local share is a SEQUENCE slice
    lo, hi = rank * 64, rank * 64 + 64
    tokens = rng.integers(0, 128, (2, 128)).astype(np.int32)
    targets = np.roll(tokens, -1, 1).astype(np.int32)
    targets[:, -1] = IGNORE
    losses = []
    for _ in range(3):
        losses.append(float(tr.train_step(tokens[:, lo:hi],
                                          targets[:, lo:hi])))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses

    ckpt_dir = os.environ.get("TEST_CKPT_DIR")
    if ckpt_dir:
        tr.save_checkpoint(ckpt_dir)   # whole-tree fetch is collective
        tr.flush_checkpoints()

    print(f"lm worker rank={rank} OK losses={losses}", flush=True)
    dist_init.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
