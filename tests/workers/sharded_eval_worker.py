"""Worker for the 2-process sharded-evaluation integration test.

Each process gets 2 fake CPU devices (4-device mesh over 2 processes);
evaluate_sharded must reproduce the replicated evaluate() exactly, with
eval batches assembled into global arrays across processes.
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

from distributed_pytorch_tpu import eval as evaluation  # noqa: E402
from distributed_pytorch_tpu.parallel import init as dist_init  # noqa: E402
from distributed_pytorch_tpu.parallel.mesh import make_mesh  # noqa: E402
from distributed_pytorch_tpu.train import TrainConfig, Trainer  # noqa: E402


def main() -> int:
    dist_init.init_from_env(timeout_s=120)
    mesh = make_mesh()
    trainer = Trainer(TrainConfig(model=os.environ.get("TEST_MODEL", "VGG11"), strategy="ddp", batch_size=4), mesh=mesh)

    class DS:
        rng = np.random.default_rng(0)
        images = rng.integers(0, 256, (64, 32, 32, 3)).astype(np.uint8)
        labels = rng.integers(0, 10, 64).astype(np.int32)

    loss, acc = evaluation.evaluate_sharded(
        trainer.params, trainer.eval_state(), DS, mesh, batch_size=16,
        log=None)
    batches = [(DS.images[i:i + 16], DS.labels[i:i + 16])
               for i in range(0, 64, 16)]
    ref_loss, ref_acc = evaluation.evaluate(
        trainer.params, trainer.eval_state(), batches, log=None)
    assert abs(loss - ref_loss) < 1e-4, (loss, ref_loss)
    assert acc == ref_acc, (acc, ref_acc)
    print("OK", flush=True)
    dist_init.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
