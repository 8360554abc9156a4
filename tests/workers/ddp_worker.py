"""Worker for the multi-process distributed-training integration tests.

Launched by distributed_pytorch_tpu.launch with env-var rendezvous; each
process gets TEST_DEVICES_PER_PROC (default 2) fake CPU devices, so the
gang trains over a real world_size-process mesh: jax.distributed
rendezvous, cross-process collectives, and the
make_array_from_process_local_data batch-assembly path.  TEST_MODEL
(default VGG11) selects the model — the 4-process test uses TINY to keep
the one-core compile cost sane.
"""

import os
import sys

_DEV_PER_PROC = int(os.environ.get("TEST_DEVICES_PER_PROC", "2"))
_MODEL = os.environ.get("TEST_MODEL", "VGG11")
_STRATEGY = os.environ.get("TEST_STRATEGY", "ddp")

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


def main() -> int:
    dist_init.init_from_env(timeout_s=120)
    rank, world = dist_init.process_info()
    want_world = int(os.environ["WORLD_SIZE"])
    assert world == want_world, (world, want_world)
    n_dev = len(jax.devices())
    want_dev = world * _DEV_PER_PROC
    assert n_dev == want_dev, f"expected {want_dev} global devices, {n_dev}"

    cfg = TrainConfig(model=_MODEL, strategy=_STRATEGY, batch_size=4,
                      lr=1e-3, dcn_size=2)
    # factored-axis strategies (hierarchical) build their own
    # Mesh(('dcn','ici')) — with 2 fake devices per process, the 'dcn'
    # axis lands exactly on the process boundary (the real multislice
    # topology: ici within a host, dcn across)
    factored = _STRATEGY == "hierarchical"
    trainer = Trainer(cfg, mesh=None if factored else make_mesh())
    if factored:
        assert trainer.mesh.axis_names == ("dcn", "ici")
    # per-host share of the global batch: local devices * per-replica batch
    rng = np.random.default_rng(rank)
    local = _DEV_PER_PROC * 4
    losses = []
    for _ in range(3):
        images = rng.integers(0, 256, (local, 32, 32, 3)).astype(np.uint8)
        labels = rng.integers(0, 10, local).astype(np.int32)
        losses.append(float(trainer.train_step(images, labels)))
    assert all(np.isfinite(losses)), losses
    trainer.check_consistency()  # replicated state in sync across processes
    print(f"worker rank={rank} OK losses={losses}", flush=True)
    dist_init.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
