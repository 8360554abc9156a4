"""distributed_pytorch_tpu — a TPU-native distributed training framework.

Brand-new JAX/XLA re-design of the capabilities of
``BrianZCS/distributed_pytorch``: VGG training on CIFAR-10 with pluggable
data-parallel gradient-synchronization strategies (gather/scatter through
rank 0, per-tensor all-reduce, DDP-style fused/bucketed reduction) plus a
single-process baseline, expressed as gradient-pytree transforms over a named
``jax.sharding.Mesh`` axis under ``shard_map`` with XLA collectives over
ICI/DCN.  See SURVEY.md for the structural map of the reference.
"""

__version__ = "0.1.0"

# NOTE: deliberately NO eager subpackage imports here — the launcher
# agent (`python -m distributed_pytorch_tpu.launch`) must stay jax-free
# (it supervises workers; it must never compete with them for chips or
# import time).
