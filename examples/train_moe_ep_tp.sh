#!/usr/bin/env bash
# EP x TP Mixture-of-Experts training (round 3): experts shard over their
# own 'expert' mesh axis (all_to_all rides it), each expert's FFN width is
# additionally tensor-sharded, and the batch splits over (data, expert).
# Needs dp*ep*tp = 8 devices: a pod slice, or a virtual CPU mesh
# (JAX_PLATFORMS=cpu + the XLA_FLAGS below).
cd "$(dirname "$0")/.." || exit 1
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
JAX_PLATFORMS=cpu \
python -m distributed_pytorch_tpu.lm_cli \
  --steps 100 --batch-size 8 --seq-len 256 \
  --d-model 128 --n-layers 2 --n-heads 2 --head-dim 64 \
  --n-experts 4 \
  --dp 2 --ep 2 --tp 2 \
  --compute-dtype float32 \
  --log-every 20 --eval-every 50 "$@"
