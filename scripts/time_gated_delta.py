#!/usr/bin/env python
"""Time one linear layer's gated delta rule alone on the chip: the XLA
chunked form (``gated_delta_chunked``) against the two kernels
(``gated_delta``), forward and forward + backward, and how far the kernels'
output and gradients lie from the XLA form's.

    PYTHONPATH=. python scripts/time_gated_delta.py [--seq 8192] \
        [--heads 32] [--key-heads 16] [--dim 128]

The default shapes are ``qwen3next_train_8k``'s: one 8,192-token row of 32
value heads of 128, each of 16 key heads serving two (bfloat16 q, k and v;
float32 g and beta, the decay as the benchmark's init makes it); the XLA
form is handed q and k repeated to the value heads.  Prints one JSON line a form.  Needs the
chip: timings off it would time the Pallas interpreter.
"""

from __future__ import annotations

import argparse
import json
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from distributed_pytorch_tpu.ops import gated_delta as gd


def inputs(seq: int, heads: int, key_heads: int, dim: int, seed: int = 0):
    keys = jax.random.split(jax.random.key(seed), 6)

    def l2(x):
        return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    shape, key_shape = (1, seq, heads, dim), (1, seq, key_heads, dim)
    q = (l2(jax.random.normal(keys[0], key_shape)) / math.sqrt(dim)).astype(
        jnp.bfloat16)
    k = l2(jax.random.normal(keys[1], key_shape)).astype(jnp.bfloat16)
    v = jax.random.normal(keys[2], shape).astype(jnp.bfloat16)
    a = jax.random.normal(keys[3], shape[:3])
    g = -math.e * jax.nn.softplus(a + 1.0)
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], shape[:3]))
    weights = jax.random.normal(keys[5], shape)
    return (q, k, v, g, beta), weights


def timed(fn, args, reps: int = 10) -> tuple[float, object]:
    """Milliseconds a call, warmed, ended on the device's result."""
    out = jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / reps * 1e3, out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--key-heads", type=int, default=16)
    ap.add_argument("--dim", type=int, default=128)
    opts = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("time_gated_delta.py times the chip; none found")
    args, weights = inputs(opts.seq, opts.heads, opts.key_heads, opts.dim)
    rep = opts.heads // opts.key_heads

    def xla(q, k, *rest):
        return gd.gated_delta_chunked(jnp.repeat(q, rep, axis=2),
                                      jnp.repeat(k, rep, axis=2), *rest)

    device = jax.devices()[0].device_kind
    first = None
    for name, rule in (("xla", xla), ("kernels", gd.gated_delta)):
        def loss(*a, rule=rule):
            return jnp.sum(rule(*a)[0] * weights)

        forward = jax.jit(lambda *a, rule=rule: rule(*a)[0])
        both = jax.jit(jax.value_and_grad(loss, argnums=range(5)))
        fwd_ms, out = timed(forward, args)
        both_ms, (_, grads) = timed(both, args)
        line = {"form": name, "device": device, "fwd_ms": fwd_ms,
                "fwd_bwd_ms": both_ms}
        got = [np.asarray(x, np.float64) for x in (out, *grads)]
        if first is None:
            first = got
        else:       # each as a share of the XLA form's largest value
            line["off"] = [float(np.abs(a - b).max() / np.abs(b).max())
                           for a, b in zip(got, first)]
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
