"""Activation-memory accountant: predict + measure the LM backward's
saved-residual footprint.

Two sides, one contract:

- :func:`predict_activation_bytes` — a PURE SHAPE FUNCTION from
  (TransformerConfig, batch, seq, remat, loss_impl) to peak saved-residual
  bytes per device.  The inventory below is the jaxpr-level census of the
  repo's own stack (models/transformer.py block + ops/losses.py head),
  itemized per layer and per mode — at float32 it reproduces the census
  byte-for-byte for the dense-MLP flash stack (tests/test_memory.py pins
  <=10%).
- :func:`saved_residual_census` — the measurement: JAX's
  ``saved_residuals`` over the actual loss function (exact, CPU-friendly,
  nothing executed), with parameter/argument entries filtered out so only
  true activations count.

Third verification lane: utils/monitor.py ``record_memory`` watermarks
(``device_peak_bytes``) on a live backend, with the prediction feeding the
``default_rules`` device-memory SLO ceiling.

Why it matters (round 17 / ISSUE 14): on a real TPU, activation memory is
what caps per-device batch size, and batch size is the denominator every
gradient-sync strategy amortizes against — so the autotuner's chooser
(parallel/autotune.py) prices remat/loss_impl rungs with exactly this
predictor against a ``memory_budget_bytes``.

Known approximations (documented, not silent): GQA stacks (kv_heads <
n_heads) count the post-repeat H-sized flash residuals (slight
overcount of the pre-repeat k/v einsum outputs); MoE layers are counted
as dense-MLP layers of the same d_ff; ring attention (sp > 1) is counted
as flash over the local sequence shard.  bfloat16 compute counts the
activation-dtype items at 2 bytes and the always-f32 items (norm
statistics, flash lse/accumulators, the f32 head) at 4.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import numpy as np

# jax 0.9.0 exposes it under _src only
from jax._src.ad_checkpoint import saved_residuals as _saved_residuals

F32 = 4  # bytes; items the stack keeps in f32 regardless of compute dtype
I32 = 4


@dataclass(frozen=True)
class Residual:
    shape: tuple
    dtype: str
    bytes: int
    src: str


def saved_residual_census(fn: Callable, *args: Any) -> dict:
    """Jaxpr saved-residual census of ``fn(*args)`` (nothing is executed;
    args may be ShapeDtypeStructs).  Entries that are function ARGUMENTS
    (params, batches — held live by the caller anyway) and zero-byte
    float0 tangent placeholders are excluded, so ``bytes`` is the
    activation residual footprint the backward adds on top of the inputs.
    """
    residuals = []
    total = 0
    for aval, why in _saved_residuals(fn, *args):
        dt = str(aval.dtype)
        if "from the argument" in why or dt.startswith("[("):
            continue
        nbytes = int(np.prod(aval.shape)) * aval.dtype.itemsize if \
            aval.shape else aval.dtype.itemsize
        residuals.append(Residual(tuple(aval.shape), dt, nbytes, why))
        total += nbytes
    return {"bytes": total, "residuals": residuals}


def find_residuals(census: dict, *, min_bytes: int = 0,
                   dtype: str | None = None, last_dim: int | None = None):
    """Filter a census's residual list (the logits-pin helper: e.g.
    ``find_residuals(c, dtype='float32', last_dim=vocab)``)."""
    out = []
    for r in census["residuals"]:
        if r.bytes < min_bytes:
            continue
        if dtype is not None and r.dtype != dtype:
            continue
        if last_dim is not None and (not r.shape or r.shape[-1] != last_dim):
            continue
        out.append(r)
    return out


def predict_activation_bytes(
    model,                      # models/transformer.TransformerConfig
    *,
    batch: int,                 # per-device batch rows
    seq: int,                   # GLOBAL sequence length
    remat: str = "none",
    loss_impl: str = "dense",
    loss_chunk: int | None = None,
    dtype_bytes: int = 4,       # compute dtype itemsize (4 = f32)
    tp: int = 1,
    sp: int = 1,
) -> int:
    """Peak saved-residual activation bytes per device for one backward
    of the LM loss — the itemized census of this repo's stack as a pure
    shape function.  See module docstring for the per-mode inventory and
    the documented approximations."""
    if remat not in ("none", "full", "selective"):
        raise ValueError(f"unknown remat {remat!r}")
    if loss_impl not in ("dense", "chunked"):
        raise ValueError(f"unknown loss_impl {loss_impl!r}")
    a = dtype_bytes
    d, hd = model.d_model, model.head_dim
    h = model.n_heads // max(tp, 1)
    f = model.ff // max(tp, 1)
    t = seq // max(sp, 1)
    v = model.vocab_size
    bt = batch * t
    n_layers = model.n_layers

    if remat == "none":
        # the full block inventory: 6 F-sized MLP residuals (gate, up,
        # silu pair, product, matmul operands), 8 D-sized stream/norm
        # residuals, 5 H*hd-sized attention projections (flash q/k/v/o
        # + the pre-reshape layout copy), the flash lse (bh, 8, t),
        # rotary cos/sin tables (4 each for q and k), and the rms_norm
        # rsqrt statistics
        per_layer = (6 * bt * f * a
                     + 8 * bt * d * a
                     + 5 * bt * h * hd * a
                     + batch * h * 8 * t * F32          # flash lse
                     + 8 * t * (hd // 2) * F32          # rotary tables
                     + 4 * bt * F32                     # rms rsqrt stats
                     + 2 * d * F32 + 4 * 16 * I32)      # misc tiny
    else:
        # jax.checkpoint: only each block's input carry (+ the tiny
        # rotary freq vectors) survives to the backward ...
        per_layer = bt * d * a + 2 * (hd // 2) * F32
        if remat == "selective":
            # ... plus the policy-saved flash (o, lse) pair
            per_layer += bt * h * hd * a + batch * h * 8 * t * F32

    # head + boundaries (fixed part): 4 D-sized residuals (embed output,
    # final stream carry, final-norm f32 input, normed h) ...
    fixed = 4 * bt * d * a + 2 * bt * F32 + d * F32 + 4
    if remat != "none":
        fixed += t * I32  # pos becomes a saved checkpoint input
    if loss_impl == "dense":
        # ... the (B, T, V) f32 softmax residual and the transposed
        # embedding, plus masked_ce's index/mask scalars
        fixed += (bt * v * F32 + d * v * F32
                  + 2 * bt + 2 * bt * I32 + 2 * bt * F32)
    else:
        # ... the streamed head keeps only its (B*T,) logsumexp + the
        # integer targets — nothing V-sized
        fixed += bt * F32 + 2 * bt * I32 + 2 * bt * F32
    return n_layers * per_layer + fixed


def predict_recompute_bytes(
    model,
    *,
    batch: int,
    seq: int,
    remat: str = "none",
    loss_impl: str = "dense",
    dtype_bytes: int = 4,
    tp: int = 1,
    sp: int = 1,
) -> int:
    """Activation bytes the backward must RE-produce under this (remat,
    loss_impl) — the compute half of the memory trade, priced by the
    autotuner at the profile's calibrated ``recompute_s_per_byte`` (the
    ``quant_s_per_byte`` precedent: wire/memory saved vs compute spent,
    both in seconds, on THIS host).

    - ``remat='full'`` re-runs each block forward: everything the
      no-remat census saved minus what full still saves.
    - ``remat='selective'`` additionally skips the flash kernel (its
      (o, lse) residuals are policy-saved, so only the projections +
      MLP re-run): subtract the flash share of the block inventory.
    - ``loss_impl='chunked'`` re-materializes the chunk logits once in
      the backward: one (B, T, V) f32 pass that dense paid for in
      memory instead.
    """
    none_b = predict_activation_bytes(
        model, batch=batch, seq=seq, remat="none", loss_impl=loss_impl,
        dtype_bytes=dtype_bytes, tp=tp, sp=sp)
    saved_b = predict_activation_bytes(
        model, batch=batch, seq=seq, remat=remat, loss_impl=loss_impl,
        dtype_bytes=dtype_bytes, tp=tp, sp=sp)
    recompute = none_b - saved_b  # 0 when remat == "none"
    if remat == "selective":
        h = model.n_heads // max(tp, 1)
        t = seq // max(sp, 1)
        bt = batch * t
        # the kernel's own work (softmax over 5 H*hd-sized operands) is
        # NOT re-run — only its already-counted (o, lse) are kept, so
        # drop the remaining flash share from the recompute bill
        recompute -= model.n_layers * (
            4 * bt * h * model.head_dim * dtype_bytes)
    if loss_impl == "chunked":
        recompute += batch * (seq // max(sp, 1)) * model.vocab_size * F32
    return max(recompute, 0)
