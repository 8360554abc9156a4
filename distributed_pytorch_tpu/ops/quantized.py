"""Quantized dense compute: int8 x int8 -> int32 matmul (round 16).

The wire tricks (``dcn_compress``, ``fsdp_gather_dtype``) spend fewer
bits on LINKS; this module spends fewer bits in the MXU itself — the
EQuARX observation that weights (and forward activations) tolerate
lower precision than gradient accumulators, applied to the
transformer's dense projections:

- ``quantize_rowwise`` / ``quantize_colwise``: symmetric int8
  quantization against per-row (activation) / per-column (weight) f32
  scales — one absmax per output row/col of the product, so the
  epilogue dequant is a rank-1 outer product of scales.
- ``int8_matmul_xla``: the reference path — quantize both operands,
  one ``lax.dot_general`` on int8 with ``preferred_element_type=
  jnp.int32`` (exact integer arithmetic), dequantize.
- ``int8_matmul``: the Pallas TPU kernel — (m, n, k)-tiled grid with k
  innermost, int32 VMEM accumulator, per-row x per-col scale dequant
  in the epilogue of the last k step.  Bitwise-identical to the XLA
  path (both run the same exact integer dot over the same quantized
  operands — pinned by tests/test_lowbit.py), so CPU test runs
  exercise the interpreter while TPU runs hit the MXU's native int8
  throughput.
- ``quantized_matmul``: the training entry point ``matmul_dtype=
  "int8"`` routes through (models/transformer.py ``_proj``): int8
  forward, STRAIGHT-THROUGH backward — cotangents flow through the
  plain matmul transpose in the compute dtype, because rounding the
  gradient stream would need the EF machinery the sync paths carry
  and the forward perturbation alone is what the optimizer tracks.

Shapes are plain (m, k) @ (k, n); the transformer reshapes its 3D
einsum weights to 2D around the call.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import compat

Array = jax.Array

# MXU-native int8 tiles: the (32, 128) minimum int8 tile from the
# Pallas guide, widened to the usual 128-lane squares where the
# operands allow.  _fit ensures every grid dim divides exactly; shapes
# that cannot tile at the minimum fall back to the XLA path.
DEFAULT_BLOCK_M = 128
DEFAULT_BLOCK_N = 128
DEFAULT_BLOCK_K = 128


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def quantize_rowwise(x: Array) -> tuple[Array, Array]:
    """Symmetric int8 per-ROW quantization of a (m, k) activation:
    ``q * scale ~= x`` with ``scale`` (m, 1) f32."""
    x32 = x.astype(jnp.float32)
    scale = jnp.maximum(
        jnp.max(jnp.abs(x32), axis=1, keepdims=True) / 127.0, 1e-30)
    q = jnp.clip(jnp.round(x32 / scale), -127, 127).astype(jnp.int8)
    return q, scale


def quantize_colwise(w: Array) -> tuple[Array, Array]:
    """Symmetric int8 per-COLUMN quantization of a (k, n) weight:
    ``q * scale ~= w`` with ``scale`` (1, n) f32."""
    w32 = w.astype(jnp.float32)
    scale = jnp.maximum(
        jnp.max(jnp.abs(w32), axis=0, keepdims=True) / 127.0, 1e-30)
    q = jnp.clip(jnp.round(w32 / scale), -127, 127).astype(jnp.int8)
    return q, scale


def int8_matmul_xla(x: Array, w: Array) -> Array:
    """The XLA reference/fallback: quantize -> exact int8 dot ->
    dequant.  Output f32 (the caller casts)."""
    qx, sx = quantize_rowwise(x)
    qw, sw = quantize_colwise(w)
    acc = jax.lax.dot_general(qx, qw, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * (sx * sw)


def _matmul_kernel(qx_ref, qw_ref, sx_ref, sw_ref, o_ref, acc_ref, *,
                   n_k: int):
    """Grid (num_m, num_n, num_k), k innermost/sequential: the int32
    VMEM accumulator carries partial sums across k tiles of one (m, n)
    tile; the LAST k step applies the rank-1 scale dequant and writes
    f32."""
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jax.lax.dot_general(
        qx_ref[:], qw_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(kk == n_k - 1)
    def _epilogue():
        # same association as int8_matmul_xla (acc * (sx*sw)) so the
        # two paths stay BITWISE equal, not merely close
        o_ref[:] = acc_ref[:].astype(jnp.float32) * (sx_ref[:] * sw_ref[:])


def _fit(limit: int, dim: int, align: int) -> int | None:
    """Largest block <= limit that divides ``dim`` and is a multiple of
    ``align``; None when no such block exists (caller falls back)."""
    b = min(limit, dim)
    while b >= align:
        if dim % b == 0 and b % align == 0:
            return b
        b -= align
    return None


def int8_matmul(x: Array, w: Array, *,
                block_m: int | None = None, block_n: int | None = None,
                block_k: int | None = None,
                interpret: bool | None = None) -> Array:
    """Pallas int8 matmul of (m, k) @ (k, n): quantize both operands
    (per-row / per-col scales), run the tiled exact integer dot with
    the dequant epilogue, return f32.  Shapes that cannot tile on the
    minimum int8 tile route to ``int8_matmul_xla`` — same quantized
    operands, same exact integer sum, bitwise-equal output."""
    if interpret is None:
        interpret = _interpret_default()
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    bm = block_m if block_m is not None else _fit(DEFAULT_BLOCK_M, m, 32)
    bn = block_n if block_n is not None else _fit(DEFAULT_BLOCK_N, n, 128)
    bk = block_k if block_k is not None else _fit(DEFAULT_BLOCK_K, k, 128)
    if bm is None or bn is None or bk is None:
        return int8_matmul_xla(x, w)
    qx, sx = quantize_rowwise(x)
    qw, sw = quantize_colwise(w)
    vma = compat.vma_of(x) | compat.vma_of(w)
    kernel = functools.partial(_matmul_kernel, n_k=k // bk)
    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=compat.shape_struct((m, n), jnp.float32, vma=vma),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
        name="int8_matmul",
    )(qx, qw, sx, sw)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def quantized_matmul(x: Array, w: Array, use_kernel: bool = True) -> Array:
    """int8-forward / straight-through-backward matmul: forward runs
    the exact int8 product of the quantized operands (Pallas kernel, or
    the XLA int8 dot when ``use_kernel=False``), backward differentiates
    the PLAIN product — ``dx = g @ w.T``, ``dw = x.T @ g`` in the input
    dtype, no rounding on the gradient stream.  Off-TPU the kernel path
    would run Mosaic-interpreted, so the training entry point takes the
    XLA int8 dot there — the two are BITWISE equal (test-pinned), the
    choice is throughput only."""
    out = (int8_matmul(x, w) if use_kernel and not _interpret_default()
           else int8_matmul_xla(x, w))
    return out.astype(x.dtype)


def _qm_fwd(x, w, use_kernel):
    return quantized_matmul(x, w, use_kernel), (x, w)


def _qm_bwd(use_kernel, res, g):
    x, w = res
    dx = jnp.dot(g, w.T.astype(g.dtype)).astype(x.dtype)
    dw = jnp.dot(x.T.astype(g.dtype), g).astype(w.dtype)
    return dx, dw


quantized_matmul.defvjp(_qm_fwd, _qm_bwd)
