"""Attention ops: XLA reference + Pallas TPU flash attention (fwd/bwd).

The reference repo has no attention at all — it is a CNN project (SURVEY.md
section 5: "no attention, no sequence dimension").  This module is the
long-context capability the TPU framework adds: the hot op of every
transformer, built MXU-first:

- ``attention_reference``: plain XLA attention (einsum -> f32 softmax ->
  einsum).  O(S^2) memory — the oracle the kernel is tested against, and the
  building block of the pure-JAX ring attention (parallel/context.py).
- ``flash_attention``: Pallas TPU kernel, online-softmax tiling so the S x S
  score matrix never materializes in HBM; custom VJP with a recompute
  backward: one kernel that rebuilds a tile's scores once and takes dQ, dK
  and dV from them, while one sequence's dQ fits VMEM (``_bwd_fuses``),
  else a dQ kernel and a dK/dV kernel.  Default blocks are 1024 (q) x
  1024 (k), auto-shrunk to the largest 8-aligned divisor of the sequence
  length; scores/accumulators are f32, inputs may be bf16.  A causal call's
  tile that lies wholly outside the band (``_tile_live``) costs neither
  compute nor copy.

Shapes follow the (batch, heads, seq, head_dim) convention.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import compat

Array = jax.Array

# The block defaults come from sweeps on a v5e and a JAX this repo no longer
# has (largest tile won or tied, forward + backward at S=1024..8192;
# 1024x1024 over 512x1024 by 9-26%; block_q 256 pathological in dK/dV): a
# claim, not re-measured.  What the chip this repo has says (TPU v5 lite,
# JAX 0.9.0, PR 31, PERF.md section 6): at these blocks one layer's forward +
# backward alone, bf16 causal, takes 4.29 ms at (32, 4096, 128) with the
# fused backward against 5.58 ms with the dQ and dK/dV kernels, 13.14 against
# 17.43 ms at (28, 8192, 128), 10.72 against 14.03 ms there under a
# 4,096-key window; the forward is 1.54 / 4.72 / 3.88 ms of each.
# Short sequences auto-shrink via _fit_block.
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
# Decode reads are skipped at block granularity (dead blocks past ``pos``),
# so the decode kernel wants much finer tiles than training flash attention:
# 256 keeps the skip useful at common cache lengths (512-4k) while the
# per-grid-step overhead stays amortized (measured flat vs 512 at 4k cache).
DEFAULT_DECODE_BLOCK_K = 256
NEG_INF = -1e30  # large-negative instead of -inf: keeps exp()/max() NaN-free


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


# A kernel may use 16 MiB of a v5e core's 128 MiB of VMEM unless it asks
# for more (the compiler's "scoped vmem limit").
_SCOPED_VMEM_DEFAULT = 16 * 2**20
# What the fused backward may keep of one whole sequence in VMEM: the budget
# of ``_bwd_fuses``.
_FUSED_BWD_VMEM_BUDGET = 16 * 2**20


def _decode_compiler_params(q: Array, cache: Array, block_k: int,
                            quant: bool):
    """VMEM request of one decode grid step, or None when the default
    covers it.

    The pipeline double-buffers every operand tile: K and V tiles of
    (hkv, block_k, D), the (H, D) query and output tiles, and with int8
    KV two (hkv, 1, block_k) f32 scale tiles; the scratch (f32 acc (H, D)
    plus m and l (H, 128)) is single.  At 16 kv heads x 512 x 128 the K/V
    tiles alone are 4 MiB (int8), 8 MiB (bf16) or 16 MiB (f32): the first
    two fit the default with room for the body's f32 temporaries (2 MiB
    is asked for them), float32 does not.
    """
    h, d, hkv = q.shape[1], q.shape[-1], cache.shape[1]
    tiles = (2 * hkv * block_k * d * cache.dtype.itemsize
             + 2 * h * d * q.dtype.itemsize
             + (2 * hkv * block_k * 4 if quant else 0))
    need = 2 * tiles + (h * d + 2 * h * 128) * 4 + 2 * 2**20
    if need <= _SCOPED_VMEM_DEFAULT:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=need)


def _check_window(window, causal) -> None:
    if window is not None and (not causal or window < 1):
        raise ValueError(f"window={window!r} needs causal=True and a "
                         f"window of at least one key")


# ---------------------------------------------------------------------------
# Reference attention (the correctness oracle)
# ---------------------------------------------------------------------------

def attention_reference(
    q: Array, k: Array, v: Array, *, causal: bool = False,
    sm_scale: float | None = None, with_lse: bool = False,
    bias: Array | None = None, window: int | None = None,
):
    """Plain XLA attention over (B, H, S, D) tensors.

    Scores and softmax in float32 regardless of input dtype.  With
    ``with_lse`` also returns the row logsumexp (B, H, Sq) — the quantity
    ring attention needs to merge partial results across sequence chunks.
    ``bias`` is an additive score bias broadcastable to (B, H, Sq, Sk)
    (e.g. the NEG_INF cache-validity mask of KV-cache decode, generate.py).
    ``window`` (causal only): a query sees the ``window`` newest keys up to
    and including its own position (key > query - window).
    """
    _check_window(window, causal)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        qi = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        kj = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        s = jnp.where(qi + (sk - sq) >= kj, s, NEG_INF)
        if window is not None:
            s = jnp.where(kj > qi + (sk - sq) - window, s, NEG_INF)
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32).astype(q.dtype)
    if with_lse:
        return o, lse
    return o


# ---------------------------------------------------------------------------
# Flash attention: a call's tiles, live and dead, then the forward kernel
# ---------------------------------------------------------------------------

class _Tiling(NamedTuple):
    """What a kernel knows of its call's tiles besides their indices."""
    causal: bool
    block_q: int
    block_k: int
    window: int | None


def _causal_mask(s, i, j, tiles: _Tiling):
    """Scores of q block i against k block j with the keys a query may not
    see at NEG_INF: those after it and, with a window, those more than
    ``window - 1`` positions before it."""
    qi = i * tiles.block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kj = j * tiles.block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(qi >= kj, s, NEG_INF)
    if tiles.window is not None:
        s = jnp.where(kj > qi - tiles.window, s, NEG_INF)
    return s


def _tile_live(i, j, tiles: _Tiling):
    """Whether some query of q block i sees some key of k block j; block
    indices traced or plain integers.  A causal call's tile that is not,
    wholly above the diagonal or wholly before the band, is dead: no compute
    and, by the index maps below, no copy.  The one statement of the band's
    geometry by tile: the four kernels and ``tile_census`` read it, and
    ``_live_k_block`` and ``_live_q_block`` are it solved for one index."""
    if not tiles.causal:
        # Traced-true rather than literal True: pl.when(True) inlines the
        # body, and the Pallas HLO interpreter's vma check then rejects
        # block loads on shard_map-varying inputs (a traced cond keeps CPU
        # interpret tests working; Mosaic folds it on TPU).
        return j >= 0
    q0, k0 = i * tiles.block_q, j * tiles.block_k
    live = k0 <= q0 + tiles.block_q - 1
    if tiles.window is not None:
        live &= k0 + tiles.block_k - 1 > q0 - tiles.window
    return live


def _live_k_block(i, j, tiles: _Tiling):
    """k block j, or the live one nearest to it against q block i: the K/V
    tile a step of the (BH, num_q, num_k) grid names.  A dead step so names
    the tile the pipeline already holds and its copy is skipped, as the
    decode kernel skips dead pages."""
    if not tiles.causal:
        return j
    block_q, block_k, window = tiles.block_q, tiles.block_k, tiles.window
    lo = 0 if window is None else (
        jnp.maximum(i * block_q - window + 1, 0) // block_k)
    return jnp.clip(j, lo, (i * block_q + block_q - 1) // block_k)


def _live_q_block(j, i, tiles: _Tiling, nq: int):
    """q block i, or the live one nearest to it against k block j: the q,
    dO, lse and delta tiles a step of the (BH, num_k, num_q) grid names."""
    if not tiles.causal:
        return i
    block_q, block_k, window = tiles.block_q, tiles.block_k, tiles.window
    hi = nq - 1 if window is None else jnp.minimum(
        (j * block_k + block_k - 2 + window) // block_q, nq - 1)
    return jnp.clip(i, (j * block_k) // block_q, hi)


class TileCensus(NamedTuple):
    """Tiles of one (batch x head) slice of a call."""
    dead: int
    live: int


def tile_census(sq: int, sk: int, block_q: int, block_k: int, causal: bool,
                window: int | None = None) -> TileCensus:
    """How many tiles of a ``flash_attention`` call are dead (neither
    computed nor copied) and live, by the predicate the kernels trace.  A
    call that is not causal has no dead tile."""
    tiles = _Tiling(causal, block_q, block_k, window)
    nq, nk = sq // block_q, sk // block_k
    live = sum(bool(_tile_live(i, j, tiles))
               for i in range(nq) for j in range(nk))
    return TileCensus(nq * nk - live, live)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, sm_scale: float, tiles: _Tiling):
    """Grid (BH, num_q, num_k); the k dimension is innermost/sequential, so
    the VMEM scratch (acc/m/l) carries the online-softmax state across k
    blocks of one q block."""
    i, j = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(_tile_live(i, j, tiles))
    def _compute():
        q = q_ref[0]  # (block_q, d)
        s = jax.lax.dot_general(
            q, k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # (bq, bk)
        if tiles.causal:
            s = _causal_mask(s, i, j, tiles)
        m_prev = m_ref[:, :1]                          # (bq, 1)
        l_prev = l_ref[:, :1]                          # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)      # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)                # (bq, 1)
        p = jnp.exp(s - m_new)                         # (bq, bk) f32
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # (bq, d)
        acc_ref[:] = acc_ref[:] * alpha + pv

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_ref[:, :1]
        safe_l = jnp.maximum(l, 1e-30)
        o_ref[0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        lse = m_ref[:, 0] + jnp.log(safe_l[:, 0])      # (bq,)
        # (8, bq) broadcast: the lse buffer keeps 8 sublanes so its block
        # satisfies the TPU (8, 128) tile-divisibility rule.
        lse_ref[0] = jnp.broadcast_to(lse[None, :], lse_ref.shape[1:])


def _vma(*arrays):
    """Union of the inputs' varying mesh axes: pallas_call outputs must
    declare their vma explicitly under shard_map(check_vma=True).  On
    runtimes without vma tracking this is always empty (compat.vma_of)
    and the out_shapes below drop the kwarg."""
    out = frozenset()
    for a in arrays:
        out |= compat.vma_of(a)
    return out


def _kv_index(tiles: _Tiling):
    """Index map of a K/V tile on the (BH, num_q, num_k) grid."""
    return lambda b, i, j: (b, _live_k_block(i, j, tiles), 0)


def _fwd(q, k, v, *, sm_scale, causal, block_q, block_k, interpret,
         window=None):
    bh, sq, d = q.shape
    sk = k.shape[1]
    nq, nk = sq // block_q, sk // block_k
    vma = _vma(q, k, v)
    tiles = _Tiling(causal, block_q, block_k, window)
    kernel = functools.partial(_fwd_kernel, sm_scale=sm_scale, tiles=tiles)
    kv_index = _kv_index(tiles)
    o, lse = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            compat.shape_struct((bh, sq, d), q.dtype, vma=vma),
            compat.shape_struct((bh, 8, sq), jnp.float32, vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),    # acc
            pltpu.VMEM((block_q, 128), jnp.float32),  # running max m
            pltpu.VMEM((block_q, 128), jnp.float32),  # running sum l
        ],
        interpret=interpret,
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# Flash attention: backward kernels (recompute p from q,k + saved lse).
# ``_bwd`` runs the fused one where a sequence's dQ fits VMEM, else the two
# that follow it; all three do the same arithmetic in the same order.
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   acc_ref, *, sm_scale: float, tiles: _Tiling):
    """Grid (BH, num_q, num_k), k innermost: accumulate dQ for one q block.

    ``delta`` is precomputed outside the kernel as rowsum(do*o) - dlse, so
    one kernel serves both the o-only VJP (dlse = 0) and the (o, lse) VJP
    ring attention differentiates through (the lse cotangent folds into ds
    as ds = p * (dp - delta) exactly)."""
    i, j = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(_tile_live(i, j, tiles))
    def _compute():
        q = q_ref[0]
        s = jax.lax.dot_general(
            q, k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if tiles.causal:
            s = _causal_mask(s, i, j, tiles)
        p = jnp.exp(s - lse_ref[0, 0][:, None])        # (bq, bk)
        do = do_ref[0].astype(jnp.float32)
        delta = delta_ref[0, 0][:, None]               # (bq, 1)
        dp = jax.lax.dot_general(
            do.astype(v_ref.dtype), v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # (bq, bk)
        ds = p * (dp - delta) * sm_scale
        acc_ref[:] += jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, sm_scale: float,
                    tiles: _Tiling, dq_ref=None, dq_acc=None):
    """Grid (BH, num_k, num_q), q innermost: accumulate dK/dV for one k
    block and, given ``dq_ref`` / ``dq_acc``, dQ of the whole sequence: the
    backward of one (q block i, k block j) tile from one recomputation of
    its scores.

    dK and dV belong to the k block the inner dimension holds fixed.  dQ of
    q block i gathers over the outer dimension, so it is summed into rows
    ``i * block_q`` onwards of a float32 scratch that spans the sequence,
    in the same order over j as ``_bwd_dq_kernel`` sums it, and is cast and
    written when the sweep of this BH ends."""
    j, i = pl.program_id(1), pl.program_id(2)
    nk, nq = pl.num_programs(1), pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    if dq_acc is not None:
        @pl.when((j == 0) & (i == 0))
        def _init_dq():
            dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(_tile_live(i, j, tiles))
    def _compute():
        q, k = q_ref[0], k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if tiles.causal:
            s = _causal_mask(s, i, j, tiles)
        p = jnp.exp(s - lse_ref[0, 0][:, None])        # (bq, bk)
        do = do_ref[0].astype(jnp.float32)
        delta = delta_ref[0, 0][:, None]               # (bq, 1)
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # (bk, d)
        dp = jax.lax.dot_general(
            do.astype(v_ref.dtype), v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # (bq, bk)
        ds = (p * (dp - delta) * sm_scale).astype(q.dtype)
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # (bk, d)
        if dq_acc is not None:
            rows = pl.ds(pl.multiple_of(i * tiles.block_q, tiles.block_q),
                         tiles.block_q)
            dq_acc[rows, :] += jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)    # (bq, d)

    @pl.when(i == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    if dq_acc is not None:
        @pl.when((j == nk - 1) & (i == nq - 1))
        def _finalize_dq():
            dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc,
                      **static):
    """dQ, dK and dV of a tile from one recomputation of its scores: five
    products and one exp pass, where ``_bwd_dq_kernel`` and
    ``_bwd_dkv_kernel`` together spend seven and two."""
    _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, dq_ref=dq_ref,
                    dq_acc=dq_acc, **static)


def _lanes(d: int) -> int:
    """A row of ``d`` elements as VMEM holds it: whole 128-lane tiles."""
    return -(-d // 128) * 128


def _whole_sequence_bytes(sq: int, d: int, dtype) -> int:
    """VMEM the fused backward holds for one sequence's dQ: the float32
    accumulator and the double-buffered output block."""
    return sq * _lanes(d) * (4 + 2 * jnp.dtype(dtype).itemsize)


def _bwd_fuses(sq: int, sk: int, d: int, dtype) -> bool:
    """The backward's plan, read off its shapes: one fused kernel while what
    it keeps of a whole sequence fits ``_FUSED_BWD_VMEM_BUDGET`` (8 MiB at
    8,192 queries of 128 in bf16), else the dQ and dK/dV kernels, whose VMEM
    does not grow with the sequence (ring attention over long local chunks).
    ``sk`` is part of the plan's key and moves nothing today: the k side is
    tiled."""
    del sk
    return _whole_sequence_bytes(sq, d, dtype) <= _FUSED_BWD_VMEM_BUDGET


def _fused_bwd_compiler_params(sq: int, d: int, block_q: int, block_k: int,
                               dtype):
    """VMEM request of the fused backward, or None when the default covers
    it: the whole-sequence dQ buffers, the double-buffered tiles (q, do, k,
    v in; dk, dv out; lse and delta rows), the two (block_k, d) float32
    accumulators and two (block_q, block_k) float32 temporaries of the
    body.  The chip's compiler keeps about one (chip-less compiles, blocks
    of 1024: the least limit it accepts is 11.9 MiB at 4,096 keys of 128 in
    bf16, 16.9 at 8,192, 23.9 at 16,384, 23.3 at 8,192 in float32; this
    comes to 16.1 / 20.1 / 28.1 / 27.1)."""
    item = jnp.dtype(dtype).itemsize
    tiles = 2 * ((2 * block_q + 4 * block_k) * _lanes(d) * item
                 + 2 * 8 * block_q * 4)
    need = (_whole_sequence_bytes(sq, d, dtype) + tiles
            + 2 * block_k * _lanes(d) * 4 + 2 * block_q * block_k * 4)
    if need <= _SCOPED_VMEM_DEFAULT:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=need)


def _bwd(sm_scale, causal, block_q, block_k, interpret, residuals, do,
         dlse=None, window=None):
    q, k, v, o, lse = residuals
    bh, sq, d = q.shape
    sk = k.shape[1]
    nq, nk = sq // block_q, sk // block_k
    vma = _vma(q, k, v, o, do, lse)
    tiles = _Tiling(causal, block_q, block_k, window)

    def q_block(j, i):
        return _live_q_block(j, i, tiles, nq)

    # delta = rowsum(do*o) - dlse, packed (bh, 8, sq) like lse.  Folding the
    # lse cotangent here is exact: d s from lse is dlse*p, so
    # ds = p*(dp - rowsum(do*o)) + dlse*p = p*(dp - delta).
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)
        vma = vma | _vma(dlse)
    delta = jnp.broadcast_to(delta[:, None, :], (bh, 8, sq))

    static = dict(sm_scale=sm_scale, tiles=tiles)
    # tiles of the (BH, num_k, num_q) grid: the fused and the dK/dV kernel
    kq_in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, q_block(j, i), 0)),
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, q_block(j, i), 0)),
        pl.BlockSpec((1, 8, block_q), lambda b, j, i: (b, 0, q_block(j, i))),
        pl.BlockSpec((1, 8, block_q), lambda b, j, i: (b, 0, q_block(j, i))),
    ]
    dkv_specs = [
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
    ]
    dkv_shapes = [
        compat.shape_struct((bh, sk, d), k.dtype, vma=vma),
        compat.shape_struct((bh, sk, d), v.dtype, vma=vma),
    ]
    dkv_scratch = [
        pltpu.VMEM((block_k, d), jnp.float32),
        pltpu.VMEM((block_k, d), jnp.float32),
    ]
    dq_shape = compat.shape_struct((bh, sq, d), q.dtype, vma=vma)

    if _bwd_fuses(sq, sk, d, q.dtype):
        dq, dk, dv = pl.pallas_call(
            functools.partial(_bwd_fused_kernel, **static),
            grid=(bh, nk, nq),
            in_specs=kq_in_specs,
            out_specs=[pl.BlockSpec((1, sq, d), lambda b, j, i: (b, 0, 0)),
                       *dkv_specs],
            out_shape=[dq_shape, *dkv_shapes],
            scratch_shapes=[pltpu.VMEM((sq, d), jnp.float32), *dkv_scratch],
            compiler_params=_fused_bwd_compiler_params(
                sq, d, block_q, block_k, q.dtype),
            interpret=interpret,
        )(q, k, v, do, lse, delta)
        return dq, dk, dv

    kv_index = _kv_index(tiles)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **static),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=dq_shape,
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **static),
        grid=(bh, nk, nq),
        in_specs=kq_in_specs,
        out_specs=dkv_specs,
        out_shape=dkv_shapes,
        scratch_shapes=dkv_scratch,
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Public entry point
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, sm_scale, causal, block_q, block_k, interpret, window):
    o, _ = _fwd(q, k, v, sm_scale=sm_scale, causal=causal, block_q=block_q,
                block_k=block_k, interpret=interpret, window=window)
    return o


def _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret,
               window):
    o, lse = _fwd(q, k, v, sm_scale=sm_scale, causal=causal, block_q=block_q,
                  block_k=block_k, interpret=interpret, window=window)
    return o, (q, k, v, o, lse)


def _flash_bwd(sm_scale, causal, block_q, block_k, interpret, window, res, g):
    return _bwd(sm_scale, causal, block_q, block_k, interpret, res, g,
                window=window)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_lse(q, k, v, sm_scale, causal, block_q, block_k, interpret,
               window):
    """(o, lse) variant: lse is a differentiable OUTPUT (its cotangent from
    an online-softmax merge folds into the backward's delta term) — the
    kernel form ring attention needs (parallel/context.py)."""
    o, lse = _fwd(q, k, v, sm_scale=sm_scale, causal=causal, block_q=block_q,
                  block_k=block_k, interpret=interpret, window=window)
    return o, lse[:, 0]


def _flash_lse_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret,
                   window):
    o, lse = _fwd(q, k, v, sm_scale=sm_scale, causal=causal, block_q=block_q,
                  block_k=block_k, interpret=interpret, window=window)
    # Selective-remat seam (models/transformer.py remat="selective"): name
    # the kernel's OWN residuals so a save_only_these_names policy can pin
    # exactly (o, lse) — the remat backward then rebuilds q/k/v from the
    # layer input but never re-runs the forward kernel.  Outside a
    # checkpoint policy the tags are identity no-ops.
    o = checkpoint_name(o, "attn_out")
    lse = checkpoint_name(lse, "attn_lse")
    return (o, lse[:, 0]), (q, k, v, o, lse)


def _flash_lse_bwd(sm_scale, causal, block_q, block_k, interpret, window,
                   res, g):
    do, dlse = g
    return _bwd(sm_scale, causal, block_q, block_k, interpret, res, do,
                dlse=dlse, window=window)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


# ---------------------------------------------------------------------------
# Decode attention kernel (single-token query over a KV cache)
# ---------------------------------------------------------------------------

def _decode_kernel_body(pos_ref, q_ref, k_ref, v_ref, o_ref, acc_ref,
                        m_ref, l_ref, *, sm_scale: float, block_k: int,
                        hkv: int, g: int, ks_ref=None, vs_ref=None):
    """Grid (B, num_k_blocks), k innermost — ONE batch element per step.

    The query tile is all H = hkv*g heads at once, (H, D); the cache tile is
    (hkv, block_k, D).  A static loop over the hkv kv heads computes each
    group's (g, block_k) scores — the GQA head-repeat folded into row
    assembly, so every cache line is read once, not g times.  The online-
    softmax state update then runs vectorized over all H rows.

    ``pos`` arrives via scalar prefetch; blocks past ``pos`` are dead: their
    compute is skipped with ``pl.when`` and their DMA is skipped by the
    clamped BlockSpec index map (dead blocks map to the last live block, and
    Pallas elides the copy when the block index repeats).  Keeping the whole
    batch element's heads in one grid step keeps the grid coarse — per-step
    overhead, not bandwidth, dominates a fine decode grid.

    INT8 KV (``ks_ref``/``vs_ref`` given): the cache tiles arrive as int8
    with per-row scale tiles on the SAME index maps, so the HBM read per
    step is ~half the bf16 cache's — dequantization (int8 row x its
    scale, cast back to the query dtype so the MXU dots stay in the
    compute dtype) happens HERE, in VMEM, never as a dense bf16
    materialization on the hot path.  The scale tiles are LANE-DENSE,
    (hkv, 1, block_k): a (block_k, 1) f32 tile pads its last dim to 128
    lanes, which at 16 heads x 512 rows is 4 MiB per tile, 16 MiB for
    K and V double-buffered — over the 16 MiB scoped VMEM limit before
    the cache tiles are counted — and makes XLA copy the whole scale
    array into that padded layout on every call.  Each head's (1,
    block_k) row is transposed to the (block_k, 1) column here; the
    multiply is the same elementwise product either way.
    """
    j = pl.program_id(1)
    nk = pl.num_programs(1)
    # per-sequence position: pos_ref is (B,) — ragged batches decode with
    # exact per-sequence bounds (broadcast a scalar to (B,) for the
    # uniform case)
    pos = pos_ref[pl.program_id(0)]

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(j * block_k <= pos)
    def _compute():
        # per-kv-head scores, assembled to (H, block_k) rows
        rows = []
        for t in range(hkv):
            qg = q_ref[0, t * g:(t + 1) * g]           # (g, D)
            kt = k_ref[0, t]                           # (bk, D)
            if ks_ref is not None:
                kt = (kt.astype(jnp.float32)
                      * ks_ref[0, t].T).astype(qg.dtype)
            rows.append(jax.lax.dot_general(
                qg, kt, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32))   # (g, bk)
        s = jnp.concatenate(rows, axis=0) * sm_scale   # (H, bk)
        # exact pos+1 read bound: slots beyond pos are invalid (zero-filled
        # future positions of the cache buffer)
        slot = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(slot <= pos, s, NEG_INF)
        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                         # (H, bk)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)
        pv = []
        for t in range(hkv):
            vt = v_ref[0, t]                           # (bk, D)
            if vs_ref is not None:
                vt = (vt.astype(jnp.float32)
                      * vs_ref[0, t].T).astype(q_ref.dtype)
            pg = p[t * g:(t + 1) * g].astype(vt.dtype)
            pv.append(jax.lax.dot_general(
                pg, vt, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))   # (g, D)
        acc_ref[:] = acc_ref[:] * alpha + jnp.concatenate(pv, axis=0)

    @pl.when(j == nk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[:]
                    / jnp.maximum(l_ref[:, :1], 1e-30)).astype(o_ref.dtype)


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                   l_ref, *, sm_scale: float, block_k: int, hkv: int,
                   g: int):
    _decode_kernel_body(pos_ref, q_ref, k_ref, v_ref, o_ref, acc_ref,
                        m_ref, l_ref, sm_scale=sm_scale, block_k=block_k,
                        hkv=hkv, g=g)


def _decode_kernel_q(pos_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
                     acc_ref, m_ref, l_ref, *, sm_scale: float,
                     block_k: int, hkv: int, g: int):
    """int8 twin of ``_decode_kernel``: two extra scale-tile operands."""
    _decode_kernel_body(pos_ref, q_ref, k_ref, v_ref, o_ref, acc_ref,
                        m_ref, l_ref, sm_scale=sm_scale, block_k=block_k,
                        hkv=hkv, g=g, ks_ref=ks_ref, vs_ref=vs_ref)


def decode_attention(
    q: Array, k_cache: Array, v_cache: Array, pos: Array, *,
    k_scale: Array | None = None,
    v_scale: Array | None = None,
    sm_scale: float | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
) -> Array:
    """Single-token decode attention with exact ``pos+1`` cache-read bounds.

    ``q``: (B, H, 1, D) this step's queries; ``k_cache``/``v_cache``:
    (B, Hkv, S, D) full cache buffers (zero-filled beyond ``pos``); ``pos``:
    scalar int32, or (B,) int32 for RAGGED batches — sequence ``b`` attends
    cache slots ``[0, pos[b]]`` exactly (per-sequence read bounds: a short
    sequence in the batch reads only its own prefix, the continuous-
    batching primitive).  Returns (B, H, 1, D).

    INT8 KV cache: with ``k_scale``/``v_scale`` (B, Hkv, S, 1) float32
    per-row scales, the caches are int8 and each tile dequantizes INSIDE
    the kernel (``_decode_kernel_body``) — the HBM cache read per step is
    ~half the bf16 cache's, with no dense dequantized buffer ever
    materialized.  The scale tiles ride the same clamped index maps, so
    dead blocks' scale DMAs are elided exactly like the cache's.

    TPU-first design (the fix for the segmented-decode workaround the
    round-1 ROADMAP documented): decode at long cache is HBM-bound on cache
    reads, and the compiled XLA path must read (and mask) the whole static
    buffer — or a static per-segment bound.  Here the bound is dynamic and
    exact: dead cache blocks past ``pos`` are never fetched (clamped index
    map + copy elision) nor computed (``pl.when``).  GQA is folded in: the
    grid runs per kv head with the G = H/Hkv sharing queries as rows of one
    MXU tile, so cache lines are read ONCE per kv head, not repeated per
    query head (``jnp.repeat`` in the XLA path materializes G copies).
    """
    b, h, sq, d = q.shape
    if sq != 1:
        raise ValueError(f"decode_attention takes single-token queries, "
                         f"got sq={sq}")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("pass both k_scale and v_scale or neither")
    hkv, s = k_cache.shape[1], k_cache.shape[2]
    g = h // hkv
    if h % hkv:
        raise ValueError(f"{h} query heads do not group over {hkv} kv heads")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = _interpret_default()
    block_k = (_fit_block(DEFAULT_DECODE_BLOCK_K, s) if block_k is None
               else block_k)
    if s % block_k:
        raise ValueError(f"cache len {s} must divide block_k {block_k}")
    nk = s // block_k

    # (B, H, D) queries with each kv-head group's g queries contiguous rows
    qf = q.reshape(b, h, d)
    pos_arr = jnp.broadcast_to(jnp.atleast_1d(pos), (b,)).astype(jnp.int32)
    quant = k_scale is not None
    vma = (_vma(q, k_cache, v_cache, k_scale, v_scale) if quant
           else _vma(q, k_cache, v_cache))

    def live_block(bb, j, pos_ref):
        return jnp.minimum(j, pos_ref[bb] // block_k)

    def cache_spec(width):
        return pl.BlockSpec(
            (1, hkv, block_k, width),
            lambda bb, j, pos_ref: (bb, 0, live_block(bb, j, pos_ref), 0))

    in_specs = [pl.BlockSpec((1, h, d), lambda bb, j, pos_ref: (bb, 0, 0)),
                cache_spec(d), cache_spec(d)]
    inputs = [qf, k_cache, v_cache]
    if quant:
        # (B, Hkv, S, 1) -> (B, Hkv, 1, S): a bitcast (XLA keeps S minor
        # for a trailing-1 array), and the lane-dense tile the kernel wants
        scale_spec = pl.BlockSpec(
            (1, hkv, 1, block_k),
            lambda bb, j, pos_ref: (bb, 0, 0, live_block(bb, j, pos_ref)))
        in_specs += [scale_spec, scale_spec]
        inputs += [k_scale.reshape(b, hkv, 1, s),
                   v_scale.reshape(b, hkv, 1, s)]
    o = pl.pallas_call(
        functools.partial(_decode_kernel_q if quant else _decode_kernel,
                          sm_scale=sm_scale, block_k=block_k, hkv=hkv, g=g),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, nk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, h, d),
                                   lambda bb, j, pos_ref: (bb, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((h, d), jnp.float32),      # acc
                pltpu.VMEM((h, 128), jnp.float32),    # running max m
                pltpu.VMEM((h, 128), jnp.float32),    # running sum l
            ],
        ),
        out_shape=compat.shape_struct((b, h, d), q.dtype, vma=vma),
        compiler_params=_decode_compiler_params(qf, k_cache, block_k, quant),
        interpret=interpret,
    )(pos_arr, *inputs)
    return o.reshape(b, h, 1, d)


def _fit_block(limit: int, s: int) -> int:
    """Largest 8-aligned divisor of ``s`` that is <= ``limit`` (block sizes
    must tile the sequence exactly; 8 is the f32 sublane granule).

    Refuses degenerate tilings: a block below 128 (one MXU lane tile) is
    accepted only when it is the whole sequence — otherwise an awkward
    length like 8*prime would silently run a pathologically tiny grid."""
    for b in range(min(limit, s), 7, -1):
        if s % b == 0 and b % 8 == 0 and (b >= 128 or b == s):
            return b
    raise ValueError(
        f"sequence length {s} has no MXU-friendly divisor <= {limit} "
        f"(need an 8-aligned divisor >= 128, or s itself); pad the "
        f"sequence or pass explicit block sizes")


def flash_attention(
    q: Array, k: Array, v: Array, *,
    causal: bool = False,
    sm_scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    with_lse: bool = False,
    window: int | None = None,
) -> Array | tuple[Array, Array]:
    """Tiled attention over (B, H, S, D); differentiable (custom VJP).

    Default block sizes auto-shrink to the largest 8-aligned divisor of each
    sequence length; explicitly passed blocks must divide the lengths
    exactly.  Off-TPU the kernels run in Pallas interpret mode so CPU tests
    exercise the exact same code path.

    With ``with_lse`` also returns the row logsumexp (B, H, S) as a second
    differentiable output — the contract ring attention's online-softmax
    merge needs (the lse cotangent is handled exactly in the backward).

    ``window`` (causal only): a query sees the ``window`` newest keys up to
    and including its own position (key > query - window).  The
    kernels skip the blocks that lie wholly before the band as they skip
    those after the diagonal, compute and copy both (``tile_census`` counts
    them), and mask inside the blocks the band's edge crosses.  A window
    that covers the sequence is plain causal attention; ``None`` traces the
    kernels without a window.
    """
    if q.ndim != 4:
        raise ValueError(f"expected (B, H, S, D) q, got {q.shape}")
    _check_window(window, causal)
    if window is not None and window >= q.shape[2]:
        window = None
    b, h, sq, d = q.shape
    sk = k.shape[2]
    block_q = _fit_block(DEFAULT_BLOCK_Q, sq) if block_q is None else min(
        block_q, sq)
    block_k = _fit_block(DEFAULT_BLOCK_K, sk) if block_k is None else min(
        block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError(
            f"seq lens ({sq}, {sk}) must divide block sizes "
            f"({block_q}, {block_k})")
    if causal and sq != sk:
        raise ValueError("causal flash attention requires sq == sk")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = _interpret_default()
    qf, kf, vf = (q.reshape(b * h, sq, d), k.reshape(b * h, sk, d),
                  v.reshape(b * h, sk, d))
    if with_lse:
        o, lse = _flash_lse(qf, kf, vf, sm_scale, causal,
                            block_q, block_k, interpret, window)
        return o.reshape(b, h, sq, d), lse.reshape(b, h, sq)
    o = _flash(qf, kf, vf, sm_scale, causal, block_q, block_k, interpret,
               window)
    return o.reshape(b, h, sq, d)


def _decode_kernel_paged(pos_ref, table_ref, q_ref, k_ref, v_ref, o_ref,
                         acc_ref, m_ref, l_ref, *, sm_scale: float,
                         block_k: int, hkv: int, g: int):
    """Paged twin of ``_decode_kernel``: identical math; the cache tiles
    arrive via the block-table index map instead of a contiguous buffer,
    and ``table_ref`` (the second scalar-prefetch operand) is consumed by
    the BlockSpec index maps only."""
    del table_ref
    _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                   l_ref, sm_scale=sm_scale, block_k=block_k, hkv=hkv, g=g)


def _decode_kernel_paged_q(pos_ref, table_ref, q_ref, k_ref, v_ref,
                           ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref,
                           *, sm_scale: float, block_k: int, hkv: int,
                           g: int):
    """Paged int8 twin: the per-row scale tiles ride the block table the
    way the page gather already does (same live_page index map)."""
    del table_ref
    _decode_kernel_body(pos_ref, q_ref, k_ref, v_ref, o_ref, acc_ref,
                        m_ref, l_ref, sm_scale=sm_scale, block_k=block_k,
                        hkv=hkv, g=g, ks_ref=ks_ref, vs_ref=vs_ref)


def decode_attention_paged(
    q: Array, k_pool: Array, v_pool: Array, table: Array, pos: Array, *,
    k_scale: Array | None = None,
    v_scale: Array | None = None,
    sm_scale: float | None = None,
    interpret: bool | None = None,
) -> Array:
    """Single-token decode attention over a PAGED KV pool.

    The vLLM-style memory layout, TPU-native: instead of one contiguous
    (B, Hkv, S, D) buffer per sequence, K/V live in a shared pool of
    fixed-size pages — ``k_pool``/``v_pool``: (P, Hkv, page, D) — and each
    sequence owns the pages its ``table`` row lists: ``table``
    (B, n_pages) int32, entry j = the pool page holding cache slots
    [j*page, (j+1)*page).  ``pos``: (B,) int32 exact read bounds, as in
    ``decode_attention``.

    The page indirection costs NOTHING on the read path: the same
    scalar-prefetch BlockSpec index maps that clamp dead blocks in the
    dense kernel simply look the live block up in the table —
    ``(table[b, min(j, pos[b]//page)], ...)`` — so each grid step DMAs
    exactly one live page and dead pages' copies are elided (repeated
    index).  Entries past a sequence's allocated pages may be garbage; the
    clamp means they are never dereferenced.  Returns (B, H, 1, D).

    INT8 KV pool: with ``k_scale``/``v_scale`` (P, Hkv, page, 1) float32
    per-row scale POOLS, the caches are int8 and the scale tiles ride the
    identical live_page lookup — a shared (prefix-cached) page carries
    its scales with it, and each tile dequantizes inside the kernel
    (see ``decode_attention``).
    """
    b, h, sq, d = q.shape
    if sq != 1:
        raise ValueError(f"decode_attention_paged takes single-token "
                         f"queries, got sq={sq}")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("pass both k_scale and v_scale or neither")
    p_blocks, hkv, page, _ = k_pool.shape
    g = h // hkv
    if h % hkv:
        raise ValueError(f"{h} query heads do not group over {hkv} kv heads")
    if page % 8 or (page < 128 and p_blocks > 1):
        raise ValueError(
            f"page size {page} must be 8-aligned, and >= 128 whenever the "
            f"pool holds more than one page (got {p_blocks} pages; a "
            f"single-page pool tolerates shorter pages since no block-table "
            f"indirection happens)")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = _interpret_default()
    n_pages = table.shape[1]

    qf = q.reshape(b, h, d)
    pos_arr = jnp.broadcast_to(jnp.atleast_1d(pos), (b,)).astype(jnp.int32)
    table = table.astype(jnp.int32)
    quant = k_scale is not None
    vma = (_vma(q, k_pool, v_pool, k_scale, v_scale) if quant
           else _vma(q, k_pool, v_pool))

    def live_page(bb, j, pos_ref, table_ref):
        return table_ref[bb, jnp.minimum(j, pos_ref[bb] // page)]

    def pool_spec(width):
        return pl.BlockSpec(
            (1, hkv, page, width),
            lambda bb, j, pos_ref, table_ref: (
                live_page(bb, j, pos_ref, table_ref), 0, 0, 0))

    in_specs = [pl.BlockSpec((1, h, d),
                             lambda bb, j, pos_ref, table_ref: (bb, 0, 0)),
                pool_spec(d), pool_spec(d)]
    inputs = [qf, k_pool, v_pool]
    if quant:
        # lane-dense scale tiles, as in ``decode_attention``
        scale_spec = pl.BlockSpec(
            (1, hkv, 1, page),
            lambda bb, j, pos_ref, table_ref: (
                live_page(bb, j, pos_ref, table_ref), 0, 0, 0))
        in_specs += [scale_spec, scale_spec]
        inputs += [k_scale.reshape(p_blocks, hkv, 1, page),
                   v_scale.reshape(p_blocks, hkv, 1, page)]
    o = pl.pallas_call(
        functools.partial(
            _decode_kernel_paged_q if quant else _decode_kernel_paged,
            sm_scale=sm_scale, block_k=page, hkv=hkv, g=g),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_pages),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (1, h, d), lambda bb, j, pos_ref, table_ref: (bb, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((h, d), jnp.float32),      # acc
                pltpu.VMEM((h, 128), jnp.float32),    # running max m
                pltpu.VMEM((h, 128), jnp.float32),    # running sum l
            ],
        ),
        out_shape=compat.shape_struct((b, h, d), q.dtype, vma=vma),
        compiler_params=_decode_compiler_params(qf, k_pool, page, quant),
        interpret=interpret,
    )(pos_arr, table, *inputs)
    return o.reshape(b, h, 1, d)
