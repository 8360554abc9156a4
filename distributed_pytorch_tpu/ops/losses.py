"""Streaming (chunked) vocab cross-entropy and the unified LM head-loss seam.

Every LM trainer used to end the same way: materialize the full
``(B, T, V)`` float32 logits tensor (``h @ embed.T``) and hand it to
:func:`..ops.nn.masked_ce`.  On a real TPU that one tensor dominates peak
activation memory — for LM-base shapes it is larger than every per-layer
residual combined — and it caps the per-device batch size every gradient-sync
strategy amortizes against.

Two exports:

- :func:`head_loss` — the ONE seam all three head-loss sites route through
  (lm.py's train and eval builders and parallel/pipeline.py's wave tick;
  the round-13 ``step_metrics`` consolidation pattern).  ``loss_impl="dense"``
  traces the historical op sequence bit-for-bit; ``"chunked"`` streams.
- :func:`masked_ce_chunked` — a custom-vjp loss that scans the head
  projection + an online logsumexp over vocab chunks, so the ``(B, T, V)``
  f32 array never exists.  The largest live loss buffer is ``(B*T, chunk)``.
  The backward recomputes each chunk's logits from the saved hidden states
  and emits the hidden/embedding cotangents directly (softmax minus one-hot,
  chunk by chunk) — flash attention's recompute-from-residuals trick applied
  to the LM head.

Tensor-parallel head: with ``tp_axis``/``tp_size`` set, each rank streams
only its ``V/tp`` vocab rows (sliced from the replicated embedding by
``axis_index``) and the partial logsumexps combine with one ``pmax`` + one
``psum`` over the model axis — the same Megatron seam the dense layers use.
The backward ``psum``s the hidden cotangent and reassembles the full
embedding cotangent with a tiled ``all_gather``, keeping it replicated like
the dense path's.

Masking follows :data:`..ops.nn.IGNORE_INDEX` exactly: ignored positions
contribute zero loss and zero cotangent.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..utils import compat
from .nn import IGNORE_INDEX, masked_ce

Array = jax.Array


def default_chunk(vocab: int, cap: int = 1024) -> int:
    """Largest divisor of ``vocab`` that is <= ``cap`` (used when
    ``loss_chunk`` is left unset: bounds the streamed logits buffer at
    ``B*T x cap`` without the caller having to know the vocab's factors)."""
    if vocab <= 0:
        raise ValueError(f"vocab must be positive, got {vocab}")
    for c in range(min(cap, vocab), 0, -1):
        if vocab % c == 0:
            return c
    return 1  # unreachable: 1 divides everything


def _flatten(h: Array, targets: Array) -> tuple[Array, Array, int]:
    d = h.shape[-1]
    n = 1
    for s in h.shape[:-1]:
        n *= s
    return h.reshape(n, d), targets.reshape(n), n


def _local_rows(emb: Array, tp_axis: str | None, tp_size: int):
    """This rank's vocab slice of the replicated embedding and its global
    row offset (0 without tensor parallelism)."""
    if tp_axis is None or tp_size <= 1:
        return emb, jnp.zeros((), jnp.int32)
    v_local = emb.shape[0] // tp_size
    v0 = lax.axis_index(tp_axis) * v_local
    return lax.dynamic_slice_in_dim(emb, v0, v_local, 0), v0


def _fwd_core(h, emb, targets, chunk, tp_axis, tp_size):
    """Online-logsumexp forward: returns (ce_sum, lse, mask) with lse the
    GLOBAL per-token logsumexp (already combined across the tp head)."""
    h2, t, n = _flatten(h, targets)
    h2 = h2.astype(jnp.float32)
    mask = t != IGNORE_INDEX
    safe = jnp.where(mask, t, 0)
    emb_l, v0 = _local_rows(emb, tp_axis, tp_size)
    n_chunks = emb_l.shape[0] // chunk

    def body(carry, i):
        m, s, tl = carry
        w = lax.dynamic_slice_in_dim(emb_l, i * chunk, chunk, 0)
        lg = h2 @ w.T.astype(jnp.float32)          # (n, chunk) — the only
        m_new = jnp.maximum(m, jnp.max(lg, axis=-1))  # live logits buffer
        s = s * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(lg - m_new[:, None]), axis=-1)
        idx = safe - (v0 + i * chunk)
        own = (idx >= 0) & (idx < chunk)
        got = jnp.take_along_axis(
            lg, jnp.clip(idx, 0, chunk - 1)[:, None], axis=-1)[:, 0]
        tl = tl + jnp.where(own, got, 0.0)
        return (m_new, s, tl), None

    axes = compat.vma_of(h2) | compat.vma_of(emb_l)
    init = tuple(compat.varying(c, axes) for c in (
        jnp.full((n,), -jnp.inf, jnp.float32),
        jnp.zeros((n,), jnp.float32),
        jnp.zeros((n,), jnp.float32)))
    (m, s, tl), _ = lax.scan(body, init, jnp.arange(n_chunks))

    if tp_axis is not None and tp_size > 1:
        # combine the per-rank partial logsumexps and the (owned-by-one-
        # rank) true logit — the Megatron vocab-parallel CE combine
        mg = lax.pmax(m, tp_axis)
        sg = lax.psum(s * jnp.exp(m - mg), tp_axis)
        lse = mg + jnp.log(sg)
        tl = lax.psum(tl, tp_axis)
    else:
        lse = m + jnp.log(s)
    ce = jnp.where(mask, lse - tl, 0.0)
    return jnp.sum(ce), lse, mask


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ce_chunked(h, emb, targets, chunk, tp_axis, tp_size):
    ce_sum, _, _ = _fwd_core(h, emb, targets, chunk, tp_axis, tp_size)
    return ce_sum


def _ce_chunked_fwd(h, emb, targets, chunk, tp_axis, tp_size):
    ce_sum, lse, _ = _fwd_core(h, emb, targets, chunk, tp_axis, tp_size)
    # residuals: the hidden states (original dtype), embedding, integer
    # targets and the (n,)-sized logsumexp — NO logits-sized array
    return ce_sum, (h, emb, targets, lse)


def _ce_chunked_bwd(chunk, tp_axis, tp_size, res, g):
    h, emb, targets, lse = res
    h2, t, _ = _flatten(h, targets)
    h2 = h2.astype(jnp.float32)
    mask = t != IGNORE_INDEX
    safe = jnp.where(mask, t, 0)
    emb_l, v0 = _local_rows(emb, tp_axis, tp_size)
    v_local = emb_l.shape[0]
    n_chunks = v_local // chunk
    cols = jnp.arange(chunk)

    def body(dh, i):
        w = lax.dynamic_slice_in_dim(emb_l, i * chunk, chunk, 0)
        w32 = w.astype(jnp.float32)
        lg = h2 @ w32.T
        p = jnp.exp(lg - lse[:, None])              # softmax slice
        idx = safe - (v0 + i * chunk)
        onehot = (cols[None, :] == idx[:, None]).astype(jnp.float32)
        coeff = g * (p - onehot) * mask[:, None]    # (n, chunk)
        dh = dh + coeff @ w32
        dw = coeff.T @ h2                           # (chunk, d)
        return dh, dw

    dh0 = compat.varying(jnp.zeros_like(h2), compat.vma_of(emb_l))
    dh, dws = lax.scan(body, dh0, jnp.arange(n_chunks))
    demb = dws.reshape(v_local, h.shape[-1])
    # summed over the mesh axes the head broadcast the embedding over, as
    # autodiff sums the dense head's
    demb = compat.as_cotangent(demb, emb_l)
    if tp_axis is not None and tp_size > 1:
        # each rank holds the partial dh for ITS vocab slice and the full
        # demb for its rows: reduce / reassemble, replicated like dense
        dh = lax.psum(dh, tp_axis)
        demb = compat.all_gather_invariant(demb, tp_axis, axis=0, tiled=True)
    dh = dh.reshape(h.shape).astype(h.dtype)
    demb = demb.astype(emb.dtype)
    dtargets = np.zeros(targets.shape, dtype=jax.dtypes.float0)
    return dh, demb, dtargets


_ce_chunked.defvjp(_ce_chunked_fwd, _ce_chunked_bwd)


def masked_ce_chunked(
    h: Array,
    emb: Array,
    targets: Array,
    *,
    chunk: int,
    tp_axis: str | None = None,
    tp_size: int = 1,
) -> tuple[Array, Array]:
    """Streaming masked cross-entropy: ``(sum of CE, #unmasked tokens)``
    over ``logits = h @ emb.T`` WITHOUT materializing the logits.

    ``chunk`` must divide this rank's vocab rows (``V`` plain, ``V/tp``
    with a tp-sharded head).  Matches :func:`..ops.nn.masked_ce` on the
    same logits to ~1e-6 (online vs one-shot logsumexp rounding).
    """
    if tp_size <= 1:
        tp_axis = None
    v_local = emb.shape[0] // (tp_size if tp_axis is not None else 1)
    if chunk <= 0 or v_local % chunk:
        raise ValueError(
            f"loss_chunk {chunk} must be a positive divisor of the "
            f"per-rank vocab rows {v_local} (vocab {emb.shape[0]}"
            + (f" over the {tp_size}-way tp head" if tp_axis else "")
            + ") — the scan needs equal-sized chunks")
    ce_sum = _ce_chunked(h, emb, targets, int(chunk), tp_axis, int(tp_size))
    n = jnp.sum(targets != IGNORE_INDEX)
    return ce_sum, n


def head_loss(
    h: Array,
    emb: Array,
    targets: Array,
    *,
    loss_impl: str = "dense",
    loss_chunk: int | None = None,
    tp_axis: str | None = None,
    tp_size: int = 1,
) -> tuple[Array, Array]:
    """THE head-loss seam: final-norm hidden states + tied embedding ->
    ``(sum of masked CE, #unmasked tokens)``.

    ``loss_impl="dense"`` traces the historical op sequence bit-for-bit
    (``h.astype(f32) @ emb.T.astype(f32)`` then ``masked_ce``);
    ``"chunked"`` streams via :func:`masked_ce_chunked` with ``loss_chunk``
    (default: :func:`default_chunk` of the per-rank vocab rows).
    """
    if loss_impl == "chunked":
        v_local = emb.shape[0] // (tp_size if tp_axis is not None else 1)
        chunk = loss_chunk if loss_chunk else default_chunk(v_local)
        return masked_ce_chunked(h, emb, targets, chunk=chunk,
                                 tp_axis=tp_axis, tp_size=tp_size)
    if loss_impl != "dense":
        raise ValueError(
            f"unknown loss_impl {loss_impl!r}: expected 'dense' or "
            "'chunked'")
    logits = h.astype(jnp.float32) @ emb.T.astype(jnp.float32)
    return masked_ce(logits, targets)
