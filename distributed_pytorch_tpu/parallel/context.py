"""Ring attention: sequence/context parallelism over a mesh axis.

The reference has no sequence dimension at all (SURVEY.md section 5: its
parallelism inventory is data-parallel only); this module is the long-context
capability the TPU framework adds.  Sequences are sharded over a named mesh
axis ``seq``; each device holds a chunk of Q/K/V.  Attention over the full
sequence is computed in ``n = axis_size(seq)`` ring steps:

  step t: attend my Q chunk against the K/V chunk that started on device
  ``(my - t) mod n``, then pass my current K/V chunk to the next neighbor
  with ``lax.ppermute`` (XLA lowers this to ICI neighbor exchange, which
  overlaps with the attention compute of the current step).

Partial results are merged with the online-softmax rule — each step yields a
normalized chunk output plus its row logsumexp; two partials combine by
logaddexp-weighted averaging.  The whole thing is plain differentiable JAX
(``ppermute``'s transpose is ``ppermute``), so one ``jax.grad`` produces the
backward ring automatically.

Two sequence layouts:

- ``contiguous``: device r holds global positions [r*S_loc, (r+1)*S_loc).
  Simple, but causally imbalanced: ring steps whose source chunk is later
  are fully masked, yet run in SPMD lockstep — about half the attention
  FLOPs are wasted.
- ``zigzag`` (default for causal): the sequence is cut into 2n chunks and
  device r holds chunks [r, 2n-1-r] concatenated.  Every device then has
  exactly the same causal work at every ring step — the diagonal step is
  one local causal attention, and each of the n-1 ring steps is exactly two
  half-chunk full attentions (either both q-halves against the early k-half,
  or the late q-half against both k-halves) — no masked-out compute at all.
  Callers lay out tokens with :func:`zigzag_permutation` and positions with
  :func:`zigzag_positions`.

Per-chunk attention uses either the XLA reference (``impl='reference'``) or
the Pallas flash kernel (``impl='flash'``, ops/attention.py) — the flash
path returns its logsumexp as a differentiable output, so the merge (and its
backward, which sends a cotangent into lse) works identically for both.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops.attention import NEG_INF, attention_reference, flash_attention

Array = jax.Array


# ---------------------------------------------------------------------------
# Zigzag layout helpers (host-side; used by the data path and tests)
# ---------------------------------------------------------------------------

def zigzag_permutation(n: int, s: int) -> np.ndarray:
    """Index permutation laying a length-``s`` sequence out for an n-way
    zigzag ring: position j of the permuted sequence holds original position
    ``perm[j]``.  Shard the permuted sequence contiguously (P over the seq
    axis) and device r ends up with chunks [r, 2n-1-r].  ``s`` must divide
    into 2n equal chunks."""
    if s % (2 * n):
        raise ValueError(f"sequence length {s} not divisible into {2 * n} "
                         f"zigzag chunks")
    c = s // (2 * n)
    idx = []
    for r in range(n):
        idx.append(np.arange(r * c, (r + 1) * c))
        idx.append(np.arange((2 * n - 1 - r) * c, (2 * n - r) * c))
    return np.concatenate(idx)


def inverse_zigzag_permutation(n: int, s: int) -> np.ndarray:
    """Inverse of :func:`zigzag_permutation` (restores original order)."""
    perm = zigzag_permutation(n, s)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(s)
    return inv


def zigzag_positions(me: Array | int, n: int, s_local: int) -> Array:
    """Global positions of this device's zigzag chunk pair, (s_local,).

    Device ``me`` holds chunk ``me`` then chunk ``2n-1-me``, each of length
    s_local/2 — this is what rotary embeddings must see as absolute
    positions."""
    c = s_local // 2
    lo = me * c + jnp.arange(c)
    hi = (2 * n - 1 - me) * c + jnp.arange(c)
    return jnp.concatenate([lo, hi])


# ---------------------------------------------------------------------------
# Online-softmax merge
# ---------------------------------------------------------------------------

def _merge(o1: Array, lse1: Array, o2: Array, lse2: Array):
    """Combine two normalized partial attentions (online-softmax merge).

    ``o_i`` are (B, H, S, D) outputs normalized within their own key chunk;
    ``lse_i`` are their (B, H, S) logsumexps.  Fully-masked partials carry
    lse ~= NEG_INF and vanish smoothly (finite large-negative, no NaNs).
    """
    lse = jnp.logaddexp(lse1, lse2)
    w1 = jnp.exp(lse1 - lse)[..., None]
    w2 = jnp.exp(lse2 - lse)[..., None]
    return o1 * w1 + o2 * w2, lse


def _attn(q: Array, k: Array, v: Array, *, causal: bool, sm_scale: float,
          impl: str):
    """One chunk-pair attention returning (o_f32, lse) for the merge."""
    if impl == "flash":
        o, lse = flash_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                                 with_lse=True)
    else:
        o, lse = attention_reference(q, k, v, causal=causal,
                                     sm_scale=sm_scale, with_lse=True)
    return o.astype(jnp.float32), lse


# ---------------------------------------------------------------------------
# Ring attention
# ---------------------------------------------------------------------------

def ring_attention(
    q: Array, k: Array, v: Array, axis: str, *,
    causal: bool = True, sm_scale: float | None = None,
    impl: str = "reference", layout: str = "contiguous",
) -> Array:
    """Attention over a sequence sharded across mesh axis ``axis``.

    Args are this device's chunks, (B, H, S_local, D).  Equivalent (tested)
    to full attention over the concatenated sequence, with chunks laid out
    per ``layout`` ('contiguous' in axis-index order, or 'zigzag' — see
    module docstring; non-causal attention is key-order invariant, so
    layout only matters for ``causal``).  Peak score memory per device is
    O(S_local^2) per ring step with the reference impl, O(block^2) with
    flash — the blockwise-attention memory saving that makes million-token
    sequences feasible.
    """
    if impl not in ("reference", "flash"):
        raise ValueError(f"impl must be 'reference' or 'flash', got {impl!r}")
    if layout not in ("contiguous", "zigzag"):
        raise ValueError(f"layout must be 'contiguous' or 'zigzag', "
                         f"got {layout!r}")
    n = lax.axis_size(axis)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if n == 1:
        o, _ = _attn(q, k, v, causal=causal, sm_scale=sm_scale, impl=impl)
        return o.astype(q.dtype)
    if causal and layout == "zigzag":
        return _ring_zigzag(q, k, v, axis, n=n, sm_scale=sm_scale, impl=impl)
    return _ring_contiguous(q, k, v, axis, n=n, causal=causal,
                            sm_scale=sm_scale, impl=impl)


def _ring_contiguous(q, k, v, axis, *, n, causal, sm_scale, impl):
    me = lax.axis_index(axis)
    perm = [(i, (i + 1) % n) for i in range(n)]  # pass k/v to the right

    # Diagonal (t = 0): my own chunk — causal triangle (or full).
    acc, lse_acc = _attn(q, k, v, causal=causal, sm_scale=sm_scale, impl=impl)

    def step(carry, t):
        k_t, v_t, acc, lse_acc = carry
        # Rotate first: after t rotations the chunk in hand started on
        # device src = (me - t) mod n.
        k_t = lax.ppermute(k_t, axis, perm)
        v_t = lax.ppermute(v_t, axis, perm)
        src = (me - t) % n
        o_t, lse_t = _attn(q, k_t, v_t, causal=False, sm_scale=sm_scale,
                           impl=impl)
        if causal:
            # Chunks are contiguous in axis order: src < me -> fully
            # visible; src > me -> fully masked (lockstep no-op step).
            live = src < me
            o_t = jnp.where(live, o_t, 0.0)
            lse_t = jnp.where(live, lse_t, NEG_INF)
        acc, lse_acc = _merge(acc, lse_acc, o_t, lse_t)
        return (k_t, v_t, acc, lse_acc), None

    (_, _, acc, _), _ = lax.scan(step, (k, v, acc, lse_acc),
                                 jnp.arange(1, n))
    return acc.astype(q.dtype)


def _ring_zigzag(q, k, v, axis, *, n, sm_scale, impl):
    """Causal ring over the zigzag layout: balanced, no masked compute.

    My chunks: lo = global chunk ``me``, hi = global chunk ``2n-1-me``
    (so lo < hi always, and every other device's lo is < my hi).  At ring
    step t the K/V in hand came from src = (me-t) mod n, with chunk halves
    c_lo = src and c_hi = 2n-1-src.  Exactly two of the four (q, k) half
    pairs are causally active:

      src < me:  (q_lo, c_lo) full and (q_hi, c_lo) full
      src > me:  (q_hi, c_lo) full and (q_hi, c_hi) full

    — equal work on every device at every step, computed as two half-chunk
    full attentions with `where`-selected operands (static shapes, SPMD).
    """
    me = lax.axis_index(axis)
    sq = q.shape[2]
    if sq % 2:
        raise ValueError(f"zigzag layout needs an even local sequence "
                         f"length, got {sq}")
    c = sq // 2
    q_lo, q_hi = q[:, :, :c], q[:, :, c:]
    perm = [(i, (i + 1) % n) for i in range(n)]

    # Diagonal step: causal attention over my own [lo; hi] pair — correct
    # because all of lo precedes all of hi globally and each half is
    # internally ordered.
    o0, lse0 = _attn(q, k, v, causal=True, sm_scale=sm_scale, impl=impl)
    acc_lo, lse_lo = o0[:, :, :c], lse0[:, :, :c]
    acc_hi, lse_hi = o0[:, :, c:], lse0[:, :, c:]

    def step(carry, t):
        k_t, v_t, acc_lo, lse_lo, acc_hi, lse_hi = carry
        k_t = lax.ppermute(k_t, axis, perm)
        v_t = lax.ppermute(v_t, axis, perm)
        src = (me - t) % n
        early = src < me
        k_c_lo, k_c_hi = k_t[:, :, :c], k_t[:, :, c:]
        v_c_lo, v_c_hi = v_t[:, :, :c], v_t[:, :, c:]
        # Pair 1: (q_lo if early else q_hi) x c_lo, always fully visible.
        q1 = jnp.where(early, q_lo, q_hi)
        o1, lse1 = _attn(q1, k_c_lo, v_c_lo, causal=False,
                         sm_scale=sm_scale, impl=impl)
        # Pair 2: q_hi x (c_lo if early else c_hi), always fully visible.
        k2 = jnp.where(early, k_c_lo, k_c_hi)
        v2 = jnp.where(early, v_c_lo, v_c_hi)
        o2, lse2 = _attn(q_hi, k2, v2, causal=False, sm_scale=sm_scale,
                         impl=impl)
        # Route the two partials to the right q-half accumulators.
        om, lsem = _merge(o1, lse1, o2, lse2)   # both pairs were q_hi
        p_lo_o = jnp.where(early, o1, 0.0)
        p_lo_lse = jnp.where(early, lse1, NEG_INF)
        p_hi_o = jnp.where(early, o2, om)
        p_hi_lse = jnp.where(early, lse2, lsem)
        acc_lo, lse_lo = _merge(acc_lo, lse_lo, p_lo_o, p_lo_lse)
        acc_hi, lse_hi = _merge(acc_hi, lse_hi, p_hi_o, p_hi_lse)
        return (k_t, v_t, acc_lo, lse_lo, acc_hi, lse_hi), None

    (_, _, acc_lo, _, acc_hi, _), _ = lax.scan(
        step, (k, v, acc_lo, lse_lo, acc_hi, lse_hi), jnp.arange(1, n))
    return jnp.concatenate([acc_lo, acc_hi], axis=2).astype(q.dtype)
