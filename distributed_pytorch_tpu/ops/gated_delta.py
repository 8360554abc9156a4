"""The gated delta rule (Gated DeltaNet's linear attention) and the short
causal convolution before it.

Per value head, with a state ``S`` of (key x value) and ``S_0 = 0``::

    S_t = e^{g_t} S_{t-1} + k_t (beta_t (v_t - e^{g_t} S_{t-1}^T k_t))^T
    o_t = S_t^T q_t

- ``gated_delta_recurrent``: that recurrence token by token (a ``lax.scan``
  over positions): the oracle the chunked form is tested against.
- ``gated_delta_chunked``: the same in chunks of ``CHUNK`` positions, as
  XLA operations: the readable statement of the chunked algorithm, and the
  oracle of the kernels beside the recurrence.  Within a chunk, with ``G`` the cumulative
  sum of ``g`` and ``Gamma_ij = exp(G_i - G_j)`` for ``i >= j`` (0 above the
  diagonal; the decay never enters as ``exp(G_i) exp(-G_j)``, which
  overflows at the decays a trained model reaches), one triangular solve
  ``(I + tril_-1(beta K K^T * Gamma)) [u | w] = [beta V | beta K * e^G]``
  gives the chunk's WY factors; across chunks a ``lax.scan`` carries the
  state::

      V' = u - w S
      O  = (Q * e^G) S + (Q K^T * Gamma) V'
      S <- e^{G_last} S + (K * e^{G_last - G})^T V'

  Every product is a batched matrix product over (chunks, batch, heads);
  only the three state products are sequential, 128 steps for a row of
  8,192.  Autodiff differentiates it: the scan keeps one state a chunk, the
  chunk's own algebra is recomputed in the backward (``_within``).
- ``gated_delta``: the chunked form as two Pallas kernels under one custom
  VJP, the form the training step runs.  ``gated_delta_fwd`` walks the
  chunks in order with every head's state in VMEM, a chunk's algebra (the
  cumulative decay, the masked ``Gamma``, the inverse of ``I + A`` and the
  WY factors) never leaving it; it saves the state entering each chunk.
  ``gated_delta_bwd`` walks them in reverse with the state's gradient in
  VMEM and recomputes each chunk's algebra from the inputs and the saved
  state.  Both read and write the row layout (all heads of a chunk a grid
  step), so no transposition runs around them, and take q and k at the key
  heads' count, each key head serving its consecutive value heads.
- ``causal_conv``: depthwise over channels, ``width`` taps, position ``t``
  reads ``t - width + 1 .. t`` with zeros before 0: shifted multiply-adds
  that XLA fuses into one pass.

Shapes are (batch, time, heads, dim), as the projections give them; the
output is float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import compat
from .attention import _interpret_default, _vma

Array = jax.Array

CHUNK = 64   # positions a chunk (upstream's chunk size)


def causal_conv(x: Array, w: Array) -> Array:
    """Depthwise causal convolution of ``x`` (B, T, C) with taps ``w``
    (width, C): ``out[t] = sum_j w[j] x[t - width + 1 + j]``, so the last
    tap reads the current position.  The sums are in the wider of the two
    types (bfloat16 rows and float32 taps: float32, with no float32 copy of
    the rows)."""
    width, t = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    out = xp[:, :t] * w[0]
    for j in range(1, width):
        out = out + xp[:, j:j + t] * w[j]
    return out


def gated_delta_recurrent(q: Array, k: Array, v: Array, g: Array,
                          beta: Array) -> tuple[Array, Array]:
    """The recurrence, one position at a time: q, k (B, T, H, Dk), v
    (B, T, H, Dv), g and beta (B, T, H) -> (o (B, T, H, Dv) float32, final
    state (B, H, Dk, Dv))."""
    b, _, h, dk = k.shape

    def step(s, x):
        qt, kt, vt, gt, bt = x
        s = s * jnp.exp(gt)[..., None, None]
        vt = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", s, kt))
        s = s + kt[..., :, None] * vt[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, qt)

    s0 = compat.varying(jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32),
                        compat.vma_of(v))
    xs = tuple(jnp.moveaxis(a.astype(jnp.float32), 1, 0)
               for a in (q, k, v, g, beta))
    s, o = lax.scan(step, s0, xs)
    return jnp.moveaxis(o, 0, 1), s


def _chunks(x: Array, chunk: int) -> Array:
    """(B, T, H, ...) -> (T / chunk, B, H, chunk, ...): the scan's axis
    first, in one transposition."""
    b, t, h = x.shape[:3]
    x = x.reshape((b, t // chunk, chunk, h) + x.shape[3:])
    return jnp.moveaxis(x, (1, 3), (0, 2))


@jax.checkpoint
def _within(q, k, v, g, beta):
    """A chunk's own algebra, every chunk at once (N, B, H, C, ...): the WY
    factors ``u``, ``w`` from one triangular solve, the decayed ``Q K^T``,
    ``Q e^G``, ``K e^{G_last - G}`` and the chunk's decay ``e^{G_last}``.
    Checkpointed: its backward recomputes the squares and the solve rather
    than keep them beside the rest of a layer's backward."""
    dt, chunk = q.dtype, q.shape[-2]
    cum = jnp.cumsum(g, axis=-1)                           # G
    i = jnp.arange(chunk)
    lower = i[:, None] >= i[None, :]
    # Gamma_ij = exp(G_i - G_j) for i >= j; the difference is masked before
    # the exp, so no position above the diagonal overflows (nor its grad)
    gamma = jnp.where(lower, jnp.exp(jnp.where(
        lower, cum[..., :, None] - cum[..., None, :], 0.0)), 0.0)

    def mm(a, b):           # products in the inputs' type, sums in float32
        return jnp.matmul(a.astype(dt), jnp.swapaxes(b, -1, -2).astype(dt),
                          preferred_element_type=jnp.float32)

    kb = k * beta[..., None]
    strict = jnp.where(i[:, None] > i[None, :], mm(kb, k) * gamma, 0.0)
    rhs = jnp.concatenate([v * beta[..., None],
                           kb * jnp.exp(cum)[..., None]], axis=-1)
    uw = lax.linalg.triangular_solve(
        jnp.eye(chunk, dtype=jnp.float32) + strict,
        rhs.astype(jnp.float32), left_side=True, lower=True,
        unit_diagonal=True)
    dv = v.shape[-1]
    return (uw[..., :dv].astype(dt), uw[..., dv:].astype(dt),
            (q * jnp.exp(cum)[..., None]).astype(dt),
            (mm(q, k) * gamma).astype(dt),
            (k * jnp.exp(cum[..., -1:] - cum)[..., None]).astype(dt),
            jnp.exp(cum[..., -1]))


def _carry(s: Array, x: tuple) -> tuple[Array, tuple]:
    """One chunk across the boundary: the state ``s`` (B, H, Dk, Dv) in,
    the chunk's output and the state out (with its Frobenius norms)."""
    u, w, qg, qk, kd, decay = x
    vn = u - jnp.matmul(w, s, preferred_element_type=jnp.float32)
    o = (jnp.matmul(qg, s, preferred_element_type=jnp.float32)
         + jnp.matmul(qk, vn.astype(qk.dtype),
                      preferred_element_type=jnp.float32))
    s = decay[..., None, None] * s + jnp.matmul(
        jnp.swapaxes(kd, -1, -2), vn.astype(kd.dtype),
        preferred_element_type=jnp.float32)
    return s, (o, jnp.sqrt(jnp.sum(jnp.square(s), axis=(-2, -1))))


def gated_delta_chunked(q: Array, k: Array, v: Array, g: Array,
                        beta: Array, chunk: int = CHUNK
                        ) -> tuple[Array, Array, Array]:
    """The recurrence in chunks of ``chunk`` positions (any length; the
    tail is padded with positions that change nothing): same arguments as
    ``gated_delta_recurrent``, q, k and v in the type the products take
    (bfloat16 in a bfloat16 step; g, beta, the decay, the solve and the
    state float32); returns (o (B, T, H, Dv) float32, final state, the
    largest Frobenius norm of a state carried out of a chunk)."""
    b, t, h, dk = k.shape
    dv = v.shape[-1]
    pad = -t % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
    xs = _within(*(_chunks(a, chunk) for a in (q, k, v, g, beta)))
    s0 = compat.varying(jnp.zeros((b, h, dk, dv), jnp.float32),
                        compat.vma_of(xs[0]))
    s, (o, norms) = lax.scan(_carry, s0, xs)
    o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(b, t + pad, h, dv)
    return o[:, :t], s, jnp.max(norms)


# -- the kernels -----------------------------------------------------------------
#
# A grid step holds one chunk of every head: q, k, v blocks (1, C, H, D) of
# the row layout, g and beta (1, C, H); inside, a loop takes the heads a
# group at a time, heads leading: (G, C, D).  A product runs one of two
# ways.  ``_mm1``, one pass in the inputs' type with float32 sums: where
# both operands are in that type already (the q/k/v products and the
# state's update from them, as in the XLA form), where one is a cotangent
# against such an operand (rounded to that type, as XLA's default precision
# rounds a float32 operand in the XLA form), and the inverse's first
# estimate.  ``_mm``, at float32's precision (``Precision.HIGHEST``, or
# its terms in fewer passes where one operand is bfloat16): the cumulative
# decay, the inverse's refinement, the WY factors and their gradients, and
# every product that reads the state or its gradient or makes the
# state's gradient.

_HIGHEST = lax.Precision.HIGHEST
# a grid step holds every head of a chunk (its blocks double-buffered, the
# state, the saved state): more than the 16 MiB a kernel gets unasked, less
# than a v5e core's 128 MiB
_VMEM_LIMIT = 100 * 2**20


def _dot(a: Array, b: Array, ca: int, cb: int, precision) -> Array:
    """Per head (dimension 0): ``a``'s dimension ``ca`` contracted with
    ``b``'s ``cb``, summed in float32."""
    return lax.dot_general(a, b, (((ca,), (cb,)), ((0,), (0,))),
                           precision=precision,
                           preferred_element_type=jnp.float32)


def _mm(a: Array, b: Array, ca: int, cb: int) -> Array:
    """The product at float32's precision.  Two float32 operands run at
    ``Precision.HIGHEST``.  A bfloat16 operand against a float32 one runs
    as three one-pass products, the float32 operand split into three
    bfloat16 pieces that sum to it: every term of the exact product, which
    are the terms ``HIGHEST`` keeps of it, in half its passes."""
    if a.dtype == b.dtype or jnp.bfloat16 not in (a.dtype, b.dtype):
        return _dot(a.astype(jnp.float32), b.astype(jnp.float32), ca, cb,
                    _HIGHEST)
    if a.dtype == jnp.bfloat16:
        return sum(_dot(a, p, ca, cb, None) for p in _pieces(b))
    return sum(_dot(p, b, ca, cb, None) for p in _pieces(a))


def _pieces(x: Array) -> tuple[Array, Array, Array]:
    """Three bfloat16 arrays that sum to the float32 ``x``: its leading,
    middle and last eight bits of mantissa."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    return hi, mid, (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)


def _mm1(a: Array, b: Array, ca: int, cb: int, dt) -> Array:
    """The product of ``a`` and ``b`` in ``dt``: one pass, float32 sums."""
    return _dot(a.astype(dt), b.astype(dt), ca, cb, None)


def _lower(c: int, strict: bool) -> Array:
    i = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    return i > j if strict else i >= j


def _columns(x: Array) -> Array:
    """(C, H) -> (H, C, 1): each head's values down a column."""
    return jnp.stack([x[:, h:h + 1] for h in range(x.shape[1])])


def _rows(col: Array) -> Array:
    """(H, C, 1) -> (C, H), the inverse of ``_columns``."""
    h, c, _ = col.shape
    lane = lax.broadcasted_iota(jnp.int32, (c, h), 1)
    out = jnp.zeros((c, h), jnp.float32)
    for i in range(h):
        out = jnp.where(lane == i, col[i], out)
    return out


def _rowsum(x: Array) -> Array:
    return jnp.sum(x, axis=-1, keepdims=True)


def _unit_lower_inverse(a: Array) -> Array:
    """``(I + a)^-1`` for ``a`` strictly lower (G, C, C), C a power of 2,
    to float32's accuracy.  Blocks along the diagonal double in size: with
    ``t`` the inverse of the diagonal blocks of size s, that of the blocks
    of size 2s is ``t - t a' t``, ``a'`` the lower left quarter of each
    2s block of ``a`` (log2 C steps; every intermediate is a piece of the
    inverse or of ``a``, so nothing grows and cancels, as the powers of
    ``a`` do when a chunk's keys are alike).  That runs in bfloat16; two
    Newton steps ``t <- t + t (I - (I + a) t)`` in float32 then square its
    error twice (the error is strictly lower, so it only shrinks)."""
    c = a.shape[-1]
    i = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    eye = jnp.where(i == j, 1.0, 0.0)
    t, shift = jnp.broadcast_to(eye, a.shape), 0
    while (1 << shift) < c:
        bi, bj = (lax.shift_right_logical(x, shift) for x in (i, j))
        inside = (lax.shift_right_logical(bi, 1)
                  == lax.shift_right_logical(bj, 1)) & (bi > bj)
        half = _mm1(jnp.where(inside, a, 0.0), t, 2, 1, jnp.bfloat16)
        t = t - _mm1(t, half, 2, 1, jnp.bfloat16)
        shift += 1
    for _ in range(2):
        t = t + _mm(t, eye - t - _mm(a, t, 2, 1), 2, 1)
    return t


def _algebra(q, k, v, gc, bc) -> dict:
    """A chunk's own algebra for a group of heads: q, k (G, C, Dk) and v
    (G, C, Dv) in the products' type, the cumulative decay ``G`` and beta
    down columns (G, C, 1) float32.  What ``_within`` computes, and the
    float32 pieces its gradient needs."""
    dt, c = q.dtype, q.shape[1]
    lower = _lower(c, False)
    gb = jnp.broadcast_to(gc, gc.shape[:2] + (c,))
    # Gamma_ij = exp(G_i - G_j) for i >= j, masked before the exp
    gamma = jnp.where(lower, jnp.exp(jnp.where(
        lower, gb - jnp.swapaxes(gb, 1, 2), 0.0)), 0.0)
    kf, eg = k.astype(jnp.float32), jnp.exp(gc)
    kbf = kf * bc
    kb = kbf.astype(dt)
    a = jnp.where(_lower(c, True), _mm1(kb, k, 2, 2, dt) * gamma, 0.0)
    t = _unit_lower_inverse(a)
    # [u | w] = T [beta V | beta K e^G], the scales taken into T's
    # columns so that V and K enter in their own type
    along = lambda x: jnp.swapaxes(jnp.broadcast_to(x, gb.shape), 1, 2)
    tb = t * along(bc)
    xv, xk = _mm(tb, v, 2, 1), _mm(tb * along(eg), k, 2, 1)
    rk = kbf * eg
    p = _mm1(q, k, 2, 2, dt)
    glast = gc[:, c - 1:]                                      # (G, 1, 1)
    el = jnp.exp(glast - gc)
    return {"eg": eg, "el": el, "gamma": gamma, "kb": kb, "a": a, "t": t,
            "rk": rk, "xv": xv, "xk": xk, "p": p,
            "u": xv.astype(dt), "w": xk.astype(dt),
            "qg": (q.astype(jnp.float32) * eg).astype(dt),
            "qk": (p * gamma).astype(dt), "kd": (kf * el).astype(dt),
            # e^{G_last} a head, along the lanes of a state's row (the
            # chip's compiler broadcasts along sublanes or lanes, not both
            # at once)
            "decay": jnp.exp(jnp.broadcast_to(glast, glast.shape[:2]
                                              + v.shape[2:]))}


def _group(h: int, rep: int, dtype) -> int:
    """Value heads a pass of a kernel's loop takes: one tile of the row
    layout's sublanes in ``dtype`` (8 in float32, 16 in bfloat16) where
    that is whole key heads (``rep`` value heads a key head), or all."""
    g = 32 // jnp.dtype(dtype).itemsize
    return g if h % g == 0 and g % rep == 0 else h


def _load_columns(g_ref, b_ref, col_ref) -> None:
    """The chunk's cumulative decay ``G`` and beta, (C, H) each, down
    columns into ``col_ref`` (2, H, C, 1)."""
    c = g_ref.shape[1]
    col_ref[0] = _columns(jnp.dot(
        jnp.where(_lower(c, False), 1.0, 0.0), g_ref[0], precision=_HIGHEST,
        preferred_element_type=jnp.float32))
    col_ref[1] = _columns(b_ref[0])


def _always(body) -> None:
    """``body()`` under a condition that always holds.  Pallas's interpreter
    checks the varying mesh axes of each operation of a kernel outside a
    condition against those of its operands, which a kernel (traced without
    them) cannot meet under ``shard_map(check_vma=True)``, as the training
    step runs it; inside one it does not (the flash kernels' bodies sit
    under their tiles' conditions)."""
    pl.when(pl.program_id(0) >= 0)(body)


def _each_group(h: int, rep: int, dtype, body) -> None:
    """``body(heads, keys)`` over the groups of ``_group`` value heads,
    ``heads`` a slice of the value heads and ``keys`` of the key heads
    they read (a loop, so the kernel's code is one group's)."""
    g = _group(h, rep, dtype)

    def step(i, carry):
        body(pl.ds(pl.multiple_of(i * g, g), g),
             pl.ds(pl.multiple_of(i * (g // rep), g // rep), g // rep))
        return carry

    lax.fori_loop(0, h // g, step, 0)


def _shared(ref, keys, rep: int) -> Array:
    """The key heads ``keys`` of a (1, C, Hk, D) block, each repeated for
    the ``rep`` value heads it serves: (G, C, D)."""
    return jnp.repeat(jnp.swapaxes(ref[0, :, keys], 0, 1), rep, axis=0)


def _unshared(x: Array, rep: int) -> Array:
    """(G, C, D) per value head -> (G / rep, C, D) per key head, summed
    over the value heads a key head serves."""
    return x.reshape((-1, rep) + x.shape[1:]).sum(axis=1)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref,
                o_ref, s_out_ref, norm_ref, s_in_ref, col_ref):
    """Grid (B, chunks), chunks in order: the state rides in the final
    state's block, which stays in VMEM across the chunks of a row."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_out_ref[...] = jnp.zeros_like(s_out_ref)
        norm_ref[...] = jnp.zeros_like(norm_ref)

    h = v_ref.shape[2]
    rep = h // q_ref.shape[2]

    def group(hs, ks):
        q, k = _shared(q_ref, ks, rep), _shared(k_ref, ks, rep)
        v = jnp.swapaxes(v_ref[0, :, hs], 0, 1)
        f = _algebra(q, k, v, col_ref[0, hs], col_ref[1, hs])
        s = s_out_ref[0, hs]
        s_in_ref[0, 0, hs] = s
        c = q.shape[1]
        ws = _mm(jnp.concatenate([f["w"], f["qg"]], axis=1), s, 2, 1)
        vn = (f["u"] - ws[:, :c]).astype(q.dtype)
        o = ws[:, c:] + _mm1(f["qk"], vn, 2, 1, vn.dtype)
        o_ref[0, :, hs] = jnp.swapaxes(o, 0, 1)
        s = f["decay"] * s + _mm1(f["kd"], vn, 1, 1, vn.dtype)
        s_out_ref[0, hs] = s
        sq = jnp.sum(jnp.sum(jnp.square(s), axis=2, keepdims=True), axis=1,
                     keepdims=True)                            # (G, 1, 1)
        norm_ref[...] = jnp.maximum(norm_ref[...],
                                    jnp.sqrt(jnp.max(sq, axis=0)))

    @_always
    def _chunk():
        _load_columns(g_ref, b_ref, col_ref)
        _each_group(h, rep, q_ref.dtype, group)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s_ref, do_ref, dsf_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref,
                ds_ref, col_ref, dcol_ref):
    """Grid (B, chunks), chunks in reverse: the gradient of the state
    leaving the chunk rides in ``ds_ref``; each chunk's algebra is
    recomputed from its inputs and the state that entered it."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        ds_ref[...] = dsf_ref[0]

    c, h = q_ref.shape[1], v_ref.shape[2]
    rep = h // q_ref.shape[2]

    def group(hs, ks):
        q, k = _shared(q_ref, ks, rep), _shared(k_ref, ks, rep)
        v, do = (jnp.swapaxes(r[0, :, hs], 0, 1) for r in (v_ref, do_ref))
        dt = q.dtype
        bc = col_ref[1, hs]
        f = _algebra(q, k, v, col_ref[0, hs], bc)
        s, ds = s_ref[0, 0, hs], ds_ref[hs]
        vn = (f["u"] - _mm(f["w"], s, 2, 1)).astype(dt)
        # across the boundary: V' = u - w S, O = Qg S + QK V',
        # S' = decay S + Kd^T V'
        dvn = _mm1(f["qk"], do, 1, 1, dt) + _mm(f["kd"], ds, 2, 1)
        both = jnp.concatenate([do, dvn], axis=1)          # (G, 2C, Dv)
        ds_ref[hs] = f["decay"] * ds + _mm(
            jnp.concatenate([f["qg"], -f["w"]], axis=1), both, 1, 1)
        dqg_dw = _mm(both, s, 2, 2)             # [dQg; -dw]
        dqg = dqg_dw[:, :c]
        dqk = _mm1(do, vn, 2, 2, dt)
        dkd = _mm(vn, ds, 2, 2)
        kf, qf = k.astype(jnp.float32), q.astype(jnp.float32)
        fl = _rowsum(dkd * kf * f["el"])      # through K e^{G_last - G}
        dglast = (_rowsum(jnp.sum(f["decay"] * s * ds, axis=1,
                                  keepdims=True))
                  + jnp.sum(fl, axis=1, keepdims=True))
        # the WY factors: [u | w] = T [beta V | beta K e^G], T = (I + A)^-1
        dvw = jnp.concatenate([dvn, -dqg_dw[:, c:]], axis=2)   # [du | dw]
        dr = _mm(f["t"], dvw, 1, 1)
        drv, drk = dr[..., :dvn.shape[2]], dr[..., dvn.shape[2]:]
        da = jnp.where(_lower(c, True), -_mm(
            dr, jnp.concatenate([f["xv"], f["xk"]], axis=2), 2, 2), 0.0)
        # A = beta K K^T * Gamma (strict), QK = Q K^T * Gamma
        dbm = da * f["gamma"]
        dp = dqk * f["gamma"]
        e = dqk * f["p"] * f["gamma"] + da * f["a"]
        dkb = _mm1(dbm, k, 2, 1, dt)
        eg = f["eg"]
        dq = dqg * eg + _mm1(dp, k, 2, 1, dt)
        dk = (_mm1(dp, q, 1, 1, dt) + _mm1(dbm, f["kb"], 1, 1, dt) + dkb * bc
              + drk * bc * eg + dkd * f["el"])
        dcol_ref[1, hs] = (_rowsum(dkb * kf)
                           + _rowsum(drv * v.astype(jnp.float32))
                           + _rowsum(drk * kf) * eg)
        last = lax.broadcasted_iota(jnp.int32, fl.shape, 1) == c - 1
        dcol_ref[0, hs] = (_rowsum(dqg * qf * eg) + _rowsum(drk * f["rk"])
                           - fl + _rowsum(e) - _rowsum(jnp.swapaxes(e, 1, 2))
                           + jnp.where(last, dglast, 0.0))
        dq_ref[0, :, ks] = jnp.swapaxes(_unshared(dq, rep), 0, 1).astype(
            dq_ref.dtype)
        dk_ref[0, :, ks] = jnp.swapaxes(_unshared(dk, rep), 0, 1).astype(
            dk_ref.dtype)
        dv_ref[0, :, hs] = jnp.swapaxes(drv * bc, 0, 1).astype(dv_ref.dtype)

    @_always
    def _chunk():
        _load_columns(g_ref, b_ref, col_ref)
        _each_group(h, rep, q_ref.dtype, group)
        # G is the cumulative sum of g: g's gradient is G's summed from
        # behind
        dg_ref[0] = jnp.dot(jnp.where(_lower(c, True), 0.0, 1.0),
                            _rows(dcol_ref[0]), precision=_HIGHEST,
                            preferred_element_type=jnp.float32)
        db_ref[0] = _rows(dcol_ref[1])


def _specs(b, n, h, dk, dv, reverse=False):
    """Block specs over (B, chunks) of the row layout's operands: ``rows``
    of ``heads`` heads of width ``d``."""
    chunk = (lambda i, j: n - 1 - j) if reverse else (lambda i, j: j)
    rows = lambda heads, d: pl.BlockSpec(
        (1, CHUNK, heads, d), lambda i, j: (i, chunk(i, j), 0, 0))
    per_pos = pl.BlockSpec((1, CHUNK, h), lambda i, j: (i, chunk(i, j), 0))
    state = pl.BlockSpec((1, h, dk, dv), lambda i, j: (i, 0, 0, 0))
    saved = pl.BlockSpec((1, 1, h, dk, dv),
                         lambda i, j: (i, chunk(i, j), 0, 0, 0))
    return rows, per_pos, state, saved


def _params(interpret):
    return None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _fwd(q, k, v, g, beta, interpret):
    (b, t, hk, dk), (h, dv) = k.shape, v.shape[2:]
    n = t // CHUNK
    vma = _vma(q, k, v, g, beta)
    rows, per_pos, state, saved = _specs(b, n, h, dk, dv)
    return pl.pallas_call(
        _fwd_kernel,
        grid=(b, n),
        in_specs=[rows(hk, dk), rows(hk, dk), rows(h, dv), per_pos, per_pos],
        out_specs=[rows(h, dv), state,
                   pl.BlockSpec((1, 8, 128), lambda i, j: (i, 0, 0)), saved],
        out_shape=[compat.shape_struct((b, t, h, dv), jnp.float32, vma=vma),
                   compat.shape_struct((b, h, dk, dv), jnp.float32, vma=vma),
                   compat.shape_struct((b, 8, 128), jnp.float32, vma=vma),
                   compat.shape_struct((b, n, h, dk, dv), jnp.float32,
                                       vma=vma)],
        scratch_shapes=[pltpu.VMEM((2, h, CHUNK, 1), jnp.float32)],
        compiler_params=_params(interpret),
        interpret=interpret,
        name="gated_delta_fwd",
    )(q, k, v, g, beta)


def _bwd(interpret, res, cts):
    q, k, v, g, beta, states = res
    do, ds, _ = cts       # the largest state norm is a counter: no gradient
    (b, t, hk, dk), (h, dv) = k.shape, v.shape[2:]
    n = t // CHUNK
    vma = _vma(q, k, v, g, beta, do)
    rows, per_pos, state, saved = _specs(b, n, h, dk, dv, reverse=True)
    return tuple(pl.pallas_call(
        _bwd_kernel,
        grid=(b, n),
        in_specs=[rows(hk, dk), rows(hk, dk), rows(h, dv), per_pos, per_pos,
                  saved, rows(h, dv), state],
        out_specs=[rows(hk, dk), rows(hk, dk), rows(h, dv), per_pos,
                   per_pos],
        out_shape=[compat.shape_struct(a.shape, a.dtype, vma=vma)
                   for a in (q, k, v, g, beta)],
        scratch_shapes=[pltpu.VMEM((h, dk, dv), jnp.float32),
                        pltpu.VMEM((2, h, CHUNK, 1), jnp.float32),
                        pltpu.VMEM((2, h, CHUNK, 1), jnp.float32)],
        compiler_params=_params(interpret),
        interpret=interpret,
        name="gated_delta_bwd",
    )(q, k, v, g, beta, states, do, ds))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rule(q, k, v, g, beta, interpret):
    o, s, norm, _ = _fwd(q, k, v, g, beta, interpret)
    return o, s, norm


def _rule_fwd(q, k, v, g, beta, interpret):
    o, s, norm, states = _fwd(q, k, v, g, beta, interpret)
    return (o, s, norm), (q, k, v, g, beta, states)


_rule.defvjp(_rule_fwd, _bwd)


def gated_delta(q: Array, k: Array, v: Array, g: Array, beta: Array,
                interpret: bool | None = None
                ) -> tuple[Array, Array, Array]:
    """``gated_delta_chunked`` as the two kernels, with q and k at the key
    heads' count (B, T, Hk, Dk), each key head serving its Hv / Hk
    consecutive value heads (v (B, T, Hv, Dv), g and beta (B, T, Hv)): the
    chunked form of q and k repeated to Hv heads.  The same results (o
    (B, T, Hv, Dv) float32, the final state, the largest Frobenius norm of
    a state carried out of a chunk, which has no gradient).  On the chip
    the key and value widths must be multiples of 128; off it the kernels
    run in interpret mode at any widths."""
    if interpret is None:
        interpret = _interpret_default()
    (hk, dk), (hv, dv) = k.shape[2:], v.shape[2:]
    if hv % hk:
        raise ValueError(f"{hv} value heads cannot share {hk} key heads")
    if not interpret and (dk % 128 or dv % 128):
        raise ValueError(
            f"the gated delta kernels need key and value widths that are "
            f"multiples of 128 on the chip, got {dk} and {dv}")
    t = k.shape[1]
    pad = -t % CHUNK
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    o, s, norm = _rule(q, k, v, g.astype(jnp.float32),
                       beta.astype(jnp.float32), interpret)
    return o[:, :t], s, lax.stop_gradient(jnp.max(norm))
