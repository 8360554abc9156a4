"""The gated delta rule (Gated DeltaNet's linear attention) and the short
causal convolution before it.

Per value head, with a state ``S`` of (key x value) and ``S_0 = 0``::

    S_t = e^{g_t} S_{t-1} + k_t (beta_t (v_t - e^{g_t} S_{t-1}^T k_t))^T
    o_t = S_t^T q_t

- ``gated_delta_recurrent``: that recurrence token by token (a ``lax.scan``
  over positions): the oracle the chunked form is tested against.
- ``gated_delta_chunked``: the same in chunks of ``CHUNK`` positions, the
  form the training step runs.  Within a chunk, with ``G`` the cumulative
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
- ``causal_conv``: depthwise over channels, ``width`` taps, position ``t``
  reads ``t - width + 1 .. t`` with zeros before 0: shifted multiply-adds
  that XLA fuses into one pass.

Shapes are (batch, time, heads, dim), as the projections give them; the
output is float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..utils import compat

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
