"""Fused BatchNorm+ReLU backward (Pallas, TPU) — a MEASURED NEGATIVE
RESULT, kept as the reproducible experiment.

The round-3 profiling (ROADMAP.md MFU accounting) hypothesized that
XLA's autodiff of train-mode BN+ReLU wastes HBM passes (~7.6 effective
for the isolated fwd+bwd vs ~5-6 necessary) and that a hand-written
backward was worth ~0.15 ms/step (+2-3 MFU points).  Round 4 built it
and measured the opposite, twice over (BASELINE.md round-4 section):

- the two-kernel Pallas backward below (5 HBM-sized passes, exactly as
  designed) makes the END-TO-END step 1.72x SLOWER (4.09 vs 2.37
  ms/step, batch 256 bf16, same session): the ``custom_vjp`` boundary
  forces residual/cotangent materialization XLA's fuser would have
  elided, and the kernel's f32 elementwise work is VPU-bound (half the
  packed-bf16 vector width XLA uses);
- the SAME closed form handed to XLA as one jnp expression
  (``FUSED_BN_BWD=xla``) is still ~4% slower than plain autodiff —
  XLA's derived backward graph plus fusion already beats the naive
  pass-count model that motivated the kernel.

Conclusion: on TPU v5e, XLA's BN+ReLU backward is not the ~20% soft
target the isolated-pass arithmetic suggested; the remaining MFU gap is
structural (bf16 elementwise traffic + f32 optimizer state), not a
missing kernel.  The default path is therefore the PLAIN XLA one
(``supported`` below returns False for auto-gating); everything here
stays importable and test-pinned (tests/test_fused_bn.py) so the
experiment is re-runnable on future toolchains/chips, where the
balance may shift.

Design of the kernels (what "5 passes" means), for the record — two
Pallas kernels under a ``jax.custom_vjp``:

- the whole BN backward collapses onto two per-channel scalars: with
  ``xhat = (a - mean) * rstd``, ``y = xhat*gamma + beta``,
  ``r = relu(y)``, ``dy = dr * (y > 0)``, the closed form is

      da = gamma * rstd * (dy - (s1 + xhat * s2) / n)
      dbeta = s1 = sum(dy);   dgamma = s2 = sum(dy * xhat)

  so kernel 1 streams (dr, a) once accumulating (s1, s2) per channel
  and kernel 2 streams (dr, a) once more writing ``da`` — 5 HBM-sized
  passes total (2 reads + 2 reads + 1 write), nothing else touches the
  activation-sized arrays;
- the ReLU mask is RECOMPUTED inside the kernel with the forward's
  exact arithmetic (same dtype, same ``inv = rstd*scale`` product and
  cast order as ``ops.nn.batchnorm`` + ``relu``), so no mask is stored
  and fwd/bwd agree bitwise on which elements were clipped;
- the statistics' through-graph gradient is BAKED into ``da`` (the
  closed form above already includes the d(mean)/d(var) chains), so the
  caller must pass ``lax.stop_gradient``-wrapped mean/rstd — otherwise
  XLA would backprop its own reduction graph on top and double-count.

The FORWARD stays plain XLA (it already fuses into the conv epilogue
at ~hardware speed; reproduced here operation-for-operation so the
fused path is forward-bitwise with the unfused one).  Scope: train
mode with local (non-synced) statistics — eval and sync-BN keep the
plain path (reference semantics: SURVEY.md section 2.3 — BN is NOT
cross-replica synced, so the hot path is exactly this one).

No reference analog: the reference inherits BN backward from libtorch
(reference model.py:24 uses nn.BatchNorm2d); this is the TPU-native
equivalent of owning that kernel.
"""

from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

Array = jax.Array


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _block_rows(m: int, c: int) -> int:
    """Rows per grid step: cap the VMEM tile around 512k elements and
    keep it a divisor of m (shapes here are powers of two)."""
    bm = max(8, min(m, (1 << 19) // c))
    while m % bm:
        bm //= 2
    return max(bm, 1)


def _mask_dy_xhat(dr, a, mean, rstd, gamma, beta):
    """Shared by both kernels: the forward-exact ReLU mask (compute
    dtype, same cast order as ops.nn.batchnorm) and the f32 (dy, xhat)
    the closed-form backward consumes."""
    inv_c = (rstd * gamma).astype(a.dtype)
    y = (a - mean.astype(a.dtype)) * inv_c + beta.astype(a.dtype)
    a32 = a.astype(jnp.float32)
    # compare after an exact f32 upcast: bf16 cmp vectors are unsupported
    # by Mosaic's packed layout, and sign is preserved exactly
    dy = jnp.where(y.astype(jnp.float32) > 0,
                   dr.astype(jnp.float32), 0.0)
    xhat = (a32 - mean) * rstd
    return dy, xhat


def _reduce_kernel(dr_ref, a_ref, mean_ref, rstd_ref, gamma_ref, beta_ref,
                   s1_ref, s2_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        s1_ref[...] = jnp.zeros_like(s1_ref)
        s2_ref[...] = jnp.zeros_like(s2_ref)

    dy, xhat = _mask_dy_xhat(dr_ref[...], a_ref[...], mean_ref[...],
                             rstd_ref[...], gamma_ref[...], beta_ref[...])
    s1_ref[...] += jnp.sum(dy, 0, keepdims=True)
    s2_ref[...] += jnp.sum(dy * xhat, 0, keepdims=True)


def _apply_kernel(dr_ref, a_ref, mean_ref, rstd_ref, gamma_ref, beta_ref,
                  s1_ref, s2_ref, da_ref, *, n: float):
    dy, xhat = _mask_dy_xhat(dr_ref[...], a_ref[...], mean_ref[...],
                             rstd_ref[...], gamma_ref[...], beta_ref[...])
    coef = gamma_ref[...] * rstd_ref[...]
    da = coef * (dy - (s1_ref[...] + xhat * s2_ref[...]) * (1.0 / n))
    da_ref[...] = da.astype(da_ref.dtype)


def _bwd_pallas(dr, a, mean, rstd, gamma, beta, *, interpret: bool):
    """(da, dgamma, dbeta) for the flattened (M, C) problem.

    Narrow layers (C < 128, e.g. VGG's 64-channel conv0 — the single
    largest activation) fold ``128 // C`` rows into one 128-wide lane
    row: the channel pattern repeats, so the per-channel vectors tile
    and the two half-lane sums add back together at the end.  Without
    the fold, half of every vector lane would be padding."""
    m, c = a.shape
    n = float(m)
    fold = 128 // c if c < 128 else 1
    if fold > 1:
        m, c = m // fold, c * fold
        dr = dr.reshape(m, c)
        a = a.reshape(m, c)
        mean, rstd, gamma, beta = (jnp.tile(v, fold)
                                   for v in (mean, rstd, gamma, beta))
    bm = _block_rows(m, c)
    nsteps = m // bm
    vec = lambda v: v.reshape(1, c).astype(jnp.float32)
    mean, rstd, gamma, beta = map(vec, (mean, rstd, gamma, beta))
    row = pl.BlockSpec((bm, c), lambda i: (i, 0))
    chan = pl.BlockSpec((1, c), lambda i: (0, 0))

    s1, s2 = pl.pallas_call(
        _reduce_kernel,
        grid=(nsteps,),
        in_specs=[row, row, chan, chan, chan, chan],
        out_specs=[chan, chan],
        out_shape=[jax.ShapeDtypeStruct((1, c), jnp.float32)] * 2,
        interpret=interpret,
        name="bn_bwd_sums",
    )(dr, a, mean, rstd, gamma, beta)
    # true per-channel totals: under folding each lane column held only
    # its own rows' partial sum — collapse the fold, then re-tile so the
    # apply kernel sees full sums in every folded column
    s1 = s1.reshape(fold, -1).sum(0)
    s2 = s2.reshape(fold, -1).sum(0)

    da = pl.pallas_call(
        partial(_apply_kernel, n=n),
        grid=(nsteps,),
        in_specs=[row, row, chan, chan, chan, chan, chan, chan],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((m, c), a.dtype),
        interpret=interpret,
        name="bn_bwd_dx",
    )(dr, a, mean, rstd, gamma, beta,
      jnp.tile(s1, fold)[None], jnp.tile(s2, fold)[None])
    if fold > 1:
        da = da.reshape(m * fold, c // fold)
    return da, s2, s1


@partial(jax.custom_vjp, nondiff_argnums=(5,))
def bn_relu(a, scale, bias, mean, rstd, interpret=None):
    """relu((a - mean) * rstd * scale + bias) with the fused backward.

    ``mean``/``rstd`` must be the BATCH statistics of ``a`` wrapped in
    ``lax.stop_gradient`` (their gradient chain is baked into ``da``);
    forward arithmetic is operation-identical to
    ``ops.nn.batchnorm`` + ``relu`` in both f32 and mixed precision.
    """
    inv = rstd * scale
    if a.dtype == jnp.float32:
        y = (a - mean) * inv + bias
    else:
        y = ((a - mean.astype(a.dtype)) * inv.astype(a.dtype)
             + bias.astype(a.dtype))
    return jnp.maximum(y, 0)


def _bn_relu_fwd(a, scale, bias, mean, rstd, interpret):
    return bn_relu(a, scale, bias, mean, rstd, interpret), \
        (a, scale, bias, mean, rstd)


def _bwd_impl() -> str:
    """Experiment switch, read at trace time so it can be flipped after
    import: "pallas" (two hand kernels) or "xla" (the same closed form
    as one jnp expression XLA fuses itself); both measured SLOWER than
    plain autodiff e2e — see the module docstring."""
    return os.environ.get("FUSED_BN_BWD", "pallas")


def _bwd_xla(dr, a, mean, rstd, gamma, beta):
    """The identical closed form, left to XLA's fuser: elementwise in the
    compute dtype (mask from the forward-exact arithmetic), reductions
    accumulated in f32."""
    n = a.size // a.shape[-1]
    cd = a.dtype
    inv_c = (rstd * gamma).astype(cd)
    y = (a - mean.astype(cd)) * inv_c + beta.astype(cd)
    dy = jnp.where(y > 0, dr, jnp.zeros((), cd))
    xhat = (a.astype(jnp.float32) - mean) * rstd
    dy32 = dy.astype(jnp.float32)
    axes = tuple(range(a.ndim - 1))
    s1 = jnp.sum(dy32, axes)                 # dbeta
    s2 = jnp.sum(dy32 * xhat, axes)          # dgamma
    coef = gamma * rstd
    da = coef * (dy32 - (s1 + xhat * s2) * (1.0 / n))
    return da.astype(cd), s2, s1


def _bn_relu_bwd(interpret, res, dr):
    a, scale, bias, mean, rstd = res
    c = a.shape[-1]
    if _bwd_impl() == "xla":
        da, dgamma, dbeta = _bwd_xla(dr, a, mean, rstd, scale, bias)
    else:
        da, dgamma, dbeta = _bwd_pallas(
            dr.reshape(-1, c), a.reshape(-1, c), mean, rstd, scale, bias,
            interpret=(_interpret_default() if interpret is None
                       else interpret))
        da = da.reshape(a.shape)
    return (da, dgamma.astype(scale.dtype),
            dbeta.astype(bias.dtype), jnp.zeros_like(mean),
            jnp.zeros_like(rstd))


bn_relu.defvjp(_bn_relu_fwd, _bn_relu_bwd)


def supported(x: Array, train: bool, axis_name) -> bool:
    """Auto-gate for ``batchnorm_relu(fused=None)``: always False — the
    measured e2e result (module docstring) says the plain XLA backward
    wins on current TPUs.  ``applicable`` reports whether the kernel
    COULD run, for explicit ``fused=True`` experiments."""
    return False


def applicable(x: Array, train: bool, axis_name) -> bool:
    """Shape/mode envelope the kernel handles: train mode, local
    (non-synced) statistics, lane-aligned (or lane-foldable) channels."""
    c = x.shape[-1]
    m = x.size // c
    if not (train and axis_name is None and m % 8 == 0):
        return False
    if c % 128 == 0:
        return True
    return 128 % c == 0 and m % (8 * (128 // c)) == 0
