"""Short names for the JAX calls the framework's SPMD code is written
against (``jax.shard_map``, the vma/pcast varying-axis machinery), the
buffer-donation helper, and the differentiable fusion barrier.
"""

from __future__ import annotations

import jax
# provable varying->invariant gather (jax 0.9.0 does not re-export it)
from jax._src.lax.parallel import all_gather_invariant  # noqa: F401

shard_map = jax.shard_map
pcast = jax.lax.pcast

# Donation sites (via ``donate``): the train steps (train.py, lm.py), and
# serve.py's whole decode hot path — the lockstep block (KV cache + the
# device-side carry the overlapped dispatch chains on), the speculative
# block (cache + its staging dict, whose (slots, kv_len) stream buffer is
# rebuilt every dispatch), the suffix-prefill/chunk/insert/scatter cache
# writers.  Without donation, each of those dispatches copies the full
# paged pool per call.  tests/test_serve.py flips this constant to pin
# that donation changes no stream.
DONATION_SAFE = True


def donate(*argnums: int) -> tuple:
    """``donate_argnums`` value: the given indices, or none when a test
    has turned ``DONATION_SAFE`` off."""
    return tuple(argnums) if DONATION_SAFE else ()


def vma_of(x) -> frozenset:
    """The array's varying mesh axes."""
    return jax.typeof(x).vma


def varying(x, axes):
    """``x`` varying over the mesh axes ``axes`` as well (a loop's carry has
    to start typed as its body leaves it)."""
    missing = frozenset(axes) - vma_of(x)
    return pcast(x, tuple(missing), to="varying") if missing else x


def as_cotangent(g, primal):
    """``g`` typed as the cotangent of ``primal``: summed over the mesh
    axes it varies over and ``primal`` does not (what autodiff does for an
    input that is the same on every shard), varying over the rest."""
    extra = vma_of(g) - vma_of(primal)
    return varying(jax.lax.psum(g, tuple(extra)) if extra else g,
                   vma_of(primal))


def shape_struct(shape, dtype, vma=None):
    """``ShapeDtypeStruct`` carrying ``vma`` (None = untracked)."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


# -- differentiable fusion barrier (round 10) ------------------------------
#
# The pipeline chunk body needs the barrier on BOTH passes: the cotangent
# chain must get the same compilation boundary as the primal, or the
# unrolled-backward fusion drifts exactly like the forward one.  The
# custom_vjp below is the one definition of "identity that XLA may not
# fuse across, in either direction".

@jax.custom_vjp
def opt_barrier(x):
    """Identity that blocks XLA fusion across it, differentiable: the
    forward applies ``optimization_barrier`` to the primal, the backward
    applies it to the cotangent (parallel/pipeline.py uses it to give
    layer-scan bodies the same fusion boundary at every trip count —
    XLA unrolls trip-count-1 scans and re-fuses them sub-ulp
    differently)."""
    return jax.lax.optimization_barrier(x)


def _opt_barrier_fwd(x):
    return jax.lax.optimization_barrier(x), None


def _opt_barrier_bwd(_, g):
    return (jax.lax.optimization_barrier(g),)


opt_barrier.defvjp(_opt_barrier_fwd, _opt_barrier_bwd)
