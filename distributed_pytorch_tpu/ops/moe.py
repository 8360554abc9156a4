"""Mixture-of-Experts layers: two routed paths.

**Which path is used when.**  ``moe_apply`` (first, below) is the capacity
path: top-1 / top-2 or expert-choice routing through a one-hot ``(T, E, C)``
dispatch tensor, tokens over an expert's capacity dropped, experts exchanged
over a mesh axis with all_to_all.  It is what ``TransformerConfig(n_experts=
E)`` runs and what decode, serving, EP x TP and the pipeline use.  Its
dispatch tensor grows as T x E x C, so it is for few, wide experts.
``moe_dropless_apply`` (``TransformerConfig(moe_dropless=True)``) is the
sorted path for many narrow experts: top-k of any k over all E published
experts, the ``T x k`` picks sorted by expert and taken through grouped
matrix products (``lax.ragged_dot``), **no capacity and no dropped token at
any imbalance**.  It is told which **experts it holds** -- ``(first_expert,
how many)``: the contiguous slice of the E routed experts whose weights live
on this chip -- routes over all E, and returns the part of the layer's
result that its own experts give; picks of experts held elsewhere add
nothing here, while their weight still takes its part of the softmax over
the k picks.  The shares of all holders add up to the whole layer.  It runs
on one chip's share without an exchange (training only; with an ``axis`` it
refuses: the exchange is not written yet).

The capacity path, as first written -- the last parallelism axis the framework adds (the reference has none of
this — SURVEY.md section 5): a Switch-style top-1-routed MoE MLP whose
experts are sharded over a mesh axis.  Design:

- **Routing** (per token): softmax router over all E experts, top-1 pick,
  output scaled by the router probability (straight-through gating).
- **Capacity**: each expert accepts at most C = ceil(T * cf / E) tokens per
  routing group; overflow tokens are dropped (their MLP delta is zero —
  the residual stream passes them through), the standard Switch behavior.
- **Dispatch** is einsum against a (T, E, C) one-hot tensor — dense,
  MXU-shaped, fully differentiable (the gradient of a dropped token's
  delta is zero, as it should be).
- **Expert parallelism** (``axis``): each device holds E_local = E/n
  experts and routes its own T tokens; one ``lax.all_to_all`` carries every
  device's per-expert buffers to the expert's owner and a second carries
  results back.  XLA lowers these to ICI all-to-alls.  Since round 21 both
  trips route through ``parallel/routing.execute_a2a`` — the same executor
  the ``expert:a2a@…`` route grammar compiles to — so the wire can be
  rowwise-quantized (``dispatch_bits='int8'/'int4'``, per-token f32 scales
  riding the same exchange; activation compression, gated by the round-16
  flip-rate methodology rather than an EF ledger) and capacity-chunked
  (``a2a_chunks>1``: chunk k's combine all-to-all overlaps chunk k+1's
  expert FFN).  At the defaults (f32, 1 chunk) the emitted program is
  bitwise the pre-round-21 hand-built one.
- **Load-balance aux loss**: the Switch aux ``E * sum_e f_e * p_e`` over
  this device's tokens (f = routed fraction, p = mean router prob).
- **Router z-loss** (``z_coef``): mean squared logsumexp of the router
  logits (ST-MoE), discouraging logit blow-up; added into the returned aux.
- **Expert-choice routing** (``router_mode='experts'``): experts pick their
  top-C tokens instead of tokens picking experts (Zhou et al. 2022) —
  perfectly load-balanced by construction (balance aux is 0), tokens may
  be served by several experts or none.

All shapes are static: capacity and expert counts are trace-time constants,
so the whole layer compiles into one XLA program.
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from ..parallel import routing as _routing

Array = jax.Array
PyTree = Any


def moe_init(key: Array, d_model: int, d_ff: int, n_experts: int,
             held: int | None = None) -> PyTree:
    """Router + per-expert gated-MLP stacks.  To expert-shard, split the
    leading expert dim of w_gate/w_up/w_down over the mesh axis (the router
    stays replicated).  ``held``: the stacks hold only that many experts
    (one chip's share for ``moe_dropless_apply``); the router keeps all
    ``n_experts`` outputs."""
    ks = jax.random.split(key, 4)
    held = n_experts if held is None else held

    def dense(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)

    return {
        "router": dense(ks[0], (d_model, n_experts), d_model),
        "w_gate": dense(ks[1], (held, d_model, d_ff), d_model),
        "w_up": dense(ks[2], (held, d_model, d_ff), d_model),
        "w_down": dense(ks[3], (held, d_ff, d_model), d_ff),
    }


def moe_apply(
    params: PyTree,
    x: Array,                      # (T, D) this device's tokens
    *,
    n_experts: int,                # GLOBAL expert count E
    capacity_factor: float = 2.0,
    axis: str | None = None,       # expert-parallel mesh axis
    top_k: int = 1,                # 1 = Switch, 2 = classic top-2 MoE
    router_mode: str = "tokens",   # 'tokens' (top-k) | 'experts' (EC)
    z_coef: float = 0.0,           # router z-loss weight (added into aux)
    dispatch_bits: str = "f32",    # a2a wire precision: f32 | int8 | int4
    a2a_chunks: int = 1,           # capacity chunks for combine/FFN overlap
) -> tuple[Array, Array]:
    """Returns (out (T, D), auxiliary loss scalar).

    The aux scalar is the Switch load-balance loss (0 under expert-choice
    routing, which is balanced by construction) plus ``z_coef`` times the
    router z-loss; the caller applies its overall aux weight on top.

    Without ``axis``, ``params`` holds all E experts.  With ``axis``,
    ``params['w_*']`` hold this device's E/n expert shard and tokens are
    exchanged over the axis with all_to_all.

    ``top_k=2`` routes each token to its two best experts with gates
    normalized over the chosen pair (Shazeer-style); choice-2 tokens fill
    expert slots after every choice-1 token (lower drop priority).

    ``router_mode='experts'``: each expert picks its top-C tokens by router
    affinity (C = ceil(T * capacity_factor / E)); a token's output is the
    gate-weighted sum over every expert that picked it.

    ``dispatch_bits``: wire precision of the two expert all-to-alls
    (round 21).  'int8'/'int4' rowwise-quantize each dispatched token row
    with its f32 scale riding the same exchange — the
    ``parallel/routing`` ``expert:a2a@bits`` wire format; the backward
    cotangent is compressed identically.  'f32' is the exact hand-built
    exchange.  Requires ``axis`` — without an expert-parallel axis there
    is no wire to compress.

    ``a2a_chunks``: split the (E, C) capacity buffers into this many
    capacity slices so chunk k's combine all-to-all issues between chunk
    k's and chunk k+1's expert FFN matmuls (async collectives then hide
    the exchange behind compute).  ``1`` is the historical unchunked
    program, bitwise.  Requires ``axis`` for the same reason.

    CAVEAT (expert-choice acausality): the per-expert top-C selection ranks
    over the flattened (B*S) token dim, so in causal LM training a token's
    output depends on the router logits of FUTURE positions (and of other
    sequences in the batch).  This is inherent to expert-choice routing, not
    a bug — but it means EC train/eval loss is not reproducible by any
    autoregressive decode (decode sees only the past, and ``generate``
    approximates EC models with capacity-free token-choice mixing; it warns
    when it does).  Use ``router_mode='tokens'`` when train-vs-decode loss
    parity matters.
    """
    t, d = x.shape
    e = n_experts
    n = lax.axis_size(axis) if axis is not None else 1
    if e % n:
        raise ValueError(f"{e} experts do not shard over {n} devices")
    if router_mode not in ("tokens", "experts"):
        raise ValueError(f"router_mode must be 'tokens' or 'experts', "
                         f"got {router_mode!r}")
    if top_k not in (1, 2):
        raise ValueError(f"top_k must be 1 or 2, got {top_k}")
    if router_mode == "experts" and top_k != 1:
        raise ValueError("expert-choice routing has no top_k (experts pick "
                         "tokens); leave top_k=1")
    if dispatch_bits not in ("f32", "int8", "int4"):
        raise ValueError(f"dispatch_bits must be f32, int8, or int4, "
                         f"got {dispatch_bits!r}")
    if dispatch_bits != "f32" and axis is None:
        raise ValueError(
            f"dispatch_bits={dispatch_bits!r} quantizes the expert "
            f"all_to_all wire; without an expert-parallel axis there is "
            f"no wire to compress (the local einsum path is exact)")
    if a2a_chunks < 1:
        raise ValueError(f"a2a_chunks must be >= 1, got {a2a_chunks}")
    if a2a_chunks > 1 and axis is None:
        raise ValueError(
            f"a2a_chunks={a2a_chunks} pipelines the dispatch/combine "
            f"all_to_alls against the expert FFN; without an "
            f"expert-parallel axis there is no exchange to overlap")
    e_local = e // n
    # min(·, t): expert-choice top_k needs cap <= t; more slots than tokens
    # is meaningless in either mode.
    cap = min(max(1, math.ceil(t * top_k * capacity_factor / e)), t)

    # -- routing (f32 for a stable softmax) --------------------------------
    logits = x.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)              # (T, E)

    # Router z-loss (ST-MoE): mean logsumexp^2 keeps logits small.
    z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))

    if router_mode == "experts":
        # Experts choose tokens: per expert, top-cap tokens by affinity.
        g, idx = jax.lax.top_k(probs.T, cap)             # (E, C) each
        sel = jax.nn.one_hot(idx, t, dtype=x.dtype)      # (E, C, T)
        dispatch = jnp.einsum("ect->tec", sel)           # (T, E, C)
        combine = jnp.einsum("ect,ec->tec", sel, g.astype(x.dtype))
        aux = z_coef * z_loss                            # balanced by design
    else:
        top_probs, top_idx = jax.lax.top_k(probs, top_k)     # (T, K)
        if top_k == 1:
            gates = top_probs                            # Switch: raw prob
        else:
            gates = top_probs / jnp.sum(top_probs, axis=-1, keepdims=True)
        onehots = jax.nn.one_hot(top_idx, e, dtype=jnp.float32)  # (T, K, E)

        # Load-balance aux over the primary assignment (Switch
        # normalization: a perfectly uniform router gives aux == 1).
        frac = jnp.mean(onehots[:, 0], axis=0)
        mean_prob = jnp.mean(probs, axis=0)
        aux = e * jnp.sum(frac * mean_prob) + z_coef * z_loss

        # -- capacity & dispatch tensor (T, E, C) --------------------------
        # Slot assignment: all choice-1 tokens first (stream order), then
        # choice-2 tokens fill what remains — choice-2 drops first under
        # pressure, the standard top-2 priority.
        flat = onehots.transpose(1, 0, 2).reshape(top_k * t, e)  # (K*T, E)
        pos = (jnp.cumsum(flat, axis=0) * flat).reshape(top_k, t, e)
        keep = (pos > 0) & (pos <= cap)
        slot = (pos - 1).astype(jnp.int32)
        dispatch_k = jax.nn.one_hot(slot, cap, dtype=x.dtype) * keep[
            ..., None].astype(x.dtype)                   # (K, T, E, C)
        dispatch = jnp.sum(dispatch_k, axis=0)           # (T, E, C)
        combine = jnp.einsum("ktec,tk->tec", dispatch_k,
                             gates.astype(x.dtype))

    xin = jnp.einsum("tec,td->ecd", dispatch, x)         # (E, C, D)

    # -- per-expert SwiGLU (batched over the local expert dim) -------------
    def expert_ffn(xe: Array) -> Array:
        g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe,
                                   params["w_gate"].astype(x.dtype)))
        u = jnp.einsum("ecd,edf->ecf", xe, params["w_up"].astype(x.dtype))
        return jnp.einsum("ecf,efd->ecd", g * u,
                          params["w_down"].astype(x.dtype))

    if axis is None:
        yout = expert_ffn(xin)
    else:
        # Both trips route through the ONE a2a executor (round 21): slot
        # j of the dispatch result = the buffer device j routed to my
        # experts; combine is the exact inverse trip.
        hop = _routing.Hop("a2a", _routing._A2A_AXIS, bits=dispatch_bits)
        chunks = min(a2a_chunks, cap)
        if chunks == 1:
            xin = _routing.execute_a2a(hop, xin, direction="dispatch",
                                       axis=axis)
            yout = expert_ffn(xin)
            yout = _routing.execute_a2a(hop, yout, direction="combine",
                                        axis=axis)
        else:
            # Capacity-chunked overlap: trace order is d0 f0 c0 d1 f1 c1
            # …, so chunk k's combine all-to-all sits strictly between
            # chunk k's and chunk k+1's expert matmuls — the async
            # window XLA hides the exchange in (inspector-pinned by
            # tests/test_a2a.py).
            bounds = [(k * cap) // chunks for k in range(chunks + 1)]
            parts = []
            for k in range(chunks):
                xk = _routing.execute_a2a(
                    hop, xin[:, bounds[k]:bounds[k + 1]],
                    direction="dispatch", axis=axis)
                parts.append(_routing.execute_a2a(
                    hop, expert_ffn(xk), direction="combine", axis=axis))
            yout = jnp.concatenate(parts, axis=1)

    out = jnp.einsum("tec,ecd->td", combine, yout)       # (T, D)
    return out, aux.astype(jnp.float32)



# ---------------------------------------------------------------------------
# The dropless path: sorted picks, grouped products over the experts held
# ---------------------------------------------------------------------------

# Rows of the buffer are laid out in whole tiles an expert: XLA's grouped
# product on the TPU walks the rows in tiles of 512 and visits a tile once
# for every expert with a row in it, so where a group starts inside a tile
# the layer's time follows the routing (read on the chip: 2.4% of a step
# between seeds).  Aligned, an expert of n rows costs ceil(n / 512) visits
# wherever it lies.
ROW_TILE = 512


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rows_of_picks(x, src, dest, k):
    """One row of ``x`` (T, D) for every buffer row: ``x[src // k]``, where
    ``src`` names the pick a row holds (nought for an empty row: the
    caller masks those).  Its backward is a gather too (through ``dest``,
    the row each pick went to), where autodiff would scatter-add T x k
    rows."""
    return x[src // k]


def _rows_of_picks_fwd(x, src, dest, k):
    return x[src // k], dest


def _rows_of_picks_bwd(k, dest, g):
    return g[dest].reshape(-1, k, g.shape[-1]).sum(1), None, None


_rows_of_picks.defvjp(_rows_of_picks_fwd, _rows_of_picks_bwd)


@jax.custom_vjp
def _rows_to_picks(ys, src, dest):
    """Buffer rows back in pick order, ``ys[dest]``; backward ``g[src]``."""
    return ys[dest]


_rows_to_picks.defvjp(lambda ys, src, dest: (ys[dest], src),
                      lambda src, g: (g[src], None, None))


def merge_stats(total: dict | None, layer: dict) -> dict:
    """The counters of several dropless layers as one set: rows and drops
    add up, the load figure is the worst layer's."""
    if total is None:
        return layer
    return {"rows_here": total["rows_here"] + layer["rows_here"],
            "dropped": total["dropped"] + layer["dropped"],
            "load_max_over_mean": jnp.maximum(total["load_max_over_mean"],
                                              layer["load_max_over_mean"])}


def moe_dropless_apply(
    params: PyTree,
    x: Array,                      # (T, D) the experts' input
    *,
    top_k: int,
    first_expert: int = 0,         # index of the first expert held here
    router_input: Array | None = None,   # (T, D) what the router reads
    act: str = "silu",             # the gate branch: 'silu' | 'relu'
    axis: str | None = None,
) -> tuple[Array, dict]:
    """The part of a routed layer's result that the experts held here give:
    ``(out (T, D), stats)``.

    ``params["router"]`` is (D, E) over all E published experts;
    ``params["w_gate" | "w_up" | "w_down"]`` stack the ``held`` experts
    ``first_expert .. first_expert + held - 1``.  Per token: the router's
    logits in float32, the ``top_k`` largest, a softmax over those k; each
    pick of an expert held here contributes ``weight * down(act(gate(x)) *
    up(x))``.  The picks of held experts are laid out by expert in a row
    buffer, each expert's rows from a tile boundary on (``ROW_TILE``), one
    input row is gathered per pick, and the three products run as
    ``lax.ragged_dot`` over the held experts' groups.  The buffer holds all
    ``T x k`` picks and a tile of padding an expert, the worst case, so no
    pick is cut however uneven the routing: with every token on one expert
    the result is still exact.  Empty rows and rows past the last group are
    masked on both sides of the products.

    ``stats``: ``rows_here`` (picks routed to held experts),
    ``load_max_over_mean`` (largest group over the mean group) and
    ``dropped`` (picks of held experts that reached no product: 0), float32
    scalars of this call.
    """
    if axis is not None:
        raise NotImplementedError(
            f"moe_dropless_apply over the mesh axis {axis!r}: the dropless "
            f"path has no exchange yet (one chip's share only); use "
            f"moe_apply for expert parallelism over an axis")
    if act not in ("silu", "relu"):
        raise ValueError(f"act must be 'silu' or 'relu', got {act!r}")
    t, d = x.shape
    e = params["router"].shape[-1]
    held = params["w_gate"].shape[0]
    if not 1 <= top_k <= e:
        raise ValueError(f"top_k={top_k} of {e} experts")
    if first_expert < 0 or first_expert + held > e:
        raise ValueError(f"experts {first_expert}..{first_expert + held - 1} "
                         f"are not among the router's {e}")
    xr = x if router_input is None else router_input
    logits = jnp.dot(xr.astype(jnp.float32),
                     params["router"].astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)           # (T, E)
    top_logits, top_idx = lax.top_k(logits, top_k)              # (T, K)
    weights = jax.nn.softmax(top_logits, axis=-1)

    # -- a buffer row for every pick of a held expert, sorted by expert ----
    # each expert's rows start on a tile boundary; picks of experts held
    # elsewhere all go to the last row, in a tile no product reads
    local = top_idx - first_expert
    here = (local >= 0) & (local < held)
    group = jnp.where(here, local, held).reshape(t * top_k)
    sizes = jnp.bincount(group, length=held + 1)[:held].astype(jnp.int32)
    padded = (sizes + ROW_TILE - 1) // ROW_TILE * ROW_TILE
    n_rows = t * top_k + (held + 1) * ROW_TILE      # the worst case
    shift = jnp.cumsum(padded) - padded - (jnp.cumsum(sizes) - sizes)
    rank = jnp.argsort(jnp.argsort(group, stable=True))  # place when sorted
    dest = jnp.where(here.reshape(-1),
                     rank + jnp.append(shift, 0)[group], n_rows - 1)
    src = jnp.full((n_rows,), -1, jnp.int32).at[dest].set(
        jnp.arange(t * top_k, dtype=jnp.int32))
    live = ((src >= 0) & (jnp.arange(n_rows) < jnp.sum(padded)))[:, None]
    src = jnp.maximum(src, 0)
    rows_here = jnp.sum(sizes)

    # -- the held experts over their rows ----------------------------------
    dt = x.dtype
    xs = jnp.where(live, _rows_of_picks(x, src, dest, top_k), 0)
    gate = lax.ragged_dot(xs, params["w_gate"].astype(dt), padded)
    gate = jax.nn.relu(gate) if act == "relu" else jax.nn.silu(gate)
    up = lax.ragged_dot(xs, params["w_up"].astype(dt), padded)
    ys = lax.ragged_dot(gate * up, params["w_down"].astype(dt), padded)
    ys = jnp.where(live, ys, 0)

    # -- weight and add each token's picks ---------------------------------
    w = jnp.where(here, weights, 0.0).astype(dt)
    out = jnp.sum(_rows_to_picks(ys, src, dest).reshape(t, top_k, d)
                  * w[..., None], axis=1)
    reached = jnp.sum(live[dest, 0] & here.reshape(-1))
    mean = jnp.maximum(rows_here, 1) / held
    stats = {"rows_here": rows_here, "dropped": rows_here - reached,
             "load_max_over_mean": jnp.max(sizes) / mean}
    return out, {k: v.astype(jnp.float32) for k, v in stats.items()}
