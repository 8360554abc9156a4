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
refuses: the exchange is not written yet).  **What its row buffer costs:**
memory for the worst case (every pick held here, a tile of padding an
expert), time for what arrived: each expert's rows fill whole tiles from the
buffer's start, and everything beside the three products -- the rows
gathered in, the gate, the rows summed back per token, and the transposes of
all three in the backward -- runs in loops over the live tiles whose trip
counts are read on the device, as XLA's grouped products skip the tiles past
the last group (``moe.live_tile_share`` says what part of the buffer a step
worked over).

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
from ..utils import compat

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
# The buffer is allocated for the worst case and worked over its live part:
# every loop over its rows takes CHUNK of them a trip (whole tiles) and makes
# only the trips that hold a row of this step's routing.
CHUNK = ROW_TILE


def _over_live(f, n_live, *rows):
    """``f(*chunks) -> tuple of (CHUNK, ...)`` over the chunks of ``rows``
    (arrays whose leading dimension is the buffer's) that hold a live row,
    written into buffers of the buffer's length.  The trip count is read on
    the device; what lies past the last trip is never written
    (``lax.empty``: on the TPU no fill), so a consumer reads live chunks
    only, as XLA's grouped products do."""
    n_rows = rows[0].shape[0]
    outs = jax.eval_shape(lambda: f(*(r[:CHUNK] for r in rows)))
    init = tuple(compat.varying(lax.empty((n_rows,) + o.shape[1:], o.dtype),
                                (o.vma or frozenset()) | compat.vma_of(n_live))
                 for o in outs)

    def body(i, bufs):
        lo = i * CHUNK
        new = f(*(lax.dynamic_slice_in_dim(r, lo, CHUNK) for r in rows))
        return tuple(lax.dynamic_update_slice_in_dim(b, o, lo, 0)
                     for b, o in zip(bufs, new))

    return lax.fori_loop(0, (n_live + CHUNK - 1) // CHUNK, body, init)


def _rows(a, tok):
    """Row ``tok[r]`` of ``a`` for each r, nought where ``tok[r] < 0``."""
    return jnp.where((tok >= 0)[:, None], a[jnp.maximum(tok, 0)], 0)


def _token(pick, lay):
    """The token whose pick a buffer row holds, -1 for an empty row."""
    return jnp.where(pick >= 0, pick // lay["dest"].shape[1], -1)


def _collect(bufs, lay):
    """``(T, D)``: for each token the sum, over its picks of held experts,
    of the picks' rows of ``bufs`` (buffers, added), in float32.  The rows
    read follow the picks that arrived: the tokens are ordered by how many
    of their picks are held (``_layout``), so that "the r-th held pick"
    exists for a prefix of them, and for r = 0 .. k-1 that prefix alone is
    gathered, a chunk of tokens a trip of one loop; then the T sums go back
    to token order.  (A scatter-add of each live tile into its tokens'
    rows, sorted and unique as they are, reads fewer rows and was fifty
    times slower on the chip: PERF.md, PR 29.)"""
    front, trips = lay["front"], lay["trips"]
    n_tok = front.shape[0] // trips.shape[0]
    ends = jnp.cumsum(trips)

    def add(i, acc):
        r = jnp.sum(i >= ends)
        # unsigned, so that the slices' starts are seen to be whole chunks
        # (a signed start is wrapped first, and the update is then no
        # longer done in place)
        lo = (i - (ends[r] - trips[r])).astype(jnp.uint32) * CHUNK
        ids = lax.dynamic_slice_in_dim(
            front, r.astype(jnp.uint32) * n_tok + lo, CHUNK)
        rows = sum(_rows(b, ids).astype(jnp.float32) for b in bufs)
        return lax.dynamic_update_slice_in_dim(
            acc, lax.dynamic_slice_in_dim(acc, lo, CHUNK) + rows, lo, 0)

    acc = compat.varying(
        jnp.zeros((n_tok, bufs[0].shape[1]), jnp.float32),
        frozenset().union(*map(compat.vma_of, (front, *bufs))))
    acc = lax.fori_loop(0, ends[-1], add, acc)
    return acc[lay["back"]].astype(bufs[0].dtype)


@jax.custom_vjp
def _expand(x, w_gate, w_up, lay):
    """The gate and up products of every live buffer row: the row's token
    gathered from ``x`` (T, D), then ``lax.ragged_dot`` over the held
    experts' groups.  The backward is written by hand: the two products'
    transposes, and the rows' cotangents summed back per token by
    ``_collect`` (no (rows, D) sum of the two, no scatter of T x k rows)."""
    return _expand_fwd(x, w_gate, w_up, lay)[0]


@jax.jit
def _expand_fwd(x, w_gate, w_up, lay):
    xs, = _over_live(lambda pick: (_rows(x, _token(pick, lay)),),
                     lay["n_live"], lay["pick"])
    gate = lax.ragged_dot(xs, w_gate, lay["padded"])
    up = lax.ragged_dot(xs, w_up, lay["padded"])
    return (gate, up), (x[:0], xs, w_gate, w_up, lay)


@jax.jit
def _expand_bwd(res, cts):
    x0, xs, w_gate, w_up, lay = res
    dxs, dws = [], []
    for w, g in zip((w_gate, w_up), cts):
        dxs.append(jax.linear_transpose(
            lambda a, w=w: lax.ragged_dot(a, w, lay["padded"]), xs)(g)[0])
        dws.append(jax.linear_transpose(
            lambda b: lax.ragged_dot(xs, b, lay["padded"]), w)(g)[0])
    return (*map(compat.as_cotangent, (_collect(dxs, lay), *dws),
                 (x0, w_gate, w_up)), None)


_expand.defvjp(_expand_fwd, _expand_bwd)


def _weighted(act, dt):
    """``act(gate) * up`` of a chunk's rows, each times its pick's weight
    (float32), in the compute type."""
    def f(gate, up, w_row):
        gate = jax.nn.relu(gate) if act == "relu" else jax.nn.silu(gate)
        return ((gate * up).astype(jnp.float32) * w_row[:, None]).astype(dt)
    return f


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _contract(gate, up, w, w_down, lay, act):
    """``(T, D)``: every live row's ``act(gate) * up`` times the weight
    ``w`` (T, k) of the pick it holds, through the down product
    (``lax.ragged_dot``), summed per token (``_collect``).  The weight goes
    in before the product, which is linear in its rows, so the sum after it
    is plain and neither the product's result nor its input is kept: the
    backward gathers the tokens' cotangents into the live rows, makes the
    product's input again, and gives a pick's weight its row's dot product
    over the experts' width."""
    return _contract_fwd(gate, up, w, w_down, lay, act)[0]


@functools.partial(jax.jit, static_argnums=(5,))
def _contract_fwd(gate, up, w, w_down, lay, act):
    def hidden(gate, up, pick):
        w_row = jnp.where(pick >= 0, w.reshape(-1)[jnp.maximum(pick, 0)], 0)
        return _weighted(act, gate.dtype)(gate, up, w_row), w_row

    h, w_row = _over_live(hidden, lay["n_live"], gate, up, lay["pick"])
    ys = lax.ragged_dot(h, w_down, lay["padded"])
    return _collect([ys], lay), (gate, up, w_row, w[:0], w_down, lay)


@functools.partial(jax.jit, static_argnums=(0,))
def _contract_bwd(act, res, g):
    gate, up, w_row, w0, w_down, lay = res
    weighted = _weighted(act, gate.dtype)
    dys, h = _over_live(
        lambda pick, *rows: (_rows(g, _token(pick, lay)), weighted(*rows)),
        lay["n_live"], lay["pick"], gate, up, w_row)
    dh, = jax.linear_transpose(
        lambda a: lax.ragged_dot(a, w_down, lay["padded"]), h)(dys)
    dw_down, = jax.linear_transpose(
        lambda b: lax.ragged_dot(h, b, lay["padded"]), w_down)(dys)
    dgate, dup, dw_row = _over_live(
        lambda g, u, w_row, dh: jax.vjp(weighted, g, u, w_row)[1](dh),
        lay["n_live"], gate, up, w_row, dh)
    dw = jnp.where(lay["dest"] >= 0, dw_row[jnp.maximum(lay["dest"], 0)], 0)
    return *map(compat.as_cotangent, (dgate, dup, dw, dw_down),
                (gate, up, w0, w_down)), None


_contract.defvjp(_contract_fwd, _contract_bwd)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _layout(top_idx, first_expert, held):
    """A buffer row for every pick of a held expert, sorted by expert, each
    expert's rows from a tile boundary on, so that the live rows are the
    buffer's first tiles; the buffer could hold every pick.  Returns the
    layout (``pick``: the pick a row holds or -1; ``dest``: the row of a
    pick (T, k) or -1; ``padded``: the experts' rows in whole tiles;
    ``n_live``: their sum; ``front``, ``trips``, ``back``: the order in
    which ``_collect`` reads), which picks are held here, and the experts'
    rows."""
    t, top_k = top_idx.shape
    local = top_idx - first_expert
    here = (local >= 0) & (local < held)
    group = jnp.where(here, local, held).reshape(t * top_k)
    one_hot = group[:, None] == jnp.arange(held)     # no scatter, no gather
    sizes = jnp.sum(one_hot, axis=0, dtype=jnp.int32)
    padded = (sizes + ROW_TILE - 1) // ROW_TILE * ROW_TILE
    n_rows = -(-(t * top_k + held * ROW_TILE) // CHUNK) * CHUNK  # worst case
    shift = jnp.cumsum(padded) - padded - (jnp.cumsum(sizes) - sizes)
    rank = jnp.argsort(jnp.argsort(group, stable=True))  # place when sorted
    dest = jnp.where(here.reshape(-1),
                     rank + jnp.sum(jnp.where(one_hot, shift, 0), axis=1),
                     n_rows)
    pick = jnp.full((n_rows,), -1, jnp.int32).at[dest].set(
        jnp.arange(t * top_k, dtype=jnp.int32), mode="drop")
    dest = jnp.where(here, dest.reshape(t, top_k), -1)
    # for ``_collect``: the tokens ordered by how many of their picks are
    # held, each token's held picks moved to the front of its k places
    count = jnp.sum(here, axis=1)
    place = here[:, :, None] & (
        (jnp.cumsum(here, axis=1) - 1)[:, :, None] == jnp.arange(top_k))
    front = jnp.where(jnp.arange(top_k) < count[:, None],
                      jnp.sum(jnp.where(place, dest[:, :, None], 0), axis=1),
                      -1)
    order = jnp.argsort(-count, stable=True)        # most held picks first
    n_tok = -(-t // CHUNK) * CHUNK
    lay = {"padded": padded, "n_live": jnp.sum(padded), "pick": pick,
           "dest": dest,
           # (k x tokens,): the row of each token's r-th held pick, or -1
           "front": jnp.pad(front[order], ((0, n_tok - t), (0, 0)),
                            constant_values=-1).T.reshape(-1),
           # chunks of tokens that have an r-th held pick, r = 0 .. k-1
           "trips": (jnp.sum(count[:, None] > jnp.arange(top_k), axis=0)
                     + CHUNK - 1) // CHUNK,
           "back": jnp.zeros((t,), jnp.int32).at[order].set(
               jnp.arange(t, dtype=jnp.int32))}
    return lay, here, sizes


# the counters of one dropless layer, in the order ``lm`` reports them
STATS = ("rows_here", "load_max_over_mean", "dropped", "live_tile_share")


def merge_stats(layers: list) -> dict:
    """The counters of a model's layers as one set, each over the layers
    that report it (a leading dense layer reports none of the routed
    ones): rows and drops add up, the load figure is the worst layer's, and
    the share of the buffer that was worked over, the picks' summed scores
    and the gates are the layers' means; of the linear layers' counters the
    largest state is the worst layer's, decay and beta the layers' means."""
    names = dict.fromkeys(k for s in layers for k in s)
    each = {k: jnp.stack([s[k] for s in layers if k in s]) for k in names}
    how = {"rows_here": jnp.sum, "dropped": jnp.sum,
           "load_max_over_mean": jnp.max, "state_norm_max": jnp.max}
    return {k: how.get(k, jnp.mean)(v) for k, v in each.items()}


def moe_dropless_apply(
    params: PyTree,
    x: Array,                      # (T, D) the experts' input
    *,
    top_k: int,
    first_expert: int = 0,         # index of the first expert held here
    router_input: Array | None = None,   # (T, D) what the router reads
    act: str = "silu",             # the gate branch: 'silu' | 'relu'
    scoring: str = "softmax",      # the picks' weights: 'softmax' | 'sigmoid'
    score_scale: float = 1.0,      # 'sigmoid': times the normalised scores
    axis: str | None = None,
) -> tuple[Array, dict]:
    """The part of a routed layer's result that the experts held here give:
    ``(out (T, D), stats)``.

    ``params["router"]`` is (D, E) over all E published experts;
    ``params["w_gate" | "w_up" | "w_down"]`` stack the ``held`` experts
    ``first_expert .. first_expert + held - 1``.  Per token: the router's
    logits in float32, the ``top_k`` largest, a softmax over those k (or,
    with ``scoring="sigmoid"``, the ``top_k`` largest of sigmoid(logits),
    each over their sum and times ``score_scale``); each
    pick of an expert held here contributes ``weight * down(act(gate(x)) *
    up(x))``.  The picks of held experts are laid out by expert in a row
    buffer, each expert's rows from a tile boundary on (``ROW_TILE``), so
    the live rows are the buffer's first ``sum(padded) / ROW_TILE`` tiles;
    one input row is gathered per pick, and the three products run as
    ``lax.ragged_dot`` over the held experts' groups.  The buffer is
    *allocated* for the worst case (all ``T x k`` picks and a tile of
    padding an expert), so no pick is cut however uneven the routing: with
    every token on one expert the result is still exact.  It is *worked
    over* its live tiles alone, forward and backward (``_over_live``,
    ``_collect``): what lies past them is never written nor read, and a
    step under even routing over a quarter of the experts costs a quarter
    of the row traffic.  A pick's weight multiplies its row before the down
    product (linear in its rows), the sum over a token's picks is in
    float32.

    ``stats``: ``rows_here`` (picks routed to held experts),
    ``load_max_over_mean`` (largest group over the mean group), ``dropped``
    (picks of held experts that no live row holds: 0) and
    ``live_tile_share`` (the tiles the loops worked over / the buffer's)
    and, with sigmoid scoring, ``score_sum_mean`` (the picks' scores summed
    a token, before normalisation, the tokens' mean), float32 scalars of
    this call.
    """
    if axis is not None:
        raise NotImplementedError(
            f"moe_dropless_apply over the mesh axis {axis!r}: the dropless "
            f"path has no exchange yet (one chip's share only); use "
            f"moe_apply for expert parallelism over an axis")
    if act not in ("silu", "relu"):
        raise ValueError(f"act must be 'silu' or 'relu', got {act!r}")
    if scoring not in ("softmax", "sigmoid"):
        raise ValueError(f"scoring must be 'softmax' or 'sigmoid', got "
                         f"{scoring!r}")
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
    # the picks' scores read back through a one-hot select, whose backward
    # is a select too (top_k's own is a scatter into (T, E))
    scores = jax.nn.sigmoid(logits) if scoring == "sigmoid" else logits
    _, top_idx = lax.top_k(lax.stop_gradient(scores), top_k)    # (T, K)
    top_scores = jnp.sum(jnp.where(top_idx[..., None] == jnp.arange(e),
                                   scores[:, None, :], 0), axis=-1)
    if scoring == "sigmoid":
        score_sum = jnp.sum(top_scores, axis=-1, keepdims=True)
        weights = score_scale * top_scores / score_sum
    else:
        weights = jax.nn.softmax(top_scores, axis=-1)

    lay, here, sizes = _layout(top_idx, first_expert, held)
    n_rows, n_live = lay["pick"].shape[0], lay["n_live"]
    rows_here = jnp.sum(sizes)

    # -- the held experts over their rows ----------------------------------
    dt = x.dtype
    gate, up = _expand(x, params["w_gate"].astype(dt),
                       params["w_up"].astype(dt), lay)
    out = _contract(gate, up, jnp.where(here, weights, 0.0),
                    params["w_down"].astype(dt), lay, act)
    # a pick reaches the products if a row of the live part holds it
    reached = jnp.sum((lay["pick"] >= 0) & (jnp.arange(n_rows) < n_live))
    mean = jnp.maximum(rows_here, 1) / held
    stats = {"rows_here": rows_here, "dropped": rows_here - reached,
             "load_max_over_mean": jnp.max(sizes) / mean,
             "live_tile_share": (n_live + CHUNK - 1) // CHUNK * CHUNK / n_rows}
    if scoring == "sigmoid":
        stats["score_sum_mean"] = jnp.mean(score_sum)
    return out, {k: v.astype(jnp.float32) for k, v in stats.items()}
