"""Pipeline parallelism: microbatch pipelining over a mesh axis.

The reference's parallelism inventory is data-parallel only (SURVEY.md
section 5); this module adds the pipeline axis for models whose layer stack
does not fit one chip.  Design (the JAX SPMD formulation, not a scheduler
thread per stage):

- the transformer's L identical blocks are split into ``n * v`` logical
  chunks (n = axis_size(pipe) devices, v = ``interleave`` virtual stages
  per device); chunk ``j`` lives on device ``j % n``, so each device holds
  v round-robin chunks of L/(n*v) layers — Megatron's interleaved stage
  placement;
- a ``lax.scan`` runs a circular **wave** schedule: microbatches are
  admitted in waves of n, one per tick; a microbatch hops device
  s -> s+1 -> ... -> n-1 -> 0 -> ... around the ring v times (one
  ``lax.ppermute`` per tick), visiting chunks in order.  Within a wave
  each device is busy every tick with exactly one (chunk, microbatch) —
  lockstep-collision-free — and wave w+1 starts the tick device 0 frees
  up, so steady state has zero idle ticks;
- the fill/drain bubble is (n-1)/(v*M + n-1) in chunk-ticks — the v-fold
  bubble reduction of interleaved scheduling, here in a forward-only scan
  (``interleave=1`` degenerates to the classic GPipe schedule);
- ticks outside a device's valid window compute on zeros and are masked
  out of the loss;
- the backward schedule is NOT hand-written: ``jax.grad`` through the scan
  and ppermute yields the reverse pipeline (ppermute's transpose reverses
  the ring), with ``jax.checkpoint`` on the chunk body for activation
  remat;
- pp composes with tensor parallelism: chunk layer weights additionally
  carry the Megatron head/FFN sharding over ``tp_axis`` and the block's two
  psums run inside every chunk (mesh (data, pipe, seq, model)); with
  sequence parallelism (ring attention inside chunks over 'seq'); and with
  uniformly-MoE stacks (moe_every=1 — every layer MoE, so chunk params
  stack homogeneously; per-(chunk, microbatch) aux accumulates through the
  ticks).

Schedule index math (device s, tick t, N = n*v):
  rel = t - s                      # ticks since the wavefront passed s
  w   = rel // N                   # wave index
  k   = (rel mod N) // n           # which of my v chunks is active
  m   = w*n + (rel mod n)          # microbatch index
  active iff rel >= 0 and m < M.  Chunk ``k*n + s`` receives from chunk
  ``k*n + s - 1``, which processed the same microbatch on the previous
  device at tick t-1 — so one ring hop per tick moves every in-flight
  microbatch forward one chunk.  Device 0 at k == 0 injects the fresh
  microbatch embedding instead; device n-1 at k == v-1 finishes
  microbatch m.

Embedding/unembedding weights are replicated to every stage (cheap at these
scales) so first/last-stage special-casing is a mask, not a branch.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from ..models import transformer as tfm
from ..utils.compat import opt_barrier, pcast, vma_of

PyTree = Any


def _uniform_moe(cfg: tfm.TransformerConfig) -> bool:
    """True when EVERY layer is an MoE layer (moe_every == 1): the one MoE
    shape whose layer params stack homogeneously into pipeline chunks."""
    return bool(cfg.n_experts) and all(
        cfg.is_moe_layer(i) for i in range(cfg.n_layers))


def split_layer_params(params: PyTree, cfg: tfm.TransformerConfig,
                       n_stages: int, interleave: int = 1):
    """Re-pack per-layer params into device-stacked chunk leaves.

    Returns ``(stage_params, shared)`` where each ``stage_params`` leaf has
    shape (n_stages, interleave, layers_per_chunk, *leaf) — shard its
    leading dim over 'pipe' — and ``shared`` holds embed/final_norm
    (replicated everywhere).  Logical chunk ``j`` (contiguous layers) lands
    at [j % n_stages, j // n_stages] (round-robin interleaved placement).

    MoE models pipeline iff the stack is uniform (``moe_every == 1``, every
    layer MoE): a dense/MoE-alternating stack has heterogeneous layer
    params that cannot stack into one scanned chunk body.
    """
    if cfg.n_experts and not _uniform_moe(cfg):
        raise ValueError(
            "pipeline parallelism requires a homogeneous layer stack: "
            "dense models, or uniformly-MoE models (moe_every=1).  A "
            "dense/MoE-alternating stack (moe_every > 1) cannot stack "
            "into pipeline chunks")
    n_chunks = n_stages * interleave
    if cfg.n_layers % n_chunks:
        raise ValueError(
            f"{cfg.n_layers} layers do not split into {n_stages} stages "
            f"x {interleave} virtual stages")
    per = cfg.n_layers // n_chunks
    layers = [params[f"layer{i}"] for i in range(cfg.n_layers)]
    stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *layers)
    # (L, ...) -> (v, n, per, ...) [chunk j = k*n + s] -> (n, v, per, ...)
    stage_params = jax.tree.map(
        lambda x: jnp.moveaxis(
            x.reshape((interleave, n_stages, per) + x.shape[1:]), 0, 1),
        stacked)
    shared = {"embed": params["embed"], "final_norm": params["final_norm"]}
    return stage_params, shared


def merge_layer_params(stage_params: PyTree, shared: PyTree,
                       cfg: tfm.TransformerConfig) -> PyTree:
    """Inverse of split_layer_params (for checkpoint export/tests)."""
    flat = jax.tree.map(
        lambda x: jnp.moveaxis(x, 0, 1).reshape((-1,) + x.shape[3:]),
        stage_params)
    params = {"embed": shared["embed"], "final_norm": shared["final_norm"]}
    for i in range(cfg.n_layers):
        params[f"layer{i}"] = jax.tree.map(lambda x: x[i], flat)
    return params


def stage_specs(cfg: tfm.TransformerConfig, n_stages: int,
                tp_axis: str | None = None,
                interleave: int = 1) -> PyTree:
    """The spec tree matching split_layer_params' stage output: leading
    device dim over 'pipe'; with ``tp_axis``, each leaf's trailing dims also
    carry the Megatron head/FFN sharding (models/transformer.shard_specs),
    shifted right past the three stacking dims (device, virtual stage,
    layer-in-chunk)."""
    from jax.sharding import PartitionSpec as P

    stages_shape = jax.eval_shape(
        lambda k: split_layer_params(tfm.init(k, cfg), cfg, n_stages,
                                     interleave)[0],
        jax.random.key(0))
    if tp_axis is None:
        return jax.tree.map(lambda _: P("pipe"), stages_shape)
    layer_tp = tfm.shard_specs(cfg, tp_axis=tp_axis)["layer0"]
    return jax.tree.map(lambda spec, _: P("pipe", None, None, *spec),
                        layer_tp, stages_shape)


def _chunk(chunk_layers: PyTree, x: jax.Array,
           cfg: tfm.TransformerConfig, attn_impl: str,
           tp_axis: str | None = None,
           seq_axis: str | None = None,
           seq_layout: str = "contiguous",
           pos: jax.Array | None = None,
           is_moe: bool = False) -> tuple[jax.Array, jax.Array]:
    """Run one chunk's layers_per_chunk blocks (a homogeneous layer scan
    over the shared models/transformer.py:block body); returns (x, summed
    MoE aux).  With ``seq_axis`` the activations are sequence shards and
    each block's attention is the ring over that axis (pp x sp
    composition); ``pos`` is then the shard's absolute token positions.
    ``is_moe`` applies to every layer (uniform stacks only — see
    split_layer_params)."""
    if pos is None:
        pos = jnp.arange(x.shape[1])

    def body(carry, lp):
        # Fusion barrier at the body boundary (both passes — compat's
        # opt_barrier also barriers the cotangent): a rolled scan body is
        # a fusion unit by construction (the while-loop boundary), but XLA
        # UNROLLS trip-count-1 scans and then fuses the body with its
        # neighbours, perturbing f32 reduction vectorization sub-ulp — a
        # 1-layer pipeline chunk would train measurably ≠ the same layer
        # inside a longer chunk (found by the round-10 bitwise pins: every
        # per>=2 split exact, every per=1 split off by ~1e-10).  The
        # explicit barrier pins the body's compilation boundary at every
        # trip count; rolled splits (>= 2 layers per chunk) are bitwise ==
        # monolithic, and 1-layer chunks keep a residual ~1e-10 drift from
        # the reverse-scan residual layouts the barrier cannot reach —
        # the bitwise pins run per >= 2, the per=1 corner pins allclose.
        x, aux_acc = opt_barrier(carry)
        x, aux = tfm.block(lp, x, cfg=cfg, is_moe=is_moe, pos=pos,
                           attn_impl=attn_impl, tp_axis=tp_axis,
                           seq_axis=seq_axis, seq_layout=seq_layout)
        return opt_barrier((x, aux_acc + aux)), None

    # aux carry starts with x's vma so the scan carry types are stable
    aux0 = jnp.zeros((), jnp.float32)
    missing = tuple(a for a in vma_of(x) if a not in vma_of(aux0))
    if missing:
        aux0 = pcast(aux0, missing, to="varying")
    (x, aux), _ = lax.scan(body, (x, aux0), chunk_layers)
    return x, aux


def num_ticks(m_micro: int, n: int, interleave: int) -> int:
    """Scan length of the wave schedule: the tick after microbatch M-1
    (wave ceil(M/n)-1, in-wave slot (M-1)%n) clears the last chunk of
    device n-1."""
    waves = -(-m_micro // n)
    big_n = n * interleave
    return ((waves - 1) * big_n + (interleave - 1) * n
            + ((m_micro - 1) % n) + n)


def pipeline_loss(
    stage_params: PyTree,
    shared: PyTree,
    tokens: jax.Array,     # (M, mb, S) microbatched token ids
    targets: jax.Array,    # (M, mb, S) next-token targets (IGNORE = pad)
    *,
    cfg: tfm.TransformerConfig,
    axis: str = "pipe",
    dtype: jnp.dtype | None = None,
    attn_impl: str = "flash",
    tp_axis: str | None = None,
    seq_axis: str | None = None,
    seq_layout: str = "contiguous",
    pos: jax.Array | None = None,
    interleave: int = 1,
    remat_block_ticks: int | None = 0,
    loss_impl: str = "dense",
    loss_chunk: int | None = None,
) -> jax.Array:
    """Mean masked CE over all microbatches, computed through the pipeline.

    Runs inside shard_map with ``stage_params`` leaves carrying this
    device's (1, interleave, layers_per_chunk, ...) slice.  Returns
    ``(ce_sum, n_valid, aux_sum)``: the loss summed over this shard's
    tokens, the valid-token count, and this pipe rank's summed MoE aux
    over its chunks and all microbatches (0.0 for dense stacks) — the
    caller psums ce/n across data/pipe/seq, psums aux over 'pipe' (layers
    are split across ranks) and means it over microbatches and data/seq.

    With ``seq_axis`` (pp x sp), ``tokens``/``targets`` are sequence
    shards: every microbatch's activations stay seq-sharded through the
    pipeline hops, and each chunk's attention is the ring over
    ``seq_axis``.  The ring's collectives run inside the tick, so pipeline
    (pipe-axis ppermute) and ring (seq-axis ppermute) traffic interleave
    tick by tick.  ``pos`` is this seq shard's absolute positions.

    ``loss_impl``/``loss_chunk`` route the finishing tick's unembed
    through the unified head-loss seam (ops/losses.py head_loss):
    "dense" traces the historical logits matmul + masked_ce bit-for-bit,
    "chunked" streams the head over vocab chunks (full vocab per rank —
    the wave head does not vocab-shard over tp).
    """
    from ..ops.losses import head_loss

    me = lax.axis_index(axis)
    n = lax.axis_size(axis)
    v = interleave
    big_n = n * v
    local = jax.tree.map(lambda x: x[0], stage_params)  # (v, per, ...)
    m_micro, mb, s = tokens.shape

    # Embed all microbatches (replicated embed; masked-out ticks feed zeros).
    x_all = shared["embed"][tokens]  # (M, mb, S, D)
    if dtype is not None:
        x_all = x_all.astype(dtype)

    chunk_fn = jax.checkpoint(partial(_chunk, cfg=cfg, attn_impl=attn_impl,
                                      tp_axis=tp_axis, seq_axis=seq_axis,
                                      seq_layout=seq_layout, pos=pos,
                                      is_moe=_uniform_moe(cfg)))
    perm = [(i, (i + 1) % n) for i in range(n)]  # ring: chunk k*n+s -> +1

    # Scan carries must be varying over every axis their updates vary over:
    # the pipe axis (stage params) plus whatever the inputs carry (e.g. a
    # 'data' axis when composed with DP).
    want_vma = vma_of(x_all) | {axis}

    def _varying(x):
        missing = tuple(a for a in want_vma if a not in vma_of(x))
        return pcast(x, missing, to="varying") if missing else x

    zero_x = _varying(jnp.zeros((mb, s, x_all.shape[-1]), x_all.dtype))

    def tick(carry, t):
        prev_out, ce_acc, n_acc, aux_acc = carry
        # Activation arriving from the previous device's chunk (one ring
        # hop per tick); device 0's first chunk takes the fresh microbatch
        # embedding instead.
        recv = lax.ppermute(prev_out, axis, perm)
        rel = t - me
        w = rel // big_n                   # wave (floor: negative pre-fill)
        k = (rel % big_n) // n             # active virtual stage (>= 0)
        m = w * n + (rel % n)              # microbatch index
        valid = (rel >= 0) & (m >= 0) & (m < m_micro)
        chunk_layers = jax.tree.map(
            lambda x: lax.dynamic_index_in_dim(x, jnp.clip(k, 0, v - 1), 0,
                                               keepdims=False), local)
        m_in = jnp.clip(m, 0, m_micro - 1)
        fresh = lax.dynamic_index_in_dim(x_all, m_in, 0, keepdims=False)
        x_in = jnp.where((me == 0) & (k == 0), fresh, recv)
        out, aux = chunk_fn(chunk_layers, x_in)
        # every (chunk, microbatch) pair contributes its layers' aux once
        aux_acc = aux_acc + jnp.where(valid, aux, 0.0)
        # Last logical chunk (device n-1, slot v-1) finishes microbatch m:
        # unembed + masked CE.
        finish = (me == n - 1) & (k == v - 1) & valid
        h = tfm.rms_norm(out, shared["final_norm"], cfg.norm_eps)
        tgt = lax.dynamic_index_in_dim(targets, m_in, 0, keepdims=False)
        ce, cnt = head_loss(h, shared["embed"], tgt,
                            loss_impl=loss_impl, loss_chunk=loss_chunk)
        ce_acc = ce_acc + jnp.where(finish, ce, 0.0)
        n_acc = n_acc + jnp.where(finish, cnt, 0)
        return (out, ce_acc, n_acc, aux_acc), None

    ce0 = _varying(jnp.zeros(()))
    n0 = _varying(jnp.zeros((), jnp.int32))
    aux0 = _varying(jnp.zeros(()))

    # -- O(pp * mb) activation memory: block-remat over the tick scan ------
    # A flat scan of T ticks saves one (mb, S, D) carry per tick for the
    # backward: O(T) = O(M*v) live activations — the O(num_ticks) wall.
    # Nesting the scan (outer over blocks of ``remat_block_ticks`` ticks,
    # inner scan checkpointed) makes the backward keep only the T/block
    # block-boundary carries and rematerialize one block at a time, so peak
    # live activations are O(M*v/n + n) microbatch-sized buffers — for the
    # standard M = O(n) microbatch regime, O(pp * mb) in all.  The
    # price is one extra tick-forward per backward (the usual remat trade;
    # the per-chunk jax.checkpoint above keeps the within-block recompute
    # itself lean).  remat_block_ticks: 0 = auto (one wave, n ticks);
    # None = flat scan (the O(T) layout, kept for A/B memory tests).
    ticks = num_ticks(m_micro, n, v)
    if remat_block_ticks is None:
        (_, ce_sum, n_sum, aux_sum), _ = lax.scan(
            tick, (zero_x, ce0, n0, aux0), jnp.arange(ticks))
        return ce_sum, n_sum, aux_sum
    block = remat_block_ticks or n
    # Padded tail ticks still run a full (masked-out) chunk forward — they
    # are no-ops for the loss, not for compute.  The auto block (n) wastes
    # at most n-1 ticks; an explicit oversized block wastes up to block-1.
    t_pad = -(-ticks // block) * block

    @partial(jax.checkpoint, prevent_cse=False)
    def tick_block(carry, ts):
        carry, _ = lax.scan(tick, carry, ts)
        return carry, None

    (_, ce_sum, n_sum, aux_sum), _ = lax.scan(
        tick_block, (zero_x, ce0, n0, aux0),
        jnp.arange(t_pad).reshape(t_pad // block, block))
    return ce_sum, n_sum, aux_sum
