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


# ---------------------------------------------------------------------------
# Interleaved-1F1B over the 'pp' mesh axis (round 10).
#
# The wave schedule above is the forward-only SPMD formulation (one scanned
# tick body, backward synthesized by autodiff).  The 1F1B machinery below is
# its training-schedule sibling for lm.py's ``pp_size``: the transformer's
# layer GROUPS (models/transformer.sync_group_index — the same boundary
# schedule that places the streaming ZeRO-3 gathers and DCN sync points)
# are partitioned into ``pp_size * interleave`` contiguous chunks, chunk j
# living on physical stage j % pp_size (Megatron's round-robin interleaved
# placement), and the train step EMITS each (chunk, microbatch) forward/
# backward unit in the order of an explicit one-forward-one-backward
# timetable, with the stage-boundary activation handoffs expressed as
# ppermute transfers over the 'pp' axis.  The timetable is data (a list of
# clocks), so the schedule the program was emitted in is directly
# measurable — utils/debug.py ``assert_pipeline_schedule`` checks 1F1B
# well-formedness and the fill/drain bubble against the analytic
# (pp-1)/(pp-1+M) bound, the same way the round-8/9 inspector pins
# collective interleaving.
#
# Unlike the wave schedule, the 1F1B step's backward is NOT synthesized by
# autodiff-through-the-scan: lm.py emits one explicit ``jax.vjp`` per
# (chunk, microbatch) backward unit in timetable order, with every
# cross-device reduction written out by hand.  That makes the schedule a
# first-class program property (the thing the inspector measures).
# ---------------------------------------------------------------------------


def one_f_one_b_schedule(n_micro: int, n_stages: int,
                         interleave: int = 1) -> list[dict]:
    """The interleaved-1F1B timetable: a list of clocks, each a dict
    ``{stage: (kind, chunk, microbatch)}`` with kind "F" or "B".

    Generated by a work-conserving greedy simulation of the classic
    policy — every stage runs, each clock, its earliest-microbatch READY
    backward if one exists (a backward is ready once its own forward and
    the downstream chunk's backward finished in an EARLIER clock), else
    its earliest ready forward.  For interleave=1 this reproduces the
    textbook 1F1B schedule exactly (warmup forwards, steady-state strict
    F/B alternation, backward drain) and meets the analytic bubble bound
    (pp-1)/(pp-1+M); with interleave > 1 the virtual chunks round-robin
    through the same policy.  Per chunk, backwards execute in ascending
    microbatch order — the property that makes the 1F1B reordering a
    pure reassociation of the grad-accumulation sum (lm.py's bitwise
    claim)."""
    if n_micro < 1:
        raise ValueError(f"need >= 1 microbatch, got {n_micro}")
    n_chunks = n_stages * interleave
    done_f: dict[tuple[int, int], int] = {}   # (chunk, micro) -> clock
    done_b: dict[tuple[int, int], int] = {}
    next_f = [0] * n_chunks
    next_b = [0] * n_chunks
    clocks: list[dict] = []
    total = 2 * n_micro * n_chunks
    while len(done_f) + len(done_b) < total:
        clock: dict[int, tuple] = {}
        for s in range(n_stages):
            op = None
            cand_b = []
            for k in range(interleave):
                c = k * n_stages + s
                m = next_b[c]
                if (m < n_micro and (c, m) in done_f
                        and (c == n_chunks - 1 or (c + 1, m) in done_b)):
                    cand_b.append((m, -c))
            if cand_b:
                m, neg_c = min(cand_b)
                op = ("B", -neg_c, m)
            else:
                cand_f = []
                for k in range(interleave):
                    c = k * n_stages + s
                    m = next_f[c]
                    if m < n_micro and (c == 0 or (c - 1, m) in done_f):
                        cand_f.append((m, c))
                if cand_f:
                    m, c = min(cand_f)
                    op = ("F", c, m)
            if op is not None:
                clock[s] = op
        if not clock:  # pragma: no cover - a policy bug, not a data case
            raise AssertionError(
                f"1F1B schedule deadlocked at clock {len(clocks)} "
                f"(M={n_micro}, stages={n_stages}, v={interleave})")
        t = len(clocks)
        for s, (kind, c, m) in clock.items():
            if kind == "F":
                done_f[(c, m)] = t
                next_f[c] = m + 1
            else:
                done_b[(c, m)] = t
                next_b[c] = m + 1
        clocks.append(clock)
    return clocks


def bubble_fraction(clocks: list[dict], n_stages: int) -> float:
    """Measured bubble of a timetable: the fraction of (stage, clock)
    slots with no scheduled unit.  For the textbook 1F1B timetable this
    equals the analytic fill/drain bound exactly — see
    ``analytic_bubble_bound`` (the ONE definition of that bound — the
    schedule inspector imports it)."""
    busy = sum(len(c) for c in clocks)
    slots = n_stages * len(clocks)
    return 1.0 - busy / slots if slots else 0.0


def analytic_bubble_bound(n_stages: int, n_micro: int,
                          interleave: int = 1) -> float:
    """The interleaved-1F1B fill/drain bubble bound in chunk-clock units:
    ``(pp-1) / (pp-1 + M*v)`` — the classic (pp-1)/(pp-1+M) at
    interleave 1, shrinking v-fold with virtual stages (each of the M*v
    chunk-passes per stage is 1/v the work, but the fill/drain ramp stays
    pp-1 chunk-clocks)."""
    denom = n_stages - 1 + n_micro * interleave
    return (n_stages - 1) / denom if denom else 0.0


def schedule_tables(clocks: list[dict], n_stages: int, n_micro: int,
                    interleave: int = 1) -> dict:
    """Compile a 1F1B timetable into the dense per-(clock, stage) arrays
    the SPMD train step indexes with ``axis_index('pp')`` — the bridge
    from the timetable-as-data to the uniform per-clock program every
    rank traces.

    Returns int32/bool numpy arrays of shape (T, n_stages):

    - ``f_valid/f_k/f_m``: this stage runs a forward unit this clock, on
      its local virtual-stage slot ``f_k`` (chunk ``f_k*n + s``) and
      microbatch ``f_m``;
    - ``b_valid/b_k/b_m``: same for backward units;
    - ``fr_valid/fr_k/fr_m``: the stage RECEIVES a forward activation
      this clock (the upstream neighbour ran F on the preceding chunk),
      to stash for local slot ``fr_k``'s microbatch ``fr_m``;
    - ``br_valid/br_k/br_m``: same for backward cotangents arriving from
      the downstream neighbour.

    Invalid slots carry index 0 (the step masks them out).
    """
    import numpy as np

    n_chunks = n_stages * interleave
    t_total = len(clocks)
    z = lambda: np.zeros((t_total, n_stages), np.int32)  # noqa: E731
    f = {k: z() for k in ("f_valid", "f_k", "f_m", "b_valid", "b_k", "b_m",
                          "fr_valid", "fr_k", "fr_m",
                          "br_valid", "br_k", "br_m")}
    for t, clock in enumerate(clocks):
        for s, (kind, c, m) in clock.items():
            k = c // n_stages
            if kind == "F":
                f["f_valid"][t, s] = 1
                f["f_k"][t, s], f["f_m"][t, s] = k, m
                if c < n_chunks - 1:
                    # chunk c+1 lives on stage (s+1) % n: it receives this
                    # unit's output over the forward ring hop this clock
                    rs = (s + 1) % n_stages
                    f["fr_valid"][t, rs] = 1
                    f["fr_k"][t, rs] = (c + 1) // n_stages
                    f["fr_m"][t, rs] = m
            else:
                f["b_valid"][t, s] = 1
                f["b_k"][t, s], f["b_m"][t, s] = k, m
                if c > 0:
                    # chunk c-1's stage receives this unit's input
                    # cotangent over the reverse ring hop this clock
                    rs = (s - 1) % n_stages
                    f["br_valid"][t, rs] = 1
                    f["br_k"][t, rs] = (c - 1) // n_stages
                    f["br_m"][t, rs] = m
    return f


def stash_plan(clocks: list[dict], n_stages: int, n_micro: int,
               interleave: int = 1) -> tuple[int, int]:
    """Activation/cotangent stash depths for the 1F1B step, computed FROM
    the timetable and statically verified collision-free.

    The step keeps two rolling buffers per local chunk slot, indexed by
    ``microbatch % depth``: ``x_stash`` (chunk inputs received over the
    forward hop, read at the chunk's F clock and again at its B clock for
    the recompute-vjp) and ``cot_stash`` (output cotangents received over
    the reverse hop, read at the B clock).  A slot written at the end of
    clock ``t_w`` is live through its final read at clock ``t_r``; the
    plan asserts no later write lands on the slot before ``t_r`` — the
    bounded-stash property that gives 1F1B its O(pp * microbatch)
    activation memory (vs the flat wave scan's O(num_ticks)).

    Returns ``(x_depth, cot_depth)``.
    """
    n_chunks = n_stages * interleave
    done_f: dict = {}
    done_b: dict = {}
    for t, clock in enumerate(clocks):
        for s, (kind, c, m) in clock.items():
            (done_f if kind == "F" else done_b)[(c, m)] = t

    def min_depth(spans_by_chunk: dict) -> int:
        depth = 1
        for spans in spans_by_chunk.values():
            while True:
                by_slot: dict = {}
                for m, (t_w, t_r) in spans.items():
                    by_slot.setdefault(m % depth, []).append((t_w, t_r))
                ok = True
                for entries in by_slot.values():
                    entries.sort()
                    for (w1, r1), (w2, _) in zip(entries, entries[1:]):
                        if w2 < r1:  # overwritten while still live
                            ok = False
                if ok:
                    break
                depth += 1
        return depth

    x_spans: dict = {c: {} for c in range(1, n_chunks)}
    cot_spans: dict = {c: {} for c in range(n_chunks - 1)}
    for m in range(n_micro):
        for c in range(1, n_chunks):
            # written when the upstream F runs, last read at this B
            x_spans[c][m] = (done_f[(c - 1, m)], done_b[(c, m)])
        for c in range(n_chunks - 1):
            # written when the downstream B runs, read at this B
            cot_spans[c][m] = (done_b[(c + 1, m)], done_b[(c, m)])
    return (max(1, min_depth(x_spans)), max(1, min_depth(cot_spans)))


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

    # -- 1F1B-grade activation memory: block-remat over the tick scan ------
    # A flat scan of T ticks saves one (mb, S, D) carry per tick for the
    # backward: O(T) = O(M*v) live activations — the O(num_ticks) wall.
    # Nesting the scan (outer over blocks of ``remat_block_ticks`` ticks,
    # inner scan checkpointed) makes the backward keep only the T/block
    # block-boundary carries and rematerialize one block at a time, so peak
    # live activations are O(M*v/n + n) microbatch-sized buffers — for the
    # standard M = O(n) microbatch regime, O(pp * mb), 1F1B's bound.  The
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
