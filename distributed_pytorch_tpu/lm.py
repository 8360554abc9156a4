"""LM trainer: multi-axis (data x expert x sequence x tensor) LM training.

The VGG trainer (train.py) reproduces the reference's DP-only world; this
trainer is the framework's scale-out path for transformer LMs, composing the
parallelism axes over one ``Mesh(('data', 'expert', 'seq', 'model'))``
(the 'expert' axis is size 1 unless ``ep > 1``; batches shard over
``(data+expert, seq)``):

- **data**: batch sharded; gradient sync is the automatic cotangent ``psum``
  shard_map inserts for axis-invariant params (the 'ddp' strategy fused into
  autodiff).
- **seq**: activations sharded over the sequence; attention is the ring over
  ICI (parallel/context.py); params are seq-invariant so their cotangents
  psum over 'seq' as well.
- **model**: Megatron tensor parallelism — head/FFN-sharded weights
  (models/transformer.py shard_specs), two activation psums per layer.

Design: the *gradient* step runs inside ``shard_map`` (explicit collectives,
ring attention); the AdamW update runs as plain global ops in the same outer
``jit``, where GSPMD propagates each leaf's sharding — no hand-written specs
for optimizer state.  Loss is masked next-token cross-entropy; ``targets``
are pre-shifted host-side so sequence shards never need neighbor tokens.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .models import transformer as tfm
from .utils import faults
from .utils import compat
from .utils import monitor
from .utils import telemetry
from .utils.compat import shard_map
from .utils.tracing import span
from .ops.nn import IGNORE_INDEX, masked_ce, step_metrics  # noqa: F401
from .ops import losses
from .parallel import context as ctx
from .parallel.mesh import make_mesh

PyTree = Any

DATA, SEQ, MODEL, PIPE, EXPERT = "data", "seq", "model", "pipe", "expert"
DCN = "dcn"  # outer factor of the data axis on multislice meshes
IGNORE = IGNORE_INDEX  # target id excluded from the loss (padding)
# What the step of a model with the dropless routed layer (ops/moe.py) adds
# to ``LMTrainer.last_metrics`` after [grad-norm, param-norm], summed over
# the step's layers and chips (the load figure: the worst layer's): picks
# routed to experts held here, the largest expert's rows over the mean
# expert's, picks that reached no product (0 by construction), and the tiles
# of the worst-case row buffer that the layers' loops worked over (the mean
# over layers and chips: ~0.29 with a quarter of the experts held and even
# routing, 1 with every pick held here).
MOE_METRICS = ("moe.rows_here", "moe.load_max_over_mean", "moe.dropped",
               "moe.live_tile_share")
# ... and what follows those four where the model has the mechanism: the mean
# over tokens and routed layers of the picks' summed sigmoid scores before
# normalisation (``moe_scoring="sigmoid"``), and the mean over heads, tokens
# and layers of the attention gate (``attn_gate``: one stuck at 0 or 1 shows).
_STAT_METRICS = {"score_sum_mean": "moe.score_sum_mean",
                 "gate_mean": "attn.gate_mean"}
# how a counter is reduced over the chips of a step
_STAT_REDUCE = {"rows_here": jax.lax.psum, "dropped": jax.lax.psum,
                "load_max_over_mean": jax.lax.pmax}


def step_metric_names(model: tfm.TransformerConfig) -> tuple[str, ...]:
    """Names of what a step of ``model`` adds to ``LMTrainer.last_metrics``
    after [grad-norm, param-norm] (``model.stat_names()``, as telemetry
    names its gauges): ``MOE_METRICS`` first, for a dropless model."""
    return tuple(_STAT_METRICS.get(k, "moe." + k) for k in model.stat_names())


@dataclass
class LMTrainConfig:
    model: tfm.TransformerConfig = field(
        default_factory=lambda: tfm.PRESETS["LM-tiny"])
    lr: float = 3e-4
    warmup_steps: int = 0     # linear LR warmup
    decay_steps: int = 0      # cosine decay horizon (0 = constant LR)
    min_lr_ratio: float = 0.1
    weight_decay: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    grad_clip: float = 1.0
    aux_coef: float = 0.01  # MoE load-balance loss weight (Switch default)
    compute_dtype: str | None = "bfloat16"
    seed: int = 1
    # parallel degrees; dp * ep * sp * tp * pp must equal the mesh size
    dp: int = 1
    sp: int = 1
    tp: int = 1
    pp: int = 1          # pipeline stages; composes with dp, sp, and tp
    # Dedicated expert-parallel degree (EP x TP): MoE experts shard over
    # their own 'expert' mesh axis (E/ep experts per rank, each expert's
    # FFN tp-sharded) and the batch additionally splits over it for
    # non-MoE layers (EP ranks own distinct tokens — no duplicated
    # attention).  ep=1 keeps the round-2 experts-over-'model' layout.
    ep: int = 1
    # Multislice factoring of the data axis: dp = dcn_size slices x
    # (dp // dcn_size) chips each.  With dcn_size > 1 the mesh gains an
    # outer 'dcn' axis and the DP gradient sync becomes the EXPLICIT
    # two-level reduction (reduce-scatter over the slice, a SHARD-SIZED
    # psum across slices, all-gather back) — |grads|/ici bytes cross
    # DCN per optimizer step instead of the full payload, as a property
    # of the emitted program (jaxpr-pinned), not an assumption about
    # XLA's collective lowering.  With grad_accum = A the microbatch
    # backwards run entirely local and the accumulated grads sync ONCE
    # (_make_accum_grad_step): one shard-sized DCN exchange per
    # optimizer step, not A.
    dcn_size: int = 1
    # Slow-hop compression for the factored-mesh sync (round 11 — the
    # LM analog of TrainConfig.dcn_compress, closing the round-9
    # "needs a sync-state channel" note): "int8" runs every bucket's
    # cross-slice exchange in ``_two_level_sync`` as an int8 ring
    # (per-256-row f32 scales on each DCN transfer; the ICI
    # reduce-scatter/all-gather stay full-precision), with the dropped
    # quantization error carried as a per-device error-feedback
    # residual THROUGH THE TRAIN STEP: the step signature gains a
    # donated ``sync_state`` arg/result (LMTrainer threads it), the
    # whole-tree sync point becomes a stateful custom-vjp whose
    # residual input's cotangent IS the updated carry, and under
    # ``overlap`` each layer group's streamed point consumes/refills
    # its own residual segment.  EF invariant (test-pinned): delivered
    # shard sum + psum_dcn(residuals) == the exact two-level shard sum
    # — nothing lost, only delayed one step.  "int4" (round 16) is the
    # same machinery one rung lower: [-7, 7] levels, two nibbles packed
    # per int8 lane around every DCN ppermute (~0.51x the int8 wire
    # bytes), identical residual layout and EF invariant.  Requires
    # dcn_size > 1 (so not with pp: the pipeline mesh has no factored
    # data axis).  Dropping the carry on
    # restart is safe (residuals re-accumulate within a step;
    # checkpoints skip it).
    dcn_compress: str | None = None
    # Streaming bucket size (MB) for the factored-mesh exchange
    # (default: strategies.BUCKET_CAP_MB's ~25 MB): feeds the
    # grad-accumulation path's post-scan sync and the int8 ring's
    # bucket layout.  None keeps the historical default — the plain
    # paths are bitwise-unchanged.
    bucket_mb: float | None = None
    # "auto" (round 11): resolve dcn_compress/bucket_mb from a
    # calibrated (or injected — ``autotune_profile``) link profile by
    # minimizing predicted step-sync time (parallel/autotune.py).  The
    # resolved plan routes through the explicit knobs above unchanged
    # (auto under a forced profile trains bitwise-identically to the
    # explicit config it resolves to); LMTrainer records it as
    # ``trainer.sync_plan``.
    sync_plan: str | None = None
    # Profile source for sync_plan="auto": None = cached/calibrated, or
    # a synthetic preset name / profile-JSON path / TopologyProfile.
    autotune_profile: Any = None
    # Explicit routed sync surface (round 21, the round-20 follow-up —
    # the CNN trainer's strategy="routed" analogue): a route string in
    # the parallel/routing grammar pinning the gradient sync by hand
    # instead of searching for it ("data:psum" on a flat mesh;
    # "data:rs -> dcn:psum -> data:ag" or
    # "data:rs -> dcn:ring[int8|int4+ef] -> data:ag" on a factored
    # one).  Resolved by autotune.resolve_lm_route into the explicit
    # knobs above (the exact routes `_two_level_sync` already
    # executes), so a routed config trains BITWISE-identically to the
    # explicit config it names; anything the LM machinery cannot run —
    # other shapes, pp, combining with sync_plan="auto" or
    # dcn_compress — refuses loudly (strategies.require_lm_route).
    sync_route: str | None = None
    microbatches: int = 0  # per-step microbatches for pp (default 2*pp)
    # Virtual pipeline stages per device (Megatron interleaved placement):
    # the fill/drain bubble shrinks by this factor (parallel/pipeline.py
    # wave schedule).  Requires n_layers % (pp * interleave) == 0.
    interleave: int = 1
    # Tick-scan remat block for pp (parallel/pipeline.py): 0 = auto (one
    # wave per block — O(pp*mb) activation memory), None = flat
    # scan (O(num_ticks) memory; kept for A/B measurement), or an explicit
    # tick count.
    pp_remat_block: int | None = 0
    fsdp: bool = False   # ZeRO-3: shard params+optimizer over 'data' too
    # Quantized ZeRO-3 weight all-gathers (round 16): "int8" runs every
    # fsdp param gather (the post-backward whole-tree path and the
    # streamed per-layer-group boundary path alike — both route through
    # ``_fsdp_gather``) as an int8 exchange with per-row f32 scales:
    # quantize the local shard, all-gather int8 payload + scales over
    # 'data', dequantize at the consumer.  Weights-not-grads, so there
    # is no EF carry — the pin is a convergence-curve follow of the
    # full-precision run plus a jaxpr pin that i8 is on the wire.
    # Requires fsdp=True (no gather to quantize otherwise).  "int4" (round 18,
    # lifting the round-16 refusal) packs two nibbles per wire byte on
    # the same exchange (+/-7 levels against the identical per-row
    # scales) — 8x fewer payload bytes; same full-precision gradient
    # reduce-scatter, same curve-following pin at a looser rtol.
    # None = exact gathers.
    fsdp_gather_dtype: str | None = None
    # Low-bit dense compute (round 16): "int8" routes the transformer's
    # dense projections (attention q/k/v/o and the MLP matmuls) through
    # ops/quantized.py's int8xint8->int32 matmul on the FORWARD pass —
    # per-row activation scales, per-col weight scales, dequant in the
    # epilogue (Pallas kernel on TPU, lax.dot_general-on-int8 XLA
    # fallback elsewhere) — while the backward stays in the configured
    # compute dtype (straight-through estimator).  Flip-rate-measured
    # against bf16 like the int8 KV cache was.  None = stock matmuls.
    matmul_dtype: str | None = None
    # Backward-overlapped sync (rounds 8-9): stream the step's bulk
    # communication through the layer-group boundaries (transformer.apply
    # boundary hook) instead of emitting it all-at-once.  With fsdp
    # (round 8), each group's ZeRO-3 weight gather moves to its boundary
    # — forward all_gathers stream layer by layer and their transposes
    # (the gradient reduce-scatters) land interleaved between the
    # backward matmuls.  With dcn_size > 1 (round 9), the factored-mesh
    # two-level gradient sync streams the same way: the whole-tree
    # _dcn_sync_point becomes one per-layer-group custom-vjp point each,
    # so group N's ICI reduce-scatter -> shard-sized DCN psum ->
    # all-gather is emitted right after group N's backward matmuls and
    # the latency-hiding scheduler can run it under group N-1's backward.
    # Bitwise-identical trajectories either way (same ops, moved; the
    # two-level reduction is elementwise, so regrouping changes no sums).
    # Requires fsdp=True or dcn_size > 1: otherwise the data-axis
    # cotangent psums already sit at each param's use site and there is
    # no post-backward cluster to dissolve.
    overlap: bool = False
    # Gradient accumulation: split each global batch into grad_accum
    # microbatches, scan them accumulating gradients, apply ONE optimizer
    # step.  The CE gradient is EXACT (grads normalize by the full batch's
    # global token count, counted before the scan, so microbatch mask
    # imbalance reweights nothing).  MoE aux is a per-routing-group
    # statistic, and accumulation makes each microbatch its own group —
    # the aux term therefore shifts slightly, exactly as it does for any
    # other change of group size (dp/tp splits included).
    grad_accum: int = 1
    # Head-loss implementation (round 17): "dense" materializes the full
    # (B, T, V) f32 logits and calls masked_ce — the historical graph,
    # bit-for-bit.  "chunked" streams the head projection + an online
    # logsumexp over vocab chunks (ops/losses.py masked_ce_chunked, a
    # custom-vjp whose backward recomputes each chunk's logits and emits
    # the hidden/embedding cotangents directly) so the logits tensor
    # never exists — on real TPUs it is the single largest activation
    # and the cap on per-device batch size.  Under tp > 1 the chunked
    # head additionally shards the vocab over 'model' (per-rank partial
    # logsumexp + one pmax/psum combine).  Matches dense to ~1e-6.
    loss_impl: str = "dense"
    # Vocab rows per streamed chunk for loss_impl="chunked"; must divide
    # the per-rank vocab (V, or V // tp when tp > 1).  None = the largest
    # divisor <= 1024 (ops/losses.py default_chunk).
    loss_chunk: int | None = None
    # Activation rematerialization for the non-pp layer stack (round 17):
    # "full" wraps each transformer block in jax.checkpoint (only the
    # layer-boundary carries stay live through the backward; everything
    # else recomputes), "selective" additionally saves the flash
    # attention (o, lse) pair via checkpoint names so only the
    # projections and MLP recompute — the usual best point on the
    # memory/time curve.  Losses are bitwise-equal to remat="none" (the
    # recompute replays the identical ops).  The sync custom-vjp
    # boundaries (overlap streaming, ZeRO-3 gathers, two-level DCN
    # points) sit OUTSIDE the checkpointed block, so no sync collective
    # is re-emitted — schedule-inspector-pinned.  Does not compose with
    # pp: parallel/pipeline.py owns its own per-tick remat
    # (pp_remat_block).  "none" = historical graph.
    remat: str = "none"
    # Communication-sparse windows (round 18, the BAGUA-style system
    # relaxation the ROADMAP carried): run H local optimizer steps
    # between cross-slice exchanges.  Requires the factored multislice
    # mesh (dcn_size >= 2) — the window relaxes the SLOW hop
    # specifically: within a window every step syncs gradients over the
    # intra-slice axes only (data/expert/seq/model — ICI) and each
    # slice advances its own params p = anchor + delta with PER-SLICE
    # Adam state (delta and opt state carry a leading 'dcn' axis); at
    # step kH the accumulated deltas average across 'dcn' through the
    # same bucketed two-level exchange the per-step path uses —
    # composing with dcn_compress (int8/int4 ring + EF residual, now
    # charged once per window) and with overlap/fsdp (local steps
    # stream ICI-only sync points and ZeRO-3 gathers; the boundary
    # exchange is whole-tree).  DCN bytes/step scale ~1/H
    # (schedule-inspector-pinned); sync_every=1 is the existing
    # per-step path, bitwise (build-time branch).  Adam trajectories
    # follow the per-step curve (curve pin), they do not equal it.
    sync_every: int = 1
    # Bounded staleness S (0 <= S < H): launch the window exchange at
    # step kH but apply it at step kH+S, so the DCN round-trip can
    # drain under S steps of local compute instead of stalling the
    # boundary step.  The launch snapshots delta; the apply adds the
    # averaged delta to the anchor and subtracts the snapshot from the
    # live delta (local progress made during the S steps is kept).
    # NOTE: on a single-stream runtime the launch/apply programs still
    # execute in dispatch order — the structure bounds what a
    # multi-stream runtime may overlap; it does not force overlap.
    staleness: int = 0
    # Relaxation ceiling for the interval-aware autotuner
    # (sync_plan="auto" prices intervals H <= max_sync_every) and the
    # RunDoctor straggler actuator (monitor.SyncRelaxHook widens
    # sync_every up to this bound on a step-time SLO breach).  Default
    # 1: relaxation is strictly opt-in.
    max_sync_every: int = 1
    # DiLoCo outer optimizer (round 22): at each window boundary the
    # anchor moves by outer_opt(mean delta) instead of the plain mean —
    # Nesterov/heavy-ball momentum ON THE ANCHOR (f32, host-side per
    # device like the EF residual) recovers convergence lost to wide
    # windows, so H can widen at matched quality (measured band,
    # tests/test_diloco.py).  None (default) is the round-18 plain
    # mean, UNTOUCHED at build time; momentum==0 ∧ lr==1 collapses to
    # the same plain-add branch (OuterOptimizer.trivial) — bitwise.
    outer_opt: str | None = None      # None | "nesterov" | "momentum"
    outer_momentum: float = 0.9
    outer_lr: float = 1.0
    # Per-slice non-uniform windows (round 22): each WAN-attached slice
    # owns its own H_i (a multiple of the base sync_every, which must
    # equal min(H_i)).  At a base boundary only slices with
    # step % H_i == 0 participate: skippers contribute an EXACT zero
    # delta through a (dcn,)-shaped participation mask inside the
    # exchange (EF ledger invariant pinned) and keep accumulating
    # locally; participants' deltas average over ALL n_dcn slices and
    # everyone adopts the anchor move, so params stay replicated.  The
    # per-slice SyncRelaxHook widens a straggling slice's own H without
    # staling healthy slices.  None (default) = uniform windows,
    # bitwise (build-time branch).
    sync_every_per_slice: tuple | None = None
    @property
    def dtype(self) -> jnp.dtype | None:
        """compute_dtype resolved to a jnp dtype (None = float32 params)."""
        return jnp.dtype(self.compute_dtype) if self.compute_dtype else None

    # Ring-attention sequence layout when sp > 1: 'zigzag' (balanced causal
    # ring, ~2x fewer attention FLOPs — parallel/context.py) or 'contiguous'.
    # The step permutes the global token stream in-jit to match; the loss is
    # permutation-invariant, so trajectories equal the contiguous layout.
    seq_layout: str = "zigzag"


def validate_lm_cfg(cfg: LMTrainConfig) -> None:
    """Composition checks for (dp, ep, sp, tp, pp, interleave,
    grad_accum).  Shared by ``make_lm_mesh`` and ``LMTrainer`` so a
    caller-supplied mesh cannot skip them — e.g. ``LMTrainer(cfg(pp=2,
    grad_accum=4), mesh=m)`` must raise exactly like the mesh-built path
    (the pp step builder never reads grad_accum, so silently accepting it
    would drop the setting)."""
    if cfg.interleave < 1:
        raise ValueError(f"interleave must be >= 1, got {cfg.interleave}")
    if cfg.interleave > 1 and cfg.pp == 1:
        raise ValueError(
            "interleave (virtual pipeline stages) requires pp > 1; "
            "without a pipeline it would be silently ignored")
    if cfg.grad_accum < 1:
        raise ValueError(f"grad_accum must be >= 1, got {cfg.grad_accum}")
    if cfg.grad_accum > 1 and cfg.pp > 1:
        raise ValueError(
            "grad_accum does not compose with pp (the pipeline's "
            "microbatch schedule already bounds activation memory; use "
            "--microbatches)")
    if cfg.dcn_size < 1:
        raise ValueError(f"dcn_size must be >= 1, got {cfg.dcn_size}")
    if cfg.dcn_size > 1:
        if cfg.dp % cfg.dcn_size:
            raise ValueError(f"dp={cfg.dp} does not factor into "
                             f"dcn_size={cfg.dcn_size} slices")
        if cfg.pp > 1:
            raise ValueError("dcn_size does not compose with pp (the "
                             "pipeline mesh has no factored data axis)")
    if cfg.sync_plan not in (None, "auto"):
        raise ValueError(
            f"sync_plan must be None or 'auto', got {cfg.sync_plan!r}")
    if cfg.bucket_mb is not None and cfg.bucket_mb <= 0:
        raise ValueError(f"bucket_mb must be > 0, got {cfg.bucket_mb}")
    if cfg.dcn_compress is not None:
        if cfg.dcn_compress not in ("int8", "int4"):
            raise ValueError(
                f"dcn_compress must be None, 'int8', or 'int4', got "
                f"{cfg.dcn_compress!r}")
        if cfg.dcn_size < 2:
            raise ValueError(
                f"dcn_compress={cfg.dcn_compress!r} quantizes the "
                "cross-slice (dcn) hop of the factored-mesh sync; with "
                f"dcn_size={cfg.dcn_size} there is no DCN hop to compress")
    if (cfg.sync_every != 1 or cfg.staleness != 0
            or cfg.max_sync_every != 1 or cfg.outer_opt is not None
            or cfg.sync_every_per_slice is not None):
        # the ONE window-coherence check site (round 18,
        # parallel/strategies.py require_* consolidation): interval
        # bounds, staleness-vs-window ordering, and the combos the LM
        # windowed machinery does not cover (pipeline paths,
        # grad_accum's already-amortized exchange, flat meshes)
        from .parallel.strategies import require_sync_window
        require_sync_window(
            sync_every=cfg.sync_every, staleness=cfg.staleness,
            max_sync_every=cfg.max_sync_every, mesh=True,
            overlap=cfg.overlap, pp=cfg.pp > 1,
            grad_accum=cfg.grad_accum, dcn_size=cfg.dcn_size,
            trainer="lm", outer_opt=cfg.outer_opt,
            outer_momentum=cfg.outer_momentum, outer_lr=cfg.outer_lr,
            sync_every_per_slice=cfg.sync_every_per_slice)
    if cfg.fsdp_gather_dtype is not None:
        if cfg.fsdp_gather_dtype not in ("int8", "int4"):
            raise ValueError(
                f"fsdp_gather_dtype must be None, 'int8' or 'int4', got "
                f"{cfg.fsdp_gather_dtype!r}")
        if not cfg.fsdp:
            raise ValueError(
                "fsdp_gather_dtype quantizes the ZeRO-3 weight "
                "all-gather; with fsdp=False there is no gather to "
                "quantize")
    if cfg.matmul_dtype is not None:
        if cfg.matmul_dtype != "int8":
            raise ValueError(
                f"matmul_dtype must be None or 'int8', got "
                f"{cfg.matmul_dtype!r}")
        if cfg.pp > 1:
            raise ValueError(
                "matmul_dtype does not compose with pipeline parallelism "
                "(pp): the stage runners call the block body "
                "directly without the matmul_dtype plumbing (open item); "
                "drop one")
    if cfg.loss_impl not in ("dense", "chunked"):
        raise ValueError(
            f"loss_impl must be 'dense' or 'chunked', got "
            f"{cfg.loss_impl!r}")
    if (cfg.loss_impl == "chunked" and cfg.tp > 1 and cfg.pp == 1
            and cfg.model.vocab_size % cfg.tp):
        raise ValueError(
            f"vocab_size {cfg.model.vocab_size} must divide over "
            f"tp={cfg.tp} for the chunked (vocab-sharded) head")
    if cfg.loss_chunk is not None:
        if cfg.loss_impl != "chunked":
            raise ValueError(
                f"loss_chunk={cfg.loss_chunk} only applies to "
                "loss_impl='chunked'; the dense head has no chunk size "
                "(set loss_impl='chunked' or drop loss_chunk)")
        v = cfg.model.vocab_size
        # the streamed head shards the vocab over 'model' only on the
        # non-pp SPMD path; the pipeline head chunks the full vocab
        v_local = v // cfg.tp if cfg.tp > 1 and cfg.pp == 1 else v
        if cfg.loss_chunk <= 0 or v_local % cfg.loss_chunk:
            raise ValueError(
                f"loss_chunk={cfg.loss_chunk} must be a positive divisor "
                f"of the per-rank vocab rows ({v_local}"
                + (f" = {v} // tp={cfg.tp}" if v_local != v else "")
                + ") — the streaming scan needs equal-sized chunks")
    if cfg.remat not in ("none", "full", "selective"):
        raise ValueError(
            f"remat must be 'none', 'full' or 'selective', got "
            f"{cfg.remat!r}")
    if cfg.remat != "none" and cfg.pp > 1:
        raise ValueError(
            "remat does not compose with pipeline parallelism "
            "(pp): the pipeline scheduler owns its own "
            "rematerialization (pp_remat_block wraps each tick block in "
            "jax.checkpoint already); drop one")
    if cfg.fsdp and cfg.dp // max(cfg.dcn_size, 1) == 1:
        # param_specs shards ZeRO-3 leaves over the INNER 'data' axis
        # (slice-local); at inner size 1 there is nothing to shard and
        # the user's fsdp=True would silently buy fully replicated
        # params/optimizer state (ADVICE r5 #3) — refuse instead
        raise ValueError(
            f"fsdp=True with dp={cfg.dp}, dcn_size={cfg.dcn_size} is a "
            f"no-op: the slice-local data axis has size "
            f"dp // dcn_size = 1, so no leaf can shard over it — raise "
            f"dp (or drop fsdp)")
    if cfg.overlap:
        # the ONE capability-check site (parallel/strategies.py, round 9):
        # overlap streams ZeRO-3 gathers and/or — since round 9 — the
        # factored-mesh two-level DCN sync points, per layer group.
        # Under grad_accum > 1 the dcn exchange happens ONCE after the
        # local accumulation scan (never per microbatch), so dcn alone
        # gives overlap nothing to stream there — only fsdp does (its
        # per-microbatch gathers still stream); refuse the silent no-op.
        from .parallel.strategies import require_lm_overlap_streamable
        require_lm_overlap_streamable(
            fsdp=cfg.fsdp,
            dcn=cfg.dcn_size > 1 and cfg.grad_accum == 1)
    unpiped = cfg.model.training_only()
    if unpiped and cfg.pp > 1:
        # the pipeline runner builds the dense block on a tied table
        raise ValueError("pipeline parallelism (pp) does not "
                         "implement " + "; ".join(unpiped))
    if "window" in cfg.model.attn_kinds and cfg.sp > 1:
        raise ValueError("windowed attention layers do not compose with "
                         "sp > 1: ring attention has no window")
    if cfg.model.moe_dropless:
        # one chip's share, on the plain step: the dropless routed layer
        # has no exchange yet, and its counters ride the plain grad step
        bad = [f"{k}={getattr(cfg, k)}" for k, ok in (
            ("ep", cfg.ep == 1), ("tp", cfg.tp == 1),
            ("sync_every", cfg.sync_every == 1),
            ("dcn_size", cfg.dcn_size == 1),
            ("grad_accum", cfg.grad_accum == 1)) if not ok]
        if bad:
            raise ValueError(
                "the dropless routed layer (moe_dropless=True) runs with "
                "ep=1, tp=1, sync_every=1, dcn_size=1 and grad_accum=1; "
                "got " + ", ".join(bad))
    if cfg.ep > 1:
        if cfg.pp > 1:
            raise ValueError("the dedicated 'expert' axis does not compose "
                             "with pp (experts shard over 'model' inside "
                             "pipeline stages); use ep=1 with pp")
        if not cfg.model.n_experts:
            raise ValueError("ep > 1 requires an MoE model (n_experts > 0)")
        if cfg.model.n_experts % cfg.ep:
            raise ValueError(f"{cfg.model.n_experts} experts do not shard "
                             f"over ep={cfg.ep}")
    if (cfg.model.moe_dispatch_bits != "f32"
            or cfg.model.moe_a2a_chunks > 1):
        # The a2a knobs act where the MoE layer crosses a mesh axis (the
        # EP / tensor-axis call sites in models/transformer.block); on a
        # layout with no expert exchange they would silently no-op.
        if not cfg.model.n_experts:
            raise ValueError(
                f"moe_dispatch_bits={cfg.model.moe_dispatch_bits!r}/"
                f"moe_a2a_chunks={cfg.model.moe_a2a_chunks} configure the "
                f"expert all_to_all of an MoE model; this model is dense "
                f"(n_experts=0)")
        if cfg.ep == 1 and cfg.tp == 1:
            raise ValueError(
                f"moe_dispatch_bits={cfg.model.moe_dispatch_bits!r}/"
                f"moe_a2a_chunks={cfg.model.moe_a2a_chunks} shape the "
                f"expert all_to_all wire, but ep=1 and tp=1 route "
                f"experts locally (no exchange to compress or overlap) "
                f"— raise ep or tp, or drop the knobs")
    if cfg.pp > 1:
        from .parallel.pipeline import _uniform_moe
        if cfg.model.n_experts and not _uniform_moe(cfg.model):
            raise ValueError(
                "pp supports MoE only for uniform stacks (moe_every=1, "
                "every layer MoE); a dense/MoE-alternating stack cannot "
                "stack into homogeneous pipeline stages")
        if cfg.tp > 1 and (any(h % cfg.tp for h in cfg.model.head_counts())
                           or cfg.model.kv_heads % cfg.tp):
            raise ValueError(f"heads must divide over tp={cfg.tp}")
    elif cfg.tp > 1:
        for h in sorted(cfg.model.head_counts()):
            if h % cfg.tp:
                raise ValueError(f"n_heads {h} must divide over "
                                 f"tp={cfg.tp}")
        if cfg.model.kv_heads % cfg.tp:
            raise ValueError(
                f"n_kv_heads {cfg.model.kv_heads} must divide over "
                f"tp={cfg.tp} (replicating kv heads across tensor ranks is "
                f"not supported; lower tp or raise n_kv_heads)")


def make_lm_mesh(cfg: LMTrainConfig, devices=None) -> Mesh:
    validate_lm_cfg(cfg)
    if cfg.pp > 1:
        # pp composes with dp, sp (ring attention inside each stage's
        # layer chunks) and tp — a 4-axis mesh; unused axes have size 1.
        return make_mesh(cfg.dp * cfg.pp * cfg.sp * cfg.tp,
                         axis_names=(DATA, PIPE, SEQ, MODEL),
                         axis_shape=(cfg.dp, cfg.pp, cfg.sp, cfg.tp),
                         devices=devices)
    # The 'expert' axis is always present (size ep, usually 1 — free):
    # batch shards over (data, expert), expert weights over 'expert'.
    if cfg.dcn_size > 1:
        # multislice: the data axis factors as dcn (outer, cross-slice)
        # x data (inner, within-slice ICI)
        return make_mesh(cfg.dp * cfg.ep * cfg.sp * cfg.tp,
                         axis_names=(DCN, DATA, EXPERT, SEQ, MODEL),
                         axis_shape=(cfg.dcn_size, cfg.dp // cfg.dcn_size,
                                     cfg.ep, cfg.sp, cfg.tp),
                         devices=devices)
    return make_mesh(cfg.dp * cfg.ep * cfg.sp * cfg.tp,
                     axis_names=(DATA, EXPERT, SEQ, MODEL),
                     axis_shape=(cfg.dp, cfg.ep, cfg.sp, cfg.tp),
                     devices=devices)


def param_specs(cfg: LMTrainConfig) -> PyTree:
    """Per-leaf PartitionSpecs for the transformer params.

    Base: the Megatron tensor sharding (models/transformer.py shard_specs),
    with MoE experts on the dedicated 'expert' axis and their FFN width
    tp-sharded (EP x TP; at ep=1 the expert axis is size 1, so experts are
    simply replicated across tp with tp-sharded FFNs).
    With ``fsdp``, each leaf's first data-divisible unsharded dim
    additionally shards over 'data' (ZeRO-3): parameters and optimizer
    state shrink by the data degree per device; the train step all-gathers
    weights for use and autodiff's transpose reduce-scatters the gradients
    back.  On the factored multislice mesh (``dcn_size > 1``) 'data' is
    the SLICE-LOCAL inner axis — ZeRO-3 partitions within each slice
    (all-gathers ride ICI) and the state replicates across 'dcn', so the
    per-step cross-slice exchange stays one shard-sized gradient psum
    (the standard FSDP x multislice layout).
    """
    specs = tfm.shard_specs(cfg.model, tp_axis=MODEL,
                            ep_axis=EXPERT if cfg.ep > 1 else None)
    inner_dp = cfg.dp // cfg.dcn_size  # the mesh's actual 'data' size
    if not cfg.fsdp or inner_dp == 1:
        return specs
    shapes = jax.eval_shape(lambda k: tfm.init(k, cfg.model),
                            jax.random.key(0))

    def add_data(spec: P, shape) -> P:
        parts = list(spec) + [None] * (len(shape.shape) - len(spec))
        for i, (ax, dim) in enumerate(zip(parts, shape.shape)):
            if ax is None and dim % inner_dp == 0:
                parts[i] = DATA
                return P(*parts)
        return spec  # no divisible dim: leaf stays dp-replicated

    return jax.tree.map(add_data, specs, shapes)


def _q8_shard_gather(p: jax.Array, dim: int) -> jax.Array:
    """One fsdp leaf's all-gather, int8 on the wire (round 16,
    ``fsdp_gather_dtype="int8"``): quantize the LOCAL shard against
    per-row f32 scales (row = index along the gathered dim, so scales
    gather along the same axis as the payload), all_gather the int8
    tensor + scales over 'data', dequantize at the consumer — 4x fewer
    gather bytes for f32 params, 2x for bf16, plus one f32 scale per
    row.  Weights-not-grads: the BACKWARD is the PLAIN tiled gather's
    transpose (the ZeRO reduce-scatter of cotangents, full precision),
    a straight-through estimator — rounding the forward weights is a
    small perturbation the optimizer tracks, rounding the gradient
    stream would need the EF machinery the grad paths use."""
    axes = tuple(i for i in range(p.ndim) if i != dim)

    def _quantized(x):
        x32 = x.astype(jnp.float32)
        scale = jnp.maximum(
            jnp.max(jnp.abs(x32), axis=axes, keepdims=True) / 127.0,
            1e-30)
        q = jnp.clip(jnp.round(x32 / scale), -127, 127).astype(jnp.int8)
        qg = jax.lax.all_gather(q, DATA, axis=dim, tiled=True)
        sg = jax.lax.all_gather(scale, DATA, axis=dim, tiled=True)
        return (qg.astype(jnp.float32) * sg).astype(x.dtype)

    @jax.custom_vjp
    def g(x):
        return _quantized(x)

    def fwd(x):
        return _quantized(x), None

    def bwd(_, ct):
        return (jax.lax.psum_scatter(ct, DATA, scatter_dimension=dim,
                                     tiled=True),)

    g.defvjp(fwd, bwd)
    return g(p)


def _q4_shard_gather(p: jax.Array, dim: int) -> jax.Array:
    """One fsdp leaf's all-gather, int4 on the wire (round 18,
    ``fsdp_gather_dtype="int4"`` — lifting the round-16 refusal):
    quantize the LOCAL shard to +/-7 levels against the same per-row
    f32 scales as the int8 rung, then pack two nibbles per wire byte
    along the gathered dim (odd shard lengths pad one element, sliced
    off after the unpack) — 8x fewer gather payload bytes for f32
    params.  The gather runs untiled (leading device axis) so the
    unpack/slice happens per shard before the shards concatenate; the
    BACKWARD is unchanged from the int8 rung — the full-precision ZeRO
    reduce-scatter of cotangents (weights tolerate the 16x-coarser
    forward rounding; the gradient stream is never quantized)."""
    axes = tuple(i for i in range(p.ndim) if i != dim)
    m = p.shape[dim]

    def _quantized(x):
        x32 = x.astype(jnp.float32)
        scale = jnp.maximum(
            jnp.max(jnp.abs(x32), axis=axes, keepdims=True) / 7.0,
            1e-30)
        q = jnp.clip(jnp.round(x32 / scale), -7, 7).astype(jnp.int8)
        if m % 2:
            q = jnp.pad(q, [(0, 1) if i == dim else (0, 0)
                            for i in range(q.ndim)])
        sel = lambda start: tuple(
            slice(start, None, 2) if i == dim else slice(None)
            for i in range(q.ndim))
        packed = ((q[sel(0)] + 8).astype(jnp.uint8)
                  | ((q[sel(1)] + 8).astype(jnp.uint8) << 4))
        pg = jax.lax.all_gather(packed, DATA, axis=0)   # (n, ..packed..)
        sg = jax.lax.all_gather(scale, DATA, axis=0)    # (n, ..1-at-dim..)
        d = dim + 1  # the gather added a leading device axis
        lo = (pg & 0xF).astype(jnp.int8) - 8
        hi = ((pg >> 4) & 0xF).astype(jnp.int8) - 8
        u = jnp.stack([lo, hi], axis=d + 1)
        u = u.reshape(u.shape[:d] + (-1,) + u.shape[d + 2:])
        u = jax.lax.slice_in_dim(u, 0, m, axis=d)
        full = u.astype(jnp.float32) * sg
        # collapse (device, dim) -> the concatenated gathered dim, in
        # shard order — the tiled-gather layout the plain path produces
        full = jnp.moveaxis(full, 0, dim)
        return full.reshape(full.shape[:dim] + (-1,)
                            + full.shape[dim + 2:]).astype(x.dtype)

    @jax.custom_vjp
    def g(x):
        return _quantized(x)

    def fwd(x):
        return _quantized(x), None

    def bwd(_, ct):
        return (jax.lax.psum_scatter(ct, DATA, scatter_dimension=dim,
                                     tiled=True),)

    g.defvjp(fwd, bwd)
    return g(p)


def _fsdp_gather(params: PyTree, specs: PyTree,
                 dtype: str | None = None) -> PyTree:
    """all_gather fsdp-sharded leaves back to full (tp shards stay local).

    Inside shard_map; the transpose of these gathers is the reduce-scatter
    that delivers each device only its shard's gradient — ZeRO's comm
    pattern, synthesized by autodiff.  ``dtype="int8"`` swaps each leaf's
    gather for the quantized exchange (``_q8_shard_gather``;
    ``dtype="int4"`` the nibble-packed ``_q4_shard_gather``); the
    gradient reduce-scatter stays full-precision either way.
    """
    def gather(p, spec):
        for dim, ax in enumerate(spec):
            if ax == DATA:
                if dtype == "int8":
                    return _q8_shard_gather(p, dim)
                if dtype == "int4":
                    return _q4_shard_gather(p, dim)
                return jax.lax.all_gather(p, DATA, axis=dim, tiled=True)
        return p

    return jax.tree.map(gather, params, specs)


def _zigzag_global(cfg: LMTrainConfig, x: jax.Array) -> jax.Array:
    """Permute the GLOBAL sequence axis into the zigzag ring layout,
    inside jit (before shard_map).  Operating on the logical global array
    makes the layout correct for any process topology — multi-host runs
    where the seq axis spans processes included (a host-side permute of
    process-local slices would scramble the layout there).  XLA compiles
    the cross-shard gather; tokens are int32, so the exchange is tiny
    next to one layer's activations.  Identity unless sp > 1 and the
    layout is zigzag."""
    if cfg.sp <= 1 or cfg.seq_layout != "zigzag":
        return x
    perm = ctx.zigzag_permutation(cfg.sp, x.shape[1])  # trace-time constant
    return x[:, perm]


def _shard_positions(cfg: LMTrainConfig, s_local: int) -> jax.Array:
    """This seq-shard's absolute token positions (inside shard_map).

    Contiguous: [me*s_local, (me+1)*s_local).  Zigzag: the shard holds
    global chunks [me, 2*sp-1-me] (parallel/context.py zigzag layout).
    """
    me = jax.lax.axis_index(SEQ)
    if cfg.sp > 1 and cfg.seq_layout == "zigzag":
        return ctx.zigzag_positions(me, cfg.sp, s_local)
    return me * s_local + jnp.arange(s_local)


def pp_stage_specs(cfg: LMTrainConfig) -> PyTree:
    """Stage-stacked param specs for the pp layout — the single derivation
    of the pipe (+ optional Megatron) sharding, shared by the trainer's
    param placement and the train step's shard_map specs."""
    from .parallel import pipeline as pp
    return pp.stage_specs(cfg.model, cfg.pp,
                          tp_axis=MODEL if cfg.tp > 1 else None,
                          interleave=cfg.interleave)


def make_schedule(cfg: LMTrainConfig):
    """Constant LR, or linear warmup + cosine decay to min_lr_ratio*lr."""
    if cfg.decay_steps <= 0 and cfg.warmup_steps <= 0:
        return cfg.lr
    if cfg.decay_steps <= 0:
        return optax.linear_schedule(0.0, cfg.lr, cfg.warmup_steps)
    return optax.warmup_cosine_decay_schedule(
        init_value=0.0, peak_value=cfg.lr,
        warmup_steps=cfg.warmup_steps,
        decay_steps=cfg.decay_steps,
        end_value=cfg.lr * cfg.min_lr_ratio)


def make_optimizer(cfg: LMTrainConfig) -> optax.GradientTransformation:
    return optax.chain(
        optax.clip_by_global_norm(cfg.grad_clip),
        optax.adamw(make_schedule(cfg), b1=cfg.b1, b2=cfg.b2,
                    weight_decay=cfg.weight_decay),
    )


def _batch_axes(cfg: LMTrainConfig) -> tuple[str, ...]:
    """Axes the batch (and hence the loss reduction) shards over on the
    non-pp mesh: the factored multislice data axis adds 'dcn' outermost."""
    return ((DCN, DATA, EXPERT) if cfg.dcn_size > 1
            else (DATA, EXPERT))


def _lm_batch_spec(cfg: LMTrainConfig) -> P:
    return P(_batch_axes(cfg), SEQ)


def _spec_axes(spec) -> set:
    out = set()
    for part in spec:
        if part is None:
            continue
        out |= set(part) if isinstance(part, tuple) else {part}
    return out


def _dcn_sync_point(params: PyTree, specs: PyTree) -> PyTree:
    """Identity whose BACKWARD owns the cotangent sync of ``params`` on
    the factored multislice mesh: the data-axis reduction runs as the
    explicit two-level algorithm — reduce-scatter('data') ->
    SHARD-SIZED psum('dcn') -> all_gather_invariant('data') — instead
    of shard_map's automatic flat psum, and each leaf's remaining
    invariant axes (expert/seq, and 'model' for tp-replicated leaves,
    read off its PartitionSpec) get their flat intra-slice psums.  The
    cotangent returns fully vma-invariant, so shard_map inserts nothing
    more: the shard-sized DCN payload is a property of the program,
    pinned by tests/test_lm.py::test_dcn_payload_is_shard_sized_lm.

    Placement is the caller's: the post-backward path wraps the WHOLE
    tree once (all dcn traffic after the backward drains); overlap=True
    wraps each layer group at its boundary (``_stream_group_boundary``),
    so the groups' sync points stream through the backward."""
    @jax.custom_vjp
    def point(p):
        return p

    def fwd(p):
        return p, None

    def bwd(_, g):
        return (_two_level_sync(g, specs),)

    point.defvjp(fwd, bwd)
    return point(params)


def _local_sync_point(params: PyTree, specs: PyTree, n_dcn: int) -> PyTree:
    """``_dcn_sync_point``'s window-local sibling (round 18): identity
    whose backward syncs cotangents over every mesh axis EXCEPT 'dcn' —
    per-leaf psums over the leaf's invariant intra-slice axes (the
    ``_fsdp_gather`` transpose already reduce-scattered fsdp leaves over
    'data'), scaled by ``n_dcn`` so each slice's local step sees its
    slice-mean gradient at the full-batch rate (equal per-slice token
    counts make the scaled slice mean an unbiased estimate of the
    global mean).  The cotangent returns dcn-VARYING by construction:
    inside a sync window no gradient byte crosses DCN — the property
    the schedule inspector pins."""
    scale = jnp.float32(n_dcn)

    @jax.custom_vjp
    def point(p):
        return p

    def fwd(p):
        return p, None

    def bwd(_, g):
        leaves, td = jax.tree.flatten(g)
        out = []
        for gl, sp in zip(leaves, jax.tree.leaves(specs)):
            axes = _spec_axes(sp)
            rest = tuple(a for a in (DATA, EXPERT, SEQ, MODEL)
                         if a not in axes)
            gl = jax.lax.psum(gl, rest) if rest else gl
            out.append(gl * scale.astype(gl.dtype))
        return (jax.tree.unflatten(td, out),)

    point.defvjp(fwd, bwd)
    return point(params)


def _sync_bucket_bytes(cfg: LMTrainConfig) -> int:
    """The factored-mesh streaming bucket size in bytes —
    ``cfg.bucket_mb`` (the round-11 tunable the autotuner sets) or the
    historical strategies.BUCKET_CAP_MB default."""
    from .parallel.strategies import BUCKET_CAP_MB
    mb = cfg.bucket_mb if cfg.bucket_mb is not None else BUCKET_CAP_MB
    return int(mb * 1024 * 1024)


def _sync_partition(g_leaves: list, s_leaves: list,
                    bucket_bytes: int | None) -> list[tuple[str, list[int]]]:
    """The ONE ordered partition of the grad tree the factored-mesh sync
    walks: fsdp ('data'-sharded) leaves first, then the remaining leaves
    grouped by their sharded-axes set (first-appearance order), each run
    split into ~bucket_bytes buckets (``strategies.make_bucket_plan``).
    Returns ``[(kind, [leaf_index, ...]), ...]`` with kind 'fsdp' or
    'two_level'.  Deterministic given shapes/specs — the layout contract
    between ``_two_level_sync``'s execution and the EF-residual sizing
    (``lm_sync_state_len``), which must never disagree."""
    from .parallel.strategies import make_bucket_plan

    groups: dict = {}
    fsdp_items: list[int] = []
    for i, sp in enumerate(s_leaves):
        axes = _spec_axes(sp)
        if DATA in axes:
            fsdp_items.append(i)
        else:
            groups.setdefault(frozenset(axes), []).append(i)

    def buckets(idxs: list[int]) -> list[list[int]]:
        if not idxs:
            return []
        if bucket_bytes is None or len(idxs) <= 1:
            return [idxs]
        plan = make_bucket_plan([g_leaves[i] for i in idxs], bucket_bytes)
        return [[idxs[j] for j in b] for b in plan]

    return ([("fsdp", b) for b in buckets(fsdp_items)]
            + [("two_level", b) for items in groups.values()
               for b in buckets(items)])


def _bucket_residual_len(kind: str, total_elems: int, n_dcn: int,
                         n_ici: int) -> int:
    """EF-residual length of one bucket's int8 DCN exchange: n_dcn x the
    block-aligned ring chunk of the payload that actually crosses DCN —
    the full (already shard-sized) flat vector for fsdp buckets, the ICI
    shard (``two_level_psum`` pads to an n_ici multiple) otherwise."""
    from .parallel.strategies import QuantizedRing
    base = total_elems if kind == "fsdp" else -(-total_elems // n_ici)
    return n_dcn * QuantizedRing()._chunk(base, n_dcn)


def _residual_total_len(g_leaves: list, s_leaves: list, n_dcn: int,
                        n_ici: int, bucket_bytes: int | None) -> int:
    """Total EF-residual length for one sync of ``g_leaves`` — segments
    in ``_sync_partition`` order (the consumption order of
    ``_two_level_sync``)."""
    total = 0
    for kind, idxs in _sync_partition(g_leaves, s_leaves, bucket_bytes):
        elems = sum(int(g_leaves[i].size) for i in idxs)
        total += _bucket_residual_len(kind, elems, n_dcn, n_ici)
    return total


def _two_level_sync(g: PyTree, specs: PyTree,
                    bucket_bytes: int | None = None,
                    dcn_compress: str | None = None,
                    residual: jax.Array | None = None):
    """The factored-mesh gradient sync itself (shared by the custom-VJP
    points and the grad-accumulation path): per-leaf
    flat psums over each leaf's remaining invariant axes, then the
    grouped two-level (data, dcn) reduction over the ``_sync_partition``
    buckets.  Leaves are grouped by their sharded axes:
    ``two_level_psum`` flattens a group into ONE vector, so mixing
    (say) tp-sharded leaves — whose values legitimately vary over
    'model' — with replicated ones would poison the latter's vma.

    ``bucket_bytes`` (round 9, the grad-accumulation path) splits each
    group into ~bucket-sized pipelines (``strategies.make_bucket_plan``)
    instead of one monolithic flat vector per group: bucket N's ICI
    reduce-scatter can run under bucket N-1's DCN psum.  The plain
    reduction is elementwise, so the split changes no sums — numerics
    are bitwise bucket-independent (test-pinned).

    FSDP leaves ('data' in the spec) skip the two-level reduction
    entirely: the ``_fsdp_gather`` transpose already reduce-scattered
    their cotangent over 'data', so what arrives here IS the
    slice-local ZeRO-3 shard — the cross-slice exchange is one
    shard-sized ``psum('dcn')`` per bucket, the same DCN payload as the
    replicated-state path.

    ``dcn_compress="int8"`` (round 11) replaces every bucket's DCN
    exchange with ``QuantizedRing._ring_sum`` — int8 payloads + per-row
    f32 scales on each cross-slice transfer, the ICI steps untouched —
    consuming/refilling ``residual`` segments in partition order and
    returning ``(synced, new_residual)``.  ``"int4"`` (round 16) is the
    same exchange one rung lower: nibble-packed payloads, half the DCN
    bytes, identical residual layout (``_chunk`` is bits-independent).
    Numerics become bucket-LAYOUT-dependent through the row scales (the
    layout is the partition above, shared with the residual sizing).

    Round 20: every bucket body is a routed ``HopPlan`` compiled by
    ``parallel/routing.execute`` — fsdp buckets run the single-hop
    ``dcn:psum`` (leaf mode, one multi-operand psum) or
    ``dcn:ring[bits+ef]`` route, two_level buckets the ``data:rs →
    dcn:… → data:ag`` route via ``two_level_psum`` (itself routed).
    The op sequences are identical; the pre-existing loss/census/EF
    pins on this function now pin the route compiler."""
    from .parallel import routing
    from .parallel.strategies import QuantizedRing, two_level_psum

    g_leaves, td = jax.tree.flatten(g)
    s_leaves = jax.tree.leaves(specs)
    synced_in: list = []
    for gl, sp in zip(g_leaves, s_leaves):
        axes = _spec_axes(sp)
        rest = tuple(a for a in (EXPERT, SEQ, MODEL)
                     if a not in axes)
        synced_in.append(jax.lax.psum(gl, rest) if rest else gl)
    part = _sync_partition(g_leaves, s_leaves, bucket_bytes)
    out: list = [None] * len(g_leaves)
    if dcn_compress is None:
        for kind, idxs in part:
            vals = [synced_in[i] for i in idxs]
            if kind == "fsdp":
                # one psum primitive per bucket, per-leaf payloads (no
                # concat: leaves keep their own vma; each is already
                # data-shard-sized)
                synced, _ = routing.execute(
                    routing.HopPlan((routing.Hop("exchange", DCN),)),
                    vals, concat=False)
            else:
                synced = two_level_psum(vals, DCN, DATA)
            for i, s in zip(idxs, synced):
                out[i] = s
        return jax.tree.unflatten(td, out)
    # quantized DCN hop (int8 round 11, int4 round 16): ring-exchange
    # each bucket at the configured bit width, EF residual segments
    # consumed and refilled in partition order
    ring = QuantizedRing(bits=4 if dcn_compress == "int4" else 8)
    n_dcn = jax.lax.axis_size(DCN)
    n_ici = jax.lax.axis_size(DATA)
    offset = 0
    new_parts: list = []
    for kind, idxs in part:
        vals = [synced_in[i] for i in idxs]
        elems = sum(int(g_leaves[i].size) for i in idxs)
        seg = _bucket_residual_len(kind, elems, n_dcn, n_ici)
        res = residual[offset:offset + seg]
        offset += seg
        if kind == "fsdp":
            # the bucket is already shard-sized: ring the concatenated
            # flat vector across slices directly (the single-hop
            # dcn:ring[bits+ef] route)
            synced, new_r = routing.execute(
                routing.HopPlan((routing.Hop(
                    "exchange", DCN, algorithm="ring",
                    bits=dcn_compress, ef=True),)),
                vals, residuals=[res])
            new_parts.extend(new_r)
        else:
            captured: dict = {}

            def dcn_reduce(shard, res=res, captured=captured):
                summed, err_rows = ring._ring_sum(shard, DCN, n_dcn,
                                                  residual=res)
                captured["res"] = err_rows.ravel()
                return summed

            synced = two_level_psum(vals, DCN, DATA, dcn_reduce=dcn_reduce)
            new_parts.append(captured["res"])
        for i, s in zip(idxs, synced):
            out[i] = s
    new_residual = (jnp.concatenate(new_parts) if new_parts
                    else jnp.zeros((0,), jnp.float32))
    return jax.tree.unflatten(td, out), new_residual


def _dcn_sync_point_stateful(params: PyTree, residual: jax.Array,
                             specs: PyTree,
                             bucket_bytes: int | None,
                             dcn_compress: str = "int8") -> PyTree:
    """``_dcn_sync_point`` with the quantized (int8 or int4) DCN hop:
    the EF residual rides the forward as an inert second input and its
    COTANGENT channel carries the updated residual out of the backward
    (the strategies.sync_boundary_stateful trick) — differentiate the
    loss w.r.t. ``(params, sync_state)`` and the sync-state "gradient"
    IS the next step's carry."""
    @jax.custom_vjp
    def point(p, r):
        return p

    def fwd(p, r):
        return p, r

    def bwd(r, g):
        synced, new_r = _two_level_sync(g, specs, bucket_bytes=bucket_bytes,
                                        dcn_compress=dcn_compress,
                                        residual=r)
        return synced, new_r

    point.defvjp(fwd, bwd)
    return point(params, residual)


def _local_sized_leaves(shapes: PyTree, specs: PyTree,
                        axis_sizes: dict[str, int]) -> list:
    """Per-leaf LOCAL (per-device shard) sizes of a param subtree in
    flatten order — the shapes the grad cotangents have at the sync
    point inside shard_map (fsdp leaves arrive data-shard-sized, tp
    leaves model-shard-sized).  Leaves are ``strategies.SizedLeaf``
    stand-ins — the ONE shapes-only contract ``make_bucket_plan``
    reads."""
    from .parallel.strategies import SizedLeaf
    out: list[SizedLeaf] = []
    for sh, sp in zip(jax.tree.leaves(shapes), jax.tree.leaves(specs)):
        dims = list(sh.shape)
        for d, ax in enumerate(sp):
            if ax is None:
                continue
            for name in (ax if isinstance(ax, tuple) else (ax,)):
                dims[d] //= axis_sizes[name]
        out.append(SizedLeaf(int(np.prod(dims, dtype=np.int64) or 1),
                             sh.dtype))
    return out


def lm_sync_state_len(cfg: LMTrainConfig, mesh: Mesh) -> int:
    """Total per-device EF-residual length for ``dcn_compress="int8"``
    — the layout contract between LMTrainer's ``sync_state`` init and
    the step's consumption order: the whole-tree partition for the
    post-backward and grad-accumulation paths, or the per-layer-group
    partitions in forward (group-index) order under streaming
    ``overlap`` (exactly the walk ``_stream_group_boundary`` makes).
    Under sync windows (``sync_every > 1``) the quantized exchange
    happens ONLY at the whole-tree window boundary — local steps stream
    ICI-only points with no residual — so the layout is the whole-tree
    partition even when ``overlap`` is on."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_dcn, n_ici = sizes[DCN], sizes[DATA]
    bucket_bytes = _sync_bucket_bytes(cfg)
    specs = param_specs(cfg)
    shapes = jax.eval_shape(lambda k: tfm.init(k, cfg.model),
                            jax.random.key(0))
    streamed = (cfg.overlap and cfg.grad_accum == 1
                and cfg.sync_every == 1)
    if not streamed:
        return _residual_total_len(
            _local_sized_leaves(shapes, specs, sizes),
            jax.tree.leaves(specs), n_dcn, n_ici, bucket_bytes)
    total = 0
    for key, _ in sorted(tfm.sync_group_index(cfg.model).items(),
                         key=lambda kv: kv[1]):
        total += _residual_total_len(
            _local_sized_leaves(shapes[key], specs[key], sizes),
            jax.tree.leaves(specs[key]), n_dcn, n_ici, bucket_bytes)
    return total


def _stream_group_boundary(cfg: LMTrainConfig, specs, *, dcn_sync: bool,
                           residual: jax.Array | None = None,
                           local_n_dcn: int | None = None):
    """The streaming (``cfg.overlap``) layer-group hook: at each group's
    boundary in ``transformer.apply``, wrap the group's params in the
    two-level DCN sync point (``dcn_sync``, round 9) and/or gather its
    ZeRO-3 shards (``cfg.fsdp``, round 8) — instead of doing either
    all-at-once on the whole tree.  The ops are IDENTICAL to the
    whole-tree path (the two-level reduction is elementwise, the gathers
    are the same per-leaf all_gathers) — only their position moves, so
    trajectories are bitwise-identical; in the backward, each group's
    gradient reduce-scatter (the gather's transpose) runs first and the
    sync point's shard-sized ``psum('dcn')`` immediately after, right
    where that group's backward matmuls finish — the per-layer-group
    streaming the latency-hiding scheduler needs (utils/debug.py
    op_schedule pins the dcn-axis interleaving)."""
    # one source of truth for the boundary numbering: the model's own
    # group schedule (transformer.sync_group_index), inverted to
    # group-index -> top-level param key
    keys = {v: k for k, v in tfm.sync_group_index(cfg.model).items()}
    bucket_bytes = _sync_bucket_bytes(cfg)
    # int8 streaming (round 11): each group's stateful point consumes
    # its own residual slice; offsets advance in boundary (= group,
    # = forward) order, the same walk lm_sync_state_len sizes — the
    # closure counter is fresh per trace (the boundary is rebuilt
    # inside each loss trace).
    state = {"off": 0}

    def boundary(group: int, params):
        k = keys.get(group)
        if k is None:
            return params
        p = dict(params)
        sub = p[k]
        # forward order: sync point THEN gather, so the backward runs the
        # gather's reduce-scatter first and the point's psum('dcn') on
        # the already-scattered shard — the whole-tree op sequence
        if local_n_dcn is not None:
            # window-local streaming (round 18): the group's sync point
            # stays at its boundary but reduces intra-slice only — the
            # latency-hiding interleave without the DCN hop
            sub = _local_sync_point(sub, specs[k], local_n_dcn)
        elif dcn_sync:
            if residual is not None:
                n_dcn = jax.lax.axis_size(DCN)
                n_ici = jax.lax.axis_size(DATA)
                seg = _residual_total_len(
                    jax.tree.leaves(sub), jax.tree.leaves(specs[k]),
                    n_dcn, n_ici, bucket_bytes)
                a = state["off"]
                state["off"] = a + seg
                sub = _dcn_sync_point_stateful(sub, residual[a:a + seg],
                                               specs[k], bucket_bytes,
                                               cfg.dcn_compress)
            else:
                sub = _dcn_sync_point(sub, specs[k])
        if cfg.fsdp:
            sub = _fsdp_gather(sub, specs[k], cfg.fsdp_gather_dtype)
        p[k] = sub
        return p

    return boundary


def _build_local_loss(cfg: LMTrainConfig, specs, *, dcn_sync: bool,
                      local_window: bool = False):
    """The per-shard loss shared by every grad path.  ``dcn_sync``
    injects the custom-VJP two-level sync point on params (the a=1
    factored-mesh path); the accumulation path passes False and syncs
    ONCE after its local scan instead.  ``local_window`` (round 18, the
    sync_every > 1 local steps) injects the ICI-only sync point
    (``_local_sync_point``) instead — same streaming positions under
    ``overlap``, no DCN traffic, cotangents dcn-varying; the window
    boundary exchange handles the cross-slice hop (and the EF residual,
    when compressed) in its own program.

    With ``cfg.dcn_compress`` AND ``dcn_sync`` the returned loss is the
    STATEFUL variant ``(params, residual, tokens, targets, n_total,
    aux_w)``: the sync points become their int8-ring stateful forms and
    differentiating w.r.t. ``residual`` yields the updated EF carry
    (round 11)."""
    dtype = cfg.dtype
    # tp psums always run (free over a size-1 'model' axis) — they also carry
    # the vma bookkeeping that makes the loss provably replicated.  The ring
    # only replaces local flash attention when the seq axis is actually cut.
    tp_axis = MODEL
    seq_axis = SEQ if cfg.sp > 1 else None
    reduce_axes = _batch_axes(cfg) + (SEQ,)
    stateful = (cfg.dcn_compress is not None and dcn_sync
                and not local_window)
    bucket_bytes = _sync_bucket_bytes(cfg)

    def local_loss(params, tokens, targets, n_total, aux_w, residual=None):
        boundary = None
        if cfg.overlap and (dcn_sync or cfg.fsdp or local_window):
            # streaming (rounds 8-9): per-layer-group sync points and/or
            # ZeRO-3 gathers at the boundaries instead of whole-tree
            boundary = _stream_group_boundary(
                cfg, specs, dcn_sync=dcn_sync and not local_window,
                residual=residual,
                local_n_dcn=cfg.dcn_size if local_window else None)
        else:
            if local_window:
                params = _local_sync_point(params, specs, cfg.dcn_size)
            elif dcn_sync:
                if residual is not None:
                    # stateful whole-tree point: the quantized-ring
                    # exchange with the EF residual channel (round 11;
                    # int4 rung round 16)
                    params = _dcn_sync_point_stateful(
                        params, residual, specs, bucket_bytes,
                        cfg.dcn_compress)
                else:
                    # route the data-axis cotangent sync through the
                    # explicit two-level reduction (shard-sized DCN
                    # payload), as one whole-tree point — the
                    # post-backward contrast shape
                    params = _dcn_sync_point(params, specs)
            if cfg.fsdp:
                params = _fsdp_gather(params, specs,
                                      cfg.fsdp_gather_dtype)
        pos = _shard_positions(cfg, tokens.shape[1])
        # the unified head-loss seam (round 17, ops/losses.py): apply
        # hands the final-norm hidden states + the boundary-transformed
        # tied embedding (under streaming ZeRO-3 the GATHERED copy) to
        # head_loss, which routes dense (historical ops, bit-for-bit) or
        # chunked (streamed logits; vocab tp-sharded when tp > 1)
        head = partial(losses.head_loss, targets=targets,
                       loss_impl=cfg.loss_impl, loss_chunk=cfg.loss_chunk,
                       tp_axis=tp_axis if cfg.tp > 1 else None,
                       tp_size=cfg.tp)
        out = tfm.apply(
            params, tokens, cfg=cfg.model, dtype=dtype,
            seq_axis=seq_axis, seq_layout=cfg.seq_layout,
            tp_axis=tp_axis, pos=pos,
            ep_axis=EXPERT if cfg.ep > 1 else None,
            return_aux=True, boundary=boundary,
            matmul_dtype=cfg.matmul_dtype, remat=cfg.remat,
            head_fn=head, return_stats=cfg.model.moe_dropless)
        (ce_sum, _), aux = out[:2]
        # Global mean over every shard's tokens; the batch shards over
        # (data, expert), so 'expert' reduces like a data axis ('model'
        # shards compute identical values, no reduction needed there).
        # ``n_total`` is the caller-counted GLOBAL valid-token count of the
        # step's full batch — under gradient accumulation each microbatch
        # contributes ce_sum_i/n_total with aux_w = coef/A, so the SUM of
        # microbatch grads is exactly the unaccumulated step's gradient.
        ce_sum = jax.lax.psum(ce_sum, reduce_axes)
        aux = jax.lax.pmean(aux, reduce_axes)  # pmean'd over MODEL
        loss = ce_sum / jnp.maximum(n_total, 1) + aux_w * aux
        if cfg.model.moe_dropless:
            # the layers' counters of the whole step, beside the loss
            # (value_and_grad's aux): step_metric_names' order
            st = out[2]
            return loss, jnp.stack([
                _STAT_REDUCE.get(k, jax.lax.pmean)(st[k], reduce_axes)
                for k in cfg.model.stat_names()])
        return loss

    if stateful:
        def local_loss_st(params, residual, tokens, targets, n_total,
                          aux_w):
            return local_loss(params, tokens, targets, n_total, aux_w,
                              residual=residual)
        return local_loss_st
    return local_loss


def _make_grad_step(cfg: LMTrainConfig, mesh: Mesh):
    """The ONE shard_mapped loss-and-grad builder shared by the single-step
    and K-step-scan train paths (their loss semantics must never drift).

    With ``cfg.dcn_compress`` (round 11) the returned fn is stateful:
    ``(params, sync_state, tokens, targets, n_total, aux_w) -> (loss,
    grads, new_sync_state)``, the per-device EF residual carried as a
    ``(n_devices, L)`` array sharded one row per device."""
    specs = param_specs(cfg)
    local_loss = _build_local_loss(cfg, specs,
                                   dcn_sync=cfg.dcn_size > 1)
    bspec = _lm_batch_spec(cfg)
    if cfg.dcn_compress is None or cfg.dcn_size <= 1:
        # a dropless model's loss comes with its counters: ((loss, stats),
        # grads) in place of (loss, grads)
        counted = cfg.model.moe_dropless
        return shard_map(
            jax.value_and_grad(local_loss, has_aux=counted),
            mesh=mesh,
            in_specs=(specs, bspec, bspec, P(), P()),
            out_specs=((P(), P()) if counted else P(), specs),
            # check_vma stays ON: the automatic psum of cotangents for
            # axis-invariant params (the fused DP/SP gradient sync)
            # depends on it.
        )
    rspec = P(tuple(mesh.axis_names))
    vg = jax.value_and_grad(local_loss, argnums=(0, 1))

    def stateful(params, res, tokens, targets, n_total, aux_w):
        loss, (grads, new_r) = vg(params, res[0], tokens, targets,
                                  n_total, aux_w)
        return loss, grads, new_r[None]

    return shard_map(
        stateful, mesh=mesh,
        in_specs=(specs, rspec, bspec, bspec, P(), P()),
        out_specs=(P(), specs, rspec),
        # the int8 ring assembles its result from ppermute payloads —
        # replicated by construction, not provably (the vma_opaque trade
        # train.py makes for the same strategy); every param's data-axis
        # sync is EXPLICIT through the stateful point, so nothing here
        # relies on the automatic cotangent psums check_vma enables.
        check_vma=False)


def _make_accum_grad_step(cfg: LMTrainConfig, mesh: Mesh):
    """Gradient accumulation with ONE cross-device exchange per
    optimizer step, for the factored multislice mesh: the A microbatch
    backwards run with NO cross-slice traffic inside one shard_map (the
    per-microbatch collectives are intra-slice only: the loss's scalar
    psums, plus the ZeRO-3 weight gathers / gradient reduce-scatters
    when fsdp is on), local grads accumulate through a lax.scan, and
    the accumulated tree syncs once — per-leaf intra psums + the
    grouped two-level (data, dcn) reduction (shard-sized psum('dcn')
    for fsdp leaves), emitted per ~25 MB bucket (round 9) so the
    exchange pipelines instead of moving as one monolithic per-group
    vector.  The naive alternative (scanning the synced grad_step)
    pays A sequential shard-sized DCN round-trips per step.

    ``(params, micro_tokens (A, B, S), micro_targets, n_total, aux_w)
    -> (summed loss, synced grads)``; numerics match the scanned path
    to f32 reassociation noise (sum-then-sync == sync-then-sum)."""
    specs = param_specs(cfg)
    local_loss = _build_local_loss(cfg, specs, dcn_sync=False)
    grad_fn = jax.value_and_grad(local_loss)
    bucket_bytes = _sync_bucket_bytes(cfg)

    def local_grads(params, micro_t, micro_y, n_total, aux_w):
        def body(carry, batch):
            loss_acc, g_acc = carry
            tk, tg = batch
            loss_i, g_i = grad_fn(params, tk, tg, n_total, aux_w)
            return (loss_acc + loss_i,
                    jax.tree.map(jnp.add, g_acc, g_i)), None

        zeros = jax.tree.map(jnp.zeros_like, params)
        (loss, g), _ = jax.lax.scan(
            body, (jnp.float32(0), zeros), (micro_t, micro_y))
        return loss, g

    bspec = _lm_batch_spec(cfg)
    mspec = P(None, *bspec)  # leading scan axis unsharded
    if cfg.dcn_compress is None:
        def local_accum(params, micro_t, micro_y, n_total, aux_w):
            loss, g = local_grads(params, micro_t, micro_y, n_total, aux_w)
            # the ONE post-accumulation sync, streamed per ~bucket_mb
            # bucket (round 9) instead of as a monolithic per-group
            # tree: bucket N's ICI reduce-scatter runs under bucket
            # N-1's DCN psum
            return loss, _two_level_sync(g, specs,
                                         bucket_bytes=bucket_bytes)

        return shard_map(
            local_accum, mesh=mesh,
            in_specs=(specs, mspec, mspec, P(), P()),
            out_specs=(P(), specs))

    # quantized DCN hop (round 11; int4 rung round 16): the one
    # post-accumulation exchange rides the ring with the EF residual
    # threaded through directly (no custom-vjp needed — the sync runs
    # OUTSIDE the microbatch autodiff)
    rspec = P(tuple(mesh.axis_names))

    def local_accum_st(params, res, micro_t, micro_y, n_total, aux_w):
        loss, g = local_grads(params, micro_t, micro_y, n_total, aux_w)
        synced, new_r = _two_level_sync(g, specs, bucket_bytes=bucket_bytes,
                                        dcn_compress=cfg.dcn_compress,
                                        residual=res[0])
        return loss, synced, new_r[None]

    return shard_map(
        local_accum_st, mesh=mesh,
        in_specs=(specs, rspec, mspec, mspec, P(), P()),
        out_specs=(P(), specs, rspec),
        # vma_opaque: the ring's ppermute-assembled result (see
        # _make_grad_step's compressed branch)
        check_vma=False)


# the ONE implementation of the round-13 [grad-norm, param-norm]
# telemetry vector lives next to the loss primitives (ops/nn.py
# step_metrics) — train.py's in-scan body uses the same function
_step_metrics = step_metrics


def _make_window_grad_step(cfg: LMTrainConfig, mesh: Mesh):
    """The window-LOCAL loss-and-grad program (round 18,
    ``sync_every > 1``): each 'dcn' slice forwards at its own params
    ``p = anchor + delta[slice]`` and its gradient syncs over the
    intra-slice axes only (``_local_sync_point`` — ICI traffic, scaled
    x n_dcn), so the returned grads are dcn-VARYING and come back
    STACKED over a leading 'dcn' axis (one slice's slice-mean estimate
    per row).  ``(anchor, delta, tokens, targets, n_total, aux_w) ->
    (loss, grads)`` with loss still the global scalar (each slice's
    tokens scored under its own slice params — scalar psums only)."""
    specs = param_specs(cfg)
    local_loss = _build_local_loss(cfg, specs, dcn_sync=False,
                                   local_window=True)
    bspec = _lm_batch_spec(cfg)
    dspec = jax.tree.map(lambda s: P(DCN, *s), specs)

    def _vary_dcn(a):
        if DCN in compat.vma_of(a):
            return a
        return compat.pcast(a, (DCN,), to="varying")

    def local(anchor, delta, tokens, targets, n_total, aux_w):
        # anchor is dcn-invariant, the delta block dcn-varying: cast the
        # anchor varying so the sum is well-typed under check_vma
        p = jax.tree.map(lambda a, d: _vary_dcn(a) + d[0], anchor, delta)
        loss, g = jax.value_and_grad(local_loss)(
            p, tokens, targets, n_total, aux_w)
        return loss, jax.tree.map(lambda x: x[None], g)

    return shard_map(
        local, mesh=mesh,
        in_specs=(specs, dspec, bspec, bspec, P(), P()),
        out_specs=(P(), dspec))


def _lm_window_wire_bytes(cfg: LMTrainConfig, mesh: Mesh) -> int:
    """Predicted per-device DCN payload bytes of ONE window-boundary
    delta exchange (f32, pre-quantization) — the whole-tree
    ``_sync_partition`` walk the boundary program makes: fsdp buckets
    are already data-shard-sized, two-level buckets cross DCN as their
    ICI shard.  Feeds the per-window ``window_wire_bytes`` telemetry
    gauge (utils/telemetry.emit_sync_windows)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_data = sizes[DATA]
    specs = param_specs(cfg)
    shapes = jax.eval_shape(lambda k: tfm.init(k, cfg.model),
                            jax.random.key(0))
    leaves = _local_sized_leaves(shapes, specs, sizes)
    total = 0
    for kind, idxs in _sync_partition(leaves, jax.tree.leaves(specs),
                                      _sync_bucket_bytes(cfg)):
        elems = sum(int(leaves[i].size) for i in idxs)
        total += 4 * (elems if kind == "fsdp" else -(-elems // n_data))
    return total


def _lm_outer(cfg: LMTrainConfig):
    """The configured DiLoCo outer optimizer, or None for the plain-mean
    boundary — also None when trivial (momentum==0 ∧ lr==1), the
    build-time collapse that keeps zero-momentum bitwise ≡ round 18."""
    from .parallel.strategies import OuterOptimizer
    if cfg.sync_every > 1 and cfg.outer_opt is not None:
        outer = OuterOptimizer(cfg.outer_opt, cfg.outer_momentum,
                               cfg.outer_lr)
        if not outer.trivial:
            return outer
    return None


def make_lm_window_steps(cfg: LMTrainConfig, mesh: Mesh):
    """The communication-sparse program family (round 18,
    ``sync_every = H > 1`` on the factored multislice mesh):

    - ``local``: one optimizer step with NO cross-slice traffic —
      ``(anchor, delta, opt_state, tokens, targets[, step_no,
      fault_arm]) -> (delta, opt_state, loss, ok, met)``.  ``delta``
      (the accumulated optax updates since the last exchange) and the
      optimizer state carry a leading 'dcn' axis: each slice advances
      its own Adam trajectory at ``p = anchor + delta[slice]``
      (``jax.vmap`` over the slice axis; the anchor — the live
      ``LMTrainer.params`` — is read-only here).  ``ok``/``met`` cover
      ALL slices (gsq sums the stacked grads; the param-norm runs over
      the stacked tree, ~sqrt(n_dcn) x the per-slice figure).
    - ``exchange`` (staleness 0): average the deltas across 'dcn'
      through the SAME bucketed two-level reduction the per-step path
      uses (``_two_level_sync`` — dcn_compress rides it with the EF
      residual, now charged once per window), fold the mean into the
      anchor, zero the delta.  Each leaf prescales by
      1/(n_dcn * n_rest [* n_data]) so the redundant intra-slice psums
      cancel exactly and what lands is the plain mean over slices.
    - ``launch``/``apply`` (staleness S > 0): ``launch`` runs the same
      exchange but leaves anchor and delta untouched, returning the
      averaged delta and a SNAPSHOT of the launched delta; ``apply``
      (dispatched S steps later) folds the average into the anchor and
      subtracts the snapshot from the live delta — local progress made
      during the S steps is kept, and the DCN round-trip has S local
      steps to drain under.

    Round 22 grows two build-time variants on the boundary programs
    (the legacy plain-mean/uniform branches stay byte-identical):

    - ``cfg.outer_opt``: ``exchange``/``apply`` take (and return) the
      DiLoCo outer-momentum tree ``m`` and move the anchor by
      ``outer_opt(mean delta)`` instead of the plain add.
    - ``cfg.sync_every_per_slice``: ``exchange`` takes a host-computed
      (n_dcn,) f32 participation MASK — slices with mask==0 contribute
      an exact zero delta (masked before prescale, inside the
      shard_map, so the EF residual ledger stays exact) and keep their
      accumulated delta; the mean still divides by all n_dcn slices
      and every slice adopts the anchor move, so params stay
      replicated.  Argument order: ``[anchor, delta]``
      ``+ [sync_state] if dcn_compress + [m] if outer + [mask] if
      per-slice``; returns mirror the inputs minus the mask."""
    tx = make_optimizer(cfg)
    grad_step = _make_window_grad_step(cfg, mesh)
    specs = param_specs(cfg)
    dspec = jax.tree.map(lambda s: P(DCN, *s), specs)
    bucket_bytes = _sync_bucket_bytes(cfg)
    n_dcn = cfg.dcn_size
    n_data = cfg.dp // cfg.dcn_size
    coef = jnp.float32(cfg.aux_coef)
    compress = cfg.dcn_compress is not None
    rspec = P(tuple(mesh.axis_names))
    rest_sizes = {EXPERT: cfg.ep, SEQ: cfg.sp, MODEL: cfg.tp}

    def _prescale(dl, sp):
        axes = _spec_axes(sp)
        n_rest = int(np.prod([rest_sizes[a]
                              for a in (EXPERT, SEQ, MODEL)
                              if a not in axes], dtype=np.int64))
        denom = n_dcn * n_rest * (1 if DATA in axes else n_data)
        return dl * jnp.asarray(1.0 / denom, dl.dtype)

    def _vary_all(x):
        missing = tuple(a for a in mesh.axis_names
                        if a not in compat.vma_of(x))
        return compat.pcast(x, missing, to="varying") if missing else x

    outer = _lm_outer(cfg)
    use_outer = outer is not None
    per_slice = cfg.sync_every_per_slice is not None

    def _ex_core(delta, residual, mask=None):
        d = jax.tree.map(lambda x: x[0], delta)
        if mask is not None:
            # per-slice windows (round 22): zero a skipping slice's
            # contribution BEFORE prescale, inside the shard_map — the
            # downstream int8/int4 ring quantizes the masked value, so
            # the EF residual ledger stays exact (invariant-pinned)
            my = mask[jax.lax.axis_index(DCN)]
            d = jax.tree.map(lambda x: x * my.astype(x.dtype), d)
        d = jax.tree.map(_prescale, d, specs)
        d = jax.tree.map(_vary_all, d)
        if compress:
            d_avg, new_r = _two_level_sync(
                d, specs, bucket_bytes=bucket_bytes,
                dcn_compress=cfg.dcn_compress, residual=residual[0])
            return d_avg, new_r[None]
        return _two_level_sync(d, specs, bucket_bytes=bucket_bytes)

    ex_core_m = None
    if compress:
        if per_slice:
            ex_core_m = shard_map(
                _ex_core, mesh=mesh, in_specs=(dspec, rspec, P()),
                out_specs=(specs, rspec), check_vma=False)
        ex_core = shard_map(
            _ex_core, mesh=mesh, in_specs=(dspec, rspec),
            out_specs=(specs, rspec),
            # the ring's ppermute-assembled result (see _make_grad_step)
            check_vma=False)
    else:
        if per_slice:
            ex_core_m = shard_map(
                lambda delta, mask: _ex_core(delta, None, mask),
                mesh=mesh, in_specs=(dspec, P()), out_specs=specs,
                # the varying-index mask gather defeats the static
                # replication proof the same way the ring assembly does
                check_vma=False)
        ex_core = shard_map(
            lambda delta: _ex_core(delta, None), mesh=mesh,
            in_specs=(dspec,), out_specs=specs)

    def _mask_reset(delta, mask):
        # participants (mask==1) restart their window from zero;
        # skippers keep the accumulated delta — a jnp.where select, so
        # the kept values are bitwise untouched
        def reset(x):
            mb = mask.reshape((n_dcn,) + (1,) * (x.ndim - 1))
            return jnp.where(mb != 0, jnp.zeros_like(x), x)
        return jax.tree.map(reset, delta)

    @partial(jax.jit, donate_argnums=compat.donate(1, 2))
    def local_step(anchor, delta, opt_state, tokens, targets, step_no=0,
                   fault_arm=0.0):
        tokens = _zigzag_global(cfg, tokens)
        targets = _zigzag_global(cfg, targets)
        n_total = jnp.sum(targets != IGNORE).astype(jnp.float32)
        loss, grads = grad_step(anchor, delta, tokens, targets, n_total,
                                coef)
        grads = faults.tap_grads(grads, step_no, fault_arm)
        loss = faults.tap_loss(loss, step_no, fault_arm)
        gsq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                  for g in jax.tree.leaves(grads))
        ok = (jnp.isfinite(loss) & jnp.isfinite(gsq)).astype(jnp.float32)
        p = jax.tree.map(lambda a, d: a[None] + d, anchor, delta)
        updates, opt_state = jax.vmap(tx.update)(grads, opt_state, p)
        delta = jax.tree.map(jnp.add, delta, updates)
        met = _step_metrics(
            gsq, jax.tree.map(lambda a, d: a[None] + d, anchor, delta))
        return delta, opt_state, loss, ok, met

    if compress:
        if use_outer and per_slice:
            @partial(jax.jit, donate_argnums=compat.donate(0, 1, 2, 3))
            def exchange(anchor, delta, sync_state, m, mask):
                d_avg, sync_state = ex_core_m(delta, sync_state, mask)
                anchor, m = outer.apply(anchor, d_avg, m)
                return anchor, _mask_reset(delta, mask), sync_state, m
        elif use_outer:
            @partial(jax.jit, donate_argnums=compat.donate(0, 1, 2, 3))
            def exchange(anchor, delta, sync_state, m):
                d_avg, sync_state = ex_core(delta, sync_state)
                anchor, m = outer.apply(anchor, d_avg, m)
                return (anchor, jax.tree.map(jnp.zeros_like, delta),
                        sync_state, m)
        elif per_slice:
            @partial(jax.jit, donate_argnums=compat.donate(0, 1, 2))
            def exchange(anchor, delta, sync_state, mask):
                d_avg, sync_state = ex_core_m(delta, sync_state, mask)
                anchor = jax.tree.map(jnp.add, anchor, d_avg)
                return anchor, _mask_reset(delta, mask), sync_state
        else:
            @partial(jax.jit, donate_argnums=compat.donate(0, 1, 2))
            def exchange(anchor, delta, sync_state):
                d_avg, sync_state = ex_core(delta, sync_state)
                anchor = jax.tree.map(jnp.add, anchor, d_avg)
                return (anchor, jax.tree.map(jnp.zeros_like, delta),
                        sync_state)

        @partial(jax.jit, donate_argnums=compat.donate(1))
        def launch(delta, sync_state):
            d_avg, sync_state = ex_core(delta, sync_state)
            # delta passes through UNDONATED: the output is the
            # snapshot copy `apply` subtracts S steps later (the live
            # delta keeps evolving — and gets donated — in between)
            return d_avg, delta, sync_state
    else:
        if use_outer and per_slice:
            @partial(jax.jit, donate_argnums=compat.donate(0, 1, 2))
            def exchange(anchor, delta, m, mask):
                d_avg = ex_core_m(delta, mask)
                anchor, m = outer.apply(anchor, d_avg, m)
                return anchor, _mask_reset(delta, mask), m
        elif use_outer:
            @partial(jax.jit, donate_argnums=compat.donate(0, 1, 2))
            def exchange(anchor, delta, m):
                d_avg = ex_core(delta)
                anchor, m = outer.apply(anchor, d_avg, m)
                return anchor, jax.tree.map(jnp.zeros_like, delta), m
        elif per_slice:
            @partial(jax.jit, donate_argnums=compat.donate(0, 1))
            def exchange(anchor, delta, mask):
                d_avg = ex_core_m(delta, mask)
                anchor = jax.tree.map(jnp.add, anchor, d_avg)
                return anchor, _mask_reset(delta, mask)
        else:
            @partial(jax.jit, donate_argnums=compat.donate(0, 1))
            def exchange(anchor, delta):
                d_avg = ex_core(delta)
                anchor = jax.tree.map(jnp.add, anchor, d_avg)
                return anchor, jax.tree.map(jnp.zeros_like, delta)

        @jax.jit
        def launch(delta):
            return ex_core(delta), delta

    if use_outer:
        # staleness-deferred apply with the outer step: the momentum
        # update happens where the mean delta actually lands
        @partial(jax.jit, donate_argnums=compat.donate(0, 1, 2, 3, 4))
        def apply_pending(anchor, delta, d_avg, snap, m):
            anchor, m = outer.apply(anchor, d_avg, m)
            delta = jax.tree.map(jnp.subtract, delta, snap)
            return anchor, delta, m
    else:
        @partial(jax.jit, donate_argnums=compat.donate(0, 1, 2, 3))
        def apply_pending(anchor, delta, d_avg, snap):
            anchor = jax.tree.map(jnp.add, anchor, d_avg)
            delta = jax.tree.map(jnp.subtract, delta, snap)
            return anchor, delta

    return local_step, exchange, launch, apply_pending


def make_lm_train_step(cfg: LMTrainConfig, mesh: Mesh):
    """Compiled step: (params, opt_state, tokens, targets[, step_no]) ->
    (params, opt_state, loss, ok, met).  tokens/targets are
    (global_batch, global_seq) int32, sharded (data+expert, seq).
    ``ok`` is the per-step health flag (1.0 = loss and synced grads
    finite — one sum-of-squares pass, the training sentry's in-scan
    detection signal); ``met`` is the (2,) [grad-norm, param-norm]
    telemetry vector (``_step_metrics``); ``step_no`` (default 0) only
    matters to the chaos-harness taps, which trace to nothing without
    an installed FaultPlan.
    With ``cfg.grad_accum = A > 1``
    the batch is split into A microbatches scanned with gradient
    accumulation and ONE optimizer update — peak activation memory drops
    by ~A at the cost of A sequential forward/backward passes.  The CE
    gradient is EXACT (grads normalize by the full batch's token count, so
    microbatch mask imbalance reweights nothing); the MoE aux term is a
    per-routing-group statistic and shifts with the group split, as with
    any dp/tp regrouping."""
    tx = make_optimizer(cfg)
    grad_step = _make_grad_step(cfg, mesh)
    a = cfg.grad_accum
    if a < 1:
        raise ValueError(f"grad_accum must be >= 1, got {a}")
    # factored multislice mesh: accumulate LOCAL grads and sync once
    # (one shard-sized DCN exchange per optimizer step, not A)
    accum_step = (_make_accum_grad_step(cfg, mesh)
                  if a > 1 and cfg.dcn_size > 1 else None)
    coef = jnp.float32(cfg.aux_coef)
    compress = cfg.dcn_compress is not None and cfg.dcn_size > 1

    def _micro_split(tokens, targets):
        b = tokens.shape[0]
        if b % (a * cfg.dp * cfg.ep):
            raise ValueError(
                f"global batch {b} not divisible into grad_accum={a} "
                f"microbatches of dp*ep={cfg.dp * cfg.ep}-divisible "
                f"size")
        mb = b // a
        # INTERLEAVED split (microbatch j = rows j, j+a, j+2a, ...):
        # every device's contiguous (data, expert) block contributes
        # equally to every microbatch, so the scan's shard_map slices
        # are resharding-free (a contiguous split would all-to-all the
        # batch every iteration)
        return (tokens.reshape(mb, a, -1).swapaxes(0, 1),
                targets.reshape(mb, a, -1).swapaxes(0, 1))

    def _finish(params, opt_state, loss, grads, step_no, fault_arm):
        # chaos taps (trace-time no-ops unplanned) + sentry health flag
        moe_stats = None
        if cfg.model.moe_dropless:
            loss, moe_stats = loss
        grads = faults.tap_grads(grads, step_no, fault_arm)
        loss = faults.tap_loss(loss, step_no, fault_arm)
        gsq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                  for g in jax.tree.leaves(grads))
        ok = (jnp.isfinite(loss) & jnp.isfinite(gsq)).astype(jnp.float32)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        # round-13 telemetry scalars riding the health-flag channel:
        # grad global-norm (gsq already computed for `ok`) + post-update
        # param global-norm — always emitted, so telemetry on/off never
        # changes the compiled program
        met = _step_metrics(gsq, params)
        if moe_stats is not None:   # MOE_METRICS, after the two norms
            met = jnp.concatenate([met, moe_stats])
        return params, opt_state, loss, ok, met

    if compress:
        # stateful signature (round 11): the per-device EF residual is a
        # donated carry next to params/opt-state
        @partial(jax.jit, donate_argnums=compat.donate(0, 1, 2))
        def step_st(params, opt_state, sync_state, tokens, targets,
                    step_no=0, fault_arm=0.0):
            tokens = _zigzag_global(cfg, tokens)
            targets = _zigzag_global(cfg, targets)
            n_total = jnp.sum(targets != IGNORE).astype(jnp.float32)
            if a == 1:
                loss, grads, sync_state = grad_step(
                    params, sync_state, tokens, targets, n_total, coef)
            else:
                micro_t, micro_y = _micro_split(tokens, targets)
                loss, grads, sync_state = accum_step(
                    params, sync_state, micro_t, micro_y, n_total,
                    coef / a)
            params, opt_state, loss, ok, met = _finish(
                params, opt_state, loss, grads, step_no, fault_arm)
            return params, opt_state, sync_state, loss, ok, met

        return step_st

    @partial(jax.jit, donate_argnums=compat.donate(0, 1))
    def step(params, opt_state, tokens, targets, step_no=0,
             fault_arm=0.0):
        tokens = _zigzag_global(cfg, tokens)
        targets = _zigzag_global(cfg, targets)
        n_total = jnp.sum(targets != IGNORE).astype(jnp.float32)
        if a == 1:
            loss, grads = grad_step(params, tokens, targets, n_total, coef)
        else:
            micro_t, micro_y = _micro_split(tokens, targets)

            if accum_step is not None:
                loss, grads = accum_step(params, micro_t, micro_y,
                                         n_total, coef / a)
            else:
                def body(carry, batch):
                    loss_acc, grads_acc = carry
                    loss_i, g_i = grad_step(params, *batch, n_total,
                                            coef / a)
                    return (loss_acc + loss_i,
                            jax.tree.map(jnp.add, grads_acc, g_i)), None

                zeros = jax.tree.map(jnp.zeros_like, params)
                (loss, grads), _ = jax.lax.scan(
                    body, (jnp.float32(0), zeros), (micro_t, micro_y))
        params, opt_state, loss, ok, met = _finish(
            params, opt_state, loss, grads, step_no, fault_arm)
        return params, opt_state, loss, ok, met

    return step


def make_lm_pp_train_step(cfg: LMTrainConfig, mesh: Mesh):
    """Pipeline-parallel step over Mesh((data, pipe, seq, model)):
    tokens/targets arrive (global_batch, S) sharded (data, seq); each
    data-rank cuts its local batch into microbatches and drives the wave
    schedule (parallel/pipeline.py).  With sp > 1 each stage's layer chunks
    run ring attention over the 'seq' axis — long-context pipeline
    training (pp x sp), composing further with tp."""
    from .parallel import pipeline as pp

    tx = make_optimizer(cfg)
    dtype = cfg.dtype
    n_micro = cfg.microbatches or 2 * cfg.pp

    tp_axis = MODEL if cfg.tp > 1 else None
    seq_axis = SEQ if cfg.sp > 1 else None

    def local_loss(stage_params, shared, tokens, targets):
        b_local = tokens.shape[0]
        if b_local % n_micro:
            raise ValueError(
                f"local batch {b_local} not divisible into {n_micro} "
                f"microbatches")
        mb = b_local // n_micro
        tokens = tokens.reshape(n_micro, mb, -1)
        targets = targets.reshape(n_micro, mb, -1)
        pos = _shard_positions(cfg, tokens.shape[-1])
        ce_sum, n, aux = pp.pipeline_loss(
            stage_params, shared, tokens, targets,
            cfg=cfg.model, axis=PIPE, dtype=dtype,
            tp_axis=tp_axis, seq_axis=seq_axis,
            seq_layout=cfg.seq_layout, pos=pos,
            interleave=cfg.interleave,
            remat_block_ticks=cfg.pp_remat_block,
            loss_impl=cfg.loss_impl, loss_chunk=cfg.loss_chunk)
        ce_sum = jax.lax.psum(ce_sum, (DATA, PIPE, SEQ))
        n = jax.lax.psum(n, (DATA, PIPE, SEQ))
        # aux: layers are SPLIT across 'pipe' (sum) and each rank's
        # accumulator spans all microbatches (mean); data/seq shards each
        # computed their own routing (mean) — mirrors the dense path's
        # sum-over-layers + pmean-over-(data, seq).
        aux = jax.lax.psum(aux, PIPE) / n_micro
        aux = jax.lax.pmean(aux, (DATA, SEQ))
        return ce_sum / jnp.maximum(n, 1) + cfg.aux_coef * aux

    stage_specs = pp_stage_specs(cfg)
    shared_specs = {"embed": P(), "final_norm": P()}

    grad_step = shard_map(
        jax.value_and_grad(local_loss, argnums=(0, 1)),
        mesh=mesh,
        in_specs=(stage_specs, shared_specs, P(DATA, SEQ), P(DATA, SEQ)),
        out_specs=(P(), (stage_specs, shared_specs)),
    )

    @partial(jax.jit, donate_argnums=compat.donate(0, 1))
    def step(params, opt_state, tokens, targets, step_no=0,
             fault_arm=0.0):
        tokens = _zigzag_global(cfg, tokens)
        targets = _zigzag_global(cfg, targets)
        loss, grads = grad_step(params["stages"], params["shared"],
                                tokens, targets)
        grads = {"stages": grads[0], "shared": grads[1]}
        grads = faults.tap_grads(grads, step_no, fault_arm)
        loss = faults.tap_loss(loss, step_no, fault_arm)
        gsq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                  for g in jax.tree.leaves(grads))
        ok = (jnp.isfinite(loss) & jnp.isfinite(gsq)).astype(jnp.float32)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        met = _step_metrics(gsq, params)
        return params, opt_state, loss, ok, met

    return step


def make_lm_eval_step(cfg: LMTrainConfig, mesh: Mesh):
    """Forward-only masked-CE: (params, tokens, targets) -> (ce_sum, count),
    globally reduced.  Works for the (data, seq, model) mesh; the pp layout
    evaluates through pipeline_loss the same way."""
    dtype = cfg.dtype
    specs = param_specs(cfg)

    def local_eval(params, tokens, targets):
        if cfg.fsdp:
            # same gather dtype as training: eval sees the weights the
            # train forward saw (quantized when fsdp_gather_dtype is on)
            params = _fsdp_gather(params, specs, cfg.fsdp_gather_dtype)
        pos = _shard_positions(cfg, tokens.shape[1])
        # same head-loss seam as training (ops/losses.py head_loss):
        # dense is the historical graph bit-for-bit; no remat — there is
        # no backward to hold activations for
        head = partial(losses.head_loss, targets=targets,
                       loss_impl=cfg.loss_impl, loss_chunk=cfg.loss_chunk,
                       tp_axis=MODEL if cfg.tp > 1 else None,
                       tp_size=cfg.tp)
        ce, n = tfm.apply(params, tokens, cfg=cfg.model, dtype=dtype,
                          seq_axis=SEQ if cfg.sp > 1 else None,
                          seq_layout=cfg.seq_layout, tp_axis=MODEL,
                          ep_axis=EXPERT if cfg.ep > 1 else None, pos=pos,
                          matmul_dtype=cfg.matmul_dtype, head_fn=head)
        axes = _batch_axes(cfg) + (SEQ,)
        return (jax.lax.psum(ce, axes), jax.lax.psum(n, axes))

    bspec = _lm_batch_spec(cfg)
    sharded_eval = shard_map(
        local_eval, mesh=mesh,
        in_specs=(specs, bspec, bspec),
        out_specs=(P(), P()))

    @jax.jit
    def eval_step(params, tokens, targets):
        return sharded_eval(params, _zigzag_global(cfg, tokens),
                            _zigzag_global(cfg, targets))

    return eval_step


def make_lm_multi_step(cfg: LMTrainConfig, mesh: Mesh):
    """Compiled K-step training loop for the (data, expert, seq, model)
    layout: ``(params, opt_state, tokens, targets) -> (params, opt_state,
    losses, oks, mets)`` with tokens/targets carrying a leading scan axis
    of length K — ONE dispatch executes K optimizer steps (``oks``:
    per-step health flags, ``mets``: (K, 2) per-step [grad-norm,
    param-norm], as in ``make_lm_train_step``).  Shares
    ``_make_grad_step`` with the single-step path, so loss semantics
    cannot drift; see LMTrainer.train_steps for when the scan actually
    helps (measured)."""
    if cfg.dcn_compress is not None:
        raise ValueError("make_lm_multi_step does not thread the "
                         "stateful sync-state (EF residual) carry; with "
                         "dcn_compress use make_lm_train_step")
    if cfg.model.moe_dropless:
        raise ValueError("make_lm_multi_step does not carry the dropless "
                         "routed layer's counters; use make_lm_train_step")
    tx = make_optimizer(cfg)
    grad_step = _make_grad_step(cfg, mesh)

    @partial(jax.jit, donate_argnums=compat.donate(0, 1))
    def steps(params, opt_state, tokens, targets):
        tokens = jax.vmap(partial(_zigzag_global, cfg))(tokens)
        targets = jax.vmap(partial(_zigzag_global, cfg))(targets)

        def body(carry, batch):
            params, opt_state = carry
            tk, tg = batch
            n_total = jnp.sum(tg != IGNORE).astype(jnp.float32)
            loss, grads = grad_step(params, tk, tg, n_total,
                                    jnp.float32(cfg.aux_coef))
            gsq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                      for g in jax.tree.leaves(grads))
            ok = (jnp.isfinite(loss) & jnp.isfinite(gsq)).astype(
                jnp.float32)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            met = _step_metrics(gsq, params)
            return (params, opt_state), (loss, ok, met)

        (params, opt_state), (losses, oks, mets) = jax.lax.scan(
            body, (params, opt_state), (tokens, targets))
        return params, opt_state, losses, oks, mets

    return steps


def make_lm_pp_eval_step(cfg: LMTrainConfig, mesh: Mesh):
    """Forward-only masked-CE through the pipeline (no grad, no merge):
    (params, tokens, targets) -> (ce_sum, count), globally reduced.

    The reference evaluates after every training epoch
    (/root/reference/main.py:51-66, called at main.py:108); a pp-trained
    model must run that eval loop without leaving the pipeline layout, so
    this drives the same wave schedule as the pp train step, skipping
    autodiff (and its remat blocks — ``remat_block_ticks=None`` keeps the
    cheap flat scan, since there is no backward to hold activations for).
    """
    from .parallel import pipeline as pp

    dtype = cfg.dtype
    n_micro = cfg.microbatches or 2 * cfg.pp
    tp_axis = MODEL if cfg.tp > 1 else None
    seq_axis = SEQ if cfg.sp > 1 else None

    def local_eval(stage_params, shared, tokens, targets):
        b_local = tokens.shape[0]
        if b_local % n_micro:
            raise ValueError(
                f"eval batch (local {b_local}) not divisible into "
                f"{n_micro} microbatches")
        mb = b_local // n_micro
        tokens = tokens.reshape(n_micro, mb, -1)
        targets = targets.reshape(n_micro, mb, -1)
        pos = _shard_positions(cfg, tokens.shape[-1])
        ce_sum, n, _aux = pp.pipeline_loss(
            stage_params, shared, tokens, targets,
            cfg=cfg.model, axis=PIPE, dtype=dtype,
            tp_axis=tp_axis, seq_axis=seq_axis,
            seq_layout=cfg.seq_layout, pos=pos,
            interleave=cfg.interleave,
            remat_block_ticks=None,
            loss_impl=cfg.loss_impl, loss_chunk=cfg.loss_chunk)
        return (jax.lax.psum(ce_sum, (DATA, PIPE, SEQ)),
                jax.lax.psum(n, (DATA, PIPE, SEQ)))

    stage_specs = pp_stage_specs(cfg)
    shared_specs = {"embed": P(), "final_norm": P()}
    sharded_eval = shard_map(
        local_eval, mesh=mesh,
        in_specs=(stage_specs, shared_specs, P(DATA, SEQ), P(DATA, SEQ)),
        out_specs=(P(), P()))

    @jax.jit
    def eval_step(params, tokens, targets):
        return sharded_eval(params["stages"], params["shared"],
                            _zigzag_global(cfg, tokens),
                            _zigzag_global(cfg, targets))

    return eval_step


class LMTrainer:
    """Owns (params, opt_state) laid out over the (data, seq, model) mesh —
    the (data, pipe, seq, model) mesh when cfg.pp > 1 (the wave
    scheduler's stage-stacked layout)."""

    def __init__(self, cfg: LMTrainConfig, mesh: Mesh | None = None):
        # sync_plan="auto" (round 11): resolve FIRST into explicit
        # dcn_compress/bucket_mb knobs (parallel/autotune.py), so
        # everything below runs the exact explicit-config path — auto
        # under a forced profile is bitwise-identical to the config it
        # resolves to (test-pinned).  The explainable plan is kept on
        # the trainer.
        self.sync_plan = None
        # sync_route (round 21): the hand-pinned routed surface resolves
        # through the SAME mechanism — parse, refuse what the LM sync
        # machinery cannot execute, translate the dcn hop's wire format
        # into dcn_compress — so a routed config trains
        # bitwise-identically to the explicit config it names.
        self.sync_route_plan = None
        if cfg.sync_route is not None:
            from .parallel import autotune
            cfg, self.sync_route_plan = autotune.resolve_lm_route(cfg)
        if cfg.sync_plan == "auto":
            from .parallel import autotune
            cfg, self.sync_plan = autotune.resolve_lm_auto(cfg)
        self.cfg = cfg
        # validate even with a caller-supplied mesh: an invalid axis
        # composition (e.g. pp x grad_accum) must raise, not be silently
        # ignored by whichever step builder does not read the setting
        validate_lm_cfg(cfg)
        self.mesh = mesh if mesh is not None else make_lm_mesh(cfg)
        want = cfg.dp * cfg.ep * cfg.sp * cfg.tp * cfg.pp
        assert self.mesh.devices.size == want, (
            f"mesh has {self.mesh.devices.size} devices, config wants {want}")
        # batch sharding: (data, expert) jointly split the batch on the
        # non-pp mesh; the pp mesh has no expert axis (ep=1 enforced).
        self._batch_spec = (P(DATA, SEQ) if cfg.pp > 1
                            else _lm_batch_spec(cfg))

        if cfg.fsdp and cfg.pp > 1:
            raise ValueError("fsdp composes with the (data, seq, model) "
                             "mesh, not with pp")
        params = tfm.init(jax.random.key(cfg.seed), cfg.model)
        tx = make_optimizer(cfg)
        if cfg.pp > 1:
            from .parallel import pipeline as pp
            stages, shared = pp.split_layer_params(
                params, cfg.model, cfg.pp, interleave=cfg.interleave)
            stage_specs = pp_stage_specs(cfg)
            params = {
                "stages": jax.tree.map(
                    lambda x, s: jax.device_put(
                        x, NamedSharding(self.mesh, s)),
                    stages, stage_specs),
                "shared": jax.device_put(
                    shared, NamedSharding(self.mesh, P())),
            }
            self._install_step_fns(self._build_step_fn(cfg, self.mesh))
        else:
            specs = param_specs(cfg)
            params = jax.tree.map(
                lambda x, s: jax.device_put(x, NamedSharding(self.mesh, s)),
                params, specs)
            self._install_step_fns(self._build_step_fn(cfg, self.mesh))
        # zeros_like/elementwise init inherits each param's sharding; leaves
        # with no param ancestry (Adam's step count) come out single-device —
        # normalize them to replicated-on-mesh so every training-state leaf
        # lives on the same device set (mixing committed single-device and
        # mesh-wide args in one jit is an error).
        rep = NamedSharding(self.mesh, P())
        self.opt_state = jax.tree.map(
            lambda leaf: (jax.device_put(leaf, rep)
                          if isinstance(leaf, jax.Array)
                          and len(leaf.sharding.device_set) == 1
                          and self.mesh.devices.size > 1 else leaf),
            jax.jit(tx.init)(params))
        self.params = params
        # int8 DCN compression (round 11): the per-device EF residual
        # carried through the stateful step — one row per device,
        # sharded over the full mesh.  NOT checkpointed: dropping it on
        # restart is safe (residuals re-accumulate within one step).
        self.sync_state = None
        if cfg.dcn_compress is not None:
            n_dev = self.mesh.devices.size
            self.sync_state = jax.device_put(
                jnp.zeros((n_dev, lm_sync_state_len(cfg, self.mesh)),
                          jnp.float32),
                NamedSharding(self.mesh, P(tuple(self.mesh.axis_names))))
        # communication-sparse windows (round 18): the per-slice window
        # delta + per-slice optimizer state (leading 'dcn' axis) and the
        # staleness bookkeeping; params stay the replicated ANCHOR
        self._delta = None
        self._pending = None
        self._window_t0 = None
        self._window_wire_bytes = None
        # DiLoCo outer optimizer (round 22): the f32 momentum tree on
        # the anchor (None without outer_opt — the plain-mean boundary)
        # and the boundary-step counter the telemetry gauge reads
        self._outer_m = None
        self._outer_steps = 0
        if cfg.sync_every > 1:
            self._init_window_state()
        self._eval_fn = None
        self._multi_fn = None
        self._step = 0
        self._last_cache_size = None  # compile-lane gauge change-detect
        self.last_ok = None     # health flag(s) of the last dispatch
        # [grad gnorm, param gnorm] of the last dispatch (round-13
        # telemetry scalars; (K, 2) from train_steps), fetched lazily
        self.last_metrics = None
        self._ckptr = None
        self._ckptr_key = None
        self.restored_meta: dict = {}

    def _emit_cache_size(self, tel, fn) -> None:
        """Compile-lane gauge: the dispatched function's jit-cache entry
        count, emitted only when it CHANGES (a growing cache mid-run is
        a shape leak — exactly what the gauge exists to surface)."""
        size_of = getattr(fn, "_cache_size", None)
        if size_of is None:
            return
        try:
            n = size_of()
        except Exception:
            return
        if n != self._last_cache_size:
            self._last_cache_size = n
            tel.gauge("step_fn_cache_size", float(n), phase="compile")

    def _build_step_fn(self, cfg, mesh):
        """Build the compiled train step for ``cfg``/``mesh``, timed on
        the compile lane (round 15): one phase-"compile" span per build,
        keyed by layout + clip so a sentry tighten or elastic rebuild
        shows up as a NEW program in the trace.  Telemetry off: the
        span is a no-op and the build is byte-identical."""
        if cfg.pp > 1:
            kind, builder = "pp", make_lm_pp_train_step
        elif cfg.sync_every > 1:
            # round 18: the communication-sparse program family (local
            # step + boundary exchange + staleness launch/apply) — the
            # build returns a 4-tuple, unpacked by _install_step_fns
            kind, builder = "localsgd", make_lm_window_steps
        else:
            kind, builder = "spmd", make_lm_train_step
        with monitor.compile_span(
                "lm_step_build",
                key=(kind, cfg.grad_clip, tuple(mesh.shape.items())),
                kind=kind):
            return builder(cfg, mesh)

    def _install_step_fns(self, built) -> None:
        """Install a step-builder result: the windowed family arrives as
        a (local, exchange, launch, apply) tuple — ``step_fn`` is the
        window-LOCAL step (the hot path, what the cache-size gauge and
        the schedule inspector see); the boundary programs live beside
        it."""
        if isinstance(built, tuple):
            (self.step_fn, self._exchange_fn, self._launch_fn,
             self._apply_fn) = built
        else:
            self.step_fn = built
            self._exchange_fn = self._launch_fn = self._apply_fn = None

    def _stack_dcn(self, tree_: PyTree) -> PyTree:
        """Broadcast every array leaf one copy per 'dcn' slice (leading
        axis dcn_size, sharded over 'dcn' ahead of the leaf's own
        spec) — the per-slice optimizer-state layout of the windowed
        local steps."""
        mesh, n = self.mesh, self.cfg.dcn_size

        def f(x):
            if not isinstance(x, jax.Array):
                return x
            spec = (x.sharding.spec
                    if isinstance(x.sharding, NamedSharding) else P())
            return jax.device_put(
                jnp.broadcast_to(x[None], (n,) + x.shape),
                NamedSharding(mesh, P(DCN, *spec)))

        return jax.tree.map(f, tree_)

    def _init_window_state(self) -> None:
        """Round 18 (``sync_every > 1``): stack the optimizer state one
        copy per 'dcn' slice and zero the per-slice window delta.  The
        live ``params`` stay the replicated anchor — the last exchanged
        point, what checkpoints save and ``evaluate`` reads (mid-window
        local progress lives in the delta until the next boundary)."""
        cfg, mesh = self.cfg, self.mesh
        self.opt_state = self._stack_dcn(self.opt_state)
        specs = param_specs(cfg)
        self._delta = jax.tree.map(
            lambda p, s: jax.device_put(
                jnp.zeros((cfg.dcn_size,) + p.shape, p.dtype),
                NamedSharding(mesh, P(DCN, *s))),
            self.params, specs)
        self._pending = None
        self._window_t0 = None
        self._window_wire_bytes = _lm_window_wire_bytes(cfg, mesh)
        self._outer_m = None
        if _lm_outer(cfg) is not None:
            # f32 momentum shadows the anchor leaf-for-leaf (same
            # shardings — it moves with the anchor, never the wire)
            self._outer_m = jax.tree.map(
                lambda p: jax.device_put(
                    jnp.zeros(p.shape, jnp.float32), p.sharding),
                self.params)

    def tighten_grad_clip(self, factor: float = 0.5) -> float:
        """Multiply the gradient-clip norm by ``factor`` and rebuild the
        compiled step — the training sentry's mid-ladder escalation
        (utils/sentry.py: skip window -> tighten clip -> abort).  The
        optimizer chain's clip transform is stateless, so the live
        opt_state carries over unchanged; the recompile is a fault-path
        cost, not a hot-path one.  Returns the new clip norm."""
        self.cfg.grad_clip *= factor
        self._install_step_fns(self._build_step_fn(self.cfg, self.mesh))
        self._multi_fn = None
        return self.cfg.grad_clip

    # -- elastic resize (round 12) ----------------------------------------
    def rebuild(self, mesh: Mesh | None = None, **overrides) -> None:
        """Re-create the compiled step at a NEW parallel degree, carrying
        the live training state across — the in-process half of the
        elastic gang (parallel/elastic.py).  ``overrides`` are
        ``LMTrainConfig`` field replacements (typically ``dp=...`` and
        ``fsdp=...`` after the fleet shrank or grew); the mesh rebuilds
        from the new config unless supplied.  Params and optimizer state
        are resharded onto the new layout (host-fetched owned copies,
        then placed by the new ``param_specs`` — restoring a checkpoint
        through ``load_resharded`` afterwards is the elastic resume
        path, see ``reshard_from_checkpoint``); the sync-state carry
        re-initializes (safe to drop); compiled step/eval functions are
        discarded; the step counter survives.

        Pipeline meshes refuse: pp stage placement is baked into
        the stage-stacked step, so a pipelined gang resizes by relaunch,
        not rebuild (the lm_cli --elastic refusal mirrors this).
        Single-controller only — a multi-process gang resizes via the
        elastic agent's drain + re-rendezvous."""
        if jax.process_count() > 1:
            raise ValueError(
                "in-process rebuild is single-controller; multi-process "
                "gangs resize via the elastic agent's drain + "
                "re-rendezvous (launch.py --elastic)")
        import dataclasses
        cfg = (dataclasses.replace(self.cfg, **overrides) if overrides
               else self.cfg)
        if cfg.pp > 1:
            raise ValueError(
                "cannot resize a pipeline (pp) config for now: "
                "stage placement is baked into the stage-stacked step — "
                "relaunch at the new size instead")
        validate_lm_cfg(cfg)
        new_mesh = mesh if mesh is not None else make_lm_mesh(cfg)
        want = cfg.dp * cfg.ep * cfg.sp * cfg.tp
        if new_mesh.devices.size != want:
            raise ValueError(
                f"resized mesh has {new_mesh.devices.size} devices, "
                f"config wants {want}")
        from .utils.checkpoint import _fetch  # owned copies (donation)

        params_host = jax.tree.map(_fetch, self.params)
        opt_host = jax.tree.map(
            lambda x: _fetch(x) if isinstance(x, jax.Array) else x,
            self.opt_state)
        if self.cfg.sync_every > 1:
            # windowed -> any: the per-slice optimizer state collapses
            # to slice 0 (the rebuild drops un-exchanged window deltas
            # and per-slice Adam divergence — up to H-1 local steps of
            # progress, the same carry-drop contract as sync_state; the
            # SLO actuator widens/narrows at window boundaries where
            # the delta is zero anyway)
            opt_host = jax.tree.map(
                lambda x: x[0] if hasattr(x, "ndim") and x.ndim else x,
                opt_host)
        self.cfg = cfg
        self.mesh = new_mesh
        self._batch_spec = _lm_batch_spec(cfg)
        specs = param_specs(cfg)
        self.params = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(new_mesh, s)),
            params_host, specs)
        # target opt-state shardings come from re-initializing on the
        # resharded params (exactly the __init__ recipe, including the
        # single-device -> replicated normalization); the live VALUES
        # then re-place onto those shardings leaf by leaf
        tx = make_optimizer(cfg)
        rep = NamedSharding(new_mesh, P())
        target = jax.tree.map(
            lambda leaf: (jax.device_put(leaf, rep)
                          if isinstance(leaf, jax.Array)
                          and len(leaf.sharding.device_set) == 1
                          and new_mesh.devices.size > 1 else leaf),
            jax.jit(tx.init)(self.params))
        self.opt_state = jax.tree.map(
            lambda old, tgt: (jax.device_put(np.asarray(old), tgt.sharding)
                              if isinstance(tgt, jax.Array) else old),
            opt_host, target)
        self._install_step_fns(self._build_step_fn(cfg, new_mesh))
        self.sync_state = None
        if cfg.dcn_compress is not None:
            n_dev = new_mesh.devices.size
            self.sync_state = jax.device_put(
                jnp.zeros((n_dev, lm_sync_state_len(cfg, new_mesh)),
                          jnp.float32),
                NamedSharding(new_mesh, P(tuple(new_mesh.axis_names))))
        self._delta = None
        self._pending = None
        self._window_t0 = None
        self._window_wire_bytes = None
        self._outer_m = None  # fresh momentum after a resize (carry-drop
        # contract, same as sync_state); _init_window_state re-zeros it
        if cfg.sync_every > 1:
            self._init_window_state()
        self._eval_fn = None
        self._multi_fn = None
        self.last_ok = None
        self.last_metrics = None
        # a cached checkpointer keeps working (directory-keyed), but the
        # next restore must re-template against the new shardings — which
        # maybe_restore does by passing the live (resharded) trees

    def evaluate(self, batches) -> dict[str, float]:
        """Held-out loss/perplexity over an iterable of (tokens, targets).

        pp > 1 evaluates through the pipeline forward (the wave schedule,
        no grad) — the train→eval loop of the reference (main.py:108)
        never leaves the pipeline layout."""
        if self._eval_fn is None:
            self._eval_fn = (make_lm_pp_eval_step(self.cfg, self.mesh)
                             if self.cfg.pp > 1
                             else make_lm_eval_step(self.cfg, self.mesh))
        shd = NamedSharding(self.mesh, self._batch_spec)
        total, count = 0.0, 0
        for tokens, targets in batches:
            if jax.process_count() > 1:
                tokens = jax.make_array_from_process_local_data(shd, tokens)
                targets = jax.make_array_from_process_local_data(shd, targets)
            ce, n = self._eval_fn(self.params, tokens, targets)
            total += float(ce)
            count += int(n)
        loss = total / max(count, 1)
        return {"loss": loss, "ppl": float(np.exp(min(loss, 30.0))),
                "tokens": count}


    # -- checkpointing ----------------------------------------------------
    def _checkpointer(self, directory: str, sharded: bool = False):
        """One cached checkpointer per (directory, format): the whole-tree
        async writer's background handle must survive across save calls
        (writes never interleave; the interpreter flushes the last one at
        exit)."""
        from .utils.checkpoint import PyTreeCheckpointer, ShardedCheckpointer
        key = (directory, sharded)
        if self._ckptr_key != key:
            self._ckptr = (ShardedCheckpointer(directory) if sharded
                           else PyTreeCheckpointer(directory,
                                                   async_write=True))
            self._ckptr_key = key
        return self._ckptr

    def flush_checkpoints(self) -> None:
        """Block until any in-flight background checkpoint write has been
        published (call before reading the directory or exiting a driver
        that must observe the file)."""
        if self._ckptr is not None:
            self._ckptr.wait()

    def save_checkpoint(self, directory: str,
                        extra_meta: dict | None = None,
                        sharded: bool = False) -> None:
        """Snapshot params/opt-state/step (utils/checkpoint.py); all
        processes must call (whole-tree fetches are collectives).  Default
        format: one whole-tree npz, fetched synchronously with the
        serialization/IO overlapping the next train steps (async_write).
        ``sharded=True`` writes per-process shard files instead (no
        allgather, no full-tree host copy — utils ShardedCheckpointer).
        ``extra_meta`` rides along in the JSON meta — the CLI records the
        data-loader position here."""
        self._checkpointer(directory, sharded).save(
            {"params": self.params, "opt": self.opt_state}, self._step,
            meta=dict(extra_meta or {},
                      dp=self.cfg.dp, sp=self.cfg.sp, tp=self.cfg.tp,
                      pp=self.cfg.pp, interleave=self.cfg.interleave))

    def maybe_restore(self, directory: str) -> int:
        """Restore the latest checkpoint if present; returns the step to
        resume from (0 = fresh).  The format (whole-tree npz vs per-shard
        directory) is auto-detected, so resume works regardless of which
        saver wrote it.  The full checkpoint meta (including any
        ``extra_meta`` recorded at save) lands in ``self.restored_meta``.

        Per-shard checkpoints restore through ``load_resharded`` (round
        12): a layout that matches the save still moves only its own
        shard's bytes, and a DIFFERENT topology (the elastic-resize case
        — the gang shrank or grew since the save) is mapped saved-shard
        -> new-mesh per leaf without any host materializing a full
        array.  Values are bitwise-identical either way (test-pinned)."""
        from .utils.checkpoint import PyTreeCheckpointer, ShardedCheckpointer
        sh_list = ShardedCheckpointer(directory).list()
        npz_list = PyTreeCheckpointer(directory).list()
        if not sh_list and not npz_list:
            return 0
        # Mixed directories: resume from whichever format holds the NEWEST
        # step (a run that switched formats must not resurrect stale state).
        sharded = bool(sh_list) and (
            not npz_list or sh_list[-1][0] >= npz_list[-1][0])
        ckptr = self._checkpointer(directory, sharded)
        load = ckptr.load_resharded if sharded else ckptr.restore
        got = load({"params": self.params, "opt": self.opt_state})
        if got is None:
            return 0
        trees, meta = got
        self.params, self.opt_state = trees["params"], trees["opt"]
        self._step = meta["step"]
        self.restored_meta = meta
        return self._step

    def _to_device(self, tokens, targets, spec):
        """The host batch laid out over the mesh as ``spec`` says."""
        shd = NamedSharding(self.mesh, spec)
        with span("train.h2d"):
            if jax.process_count() > 1:
                return (jax.make_array_from_process_local_data(shd, tokens),
                        jax.make_array_from_process_local_data(shd, targets))
            return jax.device_put(tokens, shd), jax.device_put(targets, shd)

    def train_step(self, tokens: np.ndarray, targets: np.ndarray):
        if self.cfg.sync_every > 1:
            return self._train_step_windowed(tokens, targets)
        faults.maybe_delay(self._step)  # chaos: straggler (no-op unplanned)
        tokens, targets = self._to_device(tokens, targets,
                                          self._batch_spec)
        # (step_no, fault_arm) feed only the chaos taps — passed solely
        # when a plan is installed, so the clean path's compiled
        # signature (and any cached executable) is byte-identical to
        # pre-sentry builds; arm_window gives step-keyed faults their
        # one-shot semantics across sentry rollbacks
        extra = ((jnp.int32(self._step),
                  jnp.float32(faults.arm_window(self._step)))
                 if faults.step_plan() is not None else ())
        t0 = time.perf_counter()
        with span("train.dispatch"):
            if self.sync_state is not None:
                # stateful (dcn_compress) signature: the EF residual is a
                # donated carry next to params/opt-state (round 11)
                (self.params, self.opt_state, self.sync_state, loss,
                 self.last_ok, self.last_metrics) = self.step_fn(
                    self.params, self.opt_state, self.sync_state, tokens,
                    targets, *extra)
            else:
                (self.params, self.opt_state, loss, self.last_ok,
                 self.last_metrics) = self.step_fn(
                    self.params, self.opt_state, tokens, targets, *extra)
        self._step += 1
        faults.maybe_crash(self._step)  # chaos: injected process death
        tel = telemetry.active()
        if tel is not None:
            telemetry.emit_train_steps(
                tel, t0, self._step - 1, 1, loss, self.last_ok,
                self.last_metrics, span_name="lm_train_step", defer=True,
                extra_gauges=step_metric_names(self.cfg.model))
            self._emit_cache_size(tel, self.step_fn)
        return loss

    def _train_step_windowed(self, tokens, targets):
        """One local step of the sync_every > 1 schedule, plus whatever
        window bookkeeping the step count makes due: the boundary
        exchange at multiples of H (or its launch when staleness > 0)
        and the deferred apply at kH + S.  Params hold the ANCHOR (last
        exchanged, replica-identical); ``self._delta`` carries the
        dcn-stacked local drift the optimizer accumulates between
        exchanges."""
        faults.maybe_delay(self._step)
        tokens, targets = self._to_device(tokens, targets,
                                          self._batch_spec)
        extra = ((jnp.int32(self._step),
                  jnp.float32(faults.arm_window(self._step)))
                 if faults.step_plan() is not None else ())
        h, s = self.cfg.sync_every, self.cfg.staleness
        t0 = time.perf_counter()
        if self._step % h == 0:
            self._window_t0 = t0
        with span("train.dispatch"):
            (self._delta, self.opt_state, loss, self.last_ok,
             self.last_metrics) = self.step_fn(
                self.params, self._delta, self.opt_state, tokens, targets,
                *extra)
        self._step += 1
        boundary = self._step % h == 0
        if boundary:
            if s == 0:
                # round-22 boundary arg packing: [anchor, delta]
                # + [sync_state] if compressed + [m] if outer
                # + [mask] if per-slice (mask is never returned)
                per = self.cfg.sync_every_per_slice
                args = [self.params, self._delta]
                if self.sync_state is not None:
                    args.append(self.sync_state)
                if self._outer_m is not None:
                    args.append(self._outer_m)
                if per is not None:
                    args.append(jnp.asarray(
                        [1.0 if self._step % hi == 0 else 0.0
                         for hi in per], jnp.float32))
                out = list(self._exchange_fn(*args))
                self.params, self._delta = out[0], out[1]
                i = 2
                if self.sync_state is not None:
                    self.sync_state = out[i]
                    i += 1
                if self._outer_m is not None:
                    self._outer_m = out[i]
                    self._outer_steps += 1
            else:
                # staleness-hidden: enqueue the exchange now; the mean
                # delta lands at step kH + S while local compute runs
                if self.sync_state is not None:
                    d_avg, snap, self.sync_state = self._launch_fn(
                        self._delta, self.sync_state)
                else:
                    d_avg, snap = self._launch_fn(self._delta)
                self._pending = (d_avg, snap)
        elif self._pending is not None and self._step % h == s:
            d_avg, snap = self._pending
            self._pending = None
            if self._outer_m is not None:
                self.params, self._delta, self._outer_m = self._apply_fn(
                    self.params, self._delta, d_avg, snap, self._outer_m)
                self._outer_steps += 1
            else:
                self.params, self._delta = self._apply_fn(
                    self.params, self._delta, d_avg, snap)
        faults.maybe_crash(self._step)
        tel = telemetry.active()
        if tel is not None:
            telemetry.emit_train_steps(
                tel, t0, self._step - 1, 1, loss, self.last_ok,
                self.last_metrics, span_name="lm_train_step", defer=True)
            if boundary and self._window_t0 is not None:
                telemetry.emit_sync_windows(
                    tel, self._window_t0, self._step - h, h, h,
                    wire_bytes=self._window_wire_bytes, phase="train")
                if (self.cfg.sync_every_per_slice is not None
                        or self._outer_m is not None):
                    telemetry.emit_window_plan(
                        tel, step=self._step - 1,
                        sync_every_per_slice=(
                            self.cfg.sync_every_per_slice),
                        outer_steps=(self._outer_steps
                                     if self._outer_m is not None
                                     else None), phase="train")
            self._emit_cache_size(tel, self.step_fn)
        return loss

    def train_steps(self, tokens: np.ndarray, targets: np.ndarray):
        """Run ``K = tokens.shape[0]`` steps over stacked (K, B, S) batches
        as one compiled ``lax.scan`` dispatch; returns the K per-step
        losses.  Identical trajectory to K ``train_step`` calls.

        When it helps (measured, BASELINE.md): per-step jax dispatch is
        ASYNC, so at ~30 ms/step the host already hides its enqueue cost
        and this scan is ~16% SLOWER (carry double-buffering of
        params/Adam state) — use ``train_step`` there.  The scan wins
        when steps are short relative to host work per dispatch (tiny
        models; multi-host ``make_array_from_process_local_data``
        assembly per step; a host that also runs data loading).  Not
        available with pp > 1 (its step carries pipeline-stacked
        params)."""
        if self.cfg.pp > 1:
            raise ValueError("train_steps (K-step scan) supports the "
                             "(data, expert, seq, model) layout; with pp "
                             "use train_step")
        if self.cfg.grad_accum > 1:
            raise ValueError("train_steps does not implement gradient "
                             "accumulation; use train_step with "
                             "grad_accum, or stack more steps instead")
        if self.cfg.dcn_compress is not None:
            raise ValueError("train_steps does not thread the stateful "
                             "sync-state (EF residual) carry; with "
                             "dcn_compress use train_step")
        if self.cfg.sync_every > 1:
            raise ValueError("train_steps does not thread the window "
                             "delta / per-slice optimizer carries; with "
                             "sync_every > 1 use train_step")
        if self._multi_fn is None:
            with monitor.compile_span(
                    "lm_multi_build",
                    key=("multi", self.cfg.grad_clip,
                         tuple(self.mesh.shape.items()))):
                self._multi_fn = make_lm_multi_step(self.cfg, self.mesh)
        tokens, targets = self._to_device(tokens, targets,
                                          P(None, *self._batch_spec))
        t0 = time.perf_counter()
        with span("train.dispatch"):
            (self.params, self.opt_state, losses, self.last_ok,
             self.last_metrics) = self._multi_fn(
                self.params, self.opt_state, tokens, targets)
        self._step += tokens.shape[0]
        faults.maybe_crash(self._step, tokens.shape[0])
        tel = telemetry.active()
        if tel is not None:
            telemetry.emit_train_steps(
                tel, t0, self._step - tokens.shape[0], tokens.shape[0],
                losses, self.last_ok, self.last_metrics,
                span_name="lm_train_steps", defer=True)
            self._emit_cache_size(tel, self._multi_fn)
        return losses
