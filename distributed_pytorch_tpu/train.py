"""The single trainer: one jitted train step, pluggable gradient sync.

This factors the reference's five ~80%-identical ``main_*.py`` scripts
(SURVEY.md section 0) into one training loop where the gradient-sync strategy
is a plug-in (parallel/strategies.py).  The hot path — zero_grad / forward /
loss / backward / [sync] / step (reference main_all_reduce.py:36-50) — becomes
ONE compiled XLA program per step:

- single-process (strategy 'none'): plain ``jax.jit`` (reference main.py);
- data-parallel: ``shard_map`` over the mesh's ``'data'`` axis, with the
  batch sharded, params/optimizer state replicated, and per-replica
  BatchNorm statistics carried with a leading device axis (the reference
  keeps BN stats local per rank — SURVEY.md section 2.3).

The optimizer is optax ``add_decayed_weights(wd)`` then ``sgd(lr, momentum)``
— the exact update rule of torch ``SGD(lr=0.1, momentum=0.9,
weight_decay=1e-4)`` (reference main.py:103-104: grad += wd*p, then the
momentum buffer, then the step).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, replace
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .data import augment as aug, pipeline
from .models import vgg
from .ops import nn as ops
from .parallel import strategies as strat
from .parallel.mesh import DATA_AXIS, make_mesh, replicated
from .utils import compat, debug as dbg, faults, monitor, telemetry, tracing
from .utils.compat import pcast, shard_map, vma_of
from .utils.metrics import IterTimeMeter, LossMeter

PyTree = Any


@dataclass
class TrainConfig:
    """Hyper-parameters; defaults are the reference's exact settings."""

    model: str = "VGG11"
    lr: float = 0.1               # main.py:103
    momentum: float = 0.9         # main.py:104
    weight_decay: float = 1e-4    # main.py:104
    batch_size: int = 256         # per replica (main.py:18)
    # Gradient-sync strategy (parallel/strategies.py), or "auto" (round
    # 11): calibrate the topology's per-axis links (or take an injected
    # profile — ``autotune_profile``), census the model's grad tree, and
    # resolve to the named strategy + bucket/compression knobs that
    # minimize predicted step-sync time (parallel/autotune.py).  The
    # resolved plan routes through the existing strategies unchanged, so
    # auto under a forced profile trains bitwise-identically to the
    # named strategy it resolves to (test-pinned); the Trainer records
    # the explainable plan as ``trainer.sync_plan``.
    strategy: str = "ddp"
    # Backward-overlapped gradient sync (round 8): emit each ~25 MB
    # bucket's collective INSIDE the backward graph at the bucket's layer-
    # group boundary (custom_vjp sync points — strategies.OverlapSync), so
    # XLA's latency-hiding scheduler can run bucket N's reduction under
    # layer N-1's backward matmuls, instead of starting all collectives
    # only after the backward fully drains.  Requires a mesh and an
    # overlap-capable strategy (strategies.overlap_capable()); numerics
    # are bitwise-identical to the post-backward path (test-pinned).
    overlap: bool = False
    # Bucket size for overlap packing (and for the bucketed/ring
    # strategies' internal packing); None keeps each strategy's default
    # (torch DDP's 25 MB).  Small values force many buckets — useful for
    # schedule inspection on tiny models.
    overlap_bucket_mb: float | None = None
    # Number of slices for the 'hierarchical' strategy: the data axis
    # factors into Mesh(('dcn', 'ici')) with dcn_size slices (cross-slice
    # DCN traffic drops to payload/ici — see strategies.Hierarchical).
    # Ignored by single-axis strategies.
    dcn_size: int = 2
    # Slow-hop compression for the 'hierarchical' strategy (round 9):
    # "int8" runs the cross-slice shard exchange as an int8 ring (per-row
    # scales, error-feedback residuals through the sync-state carry)
    # while the ICI reduce-scatter/all-gather stay full-precision — see
    # strategies.Hierarchical's dcn_compress docstring.  "int4" (round
    # 16) drops one more rung: two nibbles per int8 lane on the wire,
    # ~0.51x the int8 DCN bytes, same EF carry.  None (default) keeps
    # the exact full-precision psum.  Rejected for strategies with no
    # DCN hop.
    dcn_compress: str | None = None
    # Declarative sync route (round 20, parallel/routing.py): a route
    # string in the hop grammar ("ici:rs → dcn:ring[int4+ef] → ici:ag";
    # plain "->" works too) executed by RoutedSync instead of a named
    # strategy.  Requires strategy="routed"; the route must be a 2-level
    # ('dcn', 'ici') plan — the trainer's factored-mesh topology (3-tier
    # wan routes run through the RoutedSync surface directly; the
    # trainer's mesh recipe only builds two tiers).  Compression and EF
    # live IN the route, so dcn_compress must stay None.
    sync_route: str | None = None
    # Profile source for strategy="auto" (parallel/autotune.py): None =
    # load the repo-local cached profile for this topology or calibrate
    # and cache one; a synthetic preset name ("uniform",
    # "fast_ici_slow_dcn", ...) or a profile-JSON path or a
    # TopologyProfile instance forces the chooser's inputs (CPU tests,
    # the dryrun).  Ignored unless strategy="auto".
    autotune_profile: Any = None
    steps_per_loop: int = 1       # K optimizer steps per device dispatch
    sync_bn: bool = False         # reference never syncs BN (SURVEY.md 2.3)
    # torch DDP's broadcast_buffers=True: BN running stats follow rank 0
    # (reference main_ddp.py:137 inherits this engine behavior); the manual
    # variants keep local per-replica stats.  None = strategy default
    # (True for the DDP-engine strategies 'ddp'/'bucketed', False otherwise).
    broadcast_buffers: bool | None = None
    compute_dtype: str | None = None  # e.g. "bfloat16" for MXU-friendly compute
    augment: bool = True
    seed: int = 1                 # torch.manual_seed(1), main.py:70
    # Communication-sparse sync (round 18, the BAGUA/local-SGD system
    # relaxation): run H local optimizer steps between cross-replica
    # exchanges — each replica (each SLICE under 'hierarchical', which
    # keeps its fast ICI mean every step and skips only the DCN hop)
    # steps on its own gradients while the window's accumulated update
    # delta is averaged once per H steps, so exchange wire bytes per
    # step scale ~1/H.  1 (default) is the existing per-step path,
    # UNTOUCHED at build time (bitwise + compile-count identical).
    # Requires a mesh, steps_per_loop % H == 0 (every dispatch ends on
    # a window boundary), and overlap=False — strategies.
    # require_sync_window is the one refusal site.  Momentum buffers
    # stay LOCAL per device across windows (they ride a leading device
    # axis like BN state), the standard local-momentum variant.
    sync_every: int = 1
    # Relaxation ceiling for the interval-aware autotuner
    # (strategy="auto" prices exposed sync time at H in powers of 2 up
    # to this) and the monitor's straggler actuator
    # (monitor.SyncRelaxHook widens sync_every within it on step-time
    # SLO breach).  Default 1: relaxation is OPT-IN — staleness is a
    # convergence trade the user must accept explicitly.
    max_sync_every: int = 1
    # DiLoCo outer optimizer (round 22): at each window boundary the
    # anchor moves by outer_opt(mean delta) instead of the plain mean —
    # Nesterov/heavy-ball momentum ON THE ANCHOR recovers convergence
    # lost to wide windows (the "wider window at matched quality"
    # claim, measured in tests/test_diloco.py).  The f32 momentum state
    # rides the sync_state carry as a flat tail, so the window scan's
    # signature is unchanged.  None (default) is the round-18 plain
    # mean, UNTOUCHED at build time; so is momentum==0 ∧ lr==1 (the
    # OuterOptimizer.trivial collapse) — bitwise, not approximately.
    outer_opt: str | None = None      # None | "nesterov" | "momentum"
    outer_momentum: float = 0.9
    outer_lr: float = 1.0

    @property
    def dtype(self):
        return jnp.dtype(self.compute_dtype) if self.compute_dtype else None

    @property
    def broadcast_buffers_resolved(self) -> bool:
        """torch DDP semantics by default exactly where the reference gets
        them from the DDP engine; reference-faithful local BN elsewhere."""
        if self.broadcast_buffers is not None:
            return self.broadcast_buffers
        return self.strategy in ("ddp", "bucketed")


def _as_varying(tree: PyTree, axis) -> PyTree:
    """Pcast leaves to device-varying over ``axis`` (a name or tuple of
    names); leaves already varying (e.g. a scan carry whose vma was unified
    with varying neighbors) pass through unchanged."""
    names = (axis,) if isinstance(axis, str) else tuple(axis)

    def cast(x):
        missing = tuple(a for a in names if a not in vma_of(x))
        if not missing:
            return x
        return pcast(x, missing, to="varying")
    return jax.tree.map(cast, tree)


def make_optimizer(cfg: TrainConfig) -> optax.GradientTransformation:
    return optax.chain(
        optax.add_decayed_weights(cfg.weight_decay),
        optax.sgd(cfg.lr, momentum=cfg.momentum),
    )


def _loss_fn(params, state, key, images, labels, *, cfg: TrainConfig,
             bn_axis: str | None, boundary=None):
    """Forward + loss on one replica's shard; images are raw uint8 NHWC.
    ``boundary`` threads the overlap sync hook into the model's layer-group
    boundaries (vgg.apply; None = historical graph, byte-identical)."""
    if cfg.augment:
        x = aug.augment(key, images)
    else:
        x = aug.normalize(images)
    logits, new_state = vgg.apply(
        params, state, x, name=cfg.model, train=True,
        dtype=cfg.dtype, bn_axis_name=bn_axis, boundary=boundary,
    )
    loss = ops.cross_entropy_loss(logits, labels)
    return loss, new_state


def _apply_bucket_mb(cfg: TrainConfig, strategy: strat.Strategy) -> None:
    """Propagate cfg.overlap_bucket_mb into the strategy's packing knob
    (shared by the overlap markers and the bucketed/ring post-backward
    paths, so both modes always agree on bucket membership)."""
    if cfg.overlap_bucket_mb is not None and hasattr(strategy,
                                                     "bucket_bytes"):
        strategy.bucket_bytes = int(cfg.overlap_bucket_mb * 1024 * 1024)


def _apply_dcn(cfg: TrainConfig, strategy: strat.Strategy) -> None:
    """Propagate cfg.dcn_compress / cfg.dcn_size into the strategy (the
    hierarchical slow-hop knobs); must run before the step is built AND
    before init_state (compression flips the strategy stateful and the
    EF residual layout reads dcn_size).  Strategies without a DCN hop
    reject the compress knob instead of silently ignoring it."""
    if hasattr(strategy, "set_dcn"):
        strategy.set_dcn(cfg.dcn_compress, cfg.dcn_size)
    elif cfg.dcn_compress is not None:
        raise ValueError(
            f"dcn_compress={cfg.dcn_compress!r} quantizes the cross-slice "
            f"hop of the factored-mesh 'hierarchical' strategy; strategy "
            f"{strategy.name!r} has no DCN hop to compress")


def _validate_overlap(cfg: TrainConfig, strategy: strat.Strategy,
                      mesh: Mesh | None) -> None:
    if not cfg.overlap:
        return
    if mesh is None:
        raise ValueError(
            "overlap=True requires a mesh: the data-axis collectives are "
            "the thing being overlapped with backward compute")
    # the ONE capability-check site (strategies.py, round 9): the refusal
    # lives next to the OverlapSync machinery it describes
    strat.require_overlap_capable(strategy)


def make_train_step(cfg: TrainConfig, strategy: strat.Strategy,
                    mesh: Mesh | None):
    """Build the compiled single train step — ``make_multi_step`` with K=1
    (one implementation of the optimizer-step semantics, not two).

    Signature: ``step(params, state, opt_state, sync_state, key, step0,
    images, labels) -> (params, state, opt_state, sync_state, loss)``; the
    per-step RNG is ``fold_in(key, step0)``.  Under a mesh, ``state`` (and
    ``sync_state`` — a stateful strategy's per-device residual; a dummy
    otherwise) leaves carry a leading device axis, and ``loss`` is the
    cross-replica mean of the per-shard losses.

    The three training-state arguments are DONATED: the step updates them in
    place on device and the caller must use the returned pytrees (passing a
    consumed buffer again raises "Array has been deleted").

    This convenience wrapper never arms the chaos taps (fault_sig=False):
    its fixed 8-arg signature has no fault_arm slot — use the Trainer (or
    make_multi_step directly) to drive step-keyed fault injection.
    """
    multi = make_multi_step(cfg, strategy, mesh, fault_sig=False)

    def step(params, state, opt_state, sync_state, key, step0, images,
             labels):
        params, state, opt_state, sync_state, losses, oks, mets = multi(
            params, state, opt_state, sync_state, key, step0,
            images[None], labels[None])
        return params, state, opt_state, sync_state, losses[0]

    return step


def _outer_of(cfg: TrainConfig) -> strat.OuterOptimizer | None:
    """The configured DiLoCo outer optimizer, or None for the plain-mean
    boundary — also None when trivial (momentum==0 ∧ lr==1), which is the
    build-time collapse that keeps zero-momentum bitwise ≡ round 18."""
    if cfg.sync_every > 1 and cfg.outer_opt is not None:
        outer = strat.OuterOptimizer(cfg.outer_opt, cfg.outer_momentum,
                                     cfg.outer_lr)
        if not outer.trivial:
            return outer
    return None


def make_multi_step(cfg: TrainConfig, strategy: strat.Strategy,
                    mesh: Mesh | None, fault_sig: bool | None = None):
    """Build a compiled K-step training loop (``lax.scan`` over stacked
    batches): ONE dispatch executes K optimizer steps on device.

    Signature: ``fn(params, state, opt_state, key, step0, images, labels) ->
    (params, state, opt_state, losses, oks, mets)`` with ``images``/
    ``labels`` carrying a leading scan axis of length K, ``losses`` shape
    (K,), ``oks`` (K,) f32 per-step health flags (1.0 = loss AND synced
    grads finite) — the in-scan detection signal of the training sentry
    (utils/sentry.py), one sum-of-squares pass over the gradient tree,
    negligible next to the backward — and ``mets`` (K, 2) f32 per-step
    device-side scalars [grad global-norm, post-update param
    global-norm] (round 13): they RIDE the same in-scan output channel
    as the health flag, so telemetry reads them from the step's normal
    outputs and toggling telemetry on/off changes NO compiled program
    (zero extra compiles, bitwise-identical losses — test-pinned).

    This is the TPU-native answer to per-step dispatch overhead: the
    reference's hot loop makes one eager dispatch per op (SURVEY.md 3.1);
    the single-step path here makes one per step; this makes one per K
    steps, which matters when a step is short next to a dispatch and its
    loss fetch.  RNG per step is ``fold_in(key, step0 + i)`` —
    identical to the single-step path's stream, so loss curves match
    exactly regardless of steps_per_loop.
    """
    tx = make_optimizer(cfg)
    # Strategy knobs FIRST: dcn compression flips `stateful`/`vma_opaque`
    # on the hierarchical strategy, and the bucket cap feeds both the
    # overlap markers and the post-backward packing.
    _apply_dcn(cfg, strategy)
    _apply_bucket_mb(cfg, strategy)
    _validate_overlap(cfg, strategy, mesh)
    # Communication-sparse windows (round 18): coherence check at the ONE
    # definition site (strategies.require_sync_window).  sync_every == 1
    # never enters the windowed builder below, so the per-step path —
    # jaxpr, specs, compile count — is byte-identical to round 17 by
    # construction, not by test luck.
    windowed = cfg.sync_every > 1
    if windowed or cfg.outer_opt is not None:
        strat.require_sync_window(
            sync_every=cfg.sync_every, max_sync_every=cfg.max_sync_every,
            mesh=mesh is not None, overlap=cfg.overlap, trainer="train",
            outer_opt=cfg.outer_opt, outer_momentum=cfg.outer_momentum,
            outer_lr=cfg.outer_lr)
    # DiLoCo outer optimizer (round 22): built ONLY when configured and
    # non-trivial, so the plain-mean boundary below stays byte-identical
    # by construction (same discipline as the sync_every==1 gate).
    outer = _outer_of(cfg)
    use_outer = outer is not None
    # The data axis may be factored: hierarchical runs over ('dcn', 'ici').
    data_axes = getattr(strategy, "axes", None) or DATA_AXIS
    bn_axis = data_axes if (cfg.sync_bn and mesh is not None) else None
    bcast_buffers = cfg.broadcast_buffers_resolved and mesh is not None
    # Stateful strategies (error-feedback ring) carry a per-device residual
    # through the scan, alongside BN state; stateless ones thread a dummy.
    stateful = getattr(strategy, "stateful", False)
    grad_fn = jax.value_and_grad(
        partial(_loss_fn, cfg=cfg, bn_axis=bn_axis), has_aux=True)

    # Backward-overlapped sync (round 8): the loss traces with per-bucket
    # custom_vjp sync points at the model's layer-group boundaries, so
    # value_and_grad returns ALREADY-SYNCED grads with each bucket's
    # collective emitted inside the backward graph; the post-backward
    # strategy call is skipped.  Stateful (EF) strategies differentiate
    # w.r.t. the residual too — its "gradient" is the updated residual
    # (strategies.sync_boundary_stateful), threaded back into the scan
    # carry exactly like the post-backward path's returned state.
    overlap = cfg.overlap
    if overlap:
        group_idx = vgg.sync_group_index(cfg.model)

        def _ov_loss(params, state, key, images, labels):
            ov = strat.OverlapSync(strategy, data_axes, params, group_idx)
            return _loss_fn(params, state, key, images, labels, cfg=cfg,
                            bn_axis=bn_axis, boundary=ov.boundary)

        def _ov_loss_stateful(params, sync_state, state, key, images,
                              labels):
            ov = strat.OverlapSync(strategy, data_axes, params, group_idx,
                                   sync_state=sync_state)
            return _loss_fn(params, state, key, images, labels, cfg=cfg,
                            bn_axis=bn_axis, boundary=ov.boundary)

        grad_fn_ov = (jax.value_and_grad(_ov_loss_stateful, argnums=(0, 1),
                                         has_aux=True)
                      if stateful
                      else jax.value_and_grad(_ov_loss, has_aux=True))

    # Chaos-harness plumbing: with an installed STEP-KEYED FaultPlan
    # (nan/inf grad, loss spike) the compiled step gains ONE trailing f32
    # arg (the host's arm_window gate for the in-jit taps); the clean
    # path's signature stays byte-identical.  The Trainer passes its
    # build-time decision so caller and program can never disagree.
    if fault_sig is None:
        fault_sig = faults.step_plan() is not None

    def scan_steps(params, state, opt_state, sync_state, key, step0,
                   images, labels, fault_arm=0.0, *, axis: str | None):
        def body(carry, batch):
            params, state, opt_state, sync_state, step = carry
            imgs, lbls = batch
            k = jax.random.fold_in(key, step)
            if axis is not None:
                k = jax.random.fold_in(k, jax.lax.axis_index(axis))
                # Per-shard grads via a device-varying view (see
                # make_train_step); the strategy's collective then restores
                # cross-replica invariance before the optimizer update.
                local_params = _as_varying(params, axis)
            else:
                local_params = params
            if overlap:
                # grads arrive pre-synced (in-backward bucket collectives);
                # the chaos taps therefore land POST-sync here — an
                # injected NaN still poisons params and trips the health
                # flag, it just no longer rides the wire first
                if stateful:
                    (loss, state), (grads, sync_state) = grad_fn_ov(
                        local_params, sync_state, state, k, imgs, lbls)
                else:
                    (loss, state), grads = grad_fn_ov(
                        local_params, state, k, imgs, lbls)
            else:
                (loss, state), grads = grad_fn(local_params, state, k,
                                               imgs, lbls)
            # chaos-harness taps: trace-time no-ops unless a FaultPlan is
            # installed (utils/faults.py) — pre-sync on the post-backward
            # path, so an injected bad shard propagates through the
            # collective like a real one
            grads = faults.tap_grads(grads, step, fault_arm)
            loss = faults.tap_loss(loss, step, fault_arm)
            if bcast_buffers and axis is not None:
                # torch DDP broadcast_buffers: BN running stats follow rank
                # 0 (buffers broadcast from rank 0 every forward — reference
                # main_ddp.py:137's engine).  Broadcasting rank 0's *updated*
                # stats here, after the local update instead of before the
                # next forward, yields the identical rank-0-authoritative
                # trajectory (next forward sees rank 0's stats either way)
                # while keeping the carried state replica-identical.
                idx = jax.lax.axis_index(axis)
                state = jax.tree.map(
                    lambda s: _as_varying(
                        jax.lax.psum(
                            jnp.where(idx == 0, s, jnp.zeros_like(s)), axis),
                        axis),
                    state)
            if not overlap:
                if stateful:
                    grads, sync_state = strategy(grads, axis, sync_state)
                else:
                    grads = strategy(grads, axis)
            # per-step health flag (sentry): finite loss + finite synced
            # grads, via one global sum-of-squares over the tree
            gsq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                      for g in jax.tree.leaves(grads))
            ok = (jnp.isfinite(loss) & jnp.isfinite(gsq)).astype(
                jnp.float32)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            # per-step telemetry scalars (round 13) riding the SAME
            # output channel as the health flag: grad global-norm (gsq
            # is already computed for `ok`) and post-update param
            # global-norm — device-side, so telemetry-on never adds a
            # program or a compile (ops.step_metrics: the ONE
            # implementation, shared with lm.py's step finishers)
            met = ops.step_metrics(gsq, params)
            return (params, state, opt_state, sync_state, step + 1), (
                loss, ok, met)

        (params, state, opt_state, sync_state, _), (losses, oks, mets) = (
            jax.lax.scan(
                body, (params, state, opt_state, sync_state, step0),
                (images, labels)))
        return params, state, opt_state, sync_state, losses, oks, mets

    if windowed:
        # Local-SGD window loop: a nested scan — outer over K/H window
        # boundaries, inner over H local steps — so the schedule
        # inspector's trip accounting (utils/debug.py multiplies nested
        # scan lengths) can PROVE the boundary collectives run once per
        # window, which a lax.cond-gated flat loop cannot (cond bodies
        # are counted every trip).  The carry tracks the window's params
        # as anchor + delta: ``anchor`` is the last exchanged (replica-
        # identical) point, ``delta`` the locally accumulated optimizer
        # updates since — the boundary then exchanges ONLY delta, and
        # plain-SGD windows are bitwise an accumulated-gradient-averaging
        # oracle by pure reassociation (tests/test_localsgd.py).
        hier = hasattr(strategy, "window_exchange")

        def scan_steps_windowed(params, state, opt_state, sync_state, key,
                                step0, images, labels, fault_arm=0.0, *,
                                axis):
            h = cfg.sync_every
            k_total = images.shape[0]
            if k_total % h:
                raise ValueError(
                    f"dispatch of {k_total} steps is not a multiple of "
                    f"sync_every={h}: every compiled dispatch must end "
                    f"on a window boundary so params leave replicated")

            def local_body(anchor, carry, batch):
                delta, state, opt_state, step = carry
                imgs, lbls = batch
                k = jax.random.fold_in(key, step)
                k = jax.random.fold_in(k, jax.lax.axis_index(axis))
                local_params = _as_varying(
                    jax.tree.map(jnp.add, anchor, delta), axis)
                (loss, state), grads = grad_fn(local_params, state, k,
                                               imgs, lbls)
                grads = faults.tap_grads(grads, step, fault_arm)
                loss = faults.tap_loss(loss, step, fault_arm)
                if bcast_buffers:
                    idx = jax.lax.axis_index(axis)
                    state = jax.tree.map(
                        lambda s: _as_varying(
                            jax.lax.psum(
                                jnp.where(idx == 0, s, jnp.zeros_like(s)),
                                axis), axis),
                        state)
                if hier:
                    # within-slice mean every step: the per-step path's
                    # ICI ops, zero DCN ops (Hierarchical.local_sync);
                    # flat strategies step fully locally instead
                    grads = strategy.local_sync(grads, axis)
                gsq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                          for g in jax.tree.leaves(grads))
                ok = (jnp.isfinite(loss) & jnp.isfinite(gsq)).astype(
                    jnp.float32)
                updates, opt_state = tx.update(grads, opt_state,
                                               local_params)
                delta = jax.tree.map(jnp.add, delta, updates)
                met = ops.step_metrics(
                    gsq, jax.tree.map(jnp.add, anchor, delta))
                return (delta, state, opt_state, step + 1), (loss, ok,
                                                             met)

            def window_body(carry, batch):
                anchor, delta, state, opt_state, sync_state, step = carry
                (delta, state, opt_state, step), outs = jax.lax.scan(
                    partial(local_body, anchor),
                    (delta, state, opt_state, step), batch)
                # boundary: cross-replica mean of the accumulated update
                # — the window's ONE slow exchange (shard-sized over dcn
                # for hierarchical, incl. the int8/int4+EF ring; the
                # full strategy collective for flat strategies)
                if use_outer:
                    # the outer momentum rides sync_state as a flat f32
                    # TAIL after the strategy's residual segments —
                    # split at a trace-time-static offset, exchange on
                    # the residual part only, then move the anchor by
                    # outer_opt(mean delta) instead of the plain add
                    m_len = strat.OuterOptimizer.state_len(anchor)
                    res_len = sync_state.shape[0] - m_len
                    res = sync_state[:res_len]
                    if hier:
                        ex = (strategy.window_exchange(delta, axis, res)
                              if stateful
                              else strategy.window_exchange(delta, axis))
                    else:
                        ex = (strategy(delta, axis, res) if stateful
                              else strategy(delta, axis))
                    if stateful:
                        d_avg, res = ex
                    else:
                        d_avg = ex
                    anchor, m_flat = outer.apply_flat(
                        anchor, d_avg, sync_state[res_len:])
                    sync_state = jnp.concatenate([res, m_flat])
                else:
                    if hier:
                        ex = (strategy.window_exchange(delta, axis,
                                                       sync_state)
                              if stateful
                              else strategy.window_exchange(delta, axis))
                    else:
                        ex = (strategy(delta, axis, sync_state)
                              if stateful else strategy(delta, axis))
                    if stateful:
                        d_avg, sync_state = ex
                    else:
                        d_avg = ex
                    anchor = jax.tree.map(jnp.add, anchor, d_avg)
                delta = jax.tree.map(jnp.zeros_like, delta)
                return (anchor, delta, state, opt_state, sync_state,
                        step), outs

            w = k_total // h
            imgs = images.reshape((w, h) + images.shape[1:])
            lbls = labels.reshape((w, h) + labels.shape[1:])
            delta = jax.tree.map(jnp.zeros_like, params)
            (params, _, state, opt_state, sync_state, _), (losses, oks,
                                                           mets) = (
                jax.lax.scan(
                    window_body,
                    (params, delta, state, opt_state, sync_state, step0),
                    (imgs, lbls)))
            return (params, state, opt_state, sync_state,
                    losses.reshape(k_total), oks.reshape(k_total),
                    mets.reshape((k_total,) + mets.shape[2:]))

    if mesh is None:
        if strategy.needs_mesh:
            raise ValueError(f"strategy {strategy.name!r} requires a mesh")

        if fault_sig:
            @partial(jax.jit, donate_argnums=compat.donate(0, 1, 2, 3))
            def multi_step(params, state, opt_state, sync_state, key,
                           step0, images, labels, fault_arm):
                return scan_steps(params, state, opt_state, sync_state,
                                  key, step0, images, labels, fault_arm,
                                  axis=None)
        else:
            @partial(jax.jit, donate_argnums=compat.donate(0, 1, 2, 3))
            def multi_step(params, state, opt_state, sync_state, key,
                           step0, images, labels):
                return scan_steps(params, state, opt_state, sync_state,
                                  key, step0, images, labels, axis=None)

        return multi_step

    if windowed:
        # Per-device momentum (local-momentum local SGD): the optimizer
        # state rides a leading device axis like BN state — it never
        # crosses the wire, so the boundary exchange stays delta-only
        # (the 1/H dcn-byte claim) at the cost of replica-local buffers.
        opt_spec = P(data_axes)

        def run_shard(params, state, opt_state, sync_state, key, step0,
                      images, labels, fault_arm):
            local_state = jax.tree.map(lambda s: s[0], state)
            local_opt = jax.tree.map(lambda s: s[0], opt_state)
            local_sync = jax.tree.map(lambda s: s[0], sync_state)
            (params, new_state, new_opt, new_sync, losses, oks,
             mets) = scan_steps_windowed(
                params, local_state, local_opt, local_sync, key, step0,
                images, labels, fault_arm, axis=data_axes)
            new_state = jax.tree.map(lambda s: s[None], new_state)
            new_opt = jax.tree.map(lambda s: s[None], new_opt)
            new_sync = jax.tree.map(lambda s: s[None], new_sync)
            return (params, new_state, new_opt, new_sync,
                    jax.lax.pmean(losses, data_axes),
                    jax.lax.pmean(oks, data_axes),
                    jax.lax.pmean(_as_varying(mets, data_axes),
                                  data_axes))
    else:
        opt_spec = P()

        def run_shard(params, state, opt_state, sync_state, key, step0,
                      images, labels, fault_arm):
            local_state = jax.tree.map(lambda s: s[0], state)
            local_sync = jax.tree.map(lambda s: s[0], sync_state)
            (params, new_state, opt_state, new_sync, losses, oks,
             mets) = scan_steps(
                params, local_state, opt_state, local_sync, key, step0,
                images, labels, fault_arm, axis=data_axes)
            new_state = jax.tree.map(lambda s: s[None], new_state)
            new_sync = jax.tree.map(lambda s: s[None], new_sync)
            # oks pmean: 1.0 iff EVERY replica's step was healthy (a
            # poisoned shard pulls the mean below 1 even before its sync
            # spreads it); mets pmean: synced grads/params are
            # replica-identical, so the mean is the value — it just also
            # PROVES invariance to the vma checker (a few scalar psums,
            # excluded from the schedule pins by their min_bytes
            # filter).  mets may arrive vma-INVARIANT (derived from
            # post-psum grads and updated params), and the runtime
            # rejects reducing an invariant value — cast varying first
            # (pass-through where already varying).
            return (params, new_state, opt_state, new_sync,
                    jax.lax.pmean(losses, data_axes),
                    jax.lax.pmean(oks, data_axes),
                    jax.lax.pmean(_as_varying(mets, data_axes),
                                  data_axes))

    if fault_sig:
        def shard_multi_step(params, state, opt_state, sync_state, key,
                             step0, images, labels, fault_arm):
            return run_shard(params, state, opt_state, sync_state, key,
                             step0, images, labels, fault_arm)
        extra_specs: tuple = (P(),)
    else:
        def shard_multi_step(params, state, opt_state, sync_state, key,
                             step0, images, labels):
            return run_shard(params, state, opt_state, sync_state, key,
                             step0, images, labels, 0.0)
        extra_specs = ()

    return jax.jit(shard_map(
        shard_multi_step,
        mesh=mesh,
        in_specs=(P(), P(data_axes), opt_spec, P(data_axes), P(), P(),
                  P(None, data_axes), P(None, data_axes)) + extra_specs,
        out_specs=(P(), P(data_axes), opt_spec, P(data_axes), P(), P(),
                   P()),
        # Ring-collective strategies assemble their result from ppermute
        # hops: bitwise replicated by construction, but not provably so to
        # the vma checker (no sanctioned varying->invariant downcast).
        check_vma=not getattr(strategy, "vma_opaque", False),
    ), donate_argnums=compat.donate(0, 1, 2, 3))


def replicate_state(state: PyTree, n: int) -> PyTree:
    """Stack BN state with a leading device axis (identical initial stats on
    every replica — same-seed construction, SURVEY.md section 2.3)."""
    return jax.tree.map(lambda s: jnp.broadcast_to(s[None], (n,) + s.shape), state)


def rank0_state(state: PyTree, mesh: Mesh | None) -> PyTree:
    """Rank 0's BN stats for evaluation (torch DDP broadcasts module buffers
    from rank 0 — reference main_ddp.py:137's engine behavior).

    Always returns host copies: the live ``state`` buffers are donated into
    the next compiled step, so a held reference would otherwise be deleted.
    Multi-host meshes: the replica-stacked state spans processes, so the
    fetch is a collective (every process must call this together).
    """
    if mesh is None:
        return jax.tree.map(np.asarray, state)

    def fetch0(s):
        if isinstance(s, jax.Array) and not s.is_fully_addressable:
            from jax.experimental import multihost_utils
            return np.asarray(
                multihost_utils.process_allgather(s, tiled=True))[0]
        return np.asarray(s)[0]

    return jax.tree.map(fetch0, state)


class Trainer:
    """Owns (params, state, opt_state) and the compiled step.

    Replaces the per-script ``main()``s: build model + optimizer from one
    seed, then drive ``train_epoch`` / ``evaluate`` (reference
    main_all_reduce.py:84-135).
    """

    def __init__(self, cfg: TrainConfig, mesh: Mesh | None = None,
                 num_devices: int | None = None):
        # strategy="auto" (round 11): resolve FIRST, to a named strategy
        # plus bucket/dcn knobs, so everything below — including the
        # bitwise-pinned step builders — runs the exact named path.  The
        # explainable plan (predicted ms + per-axis bytes) is kept on
        # the trainer; pass mesh=None so the resolved strategy's own
        # mesh recipe applies.
        self.sync_plan = None
        if cfg.strategy == "auto":
            if mesh is not None:
                # resolution decides the topology (flat vs factored) and
                # hence the mesh shape; a pre-built mesh could disagree
                # with whatever the chooser picks, which would only
                # surface as a cryptic trace-time sharding error
                raise ValueError(
                    "strategy='auto' builds its own mesh from the "
                    "resolved plan; pass mesh=None (use num_devices to "
                    "bound the fleet)")
            from .parallel import autotune
            cfg, self.sync_plan = autotune.resolve_train_auto(
                cfg, num_devices=num_devices)
        self.cfg = cfg
        if cfg.strategy == "routed" or cfg.sync_route is not None:
            # declarative routed sync (round 20): the route string IS
            # the strategy — parse it into a HopPlan and execute it with
            # RoutedSync over the trainer's factored ('dcn', 'ici') mesh
            from .parallel import routing
            if cfg.strategy != "routed" or cfg.sync_route is None:
                raise ValueError(
                    "routed sync needs BOTH strategy='routed' and a "
                    f"sync_route string (got strategy={cfg.strategy!r}, "
                    f"sync_route={cfg.sync_route!r})")
            if cfg.dcn_compress is not None:
                raise ValueError(
                    "strategy='routed' encodes compression in the route "
                    "itself (e.g. 'dcn:ring[int4+ef]'); dcn_compress "
                    "must stay None")
            route_plan = routing.parse_route(cfg.sync_route)
            if route_plan.mesh_axes() != ("dcn", "ici"):
                raise ValueError(
                    f"the trainer's mesh recipe builds two tiers "
                    f"('dcn', 'ici'); route {route_plan.describe()!r} "
                    f"spans {route_plan.mesh_axes()} — run other "
                    f"topologies through RoutedSync directly")
            self.strategy = routing.RoutedSync(
                route_plan,
                n_by_axis=None)  # bound below, from the built mesh
        else:
            self.strategy = strat.get(cfg.strategy)
        self.data_axes = getattr(self.strategy, "axes", None) or DATA_AXIS
        if self.strategy.needs_mesh and mesh is None:
            if isinstance(self.data_axes, tuple):
                n = num_devices or len(jax.devices())
                if n % cfg.dcn_size:
                    raise ValueError(
                        f"dcn_size {cfg.dcn_size} must divide the "
                        f"{n}-device fleet for strategy "
                        f"{self.strategy.name!r}")
                mesh = make_mesh(n, axis_names=self.data_axes,
                                 axis_shape=(cfg.dcn_size,
                                             n // cfg.dcn_size))
            else:
                mesh = make_mesh(num_devices)
        if (self.strategy.needs_mesh and isinstance(self.data_axes, tuple)
                and tuple(mesh.axis_names) != self.data_axes):
            raise ValueError(
                f"strategy {self.strategy.name!r} needs a mesh with axes "
                f"{self.data_axes}, got {mesh.axis_names}")
        if self.strategy.needs_mesh and isinstance(self.data_axes, tuple):
            # caller-supplied factored meshes too: the outer (dcn) extent
            # must match cfg.dcn_size — the int8 EF residual layout and
            # the bench accounting are sized from the config, and a
            # mismatch would surface as a cryptic reshape at trace time
            dcn_axis = self.data_axes[0]
            sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            if sizes[dcn_axis] != cfg.dcn_size:
                raise ValueError(
                    f"mesh {dcn_axis!r} axis has size {sizes[dcn_axis]} "
                    f"but cfg.dcn_size is {cfg.dcn_size}; pass a mesh "
                    f"matching the config (or mesh=None to build one)")
        self.mesh = mesh if self.strategy.needs_mesh else None
        self.n_replicas = self.mesh.devices.size if self.mesh else 1
        if self.mesh is not None and hasattr(self.strategy, "n_by_axis"):
            # RoutedSync sizes its EF state from static per-axis extents
            self.strategy.n_by_axis = dict(
                zip(self.mesh.axis_names,
                    (int(s) for s in self.mesh.devices.shape)))
        # strategy knobs must land before init_state (dcn compression
        # flips statefulness and the EF residual layout follows the
        # bucket plan + dcn_size) and fail fast on incapable strategies
        _apply_dcn(cfg, self.strategy)
        _apply_bucket_mb(cfg, self.strategy)
        _validate_overlap(cfg, self.strategy, self.mesh)
        # round-18 window coherence at the ONE definition site — includes
        # the dispatch-alignment refusal (steps_per_loop % sync_every)
        # so every compiled dispatch ends on a window boundary
        strat.require_sync_window(
            sync_every=cfg.sync_every, max_sync_every=cfg.max_sync_every,
            mesh=self.mesh is not None, overlap=cfg.overlap,
            steps_per_loop=cfg.steps_per_loop, trainer="train",
            outer_opt=cfg.outer_opt, outer_momentum=cfg.outer_momentum,
            outer_lr=cfg.outer_lr)

        key = jax.random.key(cfg.seed)
        self.init_key, self.data_key = jax.random.split(key)
        params, state = vgg.init(self.init_key, cfg.model)
        tx = make_optimizer(cfg)
        opt_state = tx.init(params)

        # Stateful strategies (error-feedback ring) carry a per-device
        # residual between steps, stacked like BN state; stateless ones
        # thread a zero-size dummy through the same slot.
        if getattr(self.strategy, "stateful", False):
            sync_state = self.strategy.init_state(params, self.n_replicas)
        else:
            sync_state = jnp.zeros((0,), jnp.float32)
        if _outer_of(cfg) is not None:
            # DiLoCo outer momentum (round 22): a flat f32 tail appended
            # after the strategy's residual segments — same carry slot,
            # so the window scan's signature and specs are unchanged
            sync_state = jnp.concatenate(
                [sync_state,
                 jnp.zeros((strat.OuterOptimizer.state_len(params),),
                           jnp.float32)])
        sync_state = jnp.broadcast_to(
            sync_state[None], (self.n_replicas,) + sync_state.shape)

        if self.mesh is not None:
            rep = replicated(self.mesh)
            shd = NamedSharding(self.mesh, P(self.data_axes))
            params = jax.device_put(params, rep)
            if cfg.sync_every > 1:
                # windowed mode: per-device momentum rides a leading
                # device axis like BN state (local-momentum local SGD —
                # it never crosses the wire, keeping the boundary
                # exchange delta-only)
                opt_state = jax.device_put(
                    replicate_state(opt_state, self.n_replicas), shd)
            else:
                opt_state = jax.device_put(opt_state, rep)
            state = jax.device_put(
                replicate_state(state, self.n_replicas), shd)
            sync_state = jax.device_put(sync_state, shd)
        self.params, self.state, self.opt_state = params, state, opt_state
        self.sync_state = sync_state
        self._multi_fn = None   # jitted K-step program, built lazily
        self._compiled = {}     # (images.shape, labels.shape) -> AOT executable
        self._step = 0
        self.last_ok = None     # (K,) health flags of the last dispatch
        # (K, 2) [grad gnorm, param gnorm] of the last dispatch — the
        # round-13 telemetry scalars, fetched lazily like last_ok
        self.last_metrics = None
        # snapshot the chaos-tap signature decision NOW: the AOT
        # executables are cached, so a plan installed mid-run must not
        # change the compiled arg list (install plans before building)
        self._fault_sig = faults.step_plan() is not None
        # vma-opaque strategies (ppermute-assembled results) compile with
        # check_vma=False — the static replication proof is off, so EVERY
        # freshly compiled executable (first step, and any later
        # shape-specialized recompile) has its first real step followed by
        # a DYNAMIC verification that params/opt-state are still bitwise
        # replicated (the failure mode the static checker would have
        # caught is a missing/broken collective, which desyncs
        # immediately, not gradually).  Tracked PER EXECUTABLE (shape
        # key): _executable arms the key on cache miss, train_steps
        # verifies after the first run of each armed key — so interleaved
        # precompiles/shapes each get their own check.
        self._vma_opaque = bool(
            getattr(self.strategy, "vma_opaque", False)
            and self.mesh is not None)
        self._unverified_exes: set = set()
        self._window_wire_bytes = self._compute_window_wire_bytes()

    def _compute_window_wire_bytes(self):
        """Static f32 payload of ONE window-boundary exchange (the round-18
        per-window wire gauge): the shard-sized dcn hop for hierarchical
        (per bucket, ceil(bucket/n_ici) elements), the full tree for flat
        strategies.  Compression rides below this estimate (int8 ~1/4,
        int4 ~1/8 of it); None when not windowed."""
        if self.cfg.sync_every <= 1:
            return None
        leaves = jax.tree.leaves(self.params)
        if hasattr(self.strategy, "window_exchange"):
            n_ici = max(self.n_replicas // self.cfg.dcn_size, 1)
            return sum(
                4 * -(-sum(leaves[i].size for i in b) // n_ici)
                for b in strat.make_bucket_plan(
                    leaves, self.strategy.bucket_bytes))
        return sum(4 * leaf.size for leaf in leaves)

    # -- one optimizer step over a *global* batch -------------------------
    def train_step(self, images: np.ndarray, labels: np.ndarray) -> jax.Array:
        """One step == ``train_steps`` with K=1 (same compiled path, same
        RNG stream: per-step key is fold_in(data_key, step))."""
        return self.train_steps(images[None], labels[None])[0]

    # -- K optimizer steps in one device dispatch -------------------------
    def _stage(self, images, labels):
        """Place stacked (K, global_batch, ...) arrays onto the mesh.

        Idempotent: already-staged jax.Arrays (e.g. from the prefetch
        thread) pass through — re-staging a global multi-host array through
        make_array_from_process_local_data would fail."""
        if self.mesh is None:
            return images, labels
        shd = NamedSharding(self.mesh, P(None, self.data_axes))
        if isinstance(images, jax.Array) and images.sharding == shd:
            return images, labels
        if jax.process_count() > 1:
            # Multi-host: each process contributes its local ranks' shard
            # of the global batch (the per-host DistributedSampler split,
            # reference main_all_reduce.py:112); assemble a global array.
            return (jax.make_array_from_process_local_data(shd, images),
                    jax.make_array_from_process_local_data(shd, labels))
        if images.shape[1] % self.n_replicas != 0:
            raise ValueError(
                f"global batch {images.shape[1]} not divisible by the "
                f"{self.n_replicas}-device {self.data_axes!r} mesh axis; "
                f"pass per-replica batches of equal size (the sampler "
                f"pads the epoch for exactly this reason)")
        return jax.device_put(images, shd), jax.device_put(labels, shd)

    def _executable(self, args):
        """AOT-compile the K-step program for these batch shapes (cached).

        ``lower().compile()`` builds the executable without running it, so
        callers (train_epoch) can keep compile time out of timed windows —
        the reference's iter-0 exclusion contract (main.py:43-48) would
        otherwise be diluted to 1/K by the scan."""
        key = (args[6].shape, args[7].shape)  # (images, labels)
        exe = self._compiled.get(key)
        if exe is None:
            # compile lane (round 15): per-program-hash compile time +
            # cache size on the unified stream; telemetry off = no-op
            with monitor.compile_span(
                    "aot_compile", key=key,
                    cache_size=lambda: len(self._compiled)):
                if self._multi_fn is None:
                    self._multi_fn = make_multi_step(
                        self.cfg, self.strategy, self.mesh,
                        fault_sig=self._fault_sig)
                exe = self._multi_fn.lower(*args).compile()
                self._compiled[key] = exe
            if self._vma_opaque:
                # new executable, no static vma proof: re-verify
                # replication after ITS first real step (see __init__)
                self._unverified_exes.add(key)
        return exe

    def _args(self, images, labels, fault_arm: float = 0.0):
        step0 = jnp.asarray(self._step, jnp.int32)
        args = (self.params, self.state, self.opt_state, self.sync_state,
                self.data_key, step0, images, labels)
        if self._fault_sig:
            # the compiled step carries the chaos-tap arm scalar (traced,
            # so 0.0 vs 1.0 never recompiles); clean builds have no slot
            args += (jnp.float32(fault_arm),)
        return args

    def precompile_steps(self, images: np.ndarray, labels: np.ndarray) -> None:
        """Ensure the program for these (K, batch, ...) shapes is compiled
        WITHOUT executing a step (no state is consumed)."""
        images, labels = self._stage(images, labels)
        self._executable(self._args(images, labels))

    def train_steps(self, images: np.ndarray, labels: np.ndarray) -> jax.Array:
        """Run ``K = images.shape[0]`` steps over stacked global batches
        (K, global_batch, ...) as one compiled ``lax.scan``; returns the K
        per-step losses.  Produces the identical parameter/RNG trajectory as
        K ``train_step`` calls — just one dispatch instead of K."""
        k = images.shape[0]
        if self.cfg.sync_every > 1 and k % self.cfg.sync_every:
            raise ValueError(
                f"train_steps got {k} steps with sync_every="
                f"{self.cfg.sync_every}: dispatches must be window-"
                f"aligned (k % H == 0) so params leave the step "
                f"replicated; stack window-multiple batches (train_step's "
                f"K=1 path is likewise unavailable under windows)")
        faults.maybe_delay(self._step, k)  # chaos: straggler (no-op unplanned)
        images, labels = self._stage(images, labels)
        # one-shot host arming of step-keyed grad/loss faults (consumes a
        # firing only when the plan's step falls in this dispatch window).
        # Gated on the build-time signature snapshot: a plan installed
        # AFTER construction has no arm slot in the compiled step, and
        # arming would silently consume its firing without injecting
        # (plans must be installed before building — _fault_sig note)
        args = self._args(images, labels,
                          faults.arm_window(self._step, k)
                          if self._fault_sig else 0.0)
        key = (args[6].shape, args[7].shape)
        t0 = time.perf_counter()
        (self.params, self.state, self.opt_state, self.sync_state,
         losses, oks, mets) = self._executable(args)(*args)
        # per-step health flags for the training sentry (1.0 = loss and
        # synced grads finite on every replica); fetched lazily by readers
        self.last_ok = oks
        self.last_metrics = mets
        self._step += k
        faults.maybe_crash(self._step, k)  # chaos: injected process death
        tel = telemetry.active()
        if tel is not None:
            telemetry.emit_train_steps(tel, t0, self._step - k, k, losses,
                                       oks, mets)
            if self.cfg.sync_every > 1:
                telemetry.emit_sync_windows(
                    tel, t0, self._step - k, k, self.cfg.sync_every,
                    wire_bytes=self._window_wire_bytes)
        if key in self._unverified_exes:
            self._unverified_exes.discard(key)
            self.check_consistency()
        return losses

    def train_epoch(self, loaders, epoch: int, *, log=print, on_step=None):
        """One epoch over per-replica loaders, with the reference's metric
        windows (loss/20 iters, time/40 iters excl. iter 0 — SURVEY.md 2.3).

        ``loaders``: one DataLoader per replica (the global batch is their
        concatenation), or a single loader for the single-process baseline.
        ``on_step(step)`` fires once per device dispatch (before compile) —
        the elastic CLI's heartbeat cadence, so a long epoch cannot be
        misread as a hung worker (launch.py heartbeat staleness).
        """
        if not isinstance(loaders, (list, tuple)):
            loaders = [loaders]
        # One loader per *locally-fed* replica: all of them single-host, this
        # process's shard of the mesh on multi-host.
        local = max(1, self.n_replicas // max(jax.process_count(), 1))
        assert len(loaders) == local, (
            f"got {len(loaders)} loaders for {local} local replicas")
        for dl in loaders:
            dl.set_epoch(epoch)
        loss_meter, time_meter = LossMeter(), IterTimeMeter()

        def record(batch_idx, loss_val, elapsed):
            rec = loss_meter.update(batch_idx, loss_val)
            if rec and log:
                log(f"Epoch: {epoch + 1}, Iteration: {rec.first_iter}-"
                    f"{rec.last_iter}, Average Loss: {rec.value:.3f}")
            rec = time_meter.update(batch_idx, elapsed)
            if rec and log:
                log(f"Avg Time for iteration {rec.first_iter}-{rec.last_iter}: "
                    f"{rec.value} seconds.")

        spl = max(1, self.cfg.steps_per_loop)

        def host_chunks():
            """Stack loader batches into K-step scan chunks (a ragged final
            batch flushes early — it can't stack with full ones)."""
            chunk: list[tuple[np.ndarray, np.ndarray]] = []
            for batches in zip(*loaders):
                batch = (np.concatenate([b[0] for b in batches]),
                         np.concatenate([b[1] for b in batches]))
                if chunk and batch[0].shape != chunk[0][0].shape:
                    yield chunk
                    chunk = []
                chunk.append(batch)
                if len(chunk) == spl:
                    yield chunk
                    chunk = []
            if chunk:
                yield chunk  # tail: one smaller scan, compiled once per size

        def staged():
            """Assemble + device-stage chunks; runs on the prefetch thread
            so transfer overlaps the previous chunk's compute."""
            for chunk in host_chunks():
                images = np.stack([c[0] for c in chunk])
                labels = np.stack([c[1] for c in chunk])
                if self.mesh is not None:
                    images, labels = self._stage(images, labels)
                else:
                    images, labels = jax.device_put((images, labels))
                yield len(chunk), images, labels

        batch_idx = 0
        for k, images, labels in pipeline.prefetch(staged(), depth=2):
            if on_step is not None:
                on_step(self._step)
            # Compile outside the timed window: the reference's metric
            # excludes warm-up (iter 0, main.py:43-48); with a K-step scan
            # the compile would otherwise smear across K counted iters.
            self.precompile_steps(images, labels)
            begin = time.perf_counter()
            with tracing.annotate_step(self._step):
                losses = np.asarray(self.train_steps(images, labels))
            per_step = (time.perf_counter() - begin) / k
            for loss_val in losses:
                record(batch_idx, float(loss_val), per_step)
                batch_idx += 1
        return loss_meter, time_meter

    def eval_state(self) -> PyTree:
        return rank0_state(self.state, self.mesh)

    # -- elastic resize (round 12) ----------------------------------------
    def rebuild(self, mesh: Mesh | None = None,
                num_devices: int | None = None, **overrides) -> None:
        """Re-create the compiled step on a NEW mesh, carrying the live
        training state across — the in-process half of the elastic gang
        (parallel/elastic.py): when the fleet shrinks or grows, the step
        is re-built rather than the whole process.

        Params/optimizer state are replicated, so they re-place exactly;
        replica-stacked BN state takes rank 0's stats re-stacked to the
        new replica count (the same convention as the cross-topology
        ``Checkpointer.maybe_restore``, so a rebuilt trainer and a fresh
        one restored from the last checkpoint continue BITWISE-equal —
        test-pinned); the EF sync residual re-initializes (dropping it
        is safe — residuals re-accumulate within one step).  Compiled
        executables are discarded; the step counter survives.

        Single-controller only: a multi-process gang resizes by drain +
        re-rendezvous (the worker re-runs init at the new WORLD_SIZE),
        not by in-process rebuild."""
        if jax.process_count() > 1:
            raise ValueError(
                "in-process rebuild is single-controller; multi-process "
                "gangs resize via the elastic agent's drain + "
                "re-rendezvous (launch.py --elastic)")
        was_windowed = self.cfg.sync_every > 1
        if overrides:
            # config overrides (round 18): the monitor's straggler
            # actuator widens/narrows sync_every through here — re-tune
            # step knobs on the LIVE strategy; a strategy change needs a
            # fresh Trainer (mesh recipe and sync-state layout differ)
            cfg = replace(self.cfg, **overrides)
            if cfg.strategy != self.cfg.strategy:
                raise ValueError(
                    "rebuild(**overrides) re-tunes step knobs on the "
                    "live strategy; changing the strategy itself needs "
                    "a fresh Trainer")
            strat.require_sync_window(
                sync_every=cfg.sync_every,
                max_sync_every=cfg.max_sync_every, mesh=True,
                overlap=cfg.overlap, steps_per_loop=cfg.steps_per_loop,
                trainer="train", outer_opt=cfg.outer_opt,
                outer_momentum=cfg.outer_momentum, outer_lr=cfg.outer_lr)
            self.cfg = cfg
        if not self.strategy.needs_mesh:
            raise ValueError(
                f"strategy {self.strategy.name!r} runs without a mesh; "
                f"there is no topology to resize")
        if mesh is None:
            if isinstance(self.data_axes, tuple):
                n = num_devices or len(jax.devices())
                if n % self.cfg.dcn_size:
                    raise ValueError(
                        f"dcn_size {self.cfg.dcn_size} must divide the "
                        f"resized {n}-device fleet")
                mesh = make_mesh(n, axis_names=self.data_axes,
                                 axis_shape=(self.cfg.dcn_size,
                                             n // self.cfg.dcn_size))
            else:
                mesh = make_mesh(num_devices)
        if isinstance(self.data_axes, tuple):
            if tuple(mesh.axis_names) != self.data_axes:
                raise ValueError(
                    f"strategy {self.strategy.name!r} needs a mesh with "
                    f"axes {self.data_axes}, got {mesh.axis_names}")
            # same extent check as __init__: the EF residual layout and
            # bench accounting are sized from cfg.dcn_size, and a
            # mismatched caller-supplied mesh would only surface as a
            # cryptic reshape at trace time
            dcn_axis = self.data_axes[0]
            sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            if sizes[dcn_axis] != self.cfg.dcn_size:
                raise ValueError(
                    f"resized mesh {dcn_axis!r} axis has size "
                    f"{sizes[dcn_axis]} but cfg.dcn_size is "
                    f"{self.cfg.dcn_size}; pass a matching mesh (or "
                    f"mesh=None to build one)")
        from .utils.checkpoint import _fetch  # owned copies (donation)

        params_host = jax.tree.map(_fetch, self.params)
        opt_host = jax.tree.map(_fetch, self.opt_state)
        if was_windowed:
            # per-device momentum rode a leading device axis; carry rank
            # 0's buffers across the resize (the BN rank-0 convention)
            opt_host = jax.tree.map(lambda s: s[0], opt_host)
        state0 = rank0_state(self.state, self.mesh)  # rank-0 authoritative

        self.mesh = mesh
        self.n_replicas = mesh.devices.size
        rep = replicated(mesh)
        shd = NamedSharding(mesh, P(self.data_axes))
        self.params = jax.device_put(params_host, rep)
        if self.cfg.sync_every > 1:
            self.opt_state = jax.device_put(
                replicate_state(jax.tree.map(jnp.asarray, opt_host),
                                self.n_replicas), shd)
        else:
            self.opt_state = jax.device_put(opt_host, rep)
        self.state = jax.device_put(
            replicate_state(jax.tree.map(jnp.asarray, state0),
                            self.n_replicas), shd)
        if getattr(self.strategy, "stateful", False):
            sync_state = self.strategy.init_state(params_host,
                                                  self.n_replicas)
        else:
            sync_state = jnp.zeros((0,), jnp.float32)
        if _outer_of(self.cfg) is not None:
            # fresh outer momentum after a resize (anchor topology
            # changed; same convention as the EF residual reset)
            sync_state = jnp.concatenate(
                [sync_state,
                 jnp.zeros((strat.OuterOptimizer.state_len(params_host),),
                           jnp.float32)])
        self.sync_state = jax.device_put(
            jnp.broadcast_to(sync_state[None],
                             (self.n_replicas,) + sync_state.shape), shd)
        self._multi_fn = None
        self._compiled = {}
        self._unverified_exes = set()
        self.last_ok = None
        self.last_metrics = None
        self._window_wire_bytes = self._compute_window_wire_bytes()

    def check_consistency(self) -> None:
        """Verify the DP invariants (utils/debug.py): params and optimizer
        state bitwise-identical on every replica, and finite.  The check the
        reference never does — torch DDP enforces it once by broadcast; the
        manual variants just trust same-seed init + sync (SURVEY.md 2.3).
        Under sync_every > 1 the optimizer state is per-device BY DESIGN
        (local momentum, a leading device axis) — only params, which every
        window boundary re-replicates, are checked there."""
        tree = {"params": self.params}
        if self.cfg.sync_every == 1:
            tree["opt_state"] = self.opt_state
        dbg.assert_replicas_in_sync(tree, what="params/opt_state")
        dbg.assert_finite(jax.tree.map(np.asarray, self.params),
                          what="params")
