"""CLI for transformer-LM training: the long-context/distributed entry point.

The sibling of cli.py (which preserves the reference's VGG/CIFAR contract —
reference README.md:4); this one drives lm.py's (data x seq x tensor) or
(data x pipe) meshes on a byte-level corpus:

  python -m distributed_pytorch_tpu.lm_cli --preset LM-tiny --steps 100 \\
      --dp 2 --sp 2 --tp 2 --batch-size 8 --seq-len 512

Multi-host uses the same rendezvous contract as cli.py (--master-ip /
--num-nodes / --rank, or torchrun-style env vars via --rendezvous env).
"""

from __future__ import annotations

import argparse
import sys
import time

import jax
import numpy as np

from .data import lm_corpus
from .lm import LMTrainConfig, LMTrainer
from .models import transformer as tfm
from .parallel import init as dist_init
from .utils import compile_cache
from .utils.logging import get_logger, setup_logging


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="distributed_pytorch_tpu.lm_cli",
        description="TPU-native transformer LM trainer "
                    "(dp x sp x tp, or dp x pp)")
    # rendezvous (same contract as cli.py / the reference)
    p.add_argument("--master-ip", default=None)
    p.add_argument("--num-nodes", type=int, default=1)
    p.add_argument("--rank", type=int, default=0)
    p.add_argument("--port", type=int, default=dist_init.DEFAULT_PORT)
    p.add_argument("--rendezvous", choices=["args", "env"], default="args")
    # model
    p.add_argument("--preset", default="LM-tiny",
                   choices=sorted(tfm.PRESETS))
    p.add_argument("--d-model", type=int, default=None)
    p.add_argument("--n-layers", type=int, default=None)
    p.add_argument("--n-heads", type=int, default=None)
    p.add_argument("--n-kv-heads", type=int, default=None,
                   help="grouped-query attention (default: n_heads)")
    p.add_argument("--head-dim", type=int, default=None)
    p.add_argument("--n-experts", type=int, default=None,
                   help="enable MoE layers with this many experts")
    p.add_argument("--moe-top-k", type=int, default=None,
                   help="experts per token (1=Switch, 2=top-2)")
    p.add_argument("--moe-router", default=None,
                   choices=["tokens", "experts"],
                   help="'tokens' (top-k choice) or 'experts' "
                        "(expert-choice routing)")
    p.add_argument("--router-z-coef", type=float, default=None,
                   help="router z-loss weight relative to the aux weight "
                        "(ST-MoE uses 0.1: z weight = 0.1 * aux_coef)")
    # parallelism
    p.add_argument("--dp", type=int, default=1)
    p.add_argument("--sp", type=int, default=1)
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--pp", type=int, default=1)
    p.add_argument("--ep", type=int, default=1,
                   help="dedicated expert-parallel degree (EP x TP): MoE "
                        "experts shard over their own 'expert' mesh axis")
    p.add_argument("--grad-accum", type=int, default=1,
                   help="gradient-accumulation microbatches per optimizer "
                        "step (peak activation memory drops ~A-fold; CE "
                        "gradient exact)")
    p.add_argument("--microbatches", type=int, default=0,
                   help="microbatches per optimizer step for --pp (the "
                        "wave schedule admits them pp at a time; the "
                        "local batch must divide into them; default "
                        "2*pp)")
    p.add_argument("--interleave", type=int, default=1,
                   help="virtual pipeline stages per device (shrinks the "
                        "pipeline bubble by this factor)")
    p.add_argument("--dcn-size", type=int, default=1,
                   help="multislice factoring of the data axis: dp = "
                        "dcn-size slices x (dp / dcn-size) chips; the DP "
                        "gradient sync becomes the explicit two-level "
                        "reduction (shard-sized cross-slice payload)")
    p.add_argument("--dcn-compress", default=None,
                   choices=["int8", "int4"],
                   help="quantize the cross-slice (dcn) hop of the "
                        "two-level sync: int8 (round 11) or int4 (round "
                        "16, two nibbles per wire byte) ring exchange "
                        "with per-row scales and error-feedback "
                        "residuals threaded through the train step's "
                        "sync-state carry (requires --dcn-size >= 2)")
    p.add_argument("--fsdp-gather-dtype", default=None,
                   choices=["int8", "int4"],
                   help="quantize the ZeRO-3 weight all-gathers: int8 "
                        "(round 16) sends parameters as int8 + per-row "
                        "f32 scales; int4 (round 18) packs two nibbles "
                        "per wire byte against the same scales; either "
                        "way they dequantize at the consumer and the "
                        "gradient reduce-scatters stay full-precision "
                        "(requires --fsdp)")
    p.add_argument("--matmul-dtype", default=None, choices=["int8"],
                   help="run the transformer's dense projections "
                        "(q/k/v/o and the non-MoE MLP) through the int8 "
                        "forward / straight-through backward quantized "
                        "matmul (round 16; per-row activation x per-col "
                        "weight scales, Pallas kernel on TPU, the "
                        "bitwise-equal XLA int8 dot elsewhere)")
    p.add_argument("--loss-impl", default=None,
                   choices=["dense", "chunked"],
                   help="cross-entropy head (round 17): 'dense' "
                        "materializes the (B, T, V) f32 logits; "
                        "'chunked' streams the head projection + "
                        "logsumexp over vocab chunks so the full logits "
                        "tensor never exists (matches dense to ~1e-6; "
                        "composes with --tp via per-shard partial "
                        "logsumexp)")
    p.add_argument("--loss-chunk", type=int, default=None,
                   help="vocab chunk size for --loss-impl chunked (must "
                        "divide the per-rank vocab; default: largest "
                        "divisor <= 1024)")
    p.add_argument("--remat", default=None,
                   choices=["none", "full", "selective"],
                   help="layer-stack rematerialization (round 17): "
                        "'full' saves only each block's input carry and "
                        "recomputes the block in the backward; "
                        "'selective' additionally saves the flash "
                        "kernel's (o, lse) so only the projections/MLP "
                        "recompute.  Losses bitwise-equal to 'none' "
                        "(test-pinned); does not compose with --pp "
                        "(the pipeline owns its own remat)")
    p.add_argument("--bucket-mb", type=float, default=None,
                   help="streaming bucket size for the factored-mesh "
                        "exchange (default: the 25 MB torch-DDP cap)")
    p.add_argument("--sync-plan", default=None, choices=["auto"],
                   help="'auto' (round 11): calibrate per-axis link "
                        "alpha/beta (cached repo-locally) and resolve "
                        "--dcn-compress/--bucket-mb to the plan "
                        "minimizing predicted step-sync time "
                        "(parallel/autotune.py)")
    p.add_argument("--sync-route", default=None,
                   help="pin the gradient sync route by hand (round 21, "
                        "the parallel/routing grammar; '->' accepted for "
                        "the arrow): 'data:psum' on a flat mesh, or "
                        "'data:rs -> dcn:psum -> data:ag' / 'data:rs -> "
                        "dcn:ring[int8|int4+ef] -> data:ag' on a "
                        "factored one.  Resolves into the explicit "
                        "knobs (trains bitwise-identically to them); "
                        "refuses pp, --sync-plan auto, and "
                        "--dcn-compress alongside")
    p.add_argument("--autotune-profile", default=None,
                   help="profile source for --sync-plan auto: a "
                        "synthetic preset name (incl. wan_dcn and the "
                        "3-tier ici_dcn_wan the route chooser searches) "
                        "or a profile-JSON path (default: cached/"
                        "calibrated for this topology); the resolved "
                        "plan logs its route string "
                        "(parallel/routing.py grammar)")
    p.add_argument("--sync-every", type=int, default=1,
                   help="local-SGD window (round 18): run H local "
                        "optimizer steps between cross-slice exchanges "
                        "— the ICI hop still syncs every step, the DCN "
                        "hop only at window boundaries (~1/H dcn "
                        "bytes/step; requires --dcn-size >= 2, no "
                        "--pp, --grad-accum 1)")
    p.add_argument("--staleness", type=int, default=0,
                   help="bounded staleness for --sync-every: launch the "
                        "window exchange at step kH and apply it at "
                        "kH+S, hiding DCN latency under S local steps "
                        "(0 <= S < H)")
    p.add_argument("--max-sync-every", type=int, default=None,
                   help="staleness-risk ceiling for the interval-aware "
                        "autotuner and the monitor's sync-relax "
                        "actuator (default: the --sync-every value — "
                        "relaxation stays opt-in)")
    p.add_argument("--outer-opt", choices=("nesterov", "momentum"),
                   default=None,
                   help="DiLoCo outer optimizer (round 22): move the "
                        "anchor by outer_opt(mean window delta) at each "
                        "--sync-every boundary instead of the plain "
                        "mean — momentum on the anchor recovers "
                        "convergence lost to wide windows (requires "
                        "--sync-every > 1)")
    p.add_argument("--outer-momentum", type=float, default=0.9,
                   help="outer-optimizer momentum coefficient in "
                        "[0, 1) (default 0.9; 0 with lr 1 is bitwise "
                        "the plain mean)")
    p.add_argument("--outer-lr", type=float, default=1.0,
                   help="outer-optimizer learning rate (> 0; scales "
                        "the anchor step, default 1.0)")
    p.add_argument("--sync-every-per-slice", default=None,
                   help="per-slice non-uniform windows (round 22): "
                        "comma-separated H per dcn slice (e.g. '2,4' — "
                        "one entry per --dcn-size slice, each a "
                        "multiple of --sync-every with min == "
                        "--sync-every); a slice skipping a boundary "
                        "contributes an exact zero delta and keeps "
                        "accumulating (no --staleness)")
    p.add_argument("--fsdp", action="store_true",
                   help="ZeRO-3: shard params+optimizer over the data axis")
    # elastic gang membership (round 12; launch.py --elastic is the agent
    # side): the worker publishes heartbeats and honors drain sync points.
    p.add_argument("--elastic", action="store_true",
                   help="run as an elastic-gang member (launch.py "
                        "--elastic agent): publish per-step heartbeats, "
                        "and on the agent's drain signal exit the step "
                        "loop at a SYNC POINT — flush a checkpoint and "
                        "leave with the drain exit code so the resized "
                        "gang resumes resharded (requires "
                        "--checkpoint-dir; refuses pipeline configs, "
                        "which cannot resize for now)")
    p.add_argument("--min-nodes", type=int, default=1,
                   help="elastic: smallest world size this config can "
                        "train at (validation/visibility; the agent "
                        "enforces the bound)")
    p.add_argument("--max-nodes", type=int, default=None,
                   help="elastic: largest world size (default: the "
                        "launch world size)")
    p.add_argument("--overlap", action="store_true",
                   help="stream the step's bulk communication through the "
                        "layer-group boundaries: per-group ZeRO-3 weight "
                        "gathers (--fsdp) and/or per-group two-level DCN "
                        "sync points (--dcn-size > 1), emitted in-backward "
                        "for the latency-hiding scheduler (bitwise-"
                        "identical trajectory, test-pinned)")
    # training
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch-size", type=int, default=8,
                   help="global batch (sequences per step)")
    p.add_argument("--seq-len", type=int, default=512)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--warmup-steps", type=int, default=0)
    p.add_argument("--decay-steps", type=int, default=0,
                   help="cosine-decay horizon (0 = constant LR)")
    p.add_argument("--compute-dtype", default="bfloat16",
                   choices=["bfloat16", "float32"])
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--corpus", default=None,
                   help="path to a text file (byte-level); default: "
                        "deterministic synthetic corpus")
    p.add_argument("--mmap-corpus", action="store_true",
                   help="memory-map --corpus instead of loading it into "
                        "RAM (for corpora larger than host memory; each "
                        "rank lazily reads only its own windows' pages)")
    p.add_argument("--shuffle-mode", default=None,
                   choices=["permutation", "affine"],
                   help="epoch shuffle: 'permutation' (exact "
                        "DistributedSampler semantics, O(n_windows) index "
                        "memory) or 'affine' (O(1) memory modular-affine "
                        "bijection).  Default: affine with --mmap-corpus "
                        "(whose target scale cannot index windows in RAM), "
                        "permutation otherwise")
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--eval-every", type=int, default=0,
                   help="evaluate held-out loss/ppl every N steps (holds "
                        "out the final 10%% of the corpus)")
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--checkpoint-every", type=int, default=200)
    p.add_argument("--checkpoint-sharded", action="store_true",
                   help="per-process shard files instead of one whole-tree "
                        "npz: no allgather or full-tree host copy (for "
                        "models larger than one host's memory); restore "
                        "auto-detects the format")
    # sampling after training
    p.add_argument("--generate", default=None, metavar="PROMPT",
                   help="sample text from the trained model")
    p.add_argument("--max-new", type=int, default=128)
    p.add_argument("--temperature", type=float, default=0.8)
    p.add_argument("--top-k", type=int, default=None,
                   help="sample from the k most likely tokens only")
    p.add_argument("--top-p", type=float, default=None,
                   help="nucleus sampling: smallest token set with "
                        "cumulative probability >= p")
    p.add_argument("--kv-dtype", default=None, choices=("int8",),
                   help="KV-cache storage for sampling: int8 = quantized "
                        "cache with per-row scales (half the HBM cache "
                        "read per decode step)")
    p.add_argument("--profile-dir", default=None,
                   help="write a jax.profiler trace (TensorBoard-loadable) "
                        "covering steps 2-11 (step 1 excluded: compile)")
    p.add_argument("--telemetry-dir", default=None,
                   help="unified run telemetry (round 13): stream "
                        "rank-tagged JSONL events (step spans, loss/"
                        "grad-norm/param-norm gauges, checkpoint IO, "
                        "autotune plans, sentry escalations) into this "
                        "run directory; defaults from the launcher-"
                        "exported TELEMETRY_DIR; off (and free) when "
                        "neither is set.  Merge/inspect with "
                        "scripts/telemetry_summary.py")
    p.add_argument("--log-level", default="INFO")
    return p


def model_config(args) -> tfm.TransformerConfig:
    cfg = tfm.PRESETS[args.preset]
    # byte-level corpus: the vocab is always 256
    overrides = {"vocab_size": lm_corpus.VOCAB_SIZE}
    for field in ("d_model", "n_layers", "n_heads", "n_kv_heads",
                  "head_dim", "n_experts", "moe_top_k", "moe_router",
                  "router_z_coef"):
        val = getattr(args, field)
        if val is not None:
            overrides[field] = val
    import dataclasses
    return dataclasses.replace(cfg, **overrides)


def main(argv: list[str] | None = None) -> int:
    parser = build_parser()
    args = parser.parse_args(argv)
    if args.mmap_corpus and not args.corpus:
        parser.error("--mmap-corpus requires --corpus (the synthetic "
                     "fallback is generated in RAM)")
    if args.loss_chunk is not None and args.loss_impl != "chunked":
        parser.error("--loss-chunk tunes the chunked head; pass "
                     "--loss-impl chunked (or drop the chunk size)")
    if args.remat in ("full", "selective") and args.pp > 1:
        parser.error("--remat does not compose with --pp: the "
                     "pipeline scheduler owns its own rematerialization "
                     "(each tick block is already checkpointed); drop one")
    max_sync_every = (args.max_sync_every if args.max_sync_every is not None
                      else max(args.sync_every, 1))
    sync_every_per_slice = None
    if args.sync_every_per_slice is not None:
        try:
            sync_every_per_slice = tuple(
                int(x) for x in args.sync_every_per_slice.split(","))
        except ValueError:
            parser.error("--sync-every-per-slice wants comma-separated "
                         f"ints (one H per dcn slice), got "
                         f"{args.sync_every_per_slice!r}")
    if (args.sync_every != 1 or args.staleness != 0
            or max_sync_every != 1 or args.outer_opt is not None
            or sync_every_per_slice is not None):
        # the ONE definition site for window coherence — the same check
        # validate_lm_cfg runs, surfaced at the parser so incoherent
        # combos die with a usage error instead of a traceback
        from .parallel.strategies import require_sync_window
        try:
            require_sync_window(
                sync_every=args.sync_every, staleness=args.staleness,
                max_sync_every=max_sync_every, mesh=True,
                overlap=args.overlap, pp=args.pp > 1,
                grad_accum=args.grad_accum, dcn_size=args.dcn_size,
                trainer="lm", outer_opt=args.outer_opt,
                outer_momentum=args.outer_momentum,
                outer_lr=args.outer_lr,
                sync_every_per_slice=sync_every_per_slice)
        except ValueError as e:
            parser.error(str(e))
    if args.elastic:
        # refuse loudly anything that CANNOT resize: a pipeline's stage
        # placement is baked into the stage-stacked step, so a resized
        # world has no program to resume into (LMTrainer.rebuild refuses
        # for the same reason)
        if args.pp > 1:
            parser.error(
                "--elastic cannot resize pipeline configs (--pp > 1): "
                "stage placement is baked into the compiled step; "
                "drop the pipeline axis or --elastic")
        if not args.checkpoint_dir:
            parser.error(
                "--elastic requires --checkpoint-dir: the drain sync "
                "point must flush a checkpoint for the resized gang to "
                "resume from")
        if args.min_nodes < 1 or (args.max_nodes is not None
                                  and args.max_nodes < args.min_nodes):
            parser.error("--min-nodes/--max-nodes must satisfy "
                         "1 <= min <= max")
    elif args.min_nodes != 1 or args.max_nodes is not None:
        parser.error("--min-nodes/--max-nodes configure --elastic; pass "
                     "it (or drop the bounds)")
    compile_cache.enable()
    if args.rendezvous == "env":
        dist_init.init_from_env()
    else:
        dist_init.init_distributed(args.master_ip, args.num_nodes, args.rank,
                                   port=args.port)
    setup_logging(args.log_level)
    log = get_logger("lm_cli")
    from .utils import telemetry
    tel = telemetry.enable_from_cli(args.telemetry_dir)
    if tel is not None:
        log.info("telemetry: streaming to %s", tel.run_dir)

    cfg = LMTrainConfig(
        model=model_config(args), lr=args.lr, seed=args.seed,
        compute_dtype=(None if args.compute_dtype == "float32"
                       else args.compute_dtype),
        warmup_steps=args.warmup_steps, decay_steps=args.decay_steps,
        dp=args.dp, sp=args.sp, tp=args.tp, pp=args.pp, ep=args.ep,
        microbatches=args.microbatches,
        dcn_size=args.dcn_size, grad_accum=args.grad_accum,
        interleave=args.interleave, fsdp=args.fsdp, overlap=args.overlap,
        dcn_compress=args.dcn_compress, bucket_mb=args.bucket_mb,
        fsdp_gather_dtype=args.fsdp_gather_dtype,
        matmul_dtype=args.matmul_dtype,
        loss_impl=args.loss_impl or "dense", loss_chunk=args.loss_chunk,
        remat=args.remat or "none",
        sync_every=args.sync_every, staleness=args.staleness,
        max_sync_every=max_sync_every,
        outer_opt=args.outer_opt, outer_momentum=args.outer_momentum,
        outer_lr=args.outer_lr,
        sync_every_per_slice=sync_every_per_slice,
        sync_plan=args.sync_plan, autotune_profile=args.autotune_profile,
        sync_route=args.sync_route)
    trainer = LMTrainer(cfg)
    heartbeat = drain_guard = None
    if args.elastic:
        # elastic membership: install the drain handler EARLY (a SIGTERM
        # before the first sync point must still be honored there) and
        # publish heartbeats when an elastic agent launched us (the
        # ELASTIC_DIR contract); standalone --elastic runs still get the
        # graceful drain-with-checkpoint on SIGTERM.
        from .parallel import elastic as elastic_mod
        drain_guard = elastic_mod.DrainGuard().install()
        ectx = elastic_mod.ElasticContext.from_env()
        if ectx is not None:
            heartbeat = elastic_mod.Heartbeat(
                ectx.run_dir, ectx.rank, ectx.generation)
            log.info("elastic member: rank %d/%d gen %d bounds [%d, %d]",
                     ectx.rank, ectx.world_size, ectx.generation,
                     ectx.min_nodes, ectx.max_nodes)
    log.info("model: %s | mesh: dp=%d (dcn=%d) ep=%d sp=%d tp=%d pp=%d "
             "over %d devices",
             cfg.model, args.dp, args.dcn_size, args.ep, args.sp, args.tp,
             args.pp, trainer.mesh.devices.size)

    start = 0
    if args.checkpoint_dir:
        start = trainer.maybe_restore(args.checkpoint_dir)
        if start:
            log.info("resumed at step %d", start)

    corpus = lm_corpus.load_corpus(args.corpus, mmap=args.mmap_corpus)
    log.info("corpus: %d tokens (%s)", len(corpus),
             "synthetic" if corpus.synthetic else args.corpus)
    val_loader = None
    if args.eval_every > 0 and cfg.pp == 1:
        # hold out the final 10% of the stream for evaluation
        split = int(len(corpus) * 0.9)
        val = lm_corpus.LMCorpus(corpus.tokens[split:], corpus.synthetic)
        try:
            candidate = lm_corpus.LMDataLoader(
                val, args.batch_size // max(jax.process_count(), 1),
                args.seq_len, num_replicas=max(jax.process_count(), 1),
                rank=jax.process_index(), shuffle=False)
        except ValueError:
            candidate = None
        if candidate is None or len(candidate) == 0:
            log.warning(
                "corpus too small for a 10%% eval holdout at --seq-len %d / "
                "--batch-size %d; --eval-every disabled", args.seq_len,
                args.batch_size)
        else:
            val_loader = candidate
            corpus = lm_corpus.LMCorpus(corpus.tokens[:split],
                                        corpus.synthetic)
    # each process feeds its host-local share of the global batch
    procs = jax.process_count()
    if args.batch_size % max(procs, 1):
        raise SystemExit(f"--batch-size {args.batch_size} must divide "
                         f"across {procs} processes")
    shuffle_mode = args.shuffle_mode or (
        "affine" if args.mmap_corpus else "permutation")
    loader = lm_corpus.LMDataLoader(
        corpus, args.batch_size // procs, args.seq_len,
        num_replicas=procs, rank=jax.process_index(), seed=args.seed,
        shuffle_mode=shuffle_mode,
        # elastic: world-size-independent global order, so the recorded
        # (epoch, offset) resumes losslessly after a resize re-strides
        # the loader at the new world size
        elastic_order=args.elastic)
    if len(loader) == 0:
        raise SystemExit(
            f"corpus yields 0 batches: {loader.per_rank} windows/process "
            f"at --seq-len {args.seq_len} cannot fill a batch of "
            f"{loader.batch_size}; use a larger --corpus or smaller "
            f"--batch-size/--seq-len")

    step = start
    t_last, s_last = time.perf_counter(), start
    steps_per_epoch = len(loader)
    # Loader position: checkpoints carry it explicitly (epoch + offset);
    # deriving it from the step counter is the fallback for checkpoints
    # written before the position was recorded.  An explicit position
    # survives steps_per_epoch drift (e.g. a corpus that grew) exactly.
    pos = trainer.restored_meta.get("loader") if start else None
    if pos is not None and pos.get("steps_per_epoch") != steps_per_epoch:
        log.warning(
            "checkpoint loader position was recorded at %s steps/epoch, "
            "now %d — resuming from the recorded (epoch, offset) anyway",
            pos.get("steps_per_epoch"), steps_per_epoch)
    if pos is not None:
        epoch, skip = int(pos["epoch"]), int(pos["offset"])
        if skip >= steps_per_epoch:  # recorded at an epoch boundary
            epoch, skip = epoch + 1, 0
    else:
        epoch, skip = step // steps_per_epoch, step % steps_per_epoch
    tracing = False
    while step < args.steps:
        loader.set_epoch(epoch)
        for i, (tokens, targets) in enumerate(loader):
            if i < skip:
                continue
            if heartbeat is not None:
                heartbeat.beat(step)
            if drain_guard is not None and drain_guard.sync():
                # the agent asked for a drain: every rank agreed on THIS
                # boundary (DrainGuard.sync is a collective), so the
                # checkpoint fetch below is deadlock-free; the resized
                # gang resumes from it, resharded
                pos = {"epoch": epoch, "offset": i,
                       "steps_per_epoch": steps_per_epoch}
                from .parallel import elastic as elastic_mod
                log.info("drain requested: flushing checkpoint at step "
                         "%d and leaving at the sync point", step)
                elastic_mod.drain_exit(lambda: (
                    trainer.save_checkpoint(
                        args.checkpoint_dir,
                        extra_meta={"loader": pos},
                        sharded=args.checkpoint_sharded),
                    trainer.flush_checkpoints()))
            if args.profile_dir and step == start + 1:
                jax.profiler.start_trace(args.profile_dir)
                tracing = True
            loss = trainer.train_step(tokens, targets)
            step += 1
            if tracing and step == start + 11:
                jax.block_until_ready(loss)
                jax.profiler.stop_trace()
                tracing = False
            loader_pos = {"epoch": epoch, "offset": i + 1,
                          "steps_per_epoch": steps_per_epoch}
            if step % args.log_every == 0:
                dt = time.perf_counter() - t_last
                tok_s = ((step - s_last) * args.batch_size * args.seq_len
                         / max(dt, 1e-9))
                log.info("step %d | loss %.4f | %.0f tok/s",
                         step, float(loss), tok_s)
                t_last, s_last = time.perf_counter(), step
            if (args.checkpoint_dir
                    and step % args.checkpoint_every == 0):
                trainer.save_checkpoint(args.checkpoint_dir,
                                        extra_meta={"loader": loader_pos},
                                        sharded=args.checkpoint_sharded)
            if (val_loader is not None
                    and step % args.eval_every == 0):
                m = trainer.evaluate(iter(val_loader))
                log.info("step %d | val loss %.4f | ppl %.2f (%d tokens)",
                         step, m["loss"], m["ppl"], m["tokens"])
            if step >= args.steps:
                break
        epoch, skip = epoch + 1, 0

    if tracing:  # short runs: close the trace cleanly
        jax.block_until_ready(loss)
        jax.profiler.stop_trace()

    if args.checkpoint_dir and step > start:
        # (skip when nothing trained: rewriting the just-restored
        # checkpoint would erase its recorded loader position)
        trainer.save_checkpoint(args.checkpoint_dir,
                                extra_meta={"loader": loader_pos},
                                sharded=args.checkpoint_sharded)
    if args.checkpoint_dir:
        trainer.flush_checkpoints()  # main() returning implies files exist

    if args.generate is not None:
        if cfg.pp > 1:
            log.warning("generation with pp>1 not supported; skipping")
        else:
            from . import generate as gen
            prompt = lm_corpus.encode(args.generate)[None]
            if cfg.tp > 1:
                # decode on the training mesh: params stay in their Megatron
                # (and, under --fsdp, ZeRO-3) sharding — no host gather
                from .lm import param_specs
                out = gen.generate_tp(
                    trainer.params, prompt.astype(np.int32),
                    jax.random.key(args.seed), cfg=cfg.model,
                    mesh=trainer.mesh, max_new=args.max_new,
                    temperature=args.temperature, top_k=args.top_k,
                    top_p=args.top_p, dtype=cfg.dtype,
                    kv_dtype=args.kv_dtype,
                    specs=param_specs(cfg) if cfg.fsdp else None)
            else:
                from .utils.checkpoint import _fetch
                # host-gather params (collective-safe on multi-host shardings)
                params = jax.tree.map(_fetch, trainer.params)
                out = gen.generate(
                    params,
                    prompt.astype(np.int32), jax.random.key(args.seed),
                    cfg=cfg.model, max_new=args.max_new,
                    temperature=args.temperature, top_k=args.top_k,
                    top_p=args.top_p, dtype=cfg.dtype,
                    kv_dtype=args.kv_dtype)
            text = lm_corpus.decode(np.asarray(out[0]))
            print(text)

    dist_init.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
