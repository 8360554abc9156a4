"""One CLI replacing the reference's five ``main_*.py`` scripts.

The reference ships five ~80%-identical entry scripts whose real deltas are
the sync strategy and the rendezvous mode (SURVEY.md section 0).  Here both
are flags on one entry point, preserving the reference's launch contracts:

- ``python -m distributed_pytorch_tpu.cli --strategy gather_scatter
  --master-ip 172.18.0.2 --num-nodes 4 --rank $R`` — the README.md:4 /
  main_all_reduce.py:86-92 argparse contract (per-host process, explicit
  TCP-style rendezvous on port 6585);
- ``--rendezvous env`` — the torchrun convention (main_ddp.py:93-104),
  reading MASTER_ADDR/MASTER_PORT/WORLD_SIZE/RANK;
- no distributed flags at all — the single-process baseline (main.py).

Strategy names map to the reference scripts:
  none            -> main.py            (single-process baseline)
  gather_scatter  -> main_gather.py     (rank-0 parameter-server sync)
  all_reduce      -> main_all_reduce.py (per-tensor all-reduce)
  ddp             -> main_ddp.py / main_part3.py (fused overlapped sync)
  bucketed        -> torch DDP's explicit 25MB-bucket engine

On TPU each *chip* is a data-parallel rank (the reference's "node"); with N
hosts the mesh spans all hosts' chips and the per-chip loaders shard the
global batch exactly like ``DistributedSampler(num_replicas, rank)``
(reference main_all_reduce.py:112).
"""

from __future__ import annotations

import argparse
import sys

import jax

from . import eval as evaluation
from .data import DataLoader, DistributedSampler, load
from .parallel import init as dist_init
from .parallel import strategies as _strat
from .parallel.mesh import make_mesh
from .train import TrainConfig, Trainer
from .utils import compile_cache
from .utils.logging import get_logger, setup_logging


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="distributed_pytorch_tpu",
        description="TPU-native distributed VGG/CIFAR-10 trainer",
    )
    # Reference argparse contract (main_all_reduce.py:86-92).
    p.add_argument("--master-ip", type=str, default=None,
                   help="coordinator host (rank 0), reference --master-ip")
    p.add_argument("--num-nodes", type=int, default=1,
                   help="number of host processes, reference --num-nodes")
    p.add_argument("--rank", type=int, default=0,
                   help="this host's process id, reference --rank")
    p.add_argument("--port", type=int, default=dist_init.DEFAULT_PORT)
    p.add_argument("--rendezvous", choices=["args", "env"], default="args",
                   help="'args' = explicit --master-ip/--rank "
                        "(main_all_reduce.py:96); 'env' = torchrun-style "
                        "MASTER_ADDR/RANK env vars (main_ddp.py:93-104)")
    p.add_argument("--rendezvous-timeout", type=int,
                   default=dist_init.DEFAULT_TIMEOUT_S,
                   help="seconds before rendezvous fails loudly (the "
                        "reference hangs forever: timeout=None)")
    # Training hyper-parameters; defaults are the reference's exact values.
    p.add_argument("--strategy", default="ddp",
                   choices=_strat.available() + ["auto", "routed"],
                   help="gradient-sync strategy, 'auto' (round 11): "
                        "calibrate per-axis link alpha/beta (cached "
                        "repo-locally) and resolve to the named strategy "
                        "+ bucket/compression knobs minimizing predicted "
                        "step-sync time (parallel/autotune.py), or "
                        "'routed' (round 20): execute the declarative "
                        "hop-graph given by --sync-route "
                        "(parallel/routing.py)")
    p.add_argument("--sync-route", default=None,
                   help="route string for --strategy routed, in the hop "
                        "grammar ('ici:rs -> dcn:ring[int4+ef] -> "
                        "ici:ag'): per hop axis:op with op one of rs, "
                        "slice, ag, psum, ring[int8|int4[+ef]]; must be "
                        "a 2-level ('dcn','ici') plan — the trainer's "
                        "factored-mesh topology")
    p.add_argument("--autotune-profile", default=None,
                   help="profile source for --strategy auto: a synthetic "
                        "preset name (uniform, fast_ici_slow_dcn, "
                        "inverted, slow, fast, wan_dcn, ici_dcn_wan — "
                        "the 3-tier preset the route chooser searches) "
                        "or a profile-JSON path; default: the cached/"
                        "calibrated profile for this topology")
    p.add_argument("--dcn-size", type=int, default=2,
                   help="number of slices for --strategy hierarchical: the "
                        "data axis factors into Mesh(('dcn','ici')) and "
                        "cross-slice traffic drops to payload/ici")
    p.add_argument("--dcn-compress", default=None,
                   choices=["int8", "int4"],
                   help="quantize the cross-slice (dcn) hop of --strategy "
                        "hierarchical: int8 (or int4, two nibbles per "
                        "wire byte) ring exchange with per-row scales "
                        "and error-feedback residuals; the ICI "
                        "reduce-scatter/all-gather stay full-precision")
    p.add_argument("--overlap", action="store_true",
                   help="emit each ~25 MB gradient bucket's collective "
                        "INSIDE the backward pass at its layer-group "
                        "boundary (in-backward sync points; bitwise-"
                        "identical trajectory, test-pinned) so the "
                        "latency-hiding scheduler can run bucket N's "
                        "sync under layer N-1's backward matmuls")
    p.add_argument("--overlap-bucket-mb", type=float, default=None,
                   help="bucket size for overlap packing (default: torch "
                        "DDP's 25 MB)")
    p.add_argument("--sync-every", type=int, default=1,
                   help="local-SGD window (round 18): run H local "
                        "optimizer steps between gradient exchanges — "
                        "on --strategy hierarchical the ICI hop still "
                        "syncs every step and the DCN hop only at "
                        "window boundaries (~1/H dcn bytes/step; needs "
                        "a mesh-backed strategy, no --overlap)")
    p.add_argument("--max-sync-every", type=int, default=None,
                   help="staleness-risk ceiling for --strategy auto's "
                        "interval dimension and the monitor's "
                        "sync-relax actuator (default: the --sync-every "
                        "value — relaxation stays opt-in)")
    p.add_argument("--outer-opt", default=None,
                   choices=["nesterov", "momentum"],
                   help="DiLoCo outer optimizer (round 22): at each "
                        "--sync-every window boundary, treat the averaged "
                        "window delta as an outer gradient and apply it "
                        "through a momentum/Nesterov step on the anchor "
                        "instead of adding the plain mean (zero momentum "
                        "with unit outer lr is bitwise the plain mean)")
    p.add_argument("--outer-momentum", type=float, default=0.9,
                   help="outer optimizer momentum (0 <= mu < 1; DiLoCo's "
                        "reference value is 0.9)")
    p.add_argument("--outer-lr", type=float, default=1.0,
                   help="outer optimizer learning rate on the averaged "
                        "window delta (> 0; 1.0 = step by the full mean)")
    p.add_argument("--sync-every-per-slice", default=None,
                   help="comma-separated per-slice window lengths (LM "
                        "trainer only — the VGG trainer's windows are "
                        "gang-wide; this parser refuses it loudly so the "
                        "two CLIs stay flag-compatible)")
    p.add_argument("--model", default="VGG11",
                   choices=["VGG11", "VGG13", "VGG16", "VGG19"])
    p.add_argument("--epochs", type=int, default=1)     # main.py:106
    p.add_argument("--batch-size", type=int, default=256)  # main.py:18
    p.add_argument("--lr", type=float, default=0.1)     # main.py:103
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--weight-decay", type=float, default=1e-4)
    p.add_argument("--seed", type=int, default=1)       # main.py:70
    p.add_argument("--compute-dtype", default=None,
                   choices=[None, "bfloat16", "float32"],
                   help="bfloat16 = MXU-native compute, float32 params")
    p.add_argument("--no-augment", action="store_true")
    p.add_argument("--sync-bn", action="store_true",
                   help="cross-replica BatchNorm (the reference never syncs "
                        "BN; default off for parity)")
    p.add_argument("--data-dir", default=None)
    p.add_argument("--num-devices", type=int, default=None,
                   help="limit local devices used (default: all)")
    # Capability upgrades absent from the reference.
    p.add_argument("--checkpoint-dir", default=None,
                   help="save params/opt-state/step each epoch; resume "
                        "automatically if a checkpoint exists")
    p.add_argument("--profile-dir", default=None,
                   help="write a jax.profiler trace for the first epoch")
    p.add_argument("--telemetry-dir", default=None,
                   help="unified run telemetry (round 13): stream "
                        "rank-tagged JSONL events (step spans, loss/"
                        "grad-norm/param-norm gauges, checkpoint IO, "
                        "sentry escalations) into this run directory; "
                        "defaults from the launcher-exported "
                        "TELEMETRY_DIR; off (and free) when neither is "
                        "set.  Merge/inspect with "
                        "scripts/telemetry_summary.py")
    p.add_argument("--shard-eval", action="store_true",
                   help="shard the test set over the mesh (psum'd metrics) "
                        "instead of the reference's redundant per-rank "
                        "evaluation")
    p.add_argument("--fold-bn-eval", action="store_true",
                   help="fold BatchNorm statistics into the conv weights "
                        "for evaluation (mathematically identical, one "
                        "fewer normalize pass per conv)")
    p.add_argument("--elastic", action="store_true",
                   help="run as an elastic-gang member (launch.py "
                        "--elastic agent): publish heartbeats at DISPATCH "
                        "cadence (a long epoch never reads as a hang) and "
                        "honor the agent's drain signal at EPOCH "
                        "boundaries — flush a checkpoint and exit with "
                        "the drain code so the resized gang resumes "
                        "resharded (requires --checkpoint-dir; the LM "
                        "CLI drains at step granularity)")
    p.add_argument("--min-nodes", type=int, default=1,
                   help="elastic: smallest world size this config can "
                        "train at (validation/visibility; the agent "
                        "enforces the bound)")
    p.add_argument("--max-nodes", type=int, default=None,
                   help="elastic: largest world size (default: the "
                        "launch world size)")
    p.add_argument("--debug-checks", action="store_true",
                   help="after each epoch, verify DP invariants: replicated "
                        "params/opt-state bitwise-identical on every device "
                        "and finite (utils/debug.py)")
    p.add_argument("--log-level", default="INFO")
    return p


def build_loaders(args, n_replicas: int, replica_offset: int,
                  local: int | None = None):
    """Per-replica train loaders (``local`` of them, for this host's chips)
    + one test loader.

    Each chip gets a ``DistributedSampler(num_replicas=<global chips>,
    rank=<its global index>)`` shard — the reference's per-process sampler
    (main_all_reduce.py:112) with chips as ranks.  The test set is NOT
    sharded (every rank evaluates all 10k images — main_gather.py:131).
    """
    train_set = load("train", args.data_dir)
    test_set = load("test", args.data_dir)
    if local is None:
        local = n_replicas
    if n_replicas == 1:
        train_loaders = [DataLoader(train_set, args.batch_size,
                                    shuffle=True, seed=0)]
    else:
        train_loaders = [
            DataLoader(
                train_set, args.batch_size,
                sampler=DistributedSampler(
                    len(train_set), num_replicas=n_replicas,
                    rank=replica_offset + i, shuffle=True, seed=0),
            )
            for i in range(local)
        ]
    test_loader = DataLoader(test_set, args.batch_size)
    return train_loaders, test_loader


def main(argv: list[str] | None = None) -> int:
    parser = build_parser()
    args = parser.parse_args(argv)
    if args.elastic:
        if not args.checkpoint_dir:
            parser.error(
                "--elastic requires --checkpoint-dir: the drain sync "
                "point must flush a checkpoint for the resized gang to "
                "resume from")
        if args.strategy == "none":
            parser.error(
                "--elastic needs a mesh-backed strategy (there is no "
                "topology to resize under --strategy none)")
        if args.min_nodes < 1 or (args.max_nodes is not None
                                  and args.max_nodes < args.min_nodes):
            parser.error("--min-nodes/--max-nodes must satisfy "
                         "1 <= min <= max")
    elif args.min_nodes != 1 or args.max_nodes is not None:
        parser.error("--min-nodes/--max-nodes configure --elastic; pass "
                     "it (or drop the bounds)")
    max_sync_every = (args.max_sync_every if args.max_sync_every is not None
                      else max(args.sync_every, 1))
    sync_every_per_slice = None
    if args.sync_every_per_slice is not None:
        try:
            sync_every_per_slice = tuple(
                int(x) for x in args.sync_every_per_slice.split(","))
        except ValueError:
            parser.error(
                f"--sync-every-per-slice must be a comma-separated list of "
                f"ints, got {args.sync_every_per_slice!r}")
    if (args.sync_every != 1 or max_sync_every != 1
            or args.outer_opt is not None
            or sync_every_per_slice is not None):
        # window coherence at the parser (the ONE require_* definition
        # site the Trainer re-checks): meshless strategies have no
        # collective to amortize, overlap streams the per-step sync a
        # window removes
        meshless = (args.strategy != "auto"
                    and not _strat.get(args.strategy).needs_mesh)
        try:
            _strat.require_sync_window(
                sync_every=args.sync_every,
                max_sync_every=max_sync_every,
                mesh=not meshless, overlap=args.overlap,
                trainer="train",
                outer_opt=args.outer_opt,
                outer_momentum=args.outer_momentum,
                outer_lr=args.outer_lr,
                sync_every_per_slice=sync_every_per_slice)
        except ValueError as e:
            parser.error(str(e))

    compile_cache.enable()
    # Rendezvous FIRST: jax.distributed.initialize must run before anything
    # touches a backend (even jax.process_index()), mirroring the reference's
    # init-before-everything ordering (main_all_reduce.py:96 precedes all
    # torch calls).
    if args.rendezvous == "env":
        dist_init.init_from_env(timeout_s=args.rendezvous_timeout)
    else:
        dist_init.init_distributed(
            args.master_ip, args.num_nodes, args.rank,
            port=args.port, timeout_s=args.rendezvous_timeout)
    setup_logging(args.log_level)
    log = get_logger("cli")
    from .utils import telemetry
    tel = telemetry.enable_from_cli(args.telemetry_dir)
    if tel is not None:
        log.info("telemetry: streaming to %s", tel.run_dir)
    if args.shard_eval and args.batch_size % max(jax.device_count(), 1):
        raise SystemExit(
            f"--shard-eval: --batch-size {args.batch_size} must divide "
            f"across {jax.device_count()} devices (fail fast, before a "
            f"whole epoch is spent)")
    cfg = TrainConfig(
        model=args.model, lr=args.lr, momentum=args.momentum,
        weight_decay=args.weight_decay, batch_size=args.batch_size,
        strategy=args.strategy, sync_bn=args.sync_bn,
        compute_dtype=args.compute_dtype, augment=not args.no_augment,
        seed=args.seed, dcn_size=args.dcn_size,
        dcn_compress=args.dcn_compress, overlap=args.overlap,
        overlap_bucket_mb=args.overlap_bucket_mb,
        sync_every=args.sync_every, max_sync_every=max_sync_every,
        outer_opt=args.outer_opt, outer_momentum=args.outer_momentum,
        outer_lr=args.outer_lr,
        autotune_profile=args.autotune_profile,
        sync_route=args.sync_route,
    )
    mesh = None
    # "auto" resolves inside the Trainer (which then builds whatever mesh
    # the chosen strategy needs); "routed" parses its route there too;
    # factored strategies likewise.
    factored = (args.strategy in ("auto", "routed") or
                getattr(_strat.get(args.strategy), "axes", None) is not None)
    if args.strategy != "none" and not factored:
        mesh = make_mesh(args.num_devices)
    # factored data axes (hierarchical): mesh=None lets the Trainer build
    # the ('dcn', 'ici') mesh from cfg.dcn_size — one recipe, one check.
    try:
        trainer = Trainer(cfg, mesh=mesh, num_devices=args.num_devices)
    except ValueError as e:
        raise SystemExit(str(e)) from e
    n_replicas = trainer.n_replicas
    local = max(1, n_replicas // max(jax.process_count(), 1))
    replica_offset = jax.process_index() * local
    log.info("devices=%d processes=%d strategy=%s model=%s",
             n_replicas, jax.process_count(), args.strategy, args.model)

    train_loaders, test_loader = build_loaders(args, n_replicas,
                                               replica_offset, local)

    start_epoch = 0
    ckpt = None
    if args.checkpoint_dir:
        from .utils import checkpoint as ckpt_mod
        ckpt = ckpt_mod.Checkpointer(args.checkpoint_dir)
        start_epoch = ckpt.maybe_restore(trainer)
        if start_epoch:
            log.info("resumed from checkpoint at epoch %d", start_epoch)

    heartbeat = drain_guard = None
    if args.elastic:
        # elastic membership (round 12): heartbeats when an elastic agent
        # launched us, drain-with-checkpoint on SIGTERM either way.  The
        # VGG trainer's sync points are EPOCH boundaries (train_epoch is
        # one dispatch pipeline); the LM CLI drains per step.
        from .parallel import elastic as elastic_mod
        drain_guard = elastic_mod.DrainGuard().install()
        ectx = elastic_mod.ElasticContext.from_env()
        if ectx is not None:
            heartbeat = elastic_mod.Heartbeat(
                ectx.run_dir, ectx.rank, ectx.generation)

    for epoch in range(start_epoch, args.epochs):
        if drain_guard is not None and drain_guard.sync():
            from .parallel import elastic as elastic_mod
            log.info("drain requested: flushing checkpoint at epoch %d "
                     "and leaving at the sync point", epoch)
            elastic_mod.drain_exit(lambda: ckpt.save(trainer, epoch))
        if args.profile_dir and epoch == start_epoch:
            jax.profiler.start_trace(args.profile_dir)
        # heartbeat at DISPATCH cadence (not per epoch: an epoch longer
        # than the agent's staleness bound must not read as a hang)
        trainer.train_epoch(
            train_loaders, epoch,
            on_step=(heartbeat.beat if heartbeat is not None else None))
        if args.profile_dir and epoch == start_epoch:
            jax.profiler.stop_trace()
        if args.debug_checks:
            trainer.check_consistency()
            log.info("epoch %d: replica-consistency checks passed", epoch + 1)
        if args.shard_eval and trainer.mesh is None:
            log.warning("--shard-eval ignored: strategy %s runs without a "
                        "mesh", args.strategy)
        if args.shard_eval and trainer.mesh is not None:
            evaluation.evaluate_sharded(
                trainer.params, trainer.eval_state(), test_loader.dataset,
                trainer.mesh, batch_size=args.batch_size,
                model_name=args.model, compute_dtype=cfg.dtype,
                fold_bn=args.fold_bn_eval)
        else:
            evaluation.evaluate(
                trainer.params, trainer.eval_state(), test_loader,
                model_name=args.model, compute_dtype=cfg.dtype,
                fold_bn=args.fold_bn_eval)
        if ckpt is not None:
            ckpt.save(trainer, epoch + 1)

    dist_init.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
