"""CLI + rendezvous contract tests (reference launch contracts, SURVEY §2.1
items 7 and 9)."""

import numpy as np
import pytest

from distributed_pytorch_tpu import cli
from distributed_pytorch_tpu.parallel import init as dist_init


def test_parser_reference_contract():
    """The README.md:4 argparse contract is preserved verbatim."""
    args = cli.build_parser().parse_args(
        ["--master-ip", "172.18.0.2", "--num-nodes", "4", "--rank", "2",
         "--strategy", "gather_scatter"])
    assert args.master_ip == "172.18.0.2"
    assert args.num_nodes == 4
    assert args.rank == 2
    assert args.strategy == "gather_scatter"
    assert args.port == 6585  # the reference's hard-coded port


def test_parser_defaults_match_reference():
    args = cli.build_parser().parse_args([])
    assert args.batch_size == 256    # main.py:18
    assert args.lr == 0.1            # main.py:103
    assert args.momentum == 0.9
    assert args.weight_decay == 1e-4
    assert args.epochs == 1          # main.py:106
    assert args.seed == 1            # main.py:70


def test_parser_overlap_and_dcn_flags():
    """Round-9 surface: the overlap + dcn-compression knobs reach
    TrainConfig (defaults off/None so historical invocations are
    byte-identical)."""
    args = cli.build_parser().parse_args([])
    assert args.overlap is False and args.dcn_compress is None
    assert args.overlap_bucket_mb is None
    args = cli.build_parser().parse_args(
        ["--strategy", "hierarchical", "--dcn-size", "2",
         "--dcn-compress", "int8", "--overlap",
         "--overlap-bucket-mb", "0.5"])
    assert args.dcn_compress == "int8" and args.overlap
    assert args.overlap_bucket_mb == 0.5
    from distributed_pytorch_tpu import lm_cli
    lm_args = lm_cli.build_parser().parse_args([])
    assert lm_args.dcn_size == 1 and lm_args.overlap is False
    lm_args = lm_cli.build_parser().parse_args(
        ["--dp", "4", "--dcn-size", "2", "--fsdp", "--overlap"])
    assert lm_args.dcn_size == 2 and lm_args.overlap


def test_parser_lowbit_flags():
    """Round-16 surface: --dcn-compress grows int4 on BOTH CLIs, and
    the LM CLI gains --fsdp-gather-dtype / --matmul-dtype (defaults
    None so historical invocations are byte-identical); the int4 wire
    format has no gather/matmul analogue, so those parsers refuse it."""
    import pytest

    from distributed_pytorch_tpu import lm_cli

    args = cli.build_parser().parse_args(
        ["--strategy", "hierarchical", "--dcn-size", "2",
         "--dcn-compress", "int4"])
    assert args.dcn_compress == "int4"
    lm_args = lm_cli.build_parser().parse_args([])
    assert lm_args.fsdp_gather_dtype is None
    assert lm_args.matmul_dtype is None
    lm_args = lm_cli.build_parser().parse_args(
        ["--dp", "4", "--dcn-size", "2", "--dcn-compress", "int4",
         "--fsdp", "--fsdp-gather-dtype", "int8",
         "--matmul-dtype", "int8"])
    assert lm_args.dcn_compress == "int4"
    assert lm_args.fsdp_gather_dtype == "int8"
    assert lm_args.matmul_dtype == "int8"
    # round 18 lifts the round-16 int4-gather refusal (nibble-packed
    # u8 wire, tests/test_lowbit.py); the matmul kernel still has no
    # int4 analogue
    lm_args = lm_cli.build_parser().parse_args(
        ["--fsdp", "--fsdp-gather-dtype", "int4"])
    assert lm_args.fsdp_gather_dtype == "int4"
    for bad in (["--matmul-dtype", "int4"],
                ["--dcn-compress", "fp8"]):
        with pytest.raises(SystemExit):
            lm_cli.build_parser().parse_args(bad)


def test_parser_localsgd_flags():
    """Round-18 surface: --sync-every reaches both CLIs (plus
    --staleness / --max-sync-every on the LM side) with per-step
    defaults so historical invocations are byte-identical; incoherent
    combos refuse loudly through the SAME require_sync_window check the
    trainers run, at the parser, before any mesh or compile."""
    import pytest

    from distributed_pytorch_tpu import lm_cli

    args = cli.build_parser().parse_args([])
    assert args.sync_every == 1 and args.max_sync_every is None
    args = cli.build_parser().parse_args(
        ["--strategy", "hierarchical", "--dcn-size", "2",
         "--sync-every", "4", "--max-sync-every", "8"])
    assert args.sync_every == 4 and args.max_sync_every == 8

    lm_args = lm_cli.build_parser().parse_args([])
    assert lm_args.sync_every == 1 and lm_args.staleness == 0
    assert lm_args.max_sync_every is None
    lm_args = lm_cli.build_parser().parse_args(
        ["--dp", "4", "--dcn-size", "2", "--sync-every", "4",
         "--staleness", "1", "--max-sync-every", "8"])
    assert lm_args.sync_every == 4 and lm_args.staleness == 1
    assert lm_args.max_sync_every == 8

    # refusals (argparse SystemExit, pre-init — the one definition site)
    with pytest.raises(SystemExit):  # LM windows need a factored mesh
        lm_cli.main(["--dp", "4", "--sync-every", "4"])
    with pytest.raises(SystemExit):  # staleness must leave window room
        lm_cli.main(["--dp", "4", "--dcn-size", "2",
                     "--sync-every", "4", "--staleness", "4"])
    with pytest.raises(SystemExit):  # staleness without a window
        lm_cli.main(["--staleness", "1"])
    with pytest.raises(SystemExit):  # pipeline owns its own schedule
        lm_cli.main(["--dp", "2", "--dcn-size", "2", "--sync-every", "4",
                     "--pp", "2", "--microbatches", "4"])
    with pytest.raises(SystemExit):  # VGG: overlap streams the sync
        cli.main(["--strategy", "hierarchical", "--dcn-size", "2",
                  "--sync-every", "2", "--overlap"])
    with pytest.raises(SystemExit):  # VGG: meshless has no collective
        cli.main(["--strategy", "none", "--sync-every", "2"])


def test_parser_diloco_flags():
    """Round-22 surface: --outer-opt/--outer-momentum/--outer-lr/
    --sync-every-per-slice reach both CLIs (defaults None/0.9/1.0/None
    so historical invocations are byte-identical); malformed values and
    incoherent combos refuse loudly at the parser through the SAME
    require_sync_window check the trainers run."""
    import pytest

    from distributed_pytorch_tpu import lm_cli

    for parser in (cli.build_parser(), lm_cli.build_parser()):
        args = parser.parse_args([])
        assert args.outer_opt is None
        assert args.outer_momentum == 0.9 and args.outer_lr == 1.0
        assert args.sync_every_per_slice is None

    lm_args = lm_cli.build_parser().parse_args(
        ["--dp", "4", "--dcn-size", "2", "--sync-every", "4",
         "--outer-opt", "nesterov", "--outer-momentum", "0.5",
         "--outer-lr", "0.7", "--sync-every-per-slice", "4,8"])
    assert lm_args.outer_opt == "nesterov"
    assert lm_args.outer_momentum == 0.5 and lm_args.outer_lr == 0.7
    assert lm_args.sync_every_per_slice == "4,8"

    # refusals (argparse SystemExit, pre-init — the one definition site)
    with pytest.raises(SystemExit):  # unknown outer optimizer
        lm_cli.build_parser().parse_args(["--outer-opt", "adamw"])
    with pytest.raises(SystemExit):  # outer needs a window
        lm_cli.main(["--dp", "4", "--dcn-size", "2",
                     "--outer-opt", "nesterov"])
    with pytest.raises(SystemExit):  # momentum bound
        lm_cli.main(["--dp", "4", "--dcn-size", "2", "--sync-every",
                     "4", "--outer-opt", "nesterov",
                     "--outer-momentum", "1.5"])
    with pytest.raises(SystemExit):  # malformed per-slice list
        lm_cli.main(["--dp", "4", "--dcn-size", "2", "--sync-every",
                     "4", "--sync-every-per-slice", "4,x"])
    with pytest.raises(SystemExit):  # per-slice + staleness
        lm_cli.main(["--dp", "4", "--dcn-size", "2", "--sync-every",
                     "4", "--staleness", "1",
                     "--sync-every-per-slice", "4,8"])
    with pytest.raises(SystemExit):  # min(per-slice) must be the base
        lm_cli.main(["--dp", "4", "--dcn-size", "2", "--sync-every",
                     "4", "--sync-every-per-slice", "8,8"])
    with pytest.raises(SystemExit):  # VGG windows are gang-wide
        cli.main(["--strategy", "hierarchical", "--dcn-size", "2",
                  "--sync-every", "2", "--sync-every-per-slice", "2,4"])
    with pytest.raises(SystemExit):  # VGG: outer still needs a window
        cli.main(["--strategy", "hierarchical", "--dcn-size", "2",
                  "--outer-opt", "momentum"])


def test_parser_memory_flags():
    """Round-17 surface: the LM CLI gains --loss-impl / --loss-chunk /
    --remat (defaults None so historical invocations are
    byte-identical); typo'd values and incoherent combinations refuse
    loudly at the parser, before any mesh or compile."""
    import pytest

    from distributed_pytorch_tpu import lm_cli

    lm_args = lm_cli.build_parser().parse_args([])
    assert lm_args.loss_impl is None
    assert lm_args.loss_chunk is None
    assert lm_args.remat is None
    lm_args = lm_cli.build_parser().parse_args(
        ["--loss-impl", "chunked", "--loss-chunk", "64",
         "--remat", "selective"])
    assert lm_args.loss_impl == "chunked"
    assert lm_args.loss_chunk == 64
    assert lm_args.remat == "selective"
    for bad in (["--loss-impl", "streamed"],
                ["--remat", "partial"]):
        with pytest.raises(SystemExit):
            lm_cli.build_parser().parse_args(bad)
    # incoherent combinations refuse in main(), pre-init
    with pytest.raises(SystemExit):
        lm_cli.main(["--loss-chunk", "64"])  # needs --loss-impl chunked
    with pytest.raises(SystemExit):
        lm_cli.main(["--remat", "full", "--pp", "2"])


def test_init_single_host_is_noop():
    dist_init.init_distributed(None, num_nodes=1, rank=0)  # must not raise


def test_init_requires_master_ip():
    with pytest.raises(ValueError, match="master-ip"):
        dist_init.init_distributed(None, num_nodes=4, rank=0)


def test_init_env_single_process(monkeypatch):
    monkeypatch.delenv("WORLD_SIZE", raising=False)
    dist_init.init_from_env()  # no env vars -> single-process no-op


def test_build_loaders_shards_train_not_test(tmp_path):
    args = cli.build_parser().parse_args(["--batch-size", "8"])
    train_loaders, test_loader = cli.build_loaders(args, n_replicas=4,
                                                   replica_offset=0)
    assert len(train_loaders) == 4
    # Disjoint shards covering the (padded) epoch: reference sampler
    # semantics (main_all_reduce.py:112).
    idx = [set(dl.sampler.indices().tolist()) for dl in train_loaders]
    n = sum(len(s) for s in idx)
    assert n == 4 * train_loaders[0].sampler.num_samples
    # test set unsharded (main_gather.py:131): full 10k
    assert test_loader.sampler is None
    assert len(test_loader.dataset) == 10_000


def test_cli_end_to_end_tiny(tmp_path, monkeypatch):
    """Full CLI run: 1 epoch over a tiny synthetic subset, ddp strategy on
    the virtual device mesh, with checkpointing; then resume is a no-op."""
    from distributed_pytorch_tpu.data import cifar10

    def tiny_load(split="train", data_dir=None):
        return cifar10._synthetic(64 if split == "train" else 32, seed=0)

    monkeypatch.setattr(cli, "load", tiny_load)
    ckpt_dir = str(tmp_path / "ckpt")
    rc = cli.main(["--strategy", "ddp", "--batch-size", "4",
                   "--num-devices", "2", "--no-augment",
                   "--checkpoint-dir", ckpt_dir, "--epochs", "1"])
    assert rc == 0
    from distributed_pytorch_tpu.utils.checkpoint import Checkpointer
    assert Checkpointer(ckpt_dir).latest()[0] == 1
    # Resume: start_epoch == epochs -> no training, exits cleanly.
    rc = cli.main(["--strategy", "ddp", "--batch-size", "4",
                   "--num-devices", "2", "--no-augment",
                   "--checkpoint-dir", ckpt_dir, "--epochs", "1"])
    assert rc == 0


def test_sharded_eval_matches_replicated():
    """evaluate_sharded over a 4-device mesh == plain evaluate (same params,
    same reference loss definition), at an O(devices) speedup."""
    import jax
    import numpy as np

    from distributed_pytorch_tpu import eval as evaluation
    from distributed_pytorch_tpu.data import DataLoader
    from distributed_pytorch_tpu.data.cifar10 import Dataset
    from distributed_pytorch_tpu.models import vgg
    from distributed_pytorch_tpu.parallel.mesh import make_mesh

    rng = np.random.default_rng(0)
    ds = Dataset(images=rng.integers(0, 256, (100, 32, 32, 3)).astype(np.uint8),
                 labels=rng.integers(0, 10, 100).astype(np.int32))
    params, state = vgg.init(jax.random.key(0), "VGG11")

    loss_rep, acc_rep = evaluation.evaluate(
        params, state, DataLoader(ds, 32), log=None)
    loss_sh, acc_sh = evaluation.evaluate_sharded(
        params, state, ds, make_mesh(4), batch_size=32, log=None)
    assert acc_sh == acc_rep
    np.testing.assert_allclose(loss_sh, loss_rep, rtol=1e-4)


def test_parser_pipeline_flags():
    """The pipeline knobs (--pp, --microbatches, --interleave) reach
    LMTrainConfig with defaults that leave historical invocations
    byte-identical, and what the wave schedule cannot run refuses at
    config time: in the ONE validate_lm_cfg, or where the trainer cuts
    the layer stack into stages, before any step is built."""
    import dataclasses

    from distributed_pytorch_tpu import lm_cli
    from distributed_pytorch_tpu.lm import (LMTrainConfig, LMTrainer,
                                            validate_lm_cfg)

    lm_args = lm_cli.build_parser().parse_args([])
    assert (lm_args.pp, lm_args.microbatches, lm_args.interleave) == (1, 0, 1)
    lm_args = lm_cli.build_parser().parse_args(
        ["--pp", "2", "--microbatches", "4", "--interleave", "2",
         "--n-layers", "4"])
    cfg = LMTrainConfig(model=lm_cli.model_config(lm_args), pp=lm_args.pp,
                        microbatches=lm_args.microbatches,
                        interleave=lm_args.interleave, compute_dtype=None)
    assert (cfg.pp, cfg.microbatches, cfg.interleave) == (2, 4, 2)
    validate_lm_cfg(cfg)

    for match, kw in (("interleave", dict(interleave=0)),
                      ("requires pp > 1", dict(pp=1)),
                      ("grad_accum", dict(grad_accum=2)),
                      ("remat", dict(remat="full")),
                      ("expert", dict(ep=2))):
        with pytest.raises(ValueError, match=match):
            validate_lm_cfg(dataclasses.replace(cfg, **kw))
    # 4 layers do not cut into 2 stages x 3 virtual stages
    with pytest.raises(ValueError, match="do not split"):
        LMTrainer(dataclasses.replace(cfg, interleave=3))


def test_parser_autotune_flags():
    """Round-11 surface: the autotuner knobs reach both CLIs — VGG
    --strategy auto / --autotune-profile, LM --sync-plan auto /
    --dcn-compress / --bucket-mb — with None defaults so historical
    invocations are byte-identical."""
    from distributed_pytorch_tpu import lm_cli

    args = cli.build_parser().parse_args([])
    assert args.autotune_profile is None and args.strategy == "ddp"
    args = cli.build_parser().parse_args(
        ["--strategy", "auto", "--autotune-profile", "fast_ici_slow_dcn"])
    assert args.strategy == "auto"
    assert args.autotune_profile == "fast_ici_slow_dcn"

    lm_args = lm_cli.build_parser().parse_args([])
    assert lm_args.sync_plan is None and lm_args.dcn_compress is None
    assert lm_args.bucket_mb is None and lm_args.autotune_profile is None
    lm_args = lm_cli.build_parser().parse_args(
        ["--dp", "4", "--dcn-size", "2", "--dcn-compress", "int8",
         "--bucket-mb", "4", "--sync-plan", "auto",
         "--autotune-profile", "uniform"])
    assert lm_args.dcn_compress == "int8" and lm_args.bucket_mb == 4.0
    assert lm_args.sync_plan == "auto"
    assert lm_args.autotune_profile == "uniform"

    # incoherent combos refuse through the ONE validation path
    from distributed_pytorch_tpu.lm import LMTrainConfig, validate_lm_cfg
    with pytest.raises(ValueError, match="no DCN hop"):
        validate_lm_cfg(LMTrainConfig(dp=4, dcn_compress="int8"))


def test_parser_elastic_flags():
    """Round-12 surface: --elastic/--min-nodes/--max-nodes reach both
    CLIs (defaults off so historical invocations are byte-identical),
    and configs that CANNOT resize refuse loudly at parse/validate time
    — a pipeline axis (pp > 1), a missing checkpoint dir (the
    drain sync point must flush one), bounds without --elastic, and the
    meshless VGG strategy."""
    from distributed_pytorch_tpu import lm_cli

    args = cli.build_parser().parse_args([])
    assert args.elastic is False
    assert args.min_nodes == 1 and args.max_nodes is None
    args = cli.build_parser().parse_args(
        ["--elastic", "--min-nodes", "1", "--max-nodes", "4"])
    assert args.elastic and args.max_nodes == 4

    lm_args = lm_cli.build_parser().parse_args([])
    assert lm_args.elastic is False
    assert lm_args.min_nodes == 1 and lm_args.max_nodes is None
    lm_args = lm_cli.build_parser().parse_args(
        ["--elastic", "--min-nodes", "2", "--max-nodes", "4",
         "--checkpoint-dir", "/tmp/x"])
    assert lm_args.elastic and lm_args.min_nodes == 2

    # refusals (argparse SystemExit, before any jax/rendezvous work)
    with pytest.raises(SystemExit):  # pipeline cannot resize (for now)
        lm_cli.main(["--elastic", "--checkpoint-dir", "/tmp/x",
                     "--pp", "2"])
    with pytest.raises(SystemExit):  # no checkpoint dir to drain into
        lm_cli.main(["--elastic"])
    with pytest.raises(SystemExit):  # bounds without --elastic
        lm_cli.main(["--min-nodes", "2"])
    with pytest.raises(SystemExit):  # min > max
        lm_cli.main(["--elastic", "--checkpoint-dir", "/tmp/x",
                     "--min-nodes", "3", "--max-nodes", "2"])
    with pytest.raises(SystemExit):  # VGG: no checkpoint dir
        cli.main(["--elastic"])
    with pytest.raises(SystemExit):  # VGG: nothing to resize
        cli.main(["--elastic", "--checkpoint-dir", "/tmp/x",
                  "--strategy", "none"])
    with pytest.raises(SystemExit):  # VGG: bounds without --elastic
        cli.main(["--max-nodes", "4"])
