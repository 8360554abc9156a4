"""Elastic-gang tests (round 12): detect worker loss, shrink the mesh,
reshard from checkpoint, keep training — then grow back.

Lanes (the ``elastic`` marker, wired like ``faults``):
- sampler re-keying: the global batch order is world-size-independent,
  so a mid-epoch resize drops/double-counts nothing;
- cross-topology ``load_resharded``: bitwise vs gather-then-load across
  dp / replicated / dpxtp layout pairs, with NO full-array assembly and
  the corrupt-shard quarantine-and-fall-back still engaged;
- in-process resize: ``Trainer.rebuild``/``LMTrainer.rebuild`` +
  reshard-restore continue BITWISE-equal to a fresh launch at the new
  size restored from the same checkpoint;
- the sentry's resize escalation rung (between rollback-and-skip and
  abort);
- the elastic agent itself (jax-free subprocess workers): shrink on
  death, hung-straggler detection via heartbeats, grow-back, below-min
  failure, drain accounting;
- the gang-level slow test: kill -> shrink -> resume resharded ->
  rejoin -> grow, with the acceptance bitwise pin.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from distributed_pytorch_tpu.data.sampler import ElasticSampler
from distributed_pytorch_tpu.launch import (
    ELASTIC_DRAIN_EXIT_CODE, ELASTIC_RESIZE_EXIT_CODE, ElasticConfig,
    LocalAgent)
from distributed_pytorch_tpu.utils import faults

pytestmark = pytest.mark.elastic

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _quiet(*a):
    pass


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


# -- ElasticSampler: resize-lossless data assignment -------------------------

def test_sampler_global_order_world_independent():
    """THE invariant: the global batch for step s never depends on the
    world size — what makes a resize lossless."""
    s = ElasticSampler(50, 8, seed=3)
    ref = [s.global_indices(t).tolist() for t in range(14)]
    for world in (1, 2, 4, 8):
        s.set_generation(5, world, 0)
        assert [s.global_indices(t).tolist() for t in range(14)] == ref


def test_sampler_stripes_partition_the_global_batch():
    """Per step, rank stripes are disjoint, contiguous, in rank order —
    they concatenate back into the canonical global batch at ANY
    (generation, world_size)."""
    s = ElasticSampler(50, 8, seed=3)
    for gen, world in ((0, 1), (1, 2), (2, 4), (3, 8)):
        for step in (0, 3, 7):  # incl. the padded epoch tail
            got = []
            for rank in range(world):
                s.set_generation(gen, world, rank)
                got.extend(s.indices(step).tolist())
            assert got == s.global_indices(step).tolist(), (gen, world)


def test_sampler_resize_mid_epoch_drops_and_doubles_nothing():
    """Shrink 4->2 at step 3, grow 2->4 at step 5: the union of every
    rank's consumed indices equals the world-size-independent global
    order exactly — no example dropped, none double-counted."""
    s = ElasticSampler(64, 8, seed=11)
    consumed = []
    membership = [(0, 4)] * 3 + [(1, 2)] * 2 + [(2, 4)] * 3
    for step, (gen, world) in enumerate(membership):
        for rank in range(world):
            s.set_generation(gen, world, rank)
            consumed.extend(s.indices(step).tolist())
    want = []
    for step in range(len(membership)):
        want.extend(s.global_indices(step).tolist())
    assert sorted(consumed) == sorted(want)
    # padded-epoch accounting: one epoch covers every example at least
    # once (torch drop_last=False padding repeats only the head)
    epoch0 = [i for step in range(s.steps_per_epoch)
              for i in s.global_indices(step).tolist()]
    assert set(epoch0) == set(range(64))


def test_sampler_epochs_reshuffle_deterministically():
    s = ElasticSampler(32, 8, seed=0)
    e0 = [s.global_indices(t).tolist() for t in range(4)]
    e1 = [s.global_indices(t).tolist() for t in range(4, 8)]
    assert e0 != e1
    assert e0 == [ElasticSampler(32, 8, seed=0).global_indices(t).tolist()
                  for t in range(4)]
    assert s.epoch_of(3) == 0 and s.epoch_of(4) == 1


def test_sampler_refuses_indivisible_world_and_bad_rank():
    s = ElasticSampler(32, 8)
    with pytest.raises(ValueError, match="does not divide"):
        s.set_generation(1, 3, 0)
    with pytest.raises(ValueError, match="out of range"):
        s.set_generation(1, 2, 2)


# -- elastic agent (jax-free subprocess workers) -----------------------------

_HB_PRELUDE = r"""
import json, os, signal, sys, time
d = os.environ["ELASTIC_DIR"]; rank = os.environ["RANK"]
gen = int(os.environ["RESTART_ATTEMPT"]); world = int(os.environ["WORLD_SIZE"])
flag = []
signal.signal(signal.SIGTERM, lambda *a: flag.append(1))
def beat(step):
    p = os.path.join(d, "hb_rank%s.json" % rank); t = p + ".tmp"
    with open(t, "w") as f:
        json.dump({"rank": int(rank), "step": step, "gen": gen}, f)
    os.replace(t, p)
"""


def _elastic_agent(prog, tmp_path, *, max_workers, min_workers=1,
                   hb_timeout=60.0, grow_after=2, drain_grace=10.0):
    return LocalAgent(
        ["-c", _HB_PRELUDE + prog],
        nproc_per_node=max_workers,
        monitor_interval_s=0.02,
        elastic=ElasticConfig(
            min_workers=min_workers, max_workers=max_workers,
            heartbeat_timeout_s=hb_timeout, drain_grace_s=drain_grace,
            rejoin_delay_s=0.0, grow_after_steps=grow_after,
            run_dir=str(tmp_path / "elastic")),
        log=_quiet)


def test_agent_shrinks_on_worker_loss_then_grows_back(tmp_path):
    """Rank 1 of 3 dies in generation 0: the survivors drain (SIGTERM ->
    exit 78), the gang re-rendezvouses at world 2, and once heartbeats
    advance the gang grows back to 3 — both transitions in
    GangResult.resize_events, drain outcomes accounted."""
    prog = r"""
for step in range(400):
    beat(step)
    if flag: sys.exit(78)
    if gen == 0 and rank == "1" and step == 3: sys.exit(9)
    if gen >= 2: sys.exit(0)
    time.sleep(0.03)
sys.exit(0)
"""
    result = _elastic_agent(prog, tmp_path, max_workers=3).run()
    assert result.returncode == 0, result
    moves = [(e["kind"], e["from_size"], e["to_size"], e["reason"])
             for e in result.resize_events]
    assert moves == [("shrink", 3, 2, "failure"),
                     ("grow", 2, 3, "rejoin")], result.resize_events
    assert result.resize_events[0]["rank"] == 1
    # shrink drain (2 survivors) + grow drain (2 workers) all flushed
    assert result.drain["drained"] >= 4, result.drain
    assert result.restarts_used == 2  # generations 0 -> 1 -> 2


def test_agent_detects_hung_straggler_via_heartbeat(tmp_path):
    """A worker whose PID stays alive but whose heartbeat goes stale (a
    hung collective / wedged host thread) is detected and treated as
    lost — the upgrade over dead-PID-only monitoring."""
    prog = r"""
for step in range(400):
    if gen == 0 and rank == "1" and step >= 3:
        time.sleep(60)  # hung: alive, silent
    beat(step)
    if flag: sys.exit(78)
    if gen >= 1: sys.exit(0)
    time.sleep(0.05)
sys.exit(0)
"""
    t0 = time.monotonic()
    result = _elastic_agent(prog, tmp_path, max_workers=2,
                            hb_timeout=0.5).run()
    assert result.returncode == 0, result
    assert time.monotonic() - t0 < 30
    assert [e["kind"] for e in result.resize_events] == ["shrink"]
    assert result.resize_events[0]["reason"] == "heartbeat"
    assert result.resize_events[0]["to_size"] == 1


def test_agent_below_min_fails_gang(tmp_path):
    prog = r"""
for step in range(400):
    beat(step)
    if flag: sys.exit(78)
    if gen == 0 and rank == "1" and step == 2: sys.exit(5)
    time.sleep(0.03)
"""
    result = _elastic_agent(prog, tmp_path, max_workers=2,
                            min_workers=2).run()
    assert result.returncode == 5
    assert result.failed_rank == 1
    assert result.resize_events == []


def test_agent_honors_worker_requested_resize(tmp_path):
    """The sentry's resize rung exits ELASTIC_RESIZE_EXIT_CODE: the
    agent treats it as a lost member classified 'requested' and
    reshards the gang one smaller."""
    prog = r"""
for step in range(400):
    beat(step)
    if flag: sys.exit(78)
    if gen == 0 and rank == "1" and step == 2: sys.exit(%d)
    if gen >= 1: sys.exit(0)
    time.sleep(0.03)
sys.exit(0)
""" % ELASTIC_RESIZE_EXIT_CODE
    result = _elastic_agent(prog, tmp_path, max_workers=2,
                            grow_after=10_000).run()
    assert result.returncode == 0, result
    assert [e["reason"] for e in result.resize_events] == ["requested"]


def test_agent_grow_gate_tolerates_finished_and_cold_ranks(tmp_path):
    """The grow gate reads the RUNNING ranks, not the beat history: a
    rank that beat and then finished (exit 0) must not crash or block
    the check, and a rank still cold (no beat yet this generation) must
    simply defer growth until it advances."""
    prog = r"""
if gen == 0:
    beat(0)
    if rank == "2": sys.exit(9)
    while not flag:
        time.sleep(0.02)
    sys.exit(78)
if gen == 1:
    if rank == "1":
        beat(0); beat(1)
        time.sleep(0.2)
        sys.exit(0)      # finished: leaves `running`, stays in history
    time.sleep(0.8)      # cold: rank 1 exits before our first beat
    for step in range(100):
        beat(step)
        if flag: sys.exit(78)
        time.sleep(0.05)
    sys.exit(0)
sys.exit(0)
"""
    result = _elastic_agent(prog, tmp_path, max_workers=3,
                            grow_after=2).run()
    assert result.returncode == 0, result
    moves = [(e["kind"], e["from_size"], e["to_size"])
             for e in result.resize_events]
    assert moves == [("shrink", 3, 2), ("grow", 2, 3)], result.resize_events


def test_agent_resize_budget_bounds_oscillation(tmp_path):
    """A slot that deterministically crashes must not drive an unbounded
    shrink/grow oscillation: after max_resizes shrinks, the next loss
    fails the gang instead of resharding again."""
    prog = r"""
for step in range(400):
    beat(step)
    if flag: sys.exit(78)
    if rank == "1" and step == 1: sys.exit(9)  # EVERY generation
    time.sleep(0.03)
sys.exit(0)
"""
    cfg = ElasticConfig(min_workers=1, max_workers=2,
                        heartbeat_timeout_s=60.0, drain_grace_s=10.0,
                        rejoin_delay_s=0.0, grow_after_steps=1,
                        max_resizes=2, run_dir=str(tmp_path / "e2"))
    agent = LocalAgent(["-c", _HB_PRELUDE + prog], nproc_per_node=2,
                       monitor_interval_s=0.02, elastic=cfg, log=_quiet)
    result = agent.run()
    assert result.returncode == 9
    shrinks = [e for e in result.resize_events if e["kind"] == "shrink"]
    assert len(shrinks) == 2  # the budget, then fail — no oscillation
    with pytest.raises(ValueError, match="max_resizes"):
        ElasticConfig(min_workers=1, max_workers=2, max_resizes=0)


def test_lm_loader_elastic_order_world_size_independent():
    """The lm_cli --elastic data path: with elastic_order the GLOBAL
    window stream per step is identical at every world size (rank
    stripes concatenate in rank order), so a mid-run resize resumes
    losslessly from the recorded (epoch, offset); the default
    interleaved striding does NOT have this property (pinned, so the
    flag keeps mattering)."""
    from distributed_pytorch_tpu.data import lm_corpus

    toks = np.arange(16 * 33 + 1, dtype=np.int32) % 251
    corpus = lm_corpus.LMCorpus(toks, True)

    def stream(world, batch, *, elastic, epoch=1, steps=3):
        out = []
        loaders = [lm_corpus.LMDataLoader(
            corpus, batch, 32, num_replicas=world, rank=r, seed=5,
            elastic_order=elastic) for r in range(world)]
        for dl in loaders:
            dl.set_epoch(epoch)
        its = [iter(dl) for dl in loaders]
        for _ in range(steps):
            step_rows = [next(it)[0] for it in its]  # rank order
            out.append(np.concatenate(step_rows))
        return np.stack(out)

    ref = stream(1, 4, elastic=True)
    for world in (2, 4):
        np.testing.assert_array_equal(
            stream(world, 4 // world, elastic=True), ref)
    assert not np.array_equal(stream(2, 2, elastic=False), ref)


def test_vgg_rebuild_checks_dcn_extent():
    from distributed_pytorch_tpu.parallel.mesh import make_mesh
    from distributed_pytorch_tpu.train import TrainConfig, Trainer

    tr = Trainer(TrainConfig(model="TINY", strategy="hierarchical",
                             batch_size=2, augment=False, dcn_size=2))
    with pytest.raises(ValueError, match="dcn_size"):
        tr.rebuild(mesh=make_mesh(8, axis_names=("dcn", "ici"),
                                  axis_shape=(4, 2)))


def test_elastic_config_validation_and_multinode_refusal():
    with pytest.raises(ValueError, match="min <= max"):
        ElasticConfig(min_workers=3, max_workers=2)
    with pytest.raises(ValueError, match="nnodes"):
        LocalAgent(["-c", "pass"], nnodes=2,
                   elastic=ElasticConfig(min_workers=1, max_workers=2))


def test_launch_parser_elastic_flags():
    from distributed_pytorch_tpu.launch import build_parser, main
    args = build_parser().parse_args(
        ["--elastic", "--min-nodes", "1", "--max-nodes", "4",
         "--heartbeat-timeout", "5", "--drain-grace", "7",
         "--rejoin-delay", "1", "--grow-after-steps", "2",
         "--max-resizes", "3", "--", "-c", "pass"])
    assert args.elastic and args.min_nodes == 1 and args.max_nodes == 4
    assert args.heartbeat_timeout == 5.0 and args.drain_grace == 7.0
    assert args.max_resizes == 3
    # bounds without --elastic refuse loudly
    with pytest.raises(SystemExit):
        main(["--min-nodes", "2", "--", "-c", "pass"])
    # elastic + multi-node refuses loudly (carried-forward half)
    with pytest.raises(SystemExit):
        main(["--elastic", "--nnodes", "2", "--", "-c", "pass"])
    # conflicting worker counts refuse loudly (set one, not both)
    with pytest.raises(SystemExit):
        main(["--elastic", "--nproc-per-node", "4", "--max-nodes", "8",
              "--", "-c", "pass"])


def test_exit_codes_distinct_and_shared():
    """The drain/resize codes must never collide with the chaos
    harness's injected-crash code, and the worker-side module must use
    the agent's exact values (imported, so structurally true — pinned
    anyway against a refactor splitting them)."""
    from distributed_pytorch_tpu.launch import FAULT_EXIT_CODE
    from distributed_pytorch_tpu.parallel import elastic as el
    codes = {FAULT_EXIT_CODE, ELASTIC_DRAIN_EXIT_CODE,
             ELASTIC_RESIZE_EXIT_CODE}
    assert len(codes) == 3
    assert el.ELASTIC_DRAIN_EXIT_CODE == ELASTIC_DRAIN_EXIT_CODE
    assert el.ELASTIC_RESIZE_EXIT_CODE == ELASTIC_RESIZE_EXIT_CODE


def test_heartbeat_atomic_and_agent_readable(tmp_path):
    from distributed_pytorch_tpu.parallel.elastic import Heartbeat
    hb = Heartbeat(str(tmp_path), rank=2, generation=1)
    hb.beat(7)
    agent = LocalAgent(["-c", "pass"], log=_quiet,
                       elastic=ElasticConfig(min_workers=1, max_workers=1))
    beats = agent._heartbeats(str(tmp_path))
    assert beats[2]["step"] == 7 and beats[2]["gen"] == 1
    assert not [n for n in os.listdir(tmp_path) if ".tmp" in n]


# -- rendezvous backoff: env budget/cap + attempts-used in the log -----------

def test_rdzv_env_budget_cap_and_attempts_log(monkeypatch, capsys):
    from distributed_pytorch_tpu.parallel import init as dist_init

    monkeypatch.setenv(dist_init.ATTEMPTS_ENV, "7")
    monkeypatch.setenv(dist_init.BACKOFF_CAP_ENV, "0.25")
    assert dist_init.rdzv_attempts_from_env() == 7
    assert dist_init.rdzv_backoff_cap_from_env() == 0.25
    for bad in ("many", "0", "-3"):
        monkeypatch.setenv(dist_init.ATTEMPTS_ENV, bad)
        with pytest.raises(ValueError, match=dist_init.ATTEMPTS_ENV):
            dist_init.rdzv_attempts_from_env()
    # the cap bounds EVERY delay, even at absurd attempt counts (the
    # "unbounded growth on long flaps" fix) — jitter tops out at 1.5x
    monkeypatch.setenv(dist_init.BACKOFF_CAP_ENV, "0.2")
    for attempt in (0, 7, 60):
        d = dist_init._backoff_delay(
            attempt, rank=3, base_s=1.0,
            cap_s=dist_init.rdzv_backoff_cap_from_env())
        assert d <= 0.2 * 1.5

    # a flap survived within the env budget surfaces attempts-used in
    # the ONE success log line
    monkeypatch.setenv(dist_init.ATTEMPTS_ENV, "3")
    calls = []

    def flaky_init(**kw):
        calls.append(kw)
        if len(calls) < 3:
            raise ConnectionRefusedError("injected flap")

    dist_init.init_distributed("127.0.0.1", 2, 1, timeout_s=30,
                               backoff_base_s=0.01, _initialize=flaky_init)
    assert len(calls) == 3
    assert "after 3/3 attempt(s)" in capsys.readouterr().out


# -- cross-topology load_resharded -------------------------------------------

def _mesh(n, names=("d",), shape=None):
    from jax.sharding import Mesh
    devs = np.array(jax.devices()[:n])
    if shape is not None:
        devs = devs.reshape(shape)
    return Mesh(devs, names)


def _place(mesh, spec, x):
    from jax.sharding import NamedSharding
    return jax.device_put(x, NamedSharding(mesh, spec))


def test_load_resharded_bitwise_vs_gather_then_load(tmp_path):
    """Every supported layout pair: save dp / replicated / dpxtp layouts
    on 4 devices, load_resharded onto 2- and 1-device meshes; values
    BITWISE-equal the gather-then-load reference (``restore``), with
    ZERO full-array assemblies and the per-leaf in-flight bound
    honored."""
    from jax.sharding import PartitionSpec as P
    from distributed_pytorch_tpu.utils.checkpoint import ShardedCheckpointer

    rng = np.random.default_rng(0)
    x = rng.standard_normal((8, 12)).astype(np.float32)
    y = rng.standard_normal((16,)).astype(np.float32)
    mesh4, mesh22 = _mesh(4), _mesh(4, ("d", "t"), (2, 2))
    ck = ShardedCheckpointer(str(tmp_path))
    ck.save({"t": {"dp": _place(mesh4, P("d"), x),
                   "rep": _place(mesh4, P(), x),
                   "tp": _place(mesh22, P("d", "t"), x),
                   "vec": _place(mesh4, P("d"), y),
                   "count": np.int32(7)}}, 0, meta={"z": 1})

    for n in (2, 1):
        m = _mesh(n)
        like = {"t": {"dp": _place(m, P("d"), np.zeros_like(x)),
                      "rep": _place(m, P(), np.zeros_like(x)),
                      "tp": _place(m, P("d"), np.zeros_like(x)),
                      "vec": _place(m, P("d"), np.zeros_like(y)),
                      "count": np.int32(0)}}
        got = ck.load_resharded(like)
        assert got is not None
        trees, meta = got
        assert meta["z"] == 1
        stats = ck.last_reshard_stats
        assert stats["full_assemblies"] == 0, stats
        # one-in-flight-leaf bound: never more than one leaf's worth of
        # saved chunks held on host at a time
        assert stats["peak_leaf_read_bytes"] <= x.nbytes, stats
        ref = ck.restore(like)  # the gather-then-load reference
        for k in ("dp", "rep", "tp", "vec"):
            np.testing.assert_array_equal(np.asarray(trees["t"][k]), x
                                          if k != "vec" else y)
            np.testing.assert_array_equal(np.asarray(trees["t"][k]),
                                          np.asarray(ref[0]["t"][k]))
            assert trees["t"][k].sharding.is_equivalent_to(
                like["t"][k].sharding, trees["t"][k].ndim)
        assert int(trees["t"]["count"]) == 7

    # exact-layout fast path: same mesh -> only shard-sized moves, no
    # intersection assembly at all
    like4 = {"t": {"dp": _place(mesh4, P("d"), np.zeros_like(x)),
                   "rep": _place(mesh4, P(), np.zeros_like(x)),
                   "tp": _place(mesh22, P("d", "t"), np.zeros_like(x)),
                   "vec": _place(mesh4, P("d"), np.zeros_like(y)),
                   "count": np.int32(0)}}
    ck.load_resharded(like4)
    assert ck.last_reshard_stats["intersections"] == 0
    assert ck.last_reshard_stats["exact_hits"] > 0


def test_load_resharded_corrupt_shard_quarantines_and_falls_back(tmp_path):
    """A flipped bit in one saved shard fails that generation's crc on
    the RESHARD path too: the generation is quarantined (*.corrupt) and
    load_resharded falls back to the previous one."""
    from jax.sharding import PartitionSpec as P
    from distributed_pytorch_tpu.utils.checkpoint import ShardedCheckpointer

    x0 = np.arange(8 * 8, dtype=np.float32).reshape(8, 8)
    x1 = x0 + 100.0
    mesh4, mesh2 = _mesh(4), _mesh(2)
    ck = ShardedCheckpointer(str(tmp_path))
    ck.save({"t": {"x": _place(mesh4, P("d"), x0)}}, 0)
    ck.save({"t": {"x": _place(mesh4, P("d"), x1)}}, 1)
    faults.corrupt_file(str(tmp_path / "ckpt_1" / "proc0.npz"),
                        mode="bitflip", seed=3)

    like = {"t": {"x": _place(mesh2, P("d"), np.zeros_like(x0))}}
    got = ck.load_resharded(like)
    assert got is not None
    trees, meta = got
    assert meta["step"] == 0  # fell back a generation
    np.testing.assert_array_equal(np.asarray(trees["t"]["x"]), x0)
    assert os.path.exists(str(tmp_path / "ckpt_1.corrupt"))


def test_resize_mesh_keeps_inner_axes():
    from distributed_pytorch_tpu.parallel.mesh import make_mesh, resize_mesh
    m = make_mesh(8, axis_names=("data", "model"), axis_shape=(4, 2))
    small = resize_mesh(m, 4)
    assert small.devices.shape == (2, 2)
    assert tuple(small.axis_names) == ("data", "model")
    with pytest.raises(ValueError, match="inner axes"):
        resize_mesh(m, 3)


# -- in-process resize: rebuild + reshard-restore ----------------------------

def _tiny_lm_cfg(**kw):
    from distributed_pytorch_tpu.lm import LMTrainConfig
    from distributed_pytorch_tpu.models import transformer as tfm
    model = tfm.TransformerConfig(vocab_size=64, d_model=32, n_layers=1,
                                  n_heads=2, head_dim=16, d_ff=64)
    return LMTrainConfig(model=model, compute_dtype=None, **kw)


def _lm_batch(step, bs=4, s=32):
    rng = np.random.default_rng(100 + step)
    t = rng.integers(0, 64, (bs, s)).astype(np.int32)
    return t, np.roll(t, -1, 1)


def test_lm_shrink_grow_reshard_trajectory_bitwise(tmp_path):
    """The acceptance pin, in-process: a ZeRO-3 dp=4 trainer
    checkpoints (sharded), shrinks to dp=2 via rebuild +
    load_resharded, and its post-resume loss trajectory and params are
    BITWISE-identical to a fresh dp=2 trainer restored from the same
    checkpoint; growing back to dp=4 through the same machinery
    resumes cleanly."""
    from distributed_pytorch_tpu.lm import LMTrainer
    from distributed_pytorch_tpu.parallel import elastic as el
    from distributed_pytorch_tpu.utils.checkpoint import ShardedCheckpointer

    tr = LMTrainer(_tiny_lm_cfg(dp=4, fsdp=True))
    float(tr.train_step(*_lm_batch(0)))
    ck = ShardedCheckpointer(str(tmp_path))
    ck.save({"params": tr.params, "opt": tr.opt_state}, tr._step)

    # shrink 4 -> 2 (the lost-worker path, minus the rendezvous)
    assert el.reshard_from_checkpoint(tr, str(tmp_path),
                                      dp=2, fsdp=True) == 1
    stats = tr._ckptr.last_reshard_stats
    assert stats["full_assemblies"] == 0, stats
    la = [float(tr.train_step(*_lm_batch(s))) for s in (1, 2)]

    # the reference: a fresh launch at that size from the same checkpoint
    tr2 = LMTrainer(_tiny_lm_cfg(dp=2, fsdp=True))
    assert tr2.maybe_restore(str(tmp_path)) == 1
    lb = [float(tr2.train_step(*_lm_batch(s))) for s in (1, 2)]
    assert la == lb, (la, lb)
    for a, b in zip(jax.tree.leaves(tr.params), jax.tree.leaves(tr2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(tr.opt_state),
                    jax.tree.leaves(tr2.opt_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # grow back 2 -> 4 (the rejoin path): resumes and keeps training
    ck.save({"params": tr.params, "opt": tr.opt_state}, tr._step)
    assert el.reshard_from_checkpoint(tr, str(tmp_path),
                                      dp=4, fsdp=True) == 3
    assert np.isfinite(float(tr.train_step(*_lm_batch(3))))
    assert tr.mesh.devices.size == 4


def test_lm_rebuild_refuses_pipeline_and_multiprocess_scope():
    from distributed_pytorch_tpu.lm import LMTrainer
    tr = LMTrainer(_tiny_lm_cfg(dp=2, fsdp=True))
    with pytest.raises(ValueError, match="pipeline"):
        tr.rebuild(pp=2, microbatches=4, fsdp=False, dp=1)


def test_vgg_rebuild_resumes_bitwise(tmp_path):
    """The VGG side: rebuild(mesh) re-creates the compiled step on a
    smaller mesh; restored from the last checkpoint it continues
    BITWISE-equal to a fresh trainer at that size (params, opt state,
    rank-0-authoritative BN) — then grows back and stays consistent."""
    from distributed_pytorch_tpu.parallel import elastic as el
    from distributed_pytorch_tpu.parallel.mesh import make_mesh, resize_mesh
    from distributed_pytorch_tpu.train import TrainConfig, Trainer
    from distributed_pytorch_tpu.utils.checkpoint import Checkpointer

    def batch(n, seed):
        rng = np.random.default_rng(seed)
        return (rng.integers(0, 256, (n, 32, 32, 3)).astype(np.uint8),
                rng.integers(0, 10, n).astype(np.int32))

    cfg = TrainConfig(model="TINY", strategy="ddp", batch_size=2,
                      augment=False, lr=1e-2)
    tr = Trainer(cfg, mesh=make_mesh(4))
    tr.train_step(*batch(8, 0))
    ck = Checkpointer(str(tmp_path))
    ck.save(tr, epoch=1)

    assert el.reshard_from_checkpoint(
        tr, str(tmp_path), mesh=resize_mesh(tr.mesh, 2)) == 1
    assert tr.n_replicas == 2
    la = float(tr.train_step(*batch(4, 1)))

    fresh = Trainer(cfg, mesh=make_mesh(2))
    assert ck.maybe_restore(fresh) == 1
    lb = float(fresh.train_step(*batch(4, 1)))
    assert la == lb
    for a, b in zip(jax.tree.leaves(tr.params),
                    jax.tree.leaves(fresh.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # grow back and keep training, replica invariants intact
    tr.rebuild(make_mesh(4))
    tr.train_step(*batch(8, 2))
    tr.check_consistency()


def test_vgg_rebuild_refuses_meshless_strategy():
    from distributed_pytorch_tpu.train import TrainConfig, Trainer
    tr = Trainer(TrainConfig(model="TINY", strategy="none", batch_size=2,
                             augment=False))
    with pytest.raises(ValueError, match="without a mesh"):
        tr.rebuild()


# -- sentry: the resize escalation rung --------------------------------------

def test_sentry_resize_rung_between_skip_and_abort():
    """A PERSISTENT fault climbs skip -> tighten-clip -> RESIZE (hook
    fires once, after a rollback to last-good) -> only then abort."""
    from distributed_pytorch_tpu.lm import LMTrainer
    from distributed_pytorch_tpu.utils.sentry import (
        SentryAbort, SentryConfig, TrainingSentry)

    faults.install(faults.FaultPlan(kind="nan_grad", step=2, count=99))
    tr = LMTrainer(_tiny_lm_cfg())
    resized = []

    def on_resize(stats):
        resized.append(stats)
        return True  # "resized in-process" — training continues

    sentry = TrainingSentry(
        tr, SentryConfig(checkpoint_every=100, skip_budget=1,
                         max_rollbacks=3),
        on_resize=on_resize, log=_quiet)
    batch = _lm_batch(0, bs=2)
    with pytest.raises(SentryAbort):
        for _ in range(40):
            sentry.step(*batch)
    assert len(resized) == 1            # the rung fires ONCE
    assert sentry.stats["resizes"] == 1
    # ordering: the hook saw the full rollback ladder exhausted first
    assert resized[0]["rollbacks"] == 4
    assert resized[0]["clip_tightened"] >= 2
    # after the in-process resize the ladder restarted before aborting
    # (3 more rollbacks, then the exhausted ladder aborts directly)
    assert sentry.stats["rollbacks"] == 7


def test_sentry_resize_hook_declining_aborts():
    from distributed_pytorch_tpu.lm import LMTrainer
    from distributed_pytorch_tpu.utils.sentry import (
        SentryAbort, SentryConfig, TrainingSentry)

    faults.install(faults.FaultPlan(kind="nan_grad", step=2, count=99))
    tr = LMTrainer(_tiny_lm_cfg())
    sentry = TrainingSentry(
        tr, SentryConfig(checkpoint_every=100, skip_budget=1,
                         max_rollbacks=3),
        on_resize=lambda stats: False, log=_quiet)
    batch = _lm_batch(0, bs=2)
    with pytest.raises(SentryAbort):
        for _ in range(40):
            sentry.step(*batch)
    assert sentry.stats["resizes"] == 1
    assert sentry.stats["rollbacks"] == 4  # no second ladder


# -- the gang-level proof (slow lane) ----------------------------------------

@pytest.mark.slow
def test_gang_kill_shrink_resume_rejoin_grow(tmp_path, monkeypatch):
    """The acceptance gang: a fault plan kills rank 1 of 2 mid-training;
    the elastic agent shrinks the gang to 1 (within min_nodes), the
    survivor drains at a sync point and the shrunk generation resumes
    from the last-good checkpoint RESHARDED to the smaller world — its
    post-resume loss trajectory BITWISE-identical to a fresh 1-worker
    launch restored from the same checkpoint.  When the lost worker
    returns (generation 2, the crash plan is gen-gated off), the gang
    grows back; GangResult records both resize events, and the merged
    per-step losses track an uninterrupted full-size run (no example
    dropped or double-counted across the resizes).

    Members are single-process-jax workers whose mesh spans WORLD_SIZE
    local fake devices (see resize_worker.py: the exact layout a real
    gang writes, with bitwise-replica trajectories)."""
    import shutil

    worker = os.path.join(REPO, "tests", "workers", "resize_worker.py")
    steps = 12

    def run(nproc, ckpt, out, extra=None, elastic=None):
        out.mkdir(exist_ok=True)
        ckpt.mkdir(exist_ok=True)
        with monkeypatch.context() as m:
            m.delenv("FAULT_PLAN", raising=False)
            env = dict(
                PYTHONPATH=REPO + ":" + os.environ.get("PYTHONPATH", ""),
                TEST_DEVICES_PER_PROC="2", TEST_STEPS=str(steps),
                TEST_CKPT_EVERY="1", TEST_STEP_SLEEP="0.2",
                TEST_CKPT_DIR=str(ckpt), TEST_OUT_DIR=str(out))
            env.update(extra or {})
            for k, v in env.items():
                m.setenv(k, v)
            agent = LocalAgent([worker], nproc_per_node=nproc,
                               monitor_interval_s=0.05,
                               elastic=elastic, log=_quiet)
            box = {}
            t = threading.Thread(target=lambda: box.update(r=agent.run()))
            t.start()
            t.join(timeout=420)
            assert not t.is_alive(), "gang did not finish within 420s"
            return box["r"]

    # control A: uninterrupted full-size gang
    ra = run(2, tmp_path / "ck_a", tmp_path / "out_a")
    assert ra.returncode == 0, ra

    # the elastic run: injected crash on gang rank 1, generation 0 only.
    # Round 13: the gang ALSO streams unified telemetry — workers via
    # the TELEMETRY_DIR env contract, the (in-process, threaded) agent
    # via the test-process registry, exactly as launch.py main() wires
    # it — and the bitwise pins below double as the proof that
    # telemetry-on does not perturb the trajectory.
    from distributed_pytorch_tpu.utils import telemetry
    tel_dir = tmp_path / "telemetry"
    telemetry.enable(str(tel_dir), rank=-1, gen=0, label="agent")
    plan = faults.FaultPlan(kind="crash", step=4, rank=1, gen=0)
    try:
        re_ = run(2, tmp_path / "ck_e", tmp_path / "out_e",
                  extra={"FAULT_PLAN": plan.to_env(),
                         "TELEMETRY_DIR": str(tel_dir)},
                  elastic=ElasticConfig(
                      min_workers=1, max_workers=2,
                      heartbeat_timeout_s=300,
                      drain_grace_s=30, rejoin_delay_s=0.0,
                      grow_after_steps=3))
    finally:
        telemetry.disable()
    assert re_.returncode == 0, re_
    moves = [(e["kind"], e["from_size"], e["to_size"])
             for e in re_.resize_events]
    assert moves == [("shrink", 2, 1), ("grow", 1, 2)], re_.resize_events
    assert re_.injected_failures == 1  # the chaos crash was classified
    # the shrink drain (survivor) + the grow drain both flushed at a
    # sync point instead of needing SIGKILL
    assert re_.drain["drained"] >= 2, re_.drain

    g1 = np.load(tmp_path / "out_e" / "losses_gen1.npz")
    s1, l1 = int(g1["start"]), g1["losses"]
    assert int(g1["world"]) == 1 and len(l1) >= 3

    # THE bitwise pin: a fresh 1-worker gang restored from the SAME
    # checkpoint the shrunk generation resumed from
    ck_c = tmp_path / "ck_c"
    ck_c.mkdir()
    shutil.copytree(tmp_path / "ck_e" / f"ckpt_{s1}",
                    ck_c / f"ckpt_{s1}")
    rc = run(1, ck_c, tmp_path / "out_c",
             extra={"TEST_STEPS": str(s1 + len(l1))})
    assert rc.returncode == 0, rc
    c = np.load(tmp_path / "out_c" / "losses_gen0.npz")
    assert int(c["start"]) == s1
    np.testing.assert_array_equal(c["losses"], l1)  # bitwise

    # merged per-step losses vs the uninterrupted run: every step
    # covered exactly once post-merge, trajectories tracking (any
    # dropped/double-counted example would shift the curve)
    merged = {}
    for gen in (0, 1, 2):
        z = np.load(tmp_path / "out_e" / f"losses_gen{gen}.npz")
        for j, v in enumerate(z["losses"]):
            merged[int(z["start"]) + j] = v
    assert sorted(merged) == list(range(steps))
    a = np.load(tmp_path / "out_a" / "losses_gen0.npz")
    np.testing.assert_allclose(
        np.asarray([merged[s] for s in range(steps)]), a["losses"],
        rtol=1e-3, atol=1e-5)

    # round 13 acceptance: ONE merged Chrome trace from the 2-worker
    # elastic gang — valid trace JSON carrying spans/events from BOTH
    # gang ranks across the shrink -> grow, generation-tagged.
    trace = json.loads(json.dumps(telemetry.merge_chrome_trace(
        str(tel_dir))))
    evs = trace["traceEvents"]
    assert isinstance(evs, list) and evs
    data_pids = {e["pid"] for e in evs if e.get("ph") != "M"}
    assert {-1, 0, 1} <= data_pids, data_pids  # agent + both gang ranks
    spans = [e for e in evs if e.get("ph") == "X"]
    assert {e["pid"] for e in spans} >= {0, 1}, "spans from both ranks"
    for e in spans:
        assert "gen" in e["args"] and "dur" in e and "ts" in e
    gens = {e["args"]["gen"] for e in evs if "gen" in e.get("args", {})}
    assert {0, 1, 2} <= gens, gens  # pre-shrink, shrunk, re-grown
    resizes = [e for e in evs if e.get("name") == "gang_resize"]
    assert [e["args"]["kind"] for e in resizes] == ["shrink", "grow"]
    # the worker that honored the drain marked the boundary it left at
    assert any(e.get("name") == "worker_drain" for e in evs)
    # both ranks' train spans carry the per-step gauges next to them
    gauge_names = {e["name"] for e in evs if e.get("ph") == "C"}
    assert {"loss", "grad_norm", "param_norm"} <= gauge_names
