"""Consistency-checker tests (utils/debug.py, utils/tracing.py).

These pin the DP invariants the checkers enforce: replicated state must be
bitwise-identical across devices (what torch DDP guarantees by broadcast and
the reference by same-seed init + sync — SURVEY.md 2.3), compiled steps must
be deterministic, and desync/NaN states must be *detected*, not just avoided.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_pytorch_tpu.parallel import strategies as strat
from distributed_pytorch_tpu.parallel.mesh import make_mesh
from distributed_pytorch_tpu.train import TrainConfig, Trainer
from distributed_pytorch_tpu.utils import debug as dbg
from distributed_pytorch_tpu.utils.tracing import trace


pytestmark = pytest.mark.quick  # sub-2-min tier (tests/conftest.py)

def _replicated(mesh, value: np.ndarray) -> jax.Array:
    return jax.device_put(value, NamedSharding(mesh, P()))


def _desynced(mesh, value: np.ndarray) -> jax.Array:
    """A 'replicated'-sharded array whose device copies actually differ —
    the bug state replica_desync exists to catch."""
    sharding = NamedSharding(mesh, P())
    bufs = []
    for i, d in enumerate(mesh.devices.flat):
        v = value.copy()
        if i == len(mesh.devices.flat) - 1:
            v[0] += 1.0  # one replica drifted
        bufs.append(jax.device_put(v, d))
    return jax.make_array_from_single_device_arrays(
        value.shape, sharding, bufs)


def test_replica_desync_clean_and_dirty():
    mesh = make_mesh(4)
    good = _replicated(mesh, np.ones((8,), np.float32))
    bad = _desynced(mesh, np.ones((8,), np.float32))
    assert dbg.replica_desync({"w": good}) == []
    assert dbg.replica_desync({"w": good, "v": bad}) == ["['v']"]
    with pytest.raises(dbg.ConsistencyError, match="desynced"):
        dbg.assert_replicas_in_sync({"v": bad})


def test_replica_desync_skips_sharded_leaves():
    mesh = make_mesh(4)
    sharded = jax.device_put(np.arange(16, dtype=np.float32),
                             NamedSharding(mesh, P("data")))
    assert dbg.replica_desync({"x": sharded}) == []


def test_trainer_consistency_after_steps():
    mesh = make_mesh(4)
    t = Trainer(TrainConfig(strategy="ddp", batch_size=4), mesh=mesh)
    rng = np.random.default_rng(0)
    imgs = rng.integers(0, 256, (16, 32, 32, 3)).astype(np.uint8)
    labels = rng.integers(0, 10, 16).astype(np.int32)
    for _ in range(2):
        t.train_step(imgs, labels)
    t.check_consistency()  # replicated state stayed in sync through sync'd grads


def test_check_determinism_passes_for_pure_fn():
    @jax.jit
    def f(x):
        return {"y": x * 2.0, "z": jnp.sum(x)}

    dbg.check_determinism(f, jnp.arange(8.0))


def test_check_determinism_catches_impure_fn():
    state = {"n": 0}

    def impure(x):
        state["n"] += 1
        return x + state["n"]

    with pytest.raises(dbg.ConsistencyError, match="differs"):
        dbg.check_determinism(impure, jnp.zeros((4,)))


def test_assert_finite():
    dbg.assert_finite({"a": np.ones(3), "b": jnp.zeros(2)})
    with pytest.raises(dbg.ConsistencyError, match="non-finite"):
        dbg.assert_finite({"a": np.array([1.0, np.nan])})
    # integer leaves are ignored (no NaN concept)
    dbg.assert_finite({"i": np.array([1, 2, 3])})


def test_trace_writes_profile(tmp_path):
    with trace(str(tmp_path)):
        jnp.sum(jnp.arange(16.0)).block_until_ready()
    produced = list(tmp_path.rglob("*"))
    assert produced, "profiler wrote nothing"


# -- schedule inspector (round 8): proving comm/compute overlap on CPU ------

def _train_sched(strategy: str, overlap: bool, **cfg_kw):
    """(schedule, lowered HLO text) of the real compiled train step."""
    cfg = TrainConfig(strategy=strategy, batch_size=4, augment=False,
                      model="TINY", overlap=overlap, overlap_bucket_mb=0.02,
                      broadcast_buffers=False, **cfg_kw)
    # factored-axis strategies (hierarchical): the Trainer builds its own
    # ('dcn', 'ici') mesh from cfg.dcn_size
    factored = getattr(strat.get(strategy), "axes", None) is not None
    tr = Trainer(cfg, None if factored else make_mesh(4))
    rng = np.random.default_rng(0)
    images = rng.integers(0, 256, (1, 16, 32, 32, 3)).astype(np.uint8)
    labels = rng.integers(0, 10, (1, 16)).astype(np.int32)
    img, lbl = tr._stage(images, labels)
    args = tr._args(img, lbl)
    tr.precompile_steps(images, labels)
    return (dbg.op_schedule(tr._multi_fn, *args),
            tr._multi_fn.lower(*args).as_text())


def test_overlap_schedule_interleaves_collectives():
    """THE tentpole proof, no TPU needed: with overlap=True the compiled
    train step's program places data-axis collectives STRICTLY BETWEEN
    backward matmuls (>= 2 of them — one per non-final bucket), i.e. the
    latency-hiding scheduler has collectives to run while backward compute
    is still in flight."""
    sched, hlo = _train_sched("bucketed", overlap=True)
    stats = dbg.assert_overlap_schedule(sched, axes=("data",),
                                        min_interleaved=2)
    # the 0.02 MB cap packs TINY's ~160 KB of grads into several buckets,
    # each one collective, all but the last-fired mid-backward
    assert stats["total"] >= 4
    # and the lowered module agrees the collectives exist
    assert dbg.hlo_collective_counts(hlo)["total"] >= stats["total"]


def test_post_backward_schedule_pins_all_at_the_end():
    """The historical shape, pinned so the contrast is real: overlap=False
    places every data-axis collective AFTER the final matmul of the step
    (backward fully drained before the first byte moves)."""
    sched, _ = _train_sched("bucketed", overlap=False)
    stats = dbg.assert_post_backward_schedule(sched, axes=("data",))
    assert stats["total"] >= 4 and stats["interleaved"] == 0


def test_overlap_schedule_ddp_and_ring():
    """Interleaving holds for the per-leaf (ddp) and int8-ring (EF)
    overlap modes too — including ppermute-based collectives."""
    for name in ("ddp", "quantized_ring_ef"):
        sched, _ = _train_sched(name, overlap=True)
        dbg.assert_overlap_schedule(sched, axes=("data",),
                                    min_interleaved=2)


def test_per_axis_attribution_pins_dcn_vs_ici():
    """Per-axis collective attribution (round 9): on the factored
    ('dcn', 'ici') mesh the inspector splits wire traffic by link, so
    (a) the hierarchical strategy's cross-slice claim — |grads|/ici
    bytes over DCN, a fraction of the ICI traffic — is MEASURED, and
    (b) dcn-axis interleaving is pinned separately from ici: overlap
    places >= 2 dcn collectives strictly between backward matmuls,
    post-backward places none."""
    over_sched, _ = _train_sched("hierarchical", overlap=True)
    base_sched, _ = _train_sched("hierarchical", overlap=False)

    per_axis = dbg.per_axis_collective_stats(base_sched)
    assert set(per_axis) >= {"dcn", "ici"}, per_axis
    # the slow hop moves shard-sized payloads: strictly less than the
    # within-slice traffic (ici carries the full reduce-scatter/gather)
    assert 0 < per_axis["dcn"]["bytes_executed"] < \
        per_axis["ici"]["bytes_executed"]

    dbg.assert_overlap_schedule(over_sched, axes=("dcn",),
                                min_interleaved=2, min_bytes=65)
    dbg.assert_post_backward_schedule(base_sched, axes=("dcn",),
                                      min_bytes=65)
    # int8 dcn compression shrinks ONLY the slow hop (ici byte-identical)
    int8_sched, _ = _train_sched("hierarchical", overlap=False,
                                 dcn_compress="int8")
    pa8 = dbg.per_axis_collective_stats(int8_sched)
    assert pa8["dcn"]["bytes_executed"] * 2 < \
        per_axis["dcn"]["bytes_executed"]
    assert pa8["ici"]["bytes_executed"] == \
        per_axis["ici"]["bytes_executed"]


def test_inspector_sees_ring_wire_compression():
    """The inspector's byte accounting exposes the int8 ring's wire
    compression on the SAME model/step: its collective payload is a
    fraction of ddp's f32 payload (int8 + per-block scales vs full-width
    grads) — the compressed-collective claim as a program property."""
    ddp_sched, _ = _train_sched("ddp", overlap=False)
    ring_sched, _ = _train_sched("quantized_ring", overlap=False)
    ddp_stats = dbg.collective_stats(ddp_sched, axes=("data",))
    ring_stats = dbg.collective_stats(ring_sched, axes=("data",))
    assert ring_stats["bytes"] * 3 < ddp_stats["bytes"]
    # trip-weighted accounting: the ring's hops ride a scan, so executed
    # counts exceed the static schedule (2(n-1) hops per ring) while the
    # executed wire bytes still undercut ddp's f32 payload
    assert ring_stats["executions"] > ring_stats["total"]
    assert ring_stats["bytes_executed"] < ddp_stats["bytes_executed"]


def test_op_schedule_units():
    """Unit surface: kinds, axes filtering, byte accounting, and the HLO
    counter on a hand-built program."""
    from functools import partial

    from distributed_pytorch_tpu.utils.compat import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))

    def f(w, x):
        y = x @ w                      # compute
        s = jax.lax.psum(y, "data")    # collective, per-shard (1,8) f32
        return jnp.sum(s @ w)          # compute after the collective

    fn = shard_map(f, mesh=mesh, in_specs=(P(), P("data")), out_specs=P())
    w = jnp.ones((8, 8), jnp.float32)
    x = jnp.ones((4, 8), jnp.float32)
    sched = dbg.op_schedule(fn, w, x)
    kinds = [r["kind"] for r in sched]
    assert kinds == ["compute", "collective", "compute"]
    assert sched[1]["axes"] == ("data",)
    assert sched[1]["bytes"] == 1 * 8 * 4  # per-shard (1, 8) f32 operand
    assert sched[1]["trips"] == 1

    def scanned(w, x):
        def body(c, _):
            return c + jax.lax.psum(x @ w, "data"), None
        out, _ = jax.lax.scan(body, jnp.zeros_like(x), None, length=5)
        return out

    s2 = dbg.op_schedule(
        shard_map(scanned, mesh=mesh, in_specs=(P(), P("data")),
                  out_specs=P("data")), w, x)
    st2 = dbg.collective_stats(s2, axes=("data",))
    # the scan body's collective appears once statically, 5x dynamically
    assert st2["total"] == 1 and st2["executions"] == 5
    assert st2["bytes_executed"] == 5 * st2["bytes"]
    stats = dbg.collective_stats(sched, axes=("data",))
    assert stats == {"total": 1, "interleaved": 1, "tail": 0,
                     "bytes": 32, "compute": 2,
                     "executions": 1, "bytes_executed": 32}
    # axis filtering drops non-matching collectives
    assert dbg.collective_stats(sched, axes=("model",))["total"] == 0
    # the asserts raise the right way around
    dbg.assert_overlap_schedule(sched, min_interleaved=1)
    with pytest.raises(dbg.ConsistencyError, match="post|after|final"):
        dbg.assert_post_backward_schedule(sched)
    # per-axis attribution: one stats row per axis name, multi-axis
    # collectives counted toward EACH axis; min_bytes drops small ops
    assert dbg.per_axis_collective_stats(sched) == {"data": stats}
    assert dbg.collective_stats(sched, axes=("data",),
                                min_bytes=64)["total"] == 0
    synth = [{"kind": "collective", "prim": "psum",
              "axes": ("dcn", "ici"), "bytes": 8, "trips": 1}]
    per = dbg.per_axis_collective_stats(synth)
    assert per["dcn"]["total"] == 1 and per["ici"]["total"] == 1
    # HLO counter: definition sites only, references don't double-count
    txt = ('%all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %x), ...\n'
           '%add = f32[8]{0} add(f32[8]{0} %all-reduce.1, %y)\n'
           '%cp = f32[8]{0} collective-permute(f32[8]{0} %z)\n')
    counts = dbg.hlo_collective_counts(txt)
    assert counts["all-reduce"] == 1
    assert counts["collective-permute"] == 1
    assert counts["total"] == 2
