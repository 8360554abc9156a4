"""Per-strategy gradient-sync cost on the virtual 8-device CPU mesh.

The reference's whole pedagogical point is the strategy comparison — its only
benchmark is the per-iteration wall-time print in each main_*.py (reference
main_all_reduce.py:52-62; SURVEY.md section 6).  This script generates that
table for every strategy the framework ships, with the reference's own metric
discipline: compile excluded (AOT precompile stands in for the iter-0
exclusion), per-iteration wall time averaged over a window.

Run: XLA_FLAGS=--xla_force_host_platform_device_count=8 \
     JAX_PLATFORMS=cpu python scripts/bench_strategies.py

Absolute CPU-mesh times are meaningless for TPU; the *ordering* and the
overhead-vs-fused-ddp deltas are the result (a virtual mesh still executes
every collective's real schedule — 68 sequential rank-0 crossings for
gather_scatter vs one fused reduction for ddp).

Prints one JSON line per strategy plus a markdown table on stderr.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

N_DEV = 8

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={N_DEV}").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributed_pytorch_tpu.parallel import autotune  # noqa: E402
from distributed_pytorch_tpu.parallel import routing  # noqa: E402
from distributed_pytorch_tpu.parallel import strategies as strat  # noqa: E402
from distributed_pytorch_tpu.parallel.mesh import make_mesh  # noqa: E402
from distributed_pytorch_tpu.train import TrainConfig, Trainer  # noqa: E402
from distributed_pytorch_tpu.utils import debug as dbg  # noqa: E402

PER_DEV_BATCH = int(os.environ.get("BENCH_PER_DEV_BATCH", "4"))
WINDOW = int(os.environ.get("BENCH_WINDOW", "20"))
OVERLAP = os.environ.get("BENCH_STRATEGY_OVERLAP", "0") == "1"

# Round 11: calibrate this CPU mesh's links ONCE per topology (flat and
# factored) so every row gains a predicted_ms column from the autotune
# cost model — the same table then holds the model's prediction NEXT TO
# the inspector's measured per-axis bytes, making the cost model
# auditable from one command.  (CPU-mesh absolute times are rough; the
# point is that the BYTE predictions are exact and the ms ordering is
# sane.)
_PROFILES: dict[str, autotune.TopologyProfile] = {}


def _profile_for(dcn_size: int) -> autotune.TopologyProfile:
    key = "factored" if dcn_size > 1 else "flat"
    if key not in _PROFILES:
        axes = autotune.train_topology_axes(dcn_size, N_DEV)
        mesh = make_mesh(N_DEV, axis_names=tuple(axes),
                         axis_shape=tuple(axes.values()))
        _PROFILES[key] = autotune.calibrate(
            mesh, payload_bytes=(256 << 10, 1 << 20, 4 << 20),
            inner=2, reps=2)
    return _PROFILES[key]


_CENSUS: list = []


def _census() -> autotune.GradCensus:
    if not _CENSUS:  # one abstract init for all rows (pure fn of model)
        import jax

        from distributed_pytorch_tpu.models import vgg
        _CENSUS.append(autotune.grad_census(jax.eval_shape(
            lambda k: vgg.init(k, "VGG11")[0], jax.random.key(0))))
    return _CENSUS[0]


def predicted_ms(name: str, compress: str | None, overlap: bool,
                 factored: bool,
                 bucket_mb: float | None = None) -> float | None:
    """The autotune cost model's predicted SYNC ms/step for this row
    (None where the model has no formula — e.g. the pipeline row)."""
    prof = _profile_for(2 if factored else 1)
    pred = autotune.predict_named(
        name, _census(), prof, dcn_compress=compress, overlap=overlap,
        bucket_mb=bucket_mb if bucket_mb is not None
        else strat.BUCKET_CAP_MB)
    if pred is None:
        return None
    return pred["ms_exposed" if overlap else "ms_total"]


def comm_profile(tr: Trainer, images, labels) -> dict:
    """Per-step wire accounting from the traced/lowered program
    (utils/debug.py schedule inspector, round 8) — the reproducible
    source of BASELINE.md's strategy cost table.

    ``comm_bytes_per_step`` / ``collective_count`` are PER-EXECUTION
    (scan-trip-weighted): the ring strategies' ppermute hops ride
    ``lax.scan``, so the static jaxpr holds each hop once but the wire
    sees it n-1 times — static counts would under-report the rings by
    ~(n-1)x against the psum strategies.  The static program-shape
    numbers ride along as ``*_static``/``collectives_interleaved``.
    Tracing (make_jaxpr) and lowering (no backend compile) happen once
    each; the executable itself was already compiled by the warm-up
    step."""
    img, lbl = tr._stage(images[None], labels[None])
    args = tr._args(img, lbl)
    if tr._multi_fn is None:  # build the program without compiling it
        from distributed_pytorch_tpu.train import make_multi_step
        tr._multi_fn = make_multi_step(tr.cfg, tr.strategy, tr.mesh,
                                       fault_sig=tr._fault_sig)
    sched = dbg.op_schedule(tr._multi_fn, *args)
    stats = dbg.collective_stats(sched)
    per_axis = dbg.per_axis_collective_stats(sched)
    hlo = dbg.hlo_collective_counts(tr._multi_fn.lower(*args).as_text())
    return {"comm_bytes_per_step": stats["bytes_executed"],
            "collective_count": stats["executions"],
            "comm_bytes_static": stats["bytes"],
            "collective_count_static": stats["total"],
            "collectives_interleaved": stats["interleaved"],
            # per-AXIS attribution (round 9): dcn vs ici (vs data) bytes
            # and collective counts, so the factored strategies' cross-
            # slice claim (two_level_psum: |grads|/ici over DCN) is
            # MEASURED per link, not asserted.  A multi-axis collective
            # counts toward each axis it runs over.
            "comm_bytes_by_axis": {a: s["bytes_executed"]
                                   for a, s in per_axis.items()},
            "collective_count_by_axis": {a: s["executions"]
                                         for a, s in per_axis.items()},
            "hlo_collective_count": hlo.pop("total"),
            "hlo_collectives": hlo}


def bench_strategy(name: str) -> tuple[float, dict, bool]:
    """(mean seconds/step over WINDOW iterations, comm profile, overlap
    used); compile + warm-up excluded (the reference's iter-0-excluded
    window, main.py:43-48).  ``hierarchical_int8`` / ``hierarchical_int4``
    are the hierarchical strategy with the int8- / int4-compressed DCN
    hop (TrainConfig.dcn_compress); the per-axis MB column shows the
    compression on the wire: ~9.23 MB f32 -> ~2.34 MB int8 -> ~1.17 MB
    int4 over DCN for VGG11, inspector-measured."""
    compress = None
    route = None
    if name in ("hierarchical_int8", "hierarchical_int4"):
        name, compress = "hierarchical", name.rsplit("_", 1)[1]
    if name == "routed_int4":
        # the routed row (round 20): the 2-level int4 route through the
        # declarative hop-graph executor (parallel/routing.py) — the
        # SAME wire program as the hierarchical_int4 row above it,
        # declared as a route string instead of hand-built
        name = "routed"
        route = "ici:rs → dcn:ring[int4+ef] → ici:ag"
    if name == "routed":
        factored = True
        cfg = TrainConfig(strategy="routed", sync_route=route,
                          batch_size=PER_DEV_BATCH, augment=False,
                          dcn_size=2)
        tr = Trainer(cfg)
        overlap = False
    elif name == "auto":
        # the autotuner row (round 11): resolve from the CPU-calibrated
        # factored profile, then measure the resolved plan like any row
        factored = True
        cfg = TrainConfig(strategy="auto", batch_size=PER_DEV_BATCH,
                          augment=False, dcn_size=2,
                          autotune_profile=_profile_for(2))
        tr = Trainer(cfg)
        overlap = tr.cfg.overlap
    else:
        # Factored-axis strategies (hierarchical): mesh=None lets the
        # Trainer build the ('dcn', 'ici') mesh from cfg.dcn_size — one
        # recipe.
        factored = getattr(strat.get(name), "axes", None) is not None
        mesh = make_mesh(N_DEV) if (name != "none"
                                    and not factored) else None
        overlap = (OVERLAP and name in strat.overlap_capable()
                   and name != "none")
        cfg = TrainConfig(strategy=name, batch_size=PER_DEV_BATCH,
                          augment=False, overlap=overlap,
                          dcn_compress=compress)
        tr = Trainer(cfg, mesh=mesh)
    n = tr.n_replicas
    rng = np.random.default_rng(0)
    images = rng.integers(
        0, 256, (PER_DEV_BATCH * n, 32, 32, 3)).astype(np.uint8)
    labels = rng.integers(0, 10, PER_DEV_BATCH * n).astype(np.int32)

    tr.train_step(images, labels)  # compile + warm-up (excluded)
    comm = comm_profile(tr, images, labels)
    # the cost-model column (round 11): predicted sync ms for the row's
    # ACTUAL resolved strategy/knobs, from the CPU-calibrated profile
    comm["predicted_ms"] = predicted_ms(
        tr.cfg.strategy, tr.cfg.dcn_compress, tr.cfg.overlap,
        getattr(tr.strategy, "axes", None) is not None,
        tr.cfg.overlap_bucket_mb)
    if name == "auto":
        comm["resolved"] = tr.sync_plan.summary()
    if name == "routed":
        # price the route with the hop-graph cost model and record the
        # route string next to the row's measured per-axis bytes
        priced = autotune.price_route(
            routing.parse_route(route), _census(), _profile_for(2))
        comm["predicted_ms"] = priced["ms_total"]
        comm["route"] = route
    times = []
    for _ in range(WINDOW):
        t0 = time.perf_counter()
        loss = tr.train_step(images, labels)
        float(loss)  # value fetch: the honest end-of-step barrier
        times.append(time.perf_counter() - t0)
    return sum(times) / len(times), comm, overlap


def bench_lm_fsdp_q8gather() -> tuple[float, dict, bool]:
    """The quantized ZeRO-3 all-gather row (round 16): a small LM with
    ``fsdp=True, fsdp_gather_dtype="int8"`` on the flat 8-way data mesh,
    same window discipline as the strategy rows.  The wire profile's
    'data'-axis bytes carry the int8 weight gathers (~1/4 the f32
    gather width plus the per-row scale rows) next to the cotangent
    psum_scatters; s/step is not comparable to the VGG rows (different
    model/loss) — the per-axis bytes are the content."""
    from distributed_pytorch_tpu.lm import LMTrainConfig, LMTrainer
    from distributed_pytorch_tpu.models import transformer as tfm

    model = tfm.TransformerConfig(vocab_size=256, d_model=128, n_layers=4,
                                  n_heads=2, head_dim=64, d_ff=256)
    cfg = LMTrainConfig(model=model, dp=N_DEV, fsdp=True,
                        fsdp_gather_dtype="int8", compute_dtype=None)
    tr = LMTrainer(cfg)
    rng = np.random.default_rng(0)
    batch, seq = 2 * N_DEV, 128
    toks = rng.integers(0, 256, (batch, seq)).astype(np.int32)
    tgts = np.roll(toks, -1, axis=1).astype(np.int32)

    tr.train_step(toks, tgts)  # compile + warm-up (excluded)
    sched = dbg.op_schedule(tr.step_fn, tr.params, tr.opt_state, toks, tgts)
    stats = dbg.collective_stats(sched)
    per_axis = dbg.per_axis_collective_stats(sched)
    comm = {"comm_bytes_per_step": stats["bytes_executed"],
            "collective_count": stats["executions"],
            "comm_bytes_static": stats["bytes"],
            "collective_count_static": stats["total"],
            "collectives_interleaved": stats["interleaved"],
            "comm_bytes_by_axis": {a: s["bytes_executed"]
                                   for a, s in per_axis.items()},
            "collective_count_by_axis": {a: s["executions"]
                                         for a, s in per_axis.items()},
            "hlo_collective_count": None, "hlo_collectives": None,
            # no cost-model formula for the fsdp gather row (the LM
            # chooser owns dcn compression, not the ZeRO-3 gathers)
            "predicted_ms": None}
    times = []
    for _ in range(WINDOW):
        t0 = time.perf_counter()
        loss = tr.train_step(toks, tgts)
        float(loss)  # value fetch: the honest end-of-step barrier
        times.append(time.perf_counter() - t0)
    return sum(times) / len(times), comm, False


def bench_lm_remat_selective() -> tuple[float, dict, bool]:
    """The activation-memory row (round 17): the same small LM as the
    q8gather row with ``remat="selective"`` + ``loss_impl="chunked"`` on
    the flat 8-way data mesh, same window discipline.  Its extra column
    is the accountant cross-check the table exists for: the pure-shape
    predicted activation footprint (utils/memacct) NEXT TO the exact
    jaxpr saved-residual census of the same per-device loss — the two
    must agree within 10% (test-pinned), and both should be far under
    the no-remat footprint.  s/step is not comparable to the VGG rows
    (different model/loss); the byte columns are the content."""
    from distributed_pytorch_tpu.lm import LMTrainConfig, LMTrainer
    from distributed_pytorch_tpu.models import transformer as tfm
    from distributed_pytorch_tpu.ops import losses
    from distributed_pytorch_tpu.utils import memacct

    model = tfm.TransformerConfig(vocab_size=256, d_model=128, n_layers=4,
                                  n_heads=2, head_dim=64, d_ff=256)
    cfg = LMTrainConfig(model=model, dp=N_DEV, remat="selective",
                        loss_impl="chunked", compute_dtype=None)
    tr = LMTrainer(cfg)
    rng = np.random.default_rng(0)
    batch, seq = 2 * N_DEV, 128
    toks = rng.integers(0, 256, (batch, seq)).astype(np.int32)
    tgts = np.roll(toks, -1, axis=1).astype(np.int32)

    tr.train_step(toks, tgts)  # compile + warm-up (excluded)
    sched = dbg.op_schedule(tr.step_fn, tr.params, tr.opt_state, toks, tgts)
    stats = dbg.collective_stats(sched)
    per_axis = dbg.per_axis_collective_stats(sched)
    # the predicted-vs-census pair, at the PER-DEVICE shapes the mesh
    # actually runs (batch/dp rows of the global batch)
    per_dev = batch // N_DEV
    predicted = memacct.predict_activation_bytes(
        model, batch=per_dev, seq=seq, remat="selective",
        loss_impl="chunked")
    toks1, tgts1 = toks[:per_dev], tgts[:per_dev]

    def pure_loss(params):
        head = lambda h, e: losses.head_loss(  # noqa: E731
            h, e, tgts1, loss_impl="chunked")
        ce, n = tfm.apply(params, toks1, cfg=model, attn_impl="flash",
                          remat="selective", head_fn=head)
        return ce / n

    census = memacct.saved_residual_census(
        pure_loss, tfm.init(jax.random.PRNGKey(0), model))["bytes"]
    comm = {"comm_bytes_per_step": stats["bytes_executed"],
            "collective_count": stats["executions"],
            "comm_bytes_static": stats["bytes"],
            "collective_count_static": stats["total"],
            "collectives_interleaved": stats["interleaved"],
            "comm_bytes_by_axis": {a: s["bytes_executed"]
                                   for a, s in per_axis.items()},
            "collective_count_by_axis": {a: s["executions"]
                                         for a, s in per_axis.items()},
            "hlo_collective_count": None, "hlo_collectives": None,
            "predicted_ms": None,  # sync cost model: remat changes none
            "activation_bytes_predicted": int(predicted),
            "activation_bytes_census": int(census)}
    times = []
    for _ in range(WINDOW):
        t0 = time.perf_counter()
        loss = tr.train_step(toks, tgts)
        float(loss)  # value fetch: the honest end-of-step barrier
        times.append(time.perf_counter() - t0)
    return sum(times) / len(times), comm, False


def bench_moe_a2a_int8() -> tuple[float, dict, bool]:
    """The quantized expert-dispatch row (round 21): the small LM as a
    Switch MoE (n_experts=4) over a dedicated ep=2 expert axis with the
    chooser-picked int8 all_to_all wire (``expert:a2a@int8``), same
    window discipline as the LM rows.  Its extra columns are the
    cost-model cross-check the row exists for: ``choose_moe_plan``'s
    capacity-census byte prediction (E*C rows of d+4 wire bytes, times
    a2a_per_step=4 per MoE layer) NEXT TO the schedule inspector's
    measured all_to_all bytes — the same arithmetic prices the route
    and counts the compiled program, so the pair must agree exactly
    (the ratio-1.0 pin lives in tests/test_a2a.py).  s/step is not
    comparable to the VGG rows (different model/loss); the byte
    columns are the content."""
    from distributed_pytorch_tpu.lm import LMTrainConfig, LMTrainer
    from distributed_pytorch_tpu.models import transformer as tfm

    model = tfm.TransformerConfig(vocab_size=256, d_model=128, n_layers=4,
                                  n_heads=2, head_dim=64, d_ff=256,
                                  n_experts=4)
    batch, seq = 2 * N_DEV, 128
    # the capacity census prices PER-DEVICE tokens (the batch shards
    # over the joint (data, expert) axes — N_DEV ways)
    local_tokens = batch * seq // N_DEV
    # the expert link is slow relative to quantization throughput, so
    # the chooser takes the int8 wire (the matrix tests/test_a2a.py pins)
    profile = autotune.synthetic_profile("slow", {"expert": 2})
    plan = autotune.choose_moe_plan(
        profile, axis="expert", tokens=local_tokens,
        d_model=model.d_model, n_experts=model.n_experts,
        capacity_factor=model.capacity_factor, top_k=model.moe_top_k)
    assert plan.dispatch_bits == "int8", plan.summary()
    model = dataclasses.replace(model,
                                moe_dispatch_bits=plan.dispatch_bits)
    cfg = LMTrainConfig(model=model, dp=N_DEV // 2, ep=2,
                        compute_dtype=None)
    tr = LMTrainer(cfg)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 256, (batch, seq)).astype(np.int32)
    tgts = np.roll(toks, -1, axis=1).astype(np.int32)

    tr.train_step(toks, tgts)  # compile + warm-up (excluded)
    sched = dbg.op_schedule(tr.step_fn, tr.params, tr.opt_state, toks, tgts)
    stats = dbg.collective_stats(sched)
    per_axis = dbg.per_axis_collective_stats(sched)
    n_moe = sum(model.is_moe_layer(i) for i in range(model.n_layers))
    measured_a2a = int(sum(r["bytes"] for r in sched
                           if r["kind"] == "collective"
                           and r["prim"] == "all_to_all"))
    comm = {"comm_bytes_per_step": stats["bytes_executed"],
            "collective_count": stats["executions"],
            "comm_bytes_static": stats["bytes"],
            "collective_count_static": stats["total"],
            "collectives_interleaved": stats["interleaved"],
            "comm_bytes_by_axis": {a: s["bytes_executed"]
                                   for a, s in per_axis.items()},
            "collective_count_by_axis": {a: s["executions"]
                                         for a, s in per_axis.items()},
            "hlo_collective_count": None, "hlo_collectives": None,
            # the MoE pricer's per-layer ms, scaled to the program's
            # MoE layer count (moe_every=2 -> 2 of 4 layers)
            "predicted_ms": plan.predicted_ms * n_moe,
            "route": plan.route,
            "a2a_bytes_predicted": plan.dispatch_bytes * n_moe,
            "a2a_bytes_measured": measured_a2a}
    times = []
    for _ in range(WINDOW):
        t0 = time.perf_counter()
        loss = tr.train_step(toks, tgts)
        float(loss)  # value fetch: the honest end-of-step barrier
        times.append(time.perf_counter() - t0)
    return sum(times) / len(times), comm, False


def bench_hierarchical_localsgd(
        sync_every: int = 4) -> tuple[float, dict, bool]:
    """The communication-sparse row (round 18): the hierarchical
    strategy with ``sync_every=4`` local-SGD windows on the dcn_size=2
    factored mesh — H local optimizer steps between DCN exchanges, ICI
    synced every step.  Dispatches must be window-aligned (train_step's
    K=1 path is unavailable under windows), so the timed unit is one
    H-step ``train_steps`` dispatch divided by H; s/step IS comparable
    to the VGG rows above.  The dcn/ici MB column is AMORTIZED over the
    window (utils/debug.amortized_axis_bytes): dcn ~1/H of the plain
    hierarchical row, ici unchanged — the round-18 schedule claim,
    measured here per link."""
    from distributed_pytorch_tpu.train import make_multi_step

    cfg = TrainConfig(strategy="hierarchical", dcn_size=2,
                      sync_every=sync_every, max_sync_every=sync_every,
                      steps_per_loop=sync_every,
                      batch_size=PER_DEV_BATCH, augment=False)
    tr = Trainer(cfg)  # builds the ('dcn', 'ici') mesh itself
    n = tr.n_replicas
    rng = np.random.default_rng(0)
    images = rng.integers(
        0, 256,
        (sync_every, PER_DEV_BATCH * n, 32, 32, 3)).astype(np.uint8)
    labels = rng.integers(
        0, 10, (sync_every, PER_DEV_BATCH * n)).astype(np.int32)

    tr.train_steps(images, labels)  # compile + warm-up (excluded)
    img, lbl = tr._stage(images, labels)
    args = tr._args(img, lbl)
    if tr._multi_fn is None:
        tr._multi_fn = make_multi_step(tr.cfg, tr.strategy, tr.mesh,
                                       fault_sig=tr._fault_sig)
    sched = dbg.op_schedule(tr._multi_fn, *args)
    stats = dbg.collective_stats(sched)
    per_axis = dbg.per_axis_collective_stats(sched)
    hlo = dbg.hlo_collective_counts(tr._multi_fn.lower(*args).as_text())
    comm = {"comm_bytes_per_step": stats["bytes_executed"] / sync_every,
            "collective_count": stats["executions"],
            "comm_bytes_static": stats["bytes"],
            "collective_count_static": stats["total"],
            "collectives_interleaved": stats["interleaved"],
            # per-axis bytes amortized per step over the H-step window
            "comm_bytes_by_axis": dbg.amortized_axis_bytes(
                [(sched, 1)], sync_every),
            "collective_count_by_axis": {a: s["executions"]
                                         for a, s in per_axis.items()},
            "hlo_collective_count": hlo.pop("total"),
            "hlo_collectives": hlo,
            # the amortized interval pricing lives in the autotuner's
            # SyncPlan (its sync_every dimension), not predict_named
            "predicted_ms": None,
            "sync_every": sync_every}
    times = []
    for _ in range(WINDOW):
        t0 = time.perf_counter()
        losses = tr.train_steps(images, labels)
        float(losses[-1])  # value fetch: the honest end-of-step barrier
        times.append((time.perf_counter() - t0) / sync_every)
    return sum(times) / len(times), comm, False


def bench_wan_diloco(sync_every: int = 4) -> tuple[float, dict, bool]:
    """The DiLoCo row (round 22): the hierarchical local-SGD window of
    the row above with the Nesterov OUTER optimizer applied to the
    averaged window delta at each boundary — same factored mesh, same
    amortized per-axis wire accounting, so the dcn/ici MB column must
    MATCH ``hierarchical_localsgd`` at equal H (outer momentum rides
    the anchor update, not the exchange; the wire is identical).  The
    s/step delta vs that row prices the outer step itself (one
    O(params) momentum update per window).  s/step IS comparable to
    the VGG rows above."""
    from distributed_pytorch_tpu.train import make_multi_step

    cfg = TrainConfig(strategy="hierarchical", dcn_size=2,
                      sync_every=sync_every, max_sync_every=sync_every,
                      outer_opt="nesterov", outer_momentum=0.9,
                      steps_per_loop=sync_every,
                      batch_size=PER_DEV_BATCH, augment=False)
    tr = Trainer(cfg)
    n = tr.n_replicas
    rng = np.random.default_rng(0)
    images = rng.integers(
        0, 256,
        (sync_every, PER_DEV_BATCH * n, 32, 32, 3)).astype(np.uint8)
    labels = rng.integers(
        0, 10, (sync_every, PER_DEV_BATCH * n)).astype(np.int32)

    tr.train_steps(images, labels)  # compile + warm-up (excluded)
    img, lbl = tr._stage(images, labels)
    args = tr._args(img, lbl)
    if tr._multi_fn is None:
        tr._multi_fn = make_multi_step(tr.cfg, tr.strategy, tr.mesh,
                                       fault_sig=tr._fault_sig)
    sched = dbg.op_schedule(tr._multi_fn, *args)
    stats = dbg.collective_stats(sched)
    per_axis = dbg.per_axis_collective_stats(sched)
    hlo = dbg.hlo_collective_counts(tr._multi_fn.lower(*args).as_text())
    comm = {"comm_bytes_per_step": stats["bytes_executed"] / sync_every,
            "collective_count": stats["executions"],
            "comm_bytes_static": stats["bytes"],
            "collective_count_static": stats["total"],
            "collectives_interleaved": stats["interleaved"],
            "comm_bytes_by_axis": dbg.amortized_axis_bytes(
                [(sched, 1)], sync_every),
            "collective_count_by_axis": {a: s["executions"]
                                         for a, s in per_axis.items()},
            "hlo_collective_count": hlo.pop("total"),
            "hlo_collectives": hlo,
            "predicted_ms": None,
            "sync_every": sync_every,
            "outer_opt": "nesterov"}
    times = []
    for _ in range(WINDOW):
        t0 = time.perf_counter()
        losses = tr.train_steps(images, labels)
        float(losses[-1])  # value fetch: the honest end-of-step barrier
        times.append((time.perf_counter() - t0) / sync_every)
    return sum(times) / len(times), comm, False


def main() -> None:
    names = ["none", "ddp", "bucketed", "hierarchical", "hierarchical_int8",
             "hierarchical_int4", "routed_int4", "all_reduce",
             "gather_scatter_symmetric",
             "gather_scatter", "quantized", "quantized_ring",
             "quantized_ring_ef", "auto"]
    results: dict[str, float] = {}
    comms: dict[str, dict] = {}
    for name in names:
        t, comm, overlap = bench_strategy(name)
        results[name], comms[name] = t, comm
        print(json.dumps({"strategy": name, "sec_per_step": round(t, 4),
                          "window": WINDOW,
                          "per_dev_batch": PER_DEV_BATCH,
                          "overlap": overlap,
                          **comm}), flush=True)
    # the communication-sparse row (round 18): hierarchical with
    # sync_every=4 local-SGD windows — per-axis bytes amortized over
    # the window; s/step stays comparable to the VGG rows above
    t, comm, _ = bench_hierarchical_localsgd()
    names.append("hierarchical_localsgd")
    results["hierarchical_localsgd"] = t
    comms["hierarchical_localsgd"] = comm
    print(json.dumps({"strategy": "hierarchical_localsgd",
                      "sec_per_step": round(t, 4), "window": WINDOW,
                      "per_dev_batch": PER_DEV_BATCH, "overlap": False,
                      **comm}), flush=True)
    # the DiLoCo row (round 22): the same window with the Nesterov
    # outer optimizer at the boundary — wire identical to the row
    # above, the s/step delta prices the outer step
    t, comm, _ = bench_wan_diloco()
    names.append("wan_diloco")
    results["wan_diloco"] = t
    comms["wan_diloco"] = comm
    print(json.dumps({"strategy": "wan_diloco",
                      "sec_per_step": round(t, 4), "window": WINDOW,
                      "per_dev_batch": PER_DEV_BATCH, "overlap": False,
                      **comm}), flush=True)
    # the quantized ZeRO-3 gather row (round 16): int8 weight
    # all-gathers on the wire: an LM model, so it joins the table for
    # its per-axis columns, not the vs-ddp ratio
    t, comm, _ = bench_lm_fsdp_q8gather()
    names.append("lm_fsdp_q8gather")
    results["lm_fsdp_q8gather"], comms["lm_fsdp_q8gather"] = t, comm
    print(json.dumps({"strategy": "lm_fsdp_q8gather",
                      "sec_per_step": round(t, 4), "window": WINDOW,
                      "per_dev_batch": PER_DEV_BATCH, "overlap": False,
                      **comm}), flush=True)
    # the activation-memory row (round 17): selective remat + chunked
    # CE, with the accountant's predicted bytes next to the exact jaxpr
    # census — the cross-check column, same LM caveat as above
    t, comm, _ = bench_lm_remat_selective()
    names.append("lm_remat_selective")
    results["lm_remat_selective"], comms["lm_remat_selective"] = t, comm
    print(json.dumps({"strategy": "lm_remat_selective",
                      "sec_per_step": round(t, 4), "window": WINDOW,
                      "per_dev_batch": PER_DEV_BATCH, "overlap": False,
                      **comm}), flush=True)
    # the quantized expert-dispatch row (round 21): chooser-picked
    # expert:a2a@int8 wire on the ep=2 axis, with choose_moe_plan's
    # capacity-census byte prediction next to the inspector's measured
    # all_to_all bytes — same LM caveat as above
    t, comm, _ = bench_moe_a2a_int8()
    names.append("moe_a2a_int8")
    results["moe_a2a_int8"], comms["moe_a2a_int8"] = t, comm
    print(json.dumps({"strategy": "moe_a2a_int8",
                      "sec_per_step": round(t, 4), "window": WINDOW,
                      "per_dev_batch": PER_DEV_BATCH, "overlap": False,
                      **comm}), flush=True)

    def axis_mb(c: dict) -> str:
        """dcn/ici MB column for the factored strategies, '-' otherwise."""
        by_axis = c["comm_bytes_by_axis"]
        if "dcn" in by_axis:
            return (f"{by_axis['dcn'] / 1e6:.2f}/"
                    f"{by_axis.get('ici', 0) / 1e6:.2f}")
        if "expert" in by_axis:  # the MoE row: expert all_to_all bytes
            return f"ep {by_axis['expert'] / 1e6:.2f}"
        return "-"

    def act_mb(c: dict) -> str:
        """Predicted/census activation MB — the memory row only."""
        if "activation_bytes_predicted" not in c:
            return "-"
        return (f"{c['activation_bytes_predicted'] / 1e6:.2f}/"
                f"{c['activation_bytes_census'] / 1e6:.2f}")

    ddp = results["ddp"]
    print("\n| Strategy | s/step | vs ddp | predicted sync ms | "
          "comm MB/step | dcn/ici MB | act MB pred/census | "
          "collectives (interleaved) | HLO collectives |",
          file=sys.stderr)
    print("|---|---|---|---|---|---|---|---|---|", file=sys.stderr)
    for name in names:
        c = comms[name]
        hlo = c["hlo_collective_count"]
        pred = c.get("predicted_ms")
        print(f"| {name} | {results[name]:.3f} | "
              f"{results[name] / ddp:.2f}x | "
              f"{f'{pred:.3f}' if pred is not None else '-'} | "
              f"{c['comm_bytes_per_step'] / 1e6:.2f} | "
              f"{axis_mb(c)} | {act_mb(c)} | "
              f"{c['collective_count']} ({c['collectives_interleaved']}) | "
              f"{hlo if hlo is not None else '-'} |", file=sys.stderr)
    if "auto" in comms and "resolved" in comms["auto"]:
        print(f"\nauto resolved: {comms['auto']['resolved']}",
              file=sys.stderr)
    if "moe_a2a_int8" in comms:
        c = comms["moe_a2a_int8"]
        print(f"moe_a2a_int8 ({c['route']}) a2a bytes "
              f"predicted/measured: {c['a2a_bytes_predicted']}/"
              f"{c['a2a_bytes_measured']}", file=sys.stderr)


if __name__ == "__main__":
    main()
