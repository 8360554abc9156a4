"""Multi-hop collective routing (round 20, parallel/routing.py): the
route grammar and its refusals, the hop-graph executor's bitwise pins
against the hand-built two-level paths, the hop-boundary EF invariant on
2- and 3-axis meshes, the re-quantization error curve across chained
compressed hops, the route chooser's matrix on the synthetic
uniform/wan_dcn/ici_dcn_wan profiles, the per-hop schedule-inspector
accounting, and the PROFILE_VERSION 3->4 recalibrate path."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from distributed_pytorch_tpu.parallel import autotune as at
from distributed_pytorch_tpu.parallel import routing
from distributed_pytorch_tpu.parallel import strategies as strat
from distributed_pytorch_tpu.utils import debug as dbg
from distributed_pytorch_tpu.utils.compat import shard_map

pytestmark = pytest.mark.routing


def _mesh2():
    """The trainer-shaped 2-level mesh: 2 slices x 4 chips."""
    return Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("dcn", "ici"))


def _mesh3():
    """A 3-tier mesh: 2 WAN sites x 2 slices x 2 chips."""
    return Mesh(np.array(jax.devices()[:8]).reshape(2, 2, 2),
                ("wan", "dcn", "ici"))


def _census(total_mb: float = 30.0) -> at.GradCensus:
    per = int(total_mb * 1024 * 1024 / 4 / 4)
    return at.GradCensus(tuple(
        at._SizedLeaf(s, np.dtype("float32"))
        for s in (per, 64, per, per, 128, per)))


# -- grammar + validation ---------------------------------------------------


@pytest.mark.quick
def test_hop_validation_refusals():
    """Malformed hops fail loudly at construction, not at trace time."""
    with pytest.raises(ValueError, match="kind"):
        routing.Hop("bcast", "dcn")
    with pytest.raises(ValueError, match="algorithm"):
        routing.Hop("rs", "ici", algorithm="ring")
    with pytest.raises(ValueError, match="ring exchange"):
        routing.Hop("rs", "ici", bits="int8")
    with pytest.raises(ValueError, match="ring"):
        routing.Hop("exchange", "dcn", bits="int4")  # psum is full-width
    with pytest.raises(ValueError, match="ef"):
        routing.Hop("exchange", "dcn", algorithm="ring", ef=True)


@pytest.mark.quick
def test_plan_validation_refusals():
    """Bracket discipline: ag must close the matching rs (LIFO), one
    rs/ag pair and one exchange per axis, no exchange inside its own
    open bracket."""
    rs, ag = routing.Hop("rs", "ici"), routing.Hop("ag", "ici")
    ex = routing.Hop("exchange", "dcn")
    with pytest.raises(ValueError):
        routing.HopPlan((ag,))  # ag with no open rs
    with pytest.raises(ValueError):
        routing.HopPlan((rs, routing.Hop("rs", "dcn"), ag,
                         routing.Hop("ag", "dcn")))  # crossed brackets
    with pytest.raises(ValueError):
        routing.HopPlan((rs, routing.Hop("exchange", "ici"), ag))
    with pytest.raises(ValueError):
        routing.HopPlan((rs, ex, ex, ag))  # two dcn exchanges
    with pytest.raises(ValueError):
        routing.HopPlan((rs, ag, rs, ag))  # two ici pairs
    # an exchange-free bracket is LEGAL: rs+ag IS the all-reduce
    routing.HopPlan((rs, ag)).validate()


@pytest.mark.quick
def test_route_grammar_roundtrip():
    """describe() and parse_route() are inverses over every constructor
    family, and mesh_axes() orders tiers slow -> fast."""
    plans = [
        routing.flat_route("data"),
        routing.flat_route("data", bits="int8", ef=True),
        routing.two_level_route("ici", "dcn", compress="int4"),
        routing.two_level_route("ici", None, compress=None),
        routing.two_level_route("ici", "dcn", compress=None,
                                rs_algorithm="slice"),
        routing.nested_route(("ici", "dcn", "wan"), compress="int4"),
        routing.sequential_route("ici", ("dcn", "wan"),
                                 {"dcn": "int4", "wan": "int4"}),
    ]
    for p in plans:
        assert routing.parse_route(p.describe()) == p
    assert (routing.two_level_route("ici", "dcn", compress="int4")
            .describe() == "ici:rs → dcn:ring[int4+ef] → ici:ag")
    # ascii arrows work too (CLI-friendly)
    assert (routing.parse_route("ici:rs -> dcn:psum -> ici:ag")
            == routing.two_level_route("ici", "dcn", compress=None))
    assert routing.two_level_route("ici", "dcn",
                                   compress=None).mesh_axes() == ("dcn",
                                                                  "ici")
    assert (routing.sequential_route("ici", ("dcn", "wan"), {})
            .mesh_axes() == ("wan", "dcn", "ici"))
    assert (routing.nested_route(("ici", "dcn", "wan"))
            .mesh_axes() == ("wan", "dcn", "ici"))
    for bad in ("ici:bogus", "ici", "ici:ring[int3]", ""):
        with pytest.raises(ValueError):
            routing.parse_route(bad)


@pytest.mark.quick
def test_enumerate_routes_families():
    """Over 3 axes the enumerator emits the flat joint exchange, every
    2-level split at every precision, and the nested + sequential
    3-level shapes — all structurally valid."""
    routes = routing.enumerate_routes(("ici", "dcn", "wan"))
    assert len(routes) == 15
    descs = [r.describe() for r in routes]
    assert "ici+dcn+wan:psum" in descs
    assert "ici:rs → dcn+wan:psum → ici:ag" in descs
    assert ("ici:rs → dcn:rs → wan:ring[int4+ef] → dcn:ag → ici:ag"
            in descs)
    assert ("ici:rs → dcn:ring[int4+ef] → wan:ring[int4+ef] → ici:ag"
            in descs)
    for r in routes:
        r.validate()
    # 2 axes: the flat joint psum + the one 2-level split at each of
    # {plain, int8, int4} exchange precisions
    assert [r.describe() for r in
            routing.enumerate_routes(("ici", "dcn"))] == [
        "ici+dcn:psum",
        "ici:rs → dcn:psum → ici:ag",
        "ici:rs → dcn:ring[int8+ef] → ici:ag",
        "ici:rs → dcn:ring[int4+ef] → ici:ag",
    ]


# -- executor: bitwise pins vs the hand-built paths -------------------------


def test_execute_two_level_bitwise_vs_hand_built_lax():
    """The routed executor's 2-level plan is BITWISE the hand-built
    pad -> psum_scatter(ici) -> psum(dcn) -> all-gather sequence, with
    an identical jaxpr collective census."""
    mesh = _mesh2()
    plan = routing.two_level_route("ici", "dcn", compress=None)
    g = jnp.asarray(np.random.default_rng(0).standard_normal(
        (97, 5)).astype(np.float32))

    def routed(x):
        synced, _ = routing.execute(plan, [x], scale=1.0 / 8)
        return synced[0]

    def hand(x):
        flat = x.ravel().astype(jnp.float32)
        padded = jnp.pad(flat, (0, (-flat.size) % 4))
        shard = lax.psum_scatter(padded, "ici", scatter_dimension=0,
                                 tiled=True)
        shard = lax.psum(shard, "dcn")
        full = strat._all_gather_inv(shard, "ici", axis=0, tiled=True)
        return ((full[:flat.size] * (1.0 / 8))
                .reshape(x.shape).astype(x.dtype))

    outs = {}
    for name, fn in (("routed", routed), ("hand", hand)):
        sm = jax.jit(shard_map(fn, mesh=mesh, in_specs=P(),
                               out_specs=P(), check_vma=False))
        outs[name] = np.asarray(sm(g))
        sched = dbg.op_schedule(sm, g)
        outs[name + "_census"] = [
            (r["prim"], r["axes"], r["bytes"], r["trips"])
            for r in sched if r["kind"] == "collective"]
    assert np.array_equal(outs["routed"], outs["hand"])
    assert outs["routed_census"] == outs["hand_census"]


def test_routed_sync_bitwise_vs_hierarchical_strategy():
    """RoutedSync executing the 2-level int8 route is bitwise the
    hand-built Hierarchical strategy with dcn_compress='int8' — synced
    grads AND the EF residual carry."""
    mesh = _mesh2()
    rng = np.random.default_rng(1)
    grads = {"a": rng.standard_normal((300, 7)).astype(np.float32),
             "b": rng.standard_normal((65,)).astype(np.float32)}
    n_by_axis = {"dcn": 2, "ici": 4}

    hier = strat.Hierarchical()
    hier.set_dcn("int8", 2)
    rs = routing.RoutedSync(
        routing.two_level_route("ici", "dcn", compress="int8"),
        n_by_axis=n_by_axis)
    leaves = jax.tree.leaves(grads)
    assert (rs.state_segments(leaves, 8)
            == hier.state_segments(leaves, 8))
    res0 = jnp.zeros((sum(rs.state_segments(leaves, 8)),), jnp.float32)

    def run_h(g, r):
        return hier(g, ("dcn", "ici"), r)

    def run_r(g, r):
        return rs(g, ("dcn", "ici"), r)

    outs = {}
    for name, fn in (("hier", run_h), ("routed", run_r)):
        sm = jax.jit(shard_map(fn, mesh=mesh, in_specs=(P(), P()),
                               out_specs=(P(), P()), check_vma=False))
        synced, new_r = sm(grads, res0)
        outs[name] = (jax.tree.map(np.asarray, synced),
                      np.asarray(new_r))
    assert np.array_equal(outs["hier"][0]["a"], outs["routed"][0]["a"])
    assert np.array_equal(outs["hier"][0]["b"], outs["routed"][0]["b"])
    assert np.array_equal(outs["hier"][1], outs["routed"][1])


def test_hop_boundary_ef_invariant_2axis():
    """delivered + psum(residual rows) == exact sum at the (single)
    compressed hop boundary of the 2-level int8 route."""
    mesh = _mesh2()
    plan = routing.two_level_route("ici", "dcn", compress="int8")
    rng = np.random.default_rng(2)
    scale = 3.0
    g = (rng.standard_normal(2000) * scale).astype(np.float32)
    res0 = np.zeros(
        (8, routing.residual_len(plan, g.size, {"dcn": 2, "ici": 4})),
        np.float32)

    def run(x, r):
        synced, new_r = routing.execute(plan, [x], residuals=[r[0]])
        # exact reference: rs over ici then full-precision dcn sum
        padded = jnp.pad(x, (0, (-x.size) % 4))
        shard = lax.psum_scatter(padded, "ici", scatter_dimension=0,
                                 tiled=True)
        exact_shard = lax.psum(shard, "dcn")
        # delivered shard = my slice of the gathered sum
        me = lax.axis_index("ici")
        sh = padded.size // 4
        full = jnp.pad(synced[0], (0, (-x.size) % 4))
        mine = lax.dynamic_slice(full, (me * sh,), (sh,))
        dropped = lax.psum(new_r[0].reshape(2, -1), "dcn").ravel()[:sh]
        err = jnp.max(jnp.abs(mine + dropped - exact_shard))
        return synced[0], new_r[0][None], err[None]

    spec = P(("dcn", "ici"))
    f = jax.jit(shard_map(run, mesh=mesh, in_specs=(P(), spec),
                          out_specs=(P(), spec, spec), check_vma=False))
    _, _, err = f(jnp.asarray(g), jnp.asarray(res0))
    assert float(jnp.max(err)) < 1e-4 * scale * 8


def test_hop_boundary_ef_invariant_3axis():
    """The chained sequential route keeps the EF ledger exact at EVERY
    hop boundary: delivered + psum_wan(res_wan) +
    psum_wan(psum_dcn(res_dcn)) == the exact 8-way sum."""
    mesh = _mesh3()
    sizes = {"wan": 2, "dcn": 2, "ici": 2}
    plan = routing.sequential_route("ici", ("dcn", "wan"),
                                    {"dcn": "int4", "wan": "int4"})
    rng = np.random.default_rng(3)
    scale = 2.0
    g = (rng.standard_normal(1500) * scale).astype(np.float32)
    seg = []
    for i, h in enumerate(plan.hops):
        if h.kind == "exchange" and h.ef:
            e = routing._elems_after(plan, i, g.size, sizes)
            n = sizes[h.axis]
            seg.append(n * strat.QuantizedRing()._chunk(e, n))
    assert sum(seg) == routing.residual_len(plan, g.size, sizes)
    res0 = np.zeros((8, sum(seg)), np.float32)

    def run(x, r):
        synced, new_r = routing.execute(
            plan, [x], residuals=[r[0, :seg[0]], r[0, seg[0]:]])
        padded = jnp.pad(x, (0, (-x.size) % 2))
        shard = lax.psum_scatter(padded, "ici", scatter_dimension=0,
                                 tiled=True)
        exact_shard = lax.psum(lax.psum(shard, "dcn"), "wan")
        me = lax.axis_index("ici")
        sh = padded.size // 2
        full = jnp.pad(synced[0], (0, (-x.size) % 2))
        mine = lax.dynamic_slice(full, (me * sh,), (sh,))
        drop_d = lax.psum(lax.psum(new_r[0].reshape(2, -1), "dcn"),
                          "wan").ravel()[:sh]
        drop_w = lax.psum(new_r[1].reshape(2, -1), "wan").ravel()[:sh]
        err = jnp.max(jnp.abs(mine + drop_d + drop_w - exact_shard))
        return synced[0], err[None]

    spec = P(("wan", "dcn", "ici"))
    f = jax.jit(shard_map(run, mesh=mesh, in_specs=(P(), spec),
                          out_specs=(P(), spec), check_vma=False))
    _, err = f(jnp.asarray(g), jnp.asarray(res0))
    assert float(jnp.max(err)) < 1e-4 * scale * 8


def test_requantization_error_curve():
    """Noise accumulates one term per compressed hop: the 2-compressed-
    hop sequential route's one-shot error exceeds the single compressed
    hop's, but stays the same order (EF catches the rest next step)."""
    mesh = _mesh3()
    sizes = {"wan": 2, "dcn": 2, "ici": 2}
    rng = np.random.default_rng(4)
    # per-device DISTINCT rows — replicated inputs re-quantize exactly
    # (the doubled sum lands back on the doubled grid) and would hide
    # the second hop's noise
    g = rng.standard_normal((8, 3000)).astype(np.float32)

    def one_shot_err(plan):
        seg = []
        for i, h in enumerate(plan.hops):
            if h.kind == "exchange" and h.ef:
                e = routing._elems_after(plan, i, g.shape[1], sizes)
                n = sizes[h.axis]
                seg.append(n * strat.QuantizedRing()._chunk(e, n))
        offs = np.concatenate(([0], np.cumsum(seg))).astype(int)

        def run(x, r):
            synced, _ = routing.execute(
                plan, [x[0]],
                residuals=[r[0, offs[i]:offs[i + 1]]
                           for i in range(len(seg))])
            exact = lax.psum(lax.psum(lax.psum(x[0], "ici"), "dcn"),
                             "wan")
            return (jnp.linalg.norm(synced[0] - exact)
                    / jnp.linalg.norm(exact))[None]

        spec = P(("wan", "dcn", "ici"))
        f = jax.jit(shard_map(run, mesh=mesh,
                              in_specs=(spec, spec), out_specs=spec,
                              check_vma=False))
        return float(f(jnp.asarray(g),
                       jnp.zeros((8, sum(seg)), jnp.float32))[0])

    err1 = one_shot_err(routing.sequential_route(
        "ici", ("dcn", "wan"), {"dcn": "int4"}))
    err2 = one_shot_err(routing.sequential_route(
        "ici", ("dcn", "wan"), {"dcn": "int4", "wan": "int4"}))
    assert 0 < err1 < err2 < 10 * err1
    assert err2 < 0.3  # one-shot int4 noise stays bounded even chained


# -- the route chooser ------------------------------------------------------


@pytest.mark.quick
def test_choose_sync_plan_matrix():
    """The chooser's decisions on the fixed synthetic profiles: flat on
    uniform, the 2-level int4 route on wan_dcn, and the compressed
    sequential 3-hop on the 3-tier ici_dcn_wan — each cheaper than the
    flat and 2-level alternatives it beat."""
    census = _census()
    plan = at.choose_sync_plan(
        census, at.synthetic_profile("uniform", {"dcn": 2, "ici": 4}))
    assert plan.route == "dcn+ici:psum"
    plan = at.choose_sync_plan(
        census, at.synthetic_profile("wan_dcn", {"dcn": 2, "ici": 4}))
    assert plan.route == "ici:rs → dcn:ring[int4+ef] → ici:ag"
    prof3 = at.synthetic_profile("ici_dcn_wan",
                                 {"wan": 2, "dcn": 2, "ici": 2})
    plan = at.choose_sync_plan(census, prof3)
    assert plan.route == ("ici:rs → dcn:ring[int4+ef] → "
                          "wan:ring[int4+ef] → ici:ag")
    assert plan.strategy == "routed"
    assert plan.dcn_compress == "int4"
    assert plan.per_hop and len(plan.per_hop) == 4
    assert "route" in plan.summary() and "bytes_by_hop" in plan.summary()
    assert "route:" in plan.table()
    # the acceptance pin: cheaper than the flat and EVERY 2-level shape
    best_by_family = {"flat": np.inf, "two": np.inf}
    for r in routing.enumerate_routes(("ici", "dcn", "wan")):
        ms = min(at.price_route(r, census, prof3,
                                bucket_mb=mb)["ms_total"]
                 for mb in at.BUCKET_LADDER_MB)
        if len(r.hops) == 1:
            best_by_family["flat"] = min(best_by_family["flat"], ms)
        elif len(r.hops) == 3:
            best_by_family["two"] = min(best_by_family["two"], ms)
    assert plan.predicted_ms < best_by_family["flat"]
    assert plan.predicted_ms < best_by_family["two"]


@pytest.mark.quick
def test_named_plans_carry_route_labels():
    """The legacy choosers' 2-level plans now carry their route string
    (the hand-built paths ARE routes through the compiler)."""
    census = _census()
    prof = at.synthetic_profile("fast_ici_slow_dcn",
                                {"dcn": 2, "ici": 4})
    plan = at.choose_train_plan(census, prof, dcn_size=2)
    assert plan.strategy == "hierarchical"
    assert plan.route.startswith("ici:rs → dcn:")
    assert plan.route.endswith("→ ici:ag")


# -- per-hop inspector accounting -------------------------------------------


def test_per_hop_accounting_matches_priced_plan():
    """plan_bytes_vs_schedule(by_hop=True) pairs every hop's priced
    bytes with the traced program's per-(axis, prim) rows at ratio 1.0
    on the 3-axis mesh — routed predictions stay checkable hop by
    hop."""
    mesh = _mesh3()
    sizes = {"wan": 2, "dcn": 2, "ici": 2}
    plan = routing.sequential_route("ici", ("dcn", "wan"),
                                    {"dcn": "int4", "wan": "int4"})
    total = 4096
    seg = []
    for i, h in enumerate(plan.hops):
        if h.kind == "exchange" and h.ef:
            e = routing._elems_after(plan, i, total, sizes)
            n = sizes[h.axis]
            seg.append(n * strat.QuantizedRing()._chunk(e, n))

    def step(x, r1, r2):
        synced, new_r = routing.execute(plan, [x], residuals=[r1, r2])
        return synced[0], new_r[0], new_r[1]

    sm = shard_map(step, mesh=mesh, in_specs=(P(), P(), P()),
                   out_specs=(P(), P(), P()), check_vma=False)
    args = (jnp.zeros((total,), jnp.float32),
            jnp.zeros((seg[0],), jnp.float32),
            jnp.zeros((seg[1],), jnp.float32))
    sched = dbg.op_schedule(sm, *args)

    per_hop = dbg.per_hop_collective_stats(sched)
    assert {k.split(":")[0] for k in per_hop} == {"ici", "dcn", "wan"}
    # per-hop rows partition the per-axis attribution
    per_axis = dbg.per_axis_collective_stats(sched)
    for axis in ("ici", "dcn", "wan"):
        assert sum(v["bytes_executed"] for k, v in per_hop.items()
                   if k.startswith(axis + ":")) \
            == per_axis[axis]["bytes_executed"]

    prof = at.synthetic_profile("ici_dcn_wan", sizes)
    priced = at.price_route(plan, at.grad_census(
        [jax.ShapeDtypeStruct((total,), jnp.float32)]), prof,
        bucket_mb=25.0)
    sp = at.SyncPlan(
        strategy="routed", bucket_mb=25.0, dcn_compress="int4",
        dcn_size=2, overlap=False, predicted_ms=priced["ms_total"],
        per_axis=tuple(priced["per_axis"]),
        profile_source=prof.source, census_bytes=total * 4,
        route=plan.describe(), per_hop=tuple(priced["per_hop"]))
    rows = dbg.plan_bytes_vs_schedule(sp, sched, by_hop=True,
                                      min_bytes=0)
    assert set(rows) == {h.describe() for h in plan.hops}
    for row in rows.values():
        assert row["ratio"] == pytest.approx(1.0)
    # amortized per-hop view agrees with the raw stats
    am = dbg.amortized_axis_bytes([(sched, 1)], 1, by_hop=True)
    assert am == {k: float(v["bytes_executed"])
                  for k, v in per_hop.items()}


# -- profile version + concurrent calibration -------------------------------


@pytest.mark.quick
def test_profile_version_3_cache_recalibrates(tmp_path):
    """A cached version-3 profile (pre-routing) misses loudly-silently:
    load_profile returns None so the caller recalibrates — the standing
    missing-key back-compat contract, regression-tested at the 3->4
    bump."""
    axes = {"dcn": 2, "ici": 4}
    prof = at.synthetic_profile("uniform", axes)
    path = at.save_profile(prof, str(tmp_path))
    assert at.load_profile("synthetic", axes, str(tmp_path)) is not None
    with open(path) as f:
        d = json.load(f)
    d["version"] = 3
    d.pop("concurrent_delta_pct", None)
    with open(path, "w") as f:
        json.dump(d, f)
    assert at.load_profile("synthetic", axes, str(tmp_path)) is None


@pytest.mark.quick
def test_profile_json_roundtrip_concurrent_fields():
    """concurrent_delta_pct (round 20) survives the JSON round-trip and
    defaults to None on profiles written before it existed."""
    prof = at.synthetic_profile("uniform", {"data": 8})
    assert prof.concurrent_delta_pct is None
    d = prof.to_json()
    assert "concurrent_delta_pct" in d
    d["concurrent_delta_pct"] = 12.5
    p2 = at.TopologyProfile.from_json(d)
    assert p2.concurrent_delta_pct == 12.5
    d.pop("concurrent_delta_pct")
    assert at.TopologyProfile.from_json(d).concurrent_delta_pct is None


def test_calibrate_concurrent_smoke():
    """calibrate(concurrent=True) runs the ladders against the
    background matmul stream and records the busy-vs-idle quantize
    delta."""
    from distributed_pytorch_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(8, axis_names=("dcn", "ici"), axis_shape=(2, 4))
    prof = at.calibrate(mesh, payload_bytes=(64 << 10,),
                        algos=("psum",), inner=1, reps=1,
                        concurrent=True)
    assert prof.source == "calibrated:concurrent"
    assert isinstance(prof.concurrent_delta_pct, float)
    cc = prof.measured["concurrent"]
    assert set(cc) == {"quantize_s_per_byte_idle",
                       "quantize_s_per_byte_busy", "delta_pct"}
    assert cc["quantize_s_per_byte_idle"] > 0
    assert cc["quantize_s_per_byte_busy"] > 0
    # round-trips like every other measured field
    p2 = at.TopologyProfile.from_json(prof.to_json())
    assert p2.concurrent_delta_pct == prof.concurrent_delta_pct


# -- RoutedSync state + trainer config contracts ----------------------------


@pytest.mark.quick
def test_residual_len_matches_legacy_sizing():
    """residual_len under the 2-level routes equals the hand-built
    strategies' EF sizing (Hierarchical buckets; the LM fsdp ring)."""
    total, n_dcn, n_ici = 123457, 2, 4
    ring = strat.QuantizedRing()
    plan = routing.two_level_route("ici", "dcn", compress="int8")
    assert (routing.residual_len(plan, total,
                                 {"dcn": n_dcn, "ici": n_ici})
            == n_dcn * ring._chunk(-(-total // n_ici), n_dcn))
    flat = routing.flat_route("dcn", bits="int8", ef=True)
    assert (routing.residual_len(flat, total, {"dcn": n_dcn})
            == n_dcn * ring._chunk(total, n_dcn))
    # plain routes carry no state
    assert routing.residual_len(
        routing.two_level_route("ici", "dcn", compress=None), total,
        {"dcn": n_dcn, "ici": n_ici}) == 0


@pytest.mark.quick
def test_trainer_routed_config_refusals():
    """The trainer's routed surface fails loudly on half-configured or
    out-of-topology routes."""
    from distributed_pytorch_tpu.train import TrainConfig, Trainer

    with pytest.raises(ValueError, match="sync_route"):
        Trainer(TrainConfig(strategy="routed"))
    with pytest.raises(ValueError, match="strategy='routed'|routed"):
        Trainer(TrainConfig(strategy="ddp",
                            sync_route="ici:rs → dcn:psum → ici:ag"))
    with pytest.raises(ValueError, match="dcn_compress"):
        Trainer(TrainConfig(strategy="routed", dcn_compress="int8",
                            sync_route="ici:rs → dcn:psum → ici:ag"))
    with pytest.raises(ValueError, match="two tiers"):
        Trainer(TrainConfig(
            strategy="routed",
            sync_route="ici:rs → dcn:ring[int4+ef] → "
                       "wan:ring[int4+ef] → ici:ag"))


@pytest.mark.quick
def test_routed_sync_needs_sizes_for_state():
    """Sizing EF state from a bare replica count requires the bound
    per-axis map — a loud error, not a silent misfactoring."""
    rs = routing.RoutedSync(
        routing.two_level_route("ici", "dcn", compress="int8"))
    leaves = [strat.SizedLeaf(1000, np.float32)]
    with pytest.raises(ValueError, match="n_by_axis"):
        rs.state_segments(leaves, 8)
    rs.n_by_axis = {"dcn": 2, "ici": 4}
    assert rs.state_segments(leaves, 8) == [
        2 * strat.QuantizedRing()._chunk(-(-1000 // 4), 2)]
