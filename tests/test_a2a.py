"""Expert all-to-all as a first-class routed collective (round 21).

The a2a lane: the ``expert:a2a@f32|int8|int4`` hop grammar and its
refusals, the routed executor's bitwise + collective-census identity
with the hand-built dispatch/combine it replaced, the quantized wire's
<= 0.30x byte contract with its flip-rate and loss-curve gates, the
capacity-chunked compute-overlapped combine, the ``choose_moe_plan``
matrix, the PROFILE_VERSION 4->5 recalibrate path, the per-hop
inspector ratio pins, and the LM routed surface
(``LMTrainConfig(sync_route=...)`` / ``lm_cli --sync-route``)."""

import dataclasses
import json
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from distributed_pytorch_tpu.ops import moe
from distributed_pytorch_tpu.parallel import autotune as at
from distributed_pytorch_tpu.parallel import routing
from distributed_pytorch_tpu.utils import debug as dbg
from distributed_pytorch_tpu.utils.compat import shard_map

pytestmark = pytest.mark.a2a

E, D, F, TL, N = 8, 64, 128, 64, 4

SPECS = {"router": P(), "w_gate": P("model"), "w_up": P("model"),
         "w_down": P("model")}


def _mesh4():
    return Mesh(np.array(jax.devices()[:N]), ("model",))


def _cap(t=TL, cf=2.0, top_k=1):
    # moe_apply's capacity census: C = min(max(1, ceil(T*k*cf/E)), T)
    import math
    return min(max(1, math.ceil(t * top_k * cf / E)), t)


def _setup():
    key = jax.random.key(0)
    params = moe.moe_init(key, D, F, E)
    x = jax.random.normal(jax.random.fold_in(key, 9), (N * TL, D))
    return params, x


def _ep_fn(mesh, **kw):
    def ep(params, x):
        out, aux = moe.moe_apply(params, x, n_experts=E, axis="model", **kw)
        return out, jax.lax.pmean(aux, "model")
    return jax.jit(shard_map(ep, mesh=mesh, in_specs=(SPECS, P("model")),
                             out_specs=(P("model"), P())))


def _a2a_census(sched):
    return [(r["prim"], r["axes"], r["bytes"], r["trips"])
            for r in sched if r["kind"] == "collective"
            and r["prim"] == "all_to_all"]


# -- grammar ----------------------------------------------------------------


def test_a2a_grammar_roundtrip():
    """parse_route and describe are inverses on every a2a wire width,
    and the hop carries the alltoall algorithm default."""
    for bits in ("f32", "int8", "int4"):
        route = f"expert:a2a@{bits}"
        plan = routing.parse_route(route)
        assert plan.describe() == route
        (hop,) = plan.hops
        assert hop.kind == "a2a" and hop.bits == bits
        assert hop.algorithm == "alltoall" and not hop.ef
    # a2a composes with the gradient-sync families in ONE plan string
    plan = routing.parse_route(
        "expert:a2a@int8 → data:rs → dcn:psum → data:ag")
    assert plan.describe() == (
        "expert:a2a@int8 → data:rs → dcn:psum → data:ag")


def test_a2a_grammar_refusals():
    """The a2a hop is an expert-dispatch collective: only the 'expert'
    tier, never inside an rs/ag bracket, no EF ledger, known widths."""
    with pytest.raises(ValueError, match="expert"):
        routing.parse_route("dcn:a2a@int8")  # non-expert axis
    with pytest.raises(ValueError, match="a2a"):
        # inside an open rs...ag bracket (scatter-width context)
        routing.parse_route("data:rs → expert:a2a@f32 → data:ag")
    with pytest.raises(ValueError, match="ledger"):
        routing.Hop("a2a", "expert", bits="int8", ef=True)
    with pytest.raises(ValueError, match="bits"):
        routing.parse_route("expert:a2a@int2")
    with pytest.raises(ValueError, match="two a2a hops"):
        routing.parse_route("expert:a2a@f32 → expert:a2a@int8")
    with pytest.raises(ValueError, match="alltoall"):
        routing.Hop("a2a", "expert", algorithm="ring")
    # the gradient-bucket pricer refuses a2a hops: they are activation
    # collectives, priced by choose_moe_plan's capacity census
    prof = at.synthetic_profile("uniform", {"expert": 2})
    census = at.grad_census(jax.eval_shape(
        lambda: {"w": jnp.zeros((512, 512), jnp.float32)}))
    with pytest.raises(ValueError, match="choose_moe_plan"):
        at.price_route(routing.parse_route("expert:a2a@int8"),
                       census, prof)


# -- routed executor: bitwise + census vs hand-built ------------------------


def test_execute_a2a_f32_bitwise_vs_hand_built():
    """execute_a2a at f32 is BITWISE the hand-built reshape ->
    all_to_all -> moveaxis sequence moe_apply used to inline, with an
    identical jaxpr collective census — both directions."""
    mesh = _mesh4()
    cap = 16
    hop = routing.Hop("a2a", "expert")
    xd = jnp.asarray(np.random.default_rng(0).standard_normal(
        (E, cap, D)).astype(np.float32))
    xc = jnp.asarray(np.random.default_rng(1).standard_normal(
        (E // N, N * cap, D)).astype(np.float32))

    def routed_d(v):
        return routing.execute_a2a(hop, v, direction="dispatch",
                                   axis="model")

    def hand_d(v):
        n = lax.axis_size("model")
        v = lax.all_to_all(v.reshape(n, E // n, cap, D), "model",
                           split_axis=0, concat_axis=0, tiled=False)
        return jnp.moveaxis(v, 0, 1).reshape(E // n, n * cap, D)

    def routed_c(v):
        return routing.execute_a2a(hop, v, direction="combine",
                                   axis="model")

    def hand_c(v):
        n = lax.axis_size("model")
        v = lax.all_to_all(
            jnp.moveaxis(v.reshape(E // n, n, cap, D), 1, 0), "model",
            split_axis=0, concat_axis=0, tiled=False)
        return v.reshape(E, cap, D)

    for arg, pair in ((xd, (routed_d, hand_d)), (xc, (routed_c, hand_c))):
        outs = {}
        for name, fn in zip(("routed", "hand"), pair):
            sm = jax.jit(shard_map(fn, mesh=mesh, in_specs=P(),
                                   out_specs=P(), check_vma=False))
            outs[name] = np.asarray(sm(arg))
            outs[name + "_census"] = _a2a_census(dbg.op_schedule(sm, arg))
        assert np.array_equal(outs["routed"], outs["hand"])
        assert outs["routed_census"] == outs["hand_census"]
        assert len(outs["routed_census"]) == 1  # ONE exchange, no extras


def test_moe_f32_census_is_two_a2a():
    """The routed f32 MoE program is exactly two all_to_alls (dispatch +
    combine) at the capacity census's payload — no extra collectives
    rode in with the refactor."""
    params, x = _setup()
    f = _ep_fn(_mesh4())
    sched = dbg.op_schedule(f, params, x)
    census = _a2a_census(sched)
    cap = _cap()
    assert len(census) == 2
    for prim, axes, nbytes, trips in census:
        assert axes == ("model",)
        assert nbytes == E * cap * D * 4
        assert trips == 1


# -- quantized wire ---------------------------------------------------------


def _a2a_bytes(sched):
    return sum(r["bytes"] for r in sched if r["kind"] == "collective"
               and r["prim"] == "all_to_all")


def test_quantized_dispatch_wire_contract():
    """int8 dispatch moves <= 0.30x the f32 wire bytes (payload + the
    bitcast f32 scale rows on the SAME exchange: (d+4)/4d rows); int4
    halves the payload again.  Still exactly two all_to_alls — the
    scales never get their own collective."""
    params, x = _setup()
    mesh = _mesh4()
    cap = _cap()
    scheds = {}
    for bits in ("f32", "int8", "int4"):
        f = _ep_fn(mesh, dispatch_bits=bits)
        scheds[bits] = dbg.op_schedule(f, params, x)
        assert len(_a2a_census(scheds[bits])) == 2
    f32b = _a2a_bytes(scheds["f32"])
    assert f32b == 2 * E * cap * D * 4
    assert _a2a_bytes(scheds["int8"]) == 2 * E * cap * (D + 4)
    assert _a2a_bytes(scheds["int8"]) / f32b <= 0.30
    assert _a2a_bytes(scheds["int4"]) == 2 * E * cap * (D // 2 + 4)
    assert _a2a_bytes(scheds["int4"]) / f32b <= 0.16


def test_quantized_dispatch_values_close():
    """int8 dispatch perturbs the routed tokens only at rowwise-quant
    resolution: outputs stay close to f32, and dropped-token rows (the
    zero rows of the combine) are IDENTICAL."""
    params, x = _setup()
    mesh = _mesh4()
    ref = np.asarray(_ep_fn(mesh)(params, x)[0])
    q = np.asarray(_ep_fn(mesh, dispatch_bits="int8")(params, x)[0])
    np.testing.assert_allclose(q, ref, atol=0.12, rtol=0.12)
    np.testing.assert_array_equal(np.all(ref == 0.0, axis=-1),
                                  np.all(q == 0.0, axis=-1))


def test_quantized_dispatch_gradients_flow():
    """The custom_vjp wire carries gradients: the backward all_to_alls
    are compressed too, and the int8 gradient tracks f32 closely
    (straight-through quant-dequant, rowwise scales)."""
    params, x = _setup()
    mesh = _mesh4()

    def grads(bits):
        f = _ep_fn(mesh, dispatch_bits=bits)
        g = jax.grad(lambda p: jnp.sum(jnp.sin(f(p, x)[0])))(params)
        return np.concatenate([np.asarray(v).ravel()
                               for v in jax.tree.leaves(g)])

    g32, g8 = grads("f32"), grads("int8")
    assert np.all(np.isfinite(g8)) and np.abs(g8).max() > 0
    cos = float(np.dot(g32, g8)
                / (np.linalg.norm(g32) * np.linalg.norm(g8)))
    assert cos > 0.99, cos
    # the backward wire is quantized as well: trace the grad program
    # w.r.t. params AND activations (an LM's dispatch input is a live
    # activation, so its transpose exchange is in the train step)
    f = _ep_fn(mesh, dispatch_bits="int8")
    gfn = jax.jit(lambda p, xx: jax.grad(
        lambda q, xq: jnp.sum(jnp.sin(f(q, xq)[0])),
        argnums=(0, 1))(p, xx))
    cap = _cap()
    census = _a2a_census(dbg.op_schedule(gfn, params, x))
    assert len(census) == 4  # dispatch/combine forward + transposes
    assert all(nbytes == E * cap * (D + 4) for _, _, nbytes, _ in census)


def test_quantized_dispatch_flip_rate_and_loss_band():
    """The round-16 gate applied to dispatch quantization: A/B-train the
    MoE layer from identical init with f32 vs int8 dispatch — the two
    runs' loss curves stay in a tight band, and the trained routers
    agree on >= 98% of held-out tokens (flip rate <= 0.02)."""
    params0, x = _setup()
    mesh = _mesh4()
    key = jax.random.fold_in(jax.random.key(0), 77)
    w = jax.random.normal(key, (D, D)) / np.sqrt(D)
    y = jnp.tanh(x @ w)

    def train(bits, steps=30, lr=0.2):
        f = _ep_fn(mesh, dispatch_bits=bits)

        @jax.jit
        def step(p):
            def loss(q):
                return jnp.mean((f(q, x)[0] - y) ** 2)
            l, g = jax.value_and_grad(loss)(p)
            return jax.tree.map(lambda a, b: a - lr * b, p, g), l

        p, losses = params0, []
        for _ in range(steps):
            p, l = step(p)
            losses.append(float(l))
        return p, losses

    p32, l32 = train("f32")
    p8, l8 = train("int8")
    assert l32[-1] < 0.95 * l32[0]  # both actually trained
    assert l8[-1] < 0.95 * l8[0]
    band = 0.05 * l32[0]
    assert max(abs(a - b) for a, b in zip(l32, l8)) < band, (l32, l8)
    top32 = np.asarray(jnp.argmax(x @ p32["router"], axis=-1))
    top8 = np.asarray(jnp.argmax(x @ p8["router"], axis=-1))
    flip = float((top32 != top8).mean())
    assert flip <= 0.02, flip


# -- compute-overlapped chunked combine -------------------------------------


def test_chunked_overlap_interleaves_and_matches():
    """a2a_chunks=2 slices the capacity dim so chunk k's combine sits
    STRICTLY BETWEEN expert matmuls (the overlap window the schedule
    inspector pins); the unchunked program has no such interior
    exchange.  Values: chunks=1 is bitwise the unchunked program, and
    f32 chunking is bitwise invariant (rowwise ops, exact concat)."""
    params, x = _setup()
    mesh = _mesh4()
    base = np.asarray(_ep_fn(mesh)(params, x)[0])
    np.testing.assert_array_equal(
        np.asarray(_ep_fn(mesh, a2a_chunks=1)(params, x)[0]), base)
    np.testing.assert_array_equal(
        np.asarray(_ep_fn(mesh, a2a_chunks=2)(params, x)[0]), base)

    def interior_exchanges(sched):
        prims = [r["prim"] for r in sched
                 if r["prim"] in ("dot_general", "all_to_all")]
        i0 = prims.index("all_to_all")  # chunk-0 dispatch: FFN dots after
        inner = prims[i0 + 1:]
        return sum(
            1 for i, p in enumerate(inner) if p == "all_to_all"
            and "dot_general" in inner[:i]
            and "dot_general" in inner[i + 1:])

    sched1 = dbg.op_schedule(_ep_fn(mesh, a2a_chunks=1), params, x)
    sched2 = dbg.op_schedule(_ep_fn(mesh, a2a_chunks=2), params, x)
    assert len(_a2a_census(sched1)) == 2
    assert len(_a2a_census(sched2)) == 4  # 2 per capacity chunk
    # unchunked: only the combine sits before a later dot (the
    # un-dispatch einsum); chunked adds chunk-0's combine AND chunk-1's
    # dispatch strictly between the per-chunk FFN matmuls — the
    # transfers the FFN compute can hide (2*chunks - 1 interior rows)
    assert interior_exchanges(sched1) == 1
    assert interior_exchanges(sched2) == 3


def test_chunked_quantized_compose():
    """Chunking composes with the quantized wire: 2 chunks x int8 is 4
    all_to_alls at the per-chunk compressed payload, values close."""
    params, x = _setup()
    mesh = _mesh4()
    f = _ep_fn(mesh, dispatch_bits="int8", a2a_chunks=2)
    census = _a2a_census(dbg.op_schedule(f, params, x))
    cap = _cap()
    assert len(census) == 4
    assert all(nbytes == E * (cap // 2) * (D + 4)
               for _, _, nbytes, _ in census)
    ref = np.asarray(_ep_fn(mesh)(params, x)[0])
    np.testing.assert_allclose(np.asarray(f(params, x)[0]), ref,
                               atol=0.12, rtol=0.12)


def test_moe_apply_knob_refusals():
    params, x = _setup()
    with pytest.raises(ValueError, match="dispatch_bits"):
        moe.moe_apply(params, x[:TL], n_experts=E, dispatch_bits="int2")
    with pytest.raises(ValueError, match="no wire to compress"):
        moe.moe_apply(params, x[:TL], n_experts=E, dispatch_bits="int8")
    with pytest.raises(ValueError, match="a2a_chunks"):
        moe.moe_apply(params, x[:TL], n_experts=E, a2a_chunks=0)
    with pytest.raises(ValueError, match="no exchange to overlap"):
        moe.moe_apply(params, x[:TL], n_experts=E, a2a_chunks=2)


# -- autotuner: rung, chooser matrix, version -------------------------------


def test_a2a_rung_in_calibration_ladder():
    """calibrate()'s default ladder includes the a2a rung, and its
    alpha-beta wire factor is (n-1)/n (each rank keeps 1/n in place)."""
    import inspect
    algos = inspect.signature(at.calibrate).parameters["algos"].default
    assert "a2a" in algos
    assert at._algo_factors("a2a", 4) == (1.0, 0.75)
    assert at._algo_factors("a2a", 2) == (1.0, 0.5)


def test_choose_moe_plan_matrix():
    """The chooser's decisions are explainable and pinned: int8 on
    slow/WAN expert links, f32 where the link is fast (uniform) or the
    quantize passes cost more than the wire saves (quant_bound)."""
    expected = {"wan_dcn": "int8", "slow": "int8",
                "quant_bound": "f32", "uniform": "f32"}
    kw = dict(axis="dcn", tokens=TL, d_model=D, n_experts=E)
    for preset, bits in expected.items():
        prof = at.synthetic_profile(preset, {"dcn": 2})
        plan = at.choose_moe_plan(prof, **kw)
        assert plan.dispatch_bits == bits, (preset, plan.summary())
        assert plan.route == f"expert:a2a@{bits}"
        routing.parse_route(plan.route)  # the route speaks the grammar
        assert len(plan.per_bits) == 2  # f32 + int8: int4 is opt-in
        assert "←" in plan.table()  # the pick marker on the chosen row
    # int4 joins the ladder only when asked for explicitly
    prof = at.synthetic_profile("wan_dcn", {"dcn": 2})
    plan = at.choose_moe_plan(prof, bits_options=("f32", "int8", "int4"),
                              **kw)
    assert plan.dispatch_bits == "int4"
    with pytest.raises(ValueError, match="calibrate"):
        at.choose_moe_plan(at.synthetic_profile("uniform", {"ici": 2}),
                           **kw)


def test_profile_version_4_cache_recalibrates(tmp_path):
    """A cached version-4 profile (pre-a2a-rung) misses so the caller
    recalibrates — the standing stale-cache contract, regression-tested
    at the 4->5 bump like the 3->4 one before it."""
    assert at.PROFILE_VERSION == 5
    axes = {"dcn": 2, "ici": 4}
    prof = at.synthetic_profile("uniform", axes)
    path = at.save_profile(prof, str(tmp_path))
    assert at.load_profile("synthetic", axes, str(tmp_path)) is not None
    with open(path) as f:
        d = json.load(f)
    d["version"] = at.PROFILE_VERSION - 1
    with open(path, "w") as f:
        json.dump(d, f)
    assert at.load_profile("synthetic", axes, str(tmp_path)) is None


# -- per-hop inspector accounting -------------------------------------------


@pytest.mark.parametrize("bits", ["f32", "int8"])
def test_per_hop_bytes_match_plan(bits):
    """plan_bytes_vs_schedule(by_hop=True) pairs choose_moe_plan's
    capacity-census prediction with the traced program's all_to_all
    bytes at ratio 1.0 — the same arithmetic prices the route and
    counts the program (_HOP_OP_PRIMS learned all_to_all)."""
    params, x = _setup()
    f = _ep_fn(_mesh4(), dispatch_bits=bits)
    sched = dbg.op_schedule(f, params, x)
    prof = at.synthetic_profile("slow" if bits == "int8" else "uniform",
                                {"model": N})
    # forward-only trace: dispatch + combine = 2 exchanges
    plan = at.choose_moe_plan(prof, axis="model", tokens=TL, d_model=D,
                              n_experts=E, a2a_per_step=2)
    assert plan.dispatch_bits == bits
    rows = dbg.plan_bytes_vs_schedule(plan, sched, by_hop=True,
                                      min_bytes=0)
    key = f"model:a2a@{bits}"
    assert key in rows, rows
    assert abs(rows[key]["ratio"] - 1.0) < 0.01, rows[key]


# -- the LM routed surface --------------------------------------------------


def _lm_model(**kw):
    from distributed_pytorch_tpu.models import transformer as tfm
    return tfm.TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                                 n_heads=2, head_dim=16, d_ff=64, **kw)


def test_lm_cli_sync_route_parser():
    from distributed_pytorch_tpu import lm_cli
    args = lm_cli.build_parser().parse_args([])
    assert args.sync_route is None
    args = lm_cli.build_parser().parse_args(
        ["--sync-route", "data:rs → dcn:ring[int8+ef] → data:ag"])
    assert args.sync_route == (
        "data:rs → dcn:ring[int8+ef] → data:ag")


def test_resolve_lm_route_flat_and_factored():
    """sync_route resolves to the explicit knobs the trainer executes:
    the flat psum keeps dcn_compress None; the factored int8 ring
    becomes dcn_compress='int8' — same resolve-to-named-knobs mechanism
    as sync_plan='auto'."""
    from distributed_pytorch_tpu.lm import LMTrainConfig
    cfg = LMTrainConfig(model=_lm_model(), sync_route="data:psum")
    resolved, plan = at.resolve_lm_route(cfg)
    assert resolved.sync_route is None
    assert resolved.dcn_compress is None
    assert plan.describe() == "data:psum"
    cfg = LMTrainConfig(
        model=_lm_model(), dcn_size=2,
        sync_route="data:rs → dcn:ring[int8+ef] → data:ag")
    resolved, plan = at.resolve_lm_route(cfg)
    assert resolved.sync_route is None
    assert resolved.dcn_compress == "int8"


def test_resolve_lm_route_refusals():
    from distributed_pytorch_tpu.lm import LMTrainConfig
    m = _lm_model()
    factored = "data:rs → dcn:ring[int8+ef] → data:ag"
    for cfg, match in (
            (LMTrainConfig(model=m, sync_route="data:psum",
                           sync_plan="auto"), "both"),
            (LMTrainConfig(model=m, dcn_size=2, sync_route=factored,
                           dcn_compress="int4"), "dcn_compress"),
            (LMTrainConfig(model=m, pp=2, sync_route="data:psum"),
             "pp"),
            (LMTrainConfig(model=m, sync_route=factored), "flat"),
            (LMTrainConfig(model=m, dcn_size=2, sync_route=(
                "data:rs → dcn:ring[int8] → data:ag")), "ef"),
    ):
        with pytest.raises(ValueError, match=match):
            at.resolve_lm_route(cfg)


def test_lm_moe_knob_refusals():
    """The dispatch knobs refuse silently-no-op configs: quantized or
    chunked dispatch on a dense model, or with no expert exchange to
    compress (ep=1, tp=1)."""
    from distributed_pytorch_tpu import lm
    with pytest.raises(ValueError, match="dense"):
        lm.validate_lm_cfg(lm.LMTrainConfig(
            model=_lm_model(moe_dispatch_bits="int8")))
    with pytest.raises(ValueError, match="exchange"):
        lm.validate_lm_cfg(lm.LMTrainConfig(
            model=_lm_model(n_experts=2, moe_dispatch_bits="int8")))
    with pytest.raises(ValueError, match="exchange"):
        lm.validate_lm_cfg(lm.LMTrainConfig(
            model=_lm_model(n_experts=2, moe_a2a_chunks=2)))
    with pytest.raises(ValueError, match="moe_dispatch_bits"):
        _lm_model(moe_dispatch_bits="fp8")
    with pytest.raises(ValueError, match="moe_a2a_chunks"):
        _lm_model(moe_a2a_chunks=0)
