"""Activation-memory roofline (round 17): the chunked vocab
cross-entropy head, selective remat of the LM layer stack, the
activation accountant's predict-vs-census contract, and the
memory-priced autotuner (ops/losses.py, models/transformer.py,
utils/memacct.py, parallel/autotune.py).

The numeric pins come in three strengths, matching what the machinery
guarantees: remat re-runs the SAME forward graph, so the step-1 loss is
bitwise-equal to no-remat (trajectories get a tight allclose — the
remat backward may reassociate cotangent sums); the chunked head
computes the same f32 math with an online logsumexp, so it matches the
dense head to ~1e-6; the accountant is a pure shape function held to
<= 10% of the jaxpr census (it is byte-exact for the dense-MLP flash
stack at f32 — the tolerance absorbs runtime-version jaxpr drift).
"""

from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_tpu.lm import (IGNORE, LMTrainConfig, LMTrainer,
                                        validate_lm_cfg)
from distributed_pytorch_tpu.models import transformer as tfm
from distributed_pytorch_tpu.ops import losses
from distributed_pytorch_tpu.parallel import autotune as at
from distributed_pytorch_tpu.utils import debug as dbg
from distributed_pytorch_tpu.utils import memacct, monitor

pytestmark = pytest.mark.memory


def _lm_model(**kw):
    base = dict(vocab_size=64, d_model=64, n_layers=2, n_heads=2,
                head_dim=32, d_ff=128)
    base.update(kw)
    return tfm.TransformerConfig(**base)


def _lm_data(steps=2, b=4, s=32, vocab=64):
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, vocab, (steps, b, s)).astype(np.int32)
    targets = np.roll(tokens, -1, axis=2).astype(np.int32)
    targets[:, :, -1] = IGNORE
    return tokens, targets


# The census shape: the model the accountant's inventory was itemized
# against (module docstring of utils/memacct.py).  batch=3 keeps every
# residual-filter dimension distinct: B*T=384, T=128, V=256, d_ff=160 —
# so "last dim == vocab" can only match genuinely V-sized arrays.
_CENSUS_KW = dict(vocab_size=256, d_model=64, n_heads=2, head_dim=32,
                  d_ff=160)
_CENSUS_B, _CENSUS_T = 3, 128
_census_cache: dict = {}


def _census(*, n_layers=2, remat="none", loss_impl="dense"):
    """Saved-residual census of the pure LM loss (cached: tracing the
    vjp is the cost here, and several tests share the same mode)."""
    key = (n_layers, remat, loss_impl)
    if key not in _census_cache:
        model = tfm.TransformerConfig(n_layers=n_layers, **_CENSUS_KW)
        rng = np.random.default_rng(0)
        toks = jnp.asarray(rng.integers(0, model.vocab_size,
                                        (_CENSUS_B, _CENSUS_T)), jnp.int32)
        tgts = jnp.asarray(np.roll(np.asarray(toks), -1, axis=1),
                           jnp.int32)
        params = tfm.init(jax.random.key(0), model)

        def loss(p):
            ce, n = tfm.apply(
                p, toks, cfg=model, remat=remat,
                head_fn=lambda h, e: losses.head_loss(
                    h, e, tgts, loss_impl=loss_impl))
            return ce / n

        _census_cache[key] = memacct.saved_residual_census(loss, params)
    return _census_cache[key]


# -- the chunked head -------------------------------------------------------


@pytest.mark.quick
def test_chunked_head_matches_dense_fwd_and_bwd():
    """masked_ce_chunked streams logits chunk-by-chunk but computes the
    same f32 cross-entropy: value and both grads (dh, demb) match the
    dense head at every chunk size, with masked positions honored."""
    rng = np.random.default_rng(0)
    B, T, D, V = 2, 16, 32, 64
    h = jnp.asarray(rng.standard_normal((B, T, D)), jnp.float32)
    emb = jnp.asarray(rng.standard_normal((V, D)) * 0.3, jnp.float32)
    t_np = rng.integers(0, V, (B, T)).astype(np.int32)
    t_np[:, -3:] = IGNORE  # masked tail must drop out of sums AND count
    tgts = jnp.asarray(t_np)

    def mean_loss(impl, chunk=None):
        def f(hh, ee):
            ce, n = losses.head_loss(hh, ee, tgts, loss_impl=impl,
                                     loss_chunk=chunk)
            return ce / n
        return f

    dv, dg = jax.value_and_grad(mean_loss("dense"), argnums=(0, 1))(h, emb)
    for chunk in (8, 16, 64):
        cv, cg = jax.value_and_grad(mean_loss("chunked", chunk),
                                    argnums=(0, 1))(h, emb)
        np.testing.assert_allclose(np.asarray(cv), np.asarray(dv),
                                   rtol=1e-6, atol=1e-6)
        for got, want in zip(cg, dg):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("tp", [1, 2], ids=["dp", "tp"])
def test_chunked_head_grads_match_dense_inside_shard_map(tp):
    """Inside the trainers' ``shard_map`` the embedding comes in unvarying
    and the hidden states varying over the data axis: the streamed head's
    scans trace, and both cotangents come back typed and summed as autodiff
    gives the dense head's (the vocab-sharded head's rows gathered once,
    not once per model rank)."""
    from jax.sharding import PartitionSpec as P
    rng = np.random.default_rng(0)
    B, T, D, V = 4, 8, 16, 64
    h = jnp.asarray(rng.standard_normal((B, T, D)), jnp.float32)
    emb = jnp.asarray(rng.standard_normal((V, D)) * 0.3, jnp.float32)
    tgts = jnp.asarray(rng.integers(0, V, (B, T)), jnp.int32)
    mesh = jax.make_mesh((2, 2), ("data", "model"))

    def grads(**kw):
        def f(hh, ee, tt):
            loss = lambda a, b: losses.head_loss(a, b, tt, **kw)[0]
            return jax.grad(loss, argnums=(0, 1))(hh, ee)
        return jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=(P("data"), P(), P("data")),
            out_specs=(P("data"), P())))(h, emb, tgts)

    want = grads(loss_impl="dense")
    got = grads(loss_impl="chunked", loss_chunk=16,
                tp_axis="model" if tp > 1 else None, tp_size=tp)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.quick
def test_chunked_head_rejects_bad_chunk():
    h = jnp.zeros((1, 4, 8), jnp.float32)
    emb = jnp.zeros((16, 8), jnp.float32)
    tgts = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(ValueError, match="divisor"):
        losses.masked_ce_chunked(h, emb, tgts, chunk=7)
    with pytest.raises(ValueError, match="divisor"):
        losses.masked_ce_chunked(h, emb, tgts, chunk=0)
    with pytest.raises(ValueError, match="loss_impl"):
        losses.head_loss(h, emb, tgts, loss_impl="streamed")


@pytest.mark.parametrize("kw", [
    dict(dp=2),
    dict(dp=2, grad_accum=2),
    dict(dp=2, tp=2),
    dict(dp=2, fsdp=True),
    dict(dp=2, fsdp=True, overlap=True),
], ids=["dp", "grad_accum", "tp", "fsdp", "fsdp_overlap"])
def test_trainer_chunked_matches_dense(kw):
    """loss_impl='chunked' is a drop-in for the dense head through every
    step builder: per-step training losses match across the parallelism
    matrix (the tp leg runs the vocab-SHARDED streamed head — its
    cross-rank online logsumexp reassociates, hence the 1e-5 band)."""
    model = _lm_model()
    dense = LMTrainer(LMTrainConfig(model=model, compute_dtype=None, **kw))
    chunked = LMTrainer(LMTrainConfig(model=model, compute_dtype=None,
                                      loss_impl="chunked", loss_chunk=16,
                                      **kw))
    for step, (toks, tgts) in enumerate(zip(*_lm_data())):
        ld = float(dense.train_step(toks, tgts))
        lc = float(chunked.train_step(toks, tgts))
        # step 0 is pure forward parity (the 1e-6 head contract); later
        # steps compare TRAINED trajectories, where a ~1e-7 grad
        # reassociation difference compounds through the params
        np.testing.assert_allclose(lc, ld, rtol=2e-6 if step == 0
                                   else 2e-4)


# -- selective remat --------------------------------------------------------


@pytest.mark.parametrize("remat", ["full", "selective"])
def test_remat_step1_bitwise_and_trajectory(remat):
    """remat re-runs the SAME forward graph: the step-1 loss (pure
    forward) is bitwise-equal to remat='none', and the trained
    trajectory stays within reassociation noise of it."""
    model = _lm_model()
    toks, tgts = _lm_data(steps=3)

    def traj(**kw):
        tr = LMTrainer(LMTrainConfig(model=model, dp=2, compute_dtype=None,
                                     **kw))
        return [float(tr.train_step(t, g)) for t, g in zip(toks, tgts)]

    base = traj()
    rem = traj(remat=remat)
    assert rem[0] == base[0], (remat, rem[0], base[0])  # bitwise
    np.testing.assert_allclose(rem, base, rtol=0, atol=1e-5)


def test_remat_chunked_compose_with_zero3_overlap_grad_accum():
    """The full low-memory composition — streaming ZeRO-3 + overlap +
    grad accumulation + selective remat + chunked head — trains to the
    same losses as the dense/no-remat step."""
    model = _lm_model()
    toks, tgts = _lm_data(steps=3)
    base_kw = dict(model=model, dp=2, fsdp=True, overlap=True,
                   grad_accum=2, compute_dtype=None)
    base = LMTrainer(LMTrainConfig(**base_kw))
    mem = LMTrainer(LMTrainConfig(remat="selective", loss_impl="chunked",
                                  loss_chunk=16, **base_kw))
    for t, g in zip(toks, tgts):
        lb = float(base.train_step(t, g))
        lm = float(mem.train_step(t, g))
        np.testing.assert_allclose(lm, lb, rtol=0, atol=1e-5)


def test_remat_does_not_reemit_sync_collectives():
    """The ZeRO-3 boundary hook stays OUTSIDE the checkpointed region:
    the streamed per-group weight all-gathers and gradient
    reduce-scatters appear in the step's schedule exactly as often under
    remat as without it — the backward recomputes activations, never
    collectives."""
    model = _lm_model()
    toks, tgts = _lm_data(steps=1)

    def prims(**kw):
        tr = LMTrainer(LMTrainConfig(model=model, dp=2, fsdp=True,
                                     overlap=True, compute_dtype=None,
                                     **kw))
        sched = dbg.op_schedule(tr.step_fn, tr.params, tr.opt_state,
                                toks[0], tgts[0])
        return Counter(r["prim"] for r in sched
                       if r["kind"] == "collective" and r["bytes"] >= 1024)

    base = prims()
    assert base["all_gather"] > 0 and base["reduce_scatter"] > 0, base
    for remat in ("selective", "full"):
        got = prims(remat=remat)
        assert got["all_gather"] == base["all_gather"], (remat, got, base)
        assert got["reduce_scatter"] == base["reduce_scatter"], \
            (remat, got, base)


# -- the accountant: census vs prediction -----------------------------------


def test_census_has_no_vocab_logits_under_chunked():
    """The tentpole's memory claim at jaxpr level: the dense head saves
    the f32 (B, T, V) softmax residual for its backward; the chunked
    head saves NOTHING V-sized — the logits never exist as a saved
    array."""
    V = _CENSUS_KW["vocab_size"]
    logits_bytes = _CENSUS_B * _CENSUS_T * V * 4
    dense = _census(loss_impl="dense")
    hits = memacct.find_residuals(dense, dtype="float32", last_dim=V,
                                  min_bytes=logits_bytes)
    assert hits, "dense head lost its (B, T, V) softmax residual?"
    chunked = _census(loss_impl="chunked")
    assert memacct.find_residuals(chunked, last_dim=V) == [], \
        memacct.find_residuals(chunked, last_dim=V)
    assert chunked["bytes"] < dense["bytes"] - logits_bytes / 2


def test_selective_remat_cuts_per_layer_residuals():
    """Per-layer saved bytes (the L=4 minus L=2 census difference, so
    the fixed head/boundary part cancels): selective must cut >= 2x vs
    no-remat (measured ~13x — it keeps only the block carry + the flash
    (o, lse) pair), and full must save strictly less than selective."""
    per_layer = {}
    for remat in ("none", "selective", "full"):
        c2 = _census(n_layers=2, remat=remat, loss_impl="chunked")
        c4 = _census(n_layers=4, remat=remat, loss_impl="chunked")
        per_layer[remat] = (c4["bytes"] - c2["bytes"]) / 2
        assert per_layer[remat] > 0, (remat, per_layer)
    assert per_layer["selective"] * 2 <= per_layer["none"], per_layer
    assert per_layer["full"] < per_layer["selective"], per_layer


@pytest.mark.parametrize("remat", ["none", "full", "selective"])
@pytest.mark.parametrize("loss_impl", ["dense", "chunked"])
def test_accountant_matches_census(remat, loss_impl):
    """predict_activation_bytes is a pure shape function of the config —
    within 10% of the jaxpr census in every (remat, loss_impl) mode
    (byte-exact for the dense modes at f32; the band absorbs
    runtime-version jaxpr drift)."""
    model = tfm.TransformerConfig(n_layers=2, **_CENSUS_KW)
    want = _census(remat=remat, loss_impl=loss_impl)["bytes"]
    got = memacct.predict_activation_bytes(
        model, batch=_CENSUS_B, seq=_CENSUS_T, remat=remat,
        loss_impl=loss_impl)
    assert abs(got - want) <= 0.10 * want, (remat, loss_impl, got, want)


@pytest.mark.quick
def test_predict_recompute_bytes_orders_the_rungs():
    """The recompute bill the chooser prices: zero without knobs,
    positive under any knob, and full recomputes strictly more than
    selective (which keeps the flash kernel's work)."""
    model = tfm.TransformerConfig(n_layers=2, **_CENSUS_KW)

    def rec(remat, li):
        return memacct.predict_recompute_bytes(
            model, batch=2, seq=128, remat=remat, loss_impl=li)

    assert rec("none", "dense") == 0
    assert rec("none", "chunked") == 2 * 128 * 256 * 4  # one logits pass
    assert 0 < rec("selective", "dense") < rec("full", "dense")
    assert rec("full", "chunked") > rec("full", "dense")


# -- the memory-priced autotuner --------------------------------------------


def _plan(budget, batch=8, seq=128):
    model = tfm.TransformerConfig(n_layers=2, **_CENSUS_KW)
    prof = at.synthetic_profile("uniform", {"data": 8})
    return at.choose_lm_memory_plan(model, prof, batch=batch, seq=seq,
                                    memory_budget_bytes=budget)


@pytest.mark.quick
def test_memory_plan_budget_ladder():
    """Descending budgets walk the rungs: a roomy budget buys the
    no-knob plan at the full microbatch (recompute 0); a budget sized to
    the thriftiest rung forces remat + the chunked head while KEEPING
    the microbatch (splitting serializes — it outranks rung only when no
    rung fits); tighter still drops to microbatch 1."""
    model = tfm.TransformerConfig(n_layers=2, **_CENSUS_KW)

    def act(batch, remat, li):
        return memacct.predict_activation_bytes(
            model, batch=batch, seq=128, remat=remat, loss_impl=li)

    plan = _plan(act(8, "none", "dense"))
    assert (plan.remat, plan.loss_impl, plan.microbatch,
            plan.n_micro) == ("none", "dense", 8, 1)
    assert plan.recompute_ms == 0.0
    assert len(plan.considered) == len(at.MEMORY_RUNGS)

    plan = _plan(act(8, "full", "chunked"))
    assert (plan.remat, plan.loss_impl, plan.microbatch,
            plan.n_micro) == ("full", "chunked", 8, 1)
    assert plan.recompute_ms > 0.0

    plan = _plan(act(1, "full", "chunked"))
    assert (plan.remat, plan.loss_impl, plan.microbatch,
            plan.n_micro) == ("full", "chunked", 1, 8)
    # the decision is auditable: summary round-trips, table lists rungs
    assert plan.summary()["microbatch"] == 1
    assert plan.table().count("\n") >= len(at.MEMORY_RUNGS)


@pytest.mark.quick
def test_memory_plan_refuses_unfittable_budget():
    """Below the thriftiest rung at microbatch 1 the chooser refuses
    LOUDLY — with the floor it computed, never a silent OOM plan."""
    model = tfm.TransformerConfig(n_layers=2, **_CENSUS_KW)
    floor = memacct.predict_activation_bytes(
        model, batch=1, seq=128, remat="full", loss_impl="chunked")
    with pytest.raises(ValueError,
                       match=r"no \(remat, loss_impl, microbatch\)"):
        _plan(floor - 1)
    with pytest.raises(ValueError, match="positive"):
        _plan(0)


@pytest.mark.quick
def test_profile_carries_recompute_rate():
    """Since PROFILE_VERSION 3 the calibrated recompute rate rides the
    profile like quant_s_per_byte (serde round-trip; absent key loads as
    0.0 so an older JSON is simply re-calibrated by the version gate —
    the stale-version path itself is pinned against
    ``autotune.PROFILE_VERSION`` in tests/test_routing.py and
    tests/test_a2a.py, never against a literal: the round-20 3→4 bump
    broke a hard-coded ``== 3`` here, the round-21 hygiene sweep)."""
    assert at.PROFILE_VERSION >= 3  # the recompute-rate field's floor
    prof = at.synthetic_profile("uniform", {"data": 8})
    assert prof.recompute_s_per_byte > 0
    back = at.TopologyProfile.from_json(prof.to_json())
    assert back.recompute_s_per_byte == prof.recompute_s_per_byte
    d = prof.to_json()
    del d["recompute_s_per_byte"]
    assert at.TopologyProfile.from_json(d).recompute_s_per_byte == 0.0


# -- config validation + the watermark rule ---------------------------------


@pytest.mark.quick
def test_validate_lm_cfg_memory_refusals():
    model = _lm_model()

    def check(match, **kw):
        with pytest.raises(ValueError, match=match):
            validate_lm_cfg(LMTrainConfig(model=model, **kw))

    check("loss_impl", loss_impl="streamed")
    check("loss_chunk", loss_chunk=16)                    # dense head
    check("divisor", loss_impl="chunked", loss_chunk=7)   # 7 ∤ 64
    check("divisor", loss_impl="chunked", loss_chunk=64, tp=2)  # 64 ∤ 32
    check("remat", remat="partial")
    check("pipeline", remat="full", pp=2, dp=2)


@pytest.mark.quick
def test_default_rules_device_memory_watermark():
    """The rule set stays at four by default; device_peak_bytes arms the
    accountant's live lane — a max-watermark ceiling on the
    record_memory gauge."""
    assert len(monitor.default_rules()) == 4
    rules = monitor.default_rules(device_peak_bytes=2e9)
    assert len(rules) == 5
    wm = rules[-1]
    assert wm.name == "device_memory_watermark"
    assert wm.metric == "device_peak_bytes"
    assert (wm.agg, wm.op, wm.threshold) == ("max", "<=", 2e9)
    assert wm.severity == "critical"
