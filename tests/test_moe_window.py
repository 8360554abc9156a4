"""The mechanisms a routed, two-kind decoder needs on the training path
(SmallThinker's block): windowed flash attention, the dropless routed layer
told which experts it holds, an untied head, per-layer attention kinds --
each against plain code, at small sizes on the CPU with seeded weights.

The plain side is the benchmark's reference for the family
(``benchmarks/families/moe_window_gqa/reference.py``: float32 ``jax.numpy``,
experts as a masked loop), reached as the benchmark reaches it, and the
trainer is built as the benchmark builds it (``program.build_trainer``).
Tolerances: both sides compute in float32 here, so they differ by the order
of float32 sums alone; ``TOL`` = 2e-5 of the largest entry admits that
(readings: 1e-7 to 2e-6) and is a thousandth of what one expert left out,
one wrong pick or a window one key too wide would move.
"""

import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_tpu import lm
from distributed_pytorch_tpu.generate import generate
from distributed_pytorch_tpu.models import transformer as tfm
from distributed_pytorch_tpu.ops import attention as att
from distributed_pytorch_tpu.ops import moe
from distributed_pytorch_tpu.serve import ContinuousBatcher

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
TOL = 2e-5
T, D, F, E, K = 96, 32, 16, 16, 6
EPS = 1e-6


@pytest.fixture(scope="module")
def bench():
    """The benchmark's modules, as its own entry points import them."""
    sys.path.insert(0, BENCH)
    import checks
    import families
    import program
    import reference
    import weights

    yield {"family": families.load("moe_window_gqa"), "reference": reference,
           "weights": weights, "program": program, "checks": checks}
    sys.path.remove(BENCH)


def close(a, b, tol=TOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    # entries here are of order one; a result that is exactly nought (the
    # gradient on q of a one-key window) is held to the same absolute size
    return np.abs(a - b).max() <= tol * max(np.abs(b).max(), 0.1)


def normed(x):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS)


def reference_routed(bench, params, x, first):
    """What the held experts add, by the family's reference: its ``layer``
    on a tree whose attention adds nothing (wo = 0) and whose norm scales
    are one, so the router and the experts both read ``normed(x)`` and
    ``out - x`` is the routed part alone."""
    zero = {"wq": jnp.zeros((D, 2, 16)), "wk": jnp.zeros((D, 1, 16)),
            "wv": jnp.zeros((D, 1, 16)), "wo": jnp.zeros((2, 16, D))}
    lp = {"attn_norm": jnp.ones((D,)), "mlp_norm": jnp.ones((D,)),
          "attn_global_nope": zero, "moe": params}
    cfg = {"rms_norm_eps": EPS, "num_attention_heads": 2,
           "num_key_value_heads": 1, "head_dim": 16,
           "moe_num_active_primary_experts": K, "moe_first_expert": first,
           "moe_num_primary_experts": params["w_gate"].shape[0]}
    with jax.default_matmul_precision("highest"):
        return bench["family"].reference.layer(
            lp, x, jnp.arange(x.shape[0]), cfg, None) - x


def program_routed(params, x, first):
    with jax.default_matmul_precision("highest"):
        return moe.moe_dropless_apply(params, normed(x), top_k=K,
                                      first_expert=first, act="relu")


def share_of(full, first, held):
    return {"router": full["router"],
            **{k: full[k][first:first + held]
               for k in ("w_gate", "w_up", "w_down")}}


def weighted_sum(f):
    """A scalar of ``f(params, x)`` (T, D) with a cotangent that differs
    from column to column."""
    return lambda p, x: jnp.sum(f(p, x) * jnp.cos(jnp.arange(D)))


@pytest.fixture(scope="module")
def routed():
    return (moe.moe_init(jax.random.key(1), D, F, E),
            jax.random.normal(jax.random.key(2), (T, D)))


@pytest.mark.parametrize("first,held", [(4, 4), (0, E)])
def test_dropless_layer_agrees_with_the_reference(bench, routed, first, held):
    """k = 6 of 16, with 4 held and with all held: forward, and the
    gradients on every leaf and on the input."""
    full, x = routed
    params = share_of(full, first, held)
    out, stats = program_routed(params, x, first)
    assert close(out, reference_routed(bench, params, x, first))
    assert float(stats["dropped"]) == 0
    assert 0 < float(stats["rows_here"]) <= T * K

    got = jax.grad(weighted_sum(lambda p, x: program_routed(p, x, first)[0]),
                   (0, 1))(params, x)
    want = jax.grad(weighted_sum(
        lambda p, x: reference_routed(bench, p, x, first)), (0, 1))(params, x)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert close(g, w)


def test_the_four_shares_add_up_to_the_uncut_layer(bench, routed):
    full, x = routed
    parts = [program_routed(share_of(full, first, 4), x, first)
             for first in range(0, E, 4)]
    assert close(sum(out for out, _ in parts),
                 reference_routed(bench, full, x, 0))
    # every pick lands on exactly one share
    assert sum(float(st["rows_here"]) for _, st in parts) == T * K


def test_every_token_on_one_expert_drops_nothing(bench, routed):
    full, x = routed
    # every token scores expert 3 far above the rest
    x = jnp.abs(x)
    params = dict(full, router=full["router"].at[:, 3].set(50.0))
    out, stats = program_routed(params, x, 0)
    assert float(stats["dropped"]) == 0
    assert float(stats["rows_here"]) == T * K
    assert float(stats["load_max_over_mean"]) == pytest.approx(E / K)
    assert close(out, reference_routed(bench, params, x, 0))
    # the share that holds expert 3 gets every token, exactly
    mine = share_of(params, 0, 4)
    out, stats = program_routed(mine, x, 0)
    assert float(stats["dropped"]) == 0 and float(stats["rows_here"]) >= T
    assert close(out, reference_routed(bench, mine, x, 0))


# -- routings that stress the work over the live tiles -----------------------
# 1,100 tokens, experts 0-3 of 16 held; a case says how many tokens pick each
# held expert (tokens start, start + 1, ... of the row; the other picks go to
# experts held elsewhere), so the rows, the live tiles and the counters can
# be worked out by hand.  2,048 is a multiple of every chunk length in use.
T_LIVE, HELD_LIVE = 1100, 4
ROUTINGS = {
    "even": (300, 300, 300, 300),
    "all_on_one_expert": (0, 0, T_LIVE, 0),
    "no_pick_held_here": (0, 0, 0, 0),
    "an_expert_of_one_tile": (moe.ROW_TILE, 100, 0, 7),
    "an_expert_of_one_tile_and_a_row": (moe.ROW_TILE + 1, 100, 0, 7),
    "live_rows_end_on_a_chunk": (512, 512, 512, 512),
    "live_rows_end_a_row_past_a_chunk": (513, 512, 512, 512),
}


def routed_as(counts):
    """Weights and an input under which exactly ``counts[e]`` tokens pick
    held expert e: the router reads the input's first E features one for
    one, and those features rank each token's K picks above the rest (all
    scores distinct, so both sides pick alike)."""
    full = moe.moe_init(jax.random.key(5), D, F, E)
    router = jnp.zeros((D, E)).at[:E].set(jnp.eye(E))
    t = np.arange(T_LIVE)
    want = np.zeros((T_LIVE, E), bool)
    for e, n in enumerate(counts):
        want[(t - 37 * e) % T_LIVE < n, e] = True
    for tok in t:       # fill up to K with experts held elsewhere
        spare = HELD_LIVE + (tok + np.arange(E - HELD_LIVE)) % (E - HELD_LIVE)
        want[tok, spare[:K - want[tok].sum()]] = True
    assert (want.sum(1) == K).all()
    score = np.where(want, 2.0, -2.0) + 0.3 * np.random.default_rng(0).random(
        (T_LIVE, E))
    x = jax.random.normal(jax.random.key(6), (T_LIVE, D))
    return (share_of(dict(full, router=router), 0, HELD_LIVE),
            x.at[:, :E].set(jnp.asarray(score, jnp.float32)))


@pytest.mark.parametrize("case", list(ROUTINGS))
def test_any_routing_is_exact_over_the_live_tiles(bench, case):
    """Value and every gradient (input, router, the three stacks) against
    the reference, nothing dropped, and the counters as counted by hand:
    an expert's rows take whole tiles, the layer's loops whole chunks."""
    counts = ROUTINGS[case]
    assert 2048 % moe.CHUNK == 0 and moe.CHUNK % moe.ROW_TILE == 0
    params, x = routed_as(counts)
    out, stats = program_routed(params, x, 0)
    assert close(out, reference_routed(bench, params, x, 0))
    live = sum(-(-n // moe.ROW_TILE) * moe.ROW_TILE for n in counts)
    worst = T_LIVE * K + HELD_LIVE * moe.ROW_TILE
    assert float(stats["dropped"]) == 0
    assert float(stats["rows_here"]) == sum(counts)
    assert float(stats["live_tile_share"]) == pytest.approx(
        -(-live // moe.CHUNK) * moe.CHUNK
        / (-(-worst // moe.CHUNK) * moe.CHUNK))
    if sum(counts):
        assert float(stats["load_max_over_mean"]) == pytest.approx(
            max(counts) * HELD_LIVE / sum(counts))
    else:
        assert not np.asarray(out).any()

    got = jax.grad(weighted_sum(lambda p, x: program_routed(p, x, 0)[0]),
                   (0, 1))(params, x)
    want = jax.grad(weighted_sum(
        lambda p, x: reference_routed(bench, p, x, 0)), (0, 1))(params, x)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert close(g, w)


@pytest.mark.parametrize("case", ["even", "no_pick_held_here"])
def test_nothing_is_read_past_the_live_tiles(bench, monkeypatch, case):
    """On the TPU the dead tiles of the buffers the loops write are
    uninitialised (``lax.empty``; zeros on the CPU).  With NaN there
    instead, value and gradients are finite and the reference's: no loop
    and no product reads them.  (What a product itself leaves past its
    groups is zero on the CPU: a read of that shows on the chip only, where
    the benchmark's ``correct`` sees it.)  The layer's rules are jitted, so
    this runs at a token count no other test has: nothing traced with the
    real ``lax.empty`` is found again, nor anything traced here later."""
    monkeypatch.setattr(moe.lax, "empty", lambda shape, dtype: jnp.full(
        shape, jnp.nan, dtype))
    untouched, = moe._over_live(lambda rows: (rows,), jnp.int32(0),
                                jnp.ones((2 * moe.CHUNK, 2)))
    assert np.isnan(np.asarray(untouched)).all()
    params, x = routed_as(ROUTINGS[case])
    x = x[:-4]
    got = jax.value_and_grad(weighted_sum(
        lambda p, x: program_routed(p, x, 0)[0]), (0, 1))(params, x)
    want = jax.value_and_grad(weighted_sum(
        lambda p, x: reference_routed(bench, p, x, 0)), (0, 1))(params, x)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.isfinite(np.asarray(g)).all() and close(g, w)


def test_dropless_layer_refuses_an_axis(routed):
    full, x = routed
    with pytest.raises(NotImplementedError, match="no exchange"):
        moe.moe_dropless_apply(full, x, top_k=K, axis="expert")


# -- windowed flash attention -------------------------------------------------

@pytest.fixture(scope="module")
def qkv():
    return [jax.random.normal(k, (1, 2, 512, 32), jnp.float32)
            for k in jax.random.split(jax.random.key(0), 3)]


@pytest.mark.parametrize("window,block_q,block_k",
                         [(100, 128, 128), (129, 128, 128), (300, 128, 256),
                          (37, 256, 128), (1, 128, 128)])
def test_windowed_flash_agrees_with_plain_attention(qkv, window, block_q,
                                                    block_k):
    """Forward and all three gradients (interpret mode), with the band's
    edge inside a block, just past a block boundary, wider than a block
    and down to one key."""
    def loss(f):
        def of(q, k, v):
            o = f(q, k, v)
            return jnp.sum(o * jnp.cos(jnp.arange(o.size).reshape(o.shape)))
        return of

    def flash(q, k, v):
        return att.flash_attention(q, k, v, causal=True, window=window,
                                   block_q=block_q, block_k=block_k)

    def plain(q, k, v):
        return att.attention_reference(q, k, v, causal=True, window=window)

    assert close(flash(*qkv), plain(*qkv))
    for g, w in zip(jax.grad(loss(flash), (0, 1, 2))(*qkv),
                    jax.grad(loss(plain), (0, 1, 2))(*qkv)):
        assert close(g, w)


def test_a_window_that_covers_the_row_is_plain_causal(qkv):
    for window in (512, 4096):
        got = att.flash_attention(*qkv, causal=True, window=window)
        assert jnp.array_equal(got, att.flash_attention(*qkv, causal=True))
    with pytest.raises(ValueError, match="causal"):
        att.flash_attention(*qkv, causal=False, window=64)


# -- the whole model ----------------------------------------------------------

TINY = {"hidden_size": 64, "num_hidden_layers": 4, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "moe_ffn_hidden_size": 32,
        "moe_num_primary_experts": 4, "moe_router_width": 16,
        "moe_first_expert": 8, "moe_num_active_primary_experts": 6,
        "sliding_window_size": 48, "vocab_size": 256, "rms_norm_eps": 1e-6,
        "rope_theta": 1.5e6, "global_attention_every": 4,
        "sliding_window_layout": [0, 1, 1, 1], "rope_layout": [0, 1, 1, 1],
        "tie_word_embeddings": False}
HP = {"lr": 3e-4, "weight_decay": 0.1, "b1": 0.9, "b2": 0.95,
      "grad_clip": 1.0}


def tiny_trainer(bench, remat="none"):
    cell = {"family": bench["family"], "config_file": TINY,
            "mix": {"trainer": {**HP, "compute_dtype": "float32",
                                "loss_impl": "dense", "remat": remat}}}
    return bench["program"].build_trainer(cell, jax.devices()[:1], seed=3)


def batch(seed=0):
    tok = np.random.default_rng(seed).integers(
        0, TINY["vocab_size"], (2, 128)).astype(np.int32)
    return tok, np.roll(tok, -1, 1)


@pytest.mark.parametrize("remat", ["none", "full"])
def test_whole_model_step_agrees_with_the_reference(bench, remat):
    """A period of four (one NoPE-global layer, three windowed), untied
    head, window shorter than the row, experts 8-11 of 16 held, through
    ``LMTrainer``'s step: the loss, the first gradient as AdamW gets it and
    the parameters' change, leaf by leaf, as the benchmark compares them.
    Float32 on both sides: 1e-4 admits reassociation (readings under 1e-5)
    and is far under one wrong pick or one layer of the wrong kind."""
    fam, ref, prog = bench["family"], bench["reference"], bench["program"]
    trainer = tiny_trainer(bench, remat)
    start = bench["weights"].make_params(fam, 7, TINY)
    assert jax.tree.structure(start) == jax.tree.structure(trainer.params)
    prog.reset_trainer(trainer, jax.tree.map(jnp.copy, start))
    tok, tgt = batch()
    loss = float(trainer.train_step(tok, tgt))
    rows, load, dropped, share = np.asarray(trainer.last_metrics)[2:]
    assert dropped == 0 and 0 < rows < 4 * tok.size * K and load >= 1
    assert 0 < share <= 1
    mine = {"losses": [loss],
            "grad_norms": np.asarray(ref.leaf_norms(prog.adam_first_moment(
                trainer.opt_state))) / (1 - HP["b1"]),
            "delta_norms": np.asarray(ref.diff_norms(trainer.params, start))}
    theirs = ref.with_delta_norms(
        ref.train_steps(fam.reference, jax.tree.map(jnp.copy, start),
                        [(tok, tgt)], TINY, HP), start)
    numbers = bench["checks"].train_numbers(mine, theirs)
    assert all(v < 1e-4 for v in numbers.values()), numbers


def test_a_layer_of_the_wrong_kind_is_caught(bench):
    """The plain attention path agrees with the reference's logits too, and
    the comparison is not blind to a layer's kind: the same weights run
    with the NoPE-global layer windowed and rotary read logits a thousand
    tolerances off."""
    import dataclasses

    fam, ref = bench["family"], bench["reference"]
    params = bench["weights"].make_params(fam, 7, TINY)
    tok = jnp.asarray(batch()[0][0])
    with jax.default_matmul_precision("highest"):
        want = fam.reference.project(
            fam.reference.head_params(params),
            ref.hidden(fam.reference, params, tok, TINY), TINY, None)
    good = fam.program.model_config(TINY)
    wrong = dataclasses.replace(good, attn_kinds=("window",) * 4)
    renamed = dict(params, layer0={
        ("attn_window" if k == "attn_global_nope" else k): v
        for k, v in params["layer0"].items()})
    for cfg, tree, off in ((good, params, False), (wrong, renamed, True)):
        with jax.default_matmul_precision("highest"):
            got = tfm.apply(tree, tok[None], cfg=cfg,
                            attn_impl="reference")[0]
        assert close(got, want, 1e-4) != off
        assert close(got, want, 1e-1) != off


# sha256 of the lowered text of the dense model's train step below, with the
# backward of flash attention as one fused kernel (the step the trainer
# builds) and, with the fused plan's budget at nought, as a dQ and a dK/dV
# kernel.  The digests were first taken at the parent of the PR that brought
# windows, kinds, the dropless layer and the untied head (commit 2b1df82):
# with the new configuration fields at their defaults the program is today's.
# Moved since, each time by ``ops/attention.py`` alone: PR 31, the fused
# backward kernel (the second digest; the first held with the budget at
# nought); PR 34, both: a tile's liveness is one predicate
# (``_tile_live``) where each kernel wrote its own, and the plain causal
# side's index maps hold a dead step on the nearest live tile (a ``clip``
# where the identity was).  The kernels' bodies are the parent's
# (6dce840a... fused, d3f6e824... two kernels).
DENSE_STEP_TWO_KERNELS = (
    "f0484966b2623be9482a873777b420d8781c15c53d8a7633de8f5a1d1af737d4")
DENSE_STEP = "a679c416db240a75f2797fde1fc1d1d473331ecc10fc8a588c0c3b64345016c3"


def test_the_dense_models_step_program_is_unchanged(monkeypatch):
    cfg = lm.LMTrainConfig(model=tfm.TransformerConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=128), dp=1)
    mesh = lm.make_lm_mesh(cfg, devices=jax.devices()[:1])
    trainer = lm.LMTrainer(cfg, mesh)
    tok = jnp.zeros((2, 128), jnp.int32)

    def digest():
        text = lm.make_lm_train_step(cfg, mesh).lower(
            trainer.params, trainer.opt_state, tok, tok).as_text()
        return hashlib.sha256(text.encode()).hexdigest()

    assert digest() == DENSE_STEP
    monkeypatch.setattr(att, "_FUSED_BWD_VMEM_BUDGET", 0)
    assert digest() == DENSE_STEP_TWO_KERNELS
    # and the dense tree is flat, tied and without a head of its own
    assert {"wq", "w_gate"} <= set(trainer.params["layer0"])
    assert "lm_head" not in trainer.params
    assert np.asarray(trainer.last_metrics if trainer.last_metrics is not None
                      else np.zeros(2)).shape == (2,)


# -- what refuses it ----------------------------------------------------------

@pytest.mark.parametrize("change,named", [
    ({"attn_kinds": ("global", "window"), "attn_window": 8}, "windowed"),
    ({"attn_kinds": ("global_nope", "global")}, "without rotary"),
    ({"tie_embeddings": False}, "untied output head"),
    ({"n_experts": 8, "moe_top_k": 2, "moe_dropless": True},
     "dropless routed layer"),
])
def test_decode_and_serving_refuse_what_they_do_not_implement(change, named):
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                                n_heads=2, head_dim=16, d_ff=32, **change)
    params = tfm.init(jax.random.key(0), cfg)
    with pytest.raises(NotImplementedError, match=named):
        generate(params, jnp.zeros((1, 4), jnp.int32), jax.random.key(0),
                 cfg=cfg, max_new=2)
    with pytest.raises(NotImplementedError, match=named):
        ContinuousBatcher(params, cfg, slots=2, max_len=32)


@pytest.mark.parametrize("keywords,named", [
    ({"tp": 2}, "tp=2"), ({"ep": 2, "dp": 1}, "ep=2"),
    ({"grad_accum": 2}, "grad_accum=2"), ({"pp": 2}, "pipeline"),
])
def test_the_trainer_refuses_layouts_the_dropless_layer_lacks(bench, keywords,
                                                              named):
    cfg = lm.LMTrainConfig(model=bench["family"].program.model_config(TINY),
                           **keywords)
    with pytest.raises(ValueError, match=named):
        lm.validate_lm_cfg(cfg)


def test_windowed_layers_refuse_ring_attention(bench):
    model = tfm.TransformerConfig(n_layers=2, attn_kinds=("global", "window"),
                                  attn_window=8)
    with pytest.raises(ValueError, match="ring attention has no window"):
        lm.validate_lm_cfg(lm.LMTrainConfig(model=model, sp=2))


def test_counters_reach_telemetry_by_name(bench, tmp_path):
    from distributed_pytorch_tpu.utils import telemetry

    trainer = tiny_trainer(bench)
    telemetry.enable(str(tmp_path), rank=0)
    try:
        trainer.train_step(*batch())
    finally:
        telemetry.disable()     # flushes the step's deferred gauges
    (_, records), = telemetry.read_run(str(tmp_path))
    gauges = {r["name"]: r["value"] for r in records if r["type"] == "gauge"}
    assert gauges["moe.dropped"] == 0 and gauges["moe.rows_here"] > 0
    assert gauges["moe.load_max_over_mean"] >= 1
    assert 0 < gauges["moe.live_tile_share"] <= 1
    assert lm.MOE_METRICS[3] == "moe.live_tile_share"
