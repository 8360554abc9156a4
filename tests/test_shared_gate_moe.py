"""The mechanisms a shared-expert decoder with layers of two head counts needs
on the training path (Laguna-XS.2's block): a leading dense layer of its own
width in a dropless model, a head count and rotary settings per attention
kind (YaRN-scaled partial rotary on the global layers), a per-head sigmoid
gate on the attention output, a router that scores by sigmoid and scales its
normalised picks, a shared expert beside the routed ones -- each against
plain code, at small sizes on the CPU with seeded weights.

The plain side is the benchmark's reference for the family
(``benchmarks/families/moe_shared_window_gqa/reference.py``), reached as the
benchmark reaches it; the configuration is the benchmark's file shrunk to the
family's ``tiny`` (``rehearse.shrink`` does the same), and the trainer is
built as the benchmark builds it.  Tolerances as in ``test_moe_window.py``:
float32 on both sides, so 2e-5 of the largest entry admits the order of sums
and is far under one expert left out, a gate left open or a wrong frequency.
"""

import dataclasses
import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_tpu import lm
from distributed_pytorch_tpu.generate import generate
from distributed_pytorch_tpu.models import transformer as tfm
from distributed_pytorch_tpu.ops import moe
from distributed_pytorch_tpu.ops.nn import masked_ce
from distributed_pytorch_tpu.serve import ContinuousBatcher

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
TOL = 2e-5
HP = {"lr": 3e-4, "weight_decay": 0.1, "b1": 0.9, "b2": 0.95,
      "grad_clip": 1.0}


@pytest.fixture(scope="module")
def bench():
    """The benchmark's modules, as its own entry points import them, and the
    configuration file at the family's tiny sizes."""
    sys.path.insert(0, BENCH)
    import checks
    import families
    import program
    import reference
    import weights

    fam = families.load("moe_shared_window_gqa")
    with open(os.path.join(BENCH, "configs", "laguna-xs.2.json")) as f:
        cfg = {**json.load(f), **fam.weights.tiny, "moe_first_expert": 8}
    yield {"family": fam, "reference": reference, "weights": weights,
           "program": program, "checks": checks, "cfg": cfg}
    sys.path.remove(BENCH)


def close(a, b, tol=TOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b).max() <= tol * max(np.abs(b).max(), 0.1)


def tiny_trainer(bench, remat="none"):
    cell = {"family": bench["family"], "config_file": bench["cfg"],
            "mix": {"trainer": {**HP, "compute_dtype": "float32",
                                "loss_impl": "dense", "remat": remat}}}
    return bench["program"].build_trainer(cell, jax.devices()[:1], seed=3)


def batch(bench, seed=0):
    tok = np.random.default_rng(seed).integers(
        0, bench["cfg"]["vocab_size"], (2, 128)).astype(np.int32)
    return tok, np.roll(tok, -1, 1)


# -- the whole model ----------------------------------------------------------

def test_the_tiny_model_has_every_mechanism(bench):
    model = bench["family"].program.model_config(bench["cfg"])
    assert model.attn_kinds == ("global", "window", "window", "window",
                                "global")
    assert [model.is_moe_layer(i) for i in range(5)] == [False] + [True] * 4
    assert (model.heads("global"), model.heads("window")) == (4, 6)
    assert model.dense_ff == 128 and model.ff == 32 and model.moe_shared_ff
    assert model.rope("window") is None      # plain, at the model's base
    assert model.rope("global").rotary_share == 0.5
    params = tfm.init(jax.random.key(0), model)
    want = bench["weights"].make_params(bench["family"], 7, bench["cfg"])
    assert jax.tree.structure(params) == jax.tree.structure(want)
    assert [a.shape for a in jax.tree.leaves(params)] == [
        a.shape for a in jax.tree.leaves(want)]
    specs = tfm.shard_specs(model)
    assert jax.tree.structure(specs, is_leaf=lambda x: not isinstance(
        x, dict)) == jax.tree.structure(params)
    assert params["layer0"]["w_gate"].shape == (64, 128)
    assert "moe" not in params["layer0"] and "shared" in params["layer1"]
    assert params["layer1"]["attn_window"]["wg"].shape == (64, 6)


@pytest.mark.parametrize("remat", ["none", "full"])
def test_whole_model_step_agrees_with_the_reference(bench, remat):
    """Dense first layer, both attention kinds at their head counts, the
    gate, the shared expert, the sigmoid router over experts 8-11 of 16,
    through ``LMTrainer``'s step (flash attention): the loss, the first
    gradient as AdamW gets it and the parameters' change, leaf by leaf, as
    the benchmark compares them.  Readings: 1e-7 to 2e-6."""
    fam, ref, prog = bench["family"], bench["reference"], bench["program"]
    trainer = tiny_trainer(bench, remat)
    start = bench["weights"].make_params(fam, 7, bench["cfg"])
    prog.reset_trainer(trainer, jax.tree.map(jnp.copy, start))
    tok, tgt = batch(bench)
    loss = float(trainer.train_step(tok, tgt))
    names = lm.step_metric_names(trainer.cfg.model)
    assert names == lm.MOE_METRICS + ("moe.score_sum_mean", "attn.gate_mean")
    met = dict(zip(names, np.asarray(trainer.last_metrics)[2:]))
    # four routed layers x 256 tokens x 4 picks, a quarter of them held here
    assert met["moe.dropped"] == 0 and 0 < met["moe.rows_here"] < 4 * 256 * 4
    assert 0 < met["attn.gate_mean"] < 1 and 0 < met["moe.score_sum_mean"] < 4
    mine = {"losses": [loss],
            "grad_norms": np.asarray(ref.leaf_norms(prog.adam_first_moment(
                trainer.opt_state))) / (1 - HP["b1"]),
            "delta_norms": np.asarray(ref.diff_norms(trainer.params, start))}
    theirs = ref.with_delta_norms(
        ref.train_steps(fam.reference, jax.tree.map(jnp.copy, start),
                        [(tok, tgt)], bench["cfg"], HP), start)
    numbers = bench["checks"].train_numbers(mine, theirs)
    assert all(v < 1e-4 for v in numbers.values()), numbers


def test_the_plain_attention_path_agrees_leaf_by_leaf(bench):
    """``attn_impl="reference"``: the loss and every leaf's gradient against
    the reference's, and the comparison is not blind to the new mechanisms:
    with the gate stuck at a half, the shared expert left out, the global
    layers rotated plainly or a softmax router the logits are a thousand
    tolerances off."""
    fam, ref = bench["family"], bench["reference"]
    cfg = bench["cfg"]
    model = fam.program.model_config(cfg)
    params = bench["weights"].make_params(fam, 7, cfg)
    tok, tgt = (jnp.asarray(a[:1]) for a in batch(bench))

    def loss_of(p, model=model):
        logits = tfm.apply(p, tok, cfg=model, attn_impl="reference")
        return masked_ce(logits, tgt)[0] / tgt.size

    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(loss_of)(params)
        want_loss, want = ref.loss_and_grads(fam.reference, params,
                                             np.asarray(tok), np.asarray(tgt),
                                             cfg)
        assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
        for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                                jax.tree.leaves(want)):
            assert close(g, w, 1e-4), jax.tree_util.keystr(path)
        logits = tfm.apply(params, tok, cfg=model, attn_impl="reference")
        attn1 = params["layer1"]["attn_window"]
        wrong = {
            "gate stuck": (model, {**params, "layer1": {
                **params["layer1"],
                "attn_window": {**attn1, "wg": 0 * attn1["wg"]}}}),
            "no shared expert": (dataclasses.replace(model, moe_shared_ff=0),
                                 params),
            "plain rotary": (dataclasses.replace(
                model, rope_by_kind=model.rope_by_kind[1:],
                rope_theta=cfg["rope_theta_global"]), params),
            "softmax router": (dataclasses.replace(
                model, moe_scoring="softmax", moe_score_scale=1.0), params),
        }
        for what, (other, tree) in wrong.items():
            off = tfm.apply(tree, tok, cfg=other, attn_impl="reference")
            assert not close(off, logits, 1e-2), what


# -- the sparse layer: shares, the shared expert, one crowded expert ------------

T, D, F, E, K, SCALE = 96, 32, 16, 16, 4, 2.5
EPS = 1e-6


@pytest.fixture(scope="module")
def sparse():
    """A router over 16 experts, their stacks, a shared expert and rows."""
    keys = jax.random.split(jax.random.key(11), 5)
    shared = {"w_gate": jax.random.normal(keys[0], (D, F)) / D ** 0.5,
              "w_up": jax.random.normal(keys[1], (D, F)) / D ** 0.5,
              "w_down": jax.random.normal(keys[2], (F, D)) / F ** 0.5}
    return (moe.moe_init(keys[3], D, F, E), shared,
            jax.random.normal(keys[4], (T, D)))


def share_of(full, first, held):
    return {"router": full["router"],
            **{k: full[k][first:first + held]
               for k in ("w_gate", "w_up", "w_down")}}


def reference_sparse(bench, routed, shared, x, first):
    """What a sparse layer adds, by the family's reference: its ``layer`` on
    a tree whose attention adds nothing (wo = 0) and whose norm scales are
    one, so ``out - x`` is the shared expert's and the held experts' part."""
    zero = {"wq": jnp.zeros((D, 2, 16)), "wk": jnp.zeros((D, 1, 16)),
            "wv": jnp.zeros((D, 1, 16)), "wo": jnp.zeros((2, 16, D)),
            "wg": jnp.zeros((D, 2))}
    lp = {"attn_norm": jnp.ones((D,)), "mlp_norm": jnp.ones((D,)),
          "attn_window": zero, "moe": routed, "shared": shared}
    cfg = {"rms_norm_eps": EPS, "sliding_window": 8, "rope_theta_window": 1e4,
           "num_experts_per_tok": K, "moe_first_expert": first,
           "num_experts": routed["w_gate"].shape[0],
           "moe_routed_scaling_factor": SCALE}
    with jax.default_matmul_precision("highest"):
        return bench["family"].reference.layer(
            lp, x, jnp.arange(x.shape[0]), cfg, None) - x


def program_sparse(routed, shared, x, first):
    """The same through the program's ``block`` (attention zeroed alike)."""
    model = tfm.TransformerConfig(
        vocab_size=8, d_model=D, n_layers=1, n_heads=2, n_kv_heads=1,
        head_dim=16, d_ff=F, norm_eps=EPS, attn_kinds=("window",),
        attn_window=8, attn_gate=True, n_experts=E, moe_top_k=K,
        moe_dropless=True, moe_experts_held=routed["w_gate"].shape[0],
        moe_first_expert=first, moe_scoring="sigmoid", moe_score_scale=SCALE,
        moe_shared_ff=F)
    lp = {"attn_norm": jnp.ones((D,)), "mlp_norm": jnp.ones((D,)),
          "attn_window": {"wq": jnp.zeros((D, 2, 16)),
                          "wk": jnp.zeros((D, 1, 16)),
                          "wv": jnp.zeros((D, 1, 16)),
                          "wo": jnp.zeros((2, 16, D)),
                          "wg": jnp.zeros((D, 2))},
          "moe": routed, "shared": shared}
    with jax.default_matmul_precision("highest"):
        out, _, stats = tfm.block(lp, x[None], cfg=model, is_moe=True,
                                  pos=jnp.arange(x.shape[0]), kind="window",
                                  attn_impl="reference", with_stats=True)
    return out[0] - x, stats


def test_eight_shares_and_one_shared_expert_add_up_to_the_uncut_layer(
        bench, sparse):
    """Eight chips hold two of the 16 experts each and the shared expert
    whole; the shares' parts, with the shared expert counted once (the other
    seven shares computed with theirs zeroed), are the uncut reference's
    whole sparse layer, and every pick lands on exactly one share."""
    full, shared, x = sparse
    no_shared = {**shared, "w_down": jnp.zeros_like(shared["w_down"])}
    parts = [program_sparse(share_of(full, first, 2),
                            shared if first == 0 else no_shared, x, first)
             for first in range(0, E, 2)]
    assert close(sum(out for out, _ in parts),
                 reference_sparse(bench, full, shared, x, 0))
    assert sum(float(st["rows_here"]) for _, st in parts) == T * K
    assert all(float(st["dropped"]) == 0 for _, st in parts)


@pytest.mark.parametrize("first,held", [(4, 4), (0, E)])
def test_sigmoid_routed_layer_agrees_with_the_reference(bench, sparse, first,
                                                        held):
    """Value and every gradient (input, router, stacks, shared expert) with
    4 of 16 held and with all held."""
    full, shared, x = sparse
    routed = share_of(full, first, held)
    out, stats = program_sparse(routed, shared, x, first)
    assert close(out, reference_sparse(bench, routed, shared, x, first))
    assert float(stats["dropped"]) == 0
    # the picks' scores summed a token lie between k / 2 (every pick above
    # the median score of one half) and k
    assert K / 2 < float(stats["score_sum_mean"]) < K

    def summed(f):
        return lambda r, s, x: jnp.sum(f(r, s, x) * jnp.cos(jnp.arange(D)))

    got = jax.grad(summed(lambda r, s, x: program_sparse(r, s, x, first)[0]),
                   (0, 1, 2))(routed, shared, x)
    want = jax.grad(summed(
        lambda r, s, x: reference_sparse(bench, r, s, x, first)),
        (0, 1, 2))(routed, shared, x)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert close(g, w)


def test_every_pick_on_one_expert_is_still_exact(bench, sparse):
    full, shared, x = sparse
    # every token scores expert 3 far above the rest
    x = jnp.abs(x)
    crowded = dict(full, router=full["router"].at[:, 3].set(50.0))
    mine = share_of(crowded, 0, 4)
    out, stats = program_sparse(mine, shared, x, 0)
    assert float(stats["dropped"]) == 0 and float(stats["rows_here"]) >= T
    assert float(stats["load_max_over_mean"]) >= 1
    assert close(out, reference_sparse(bench, mine, shared, x, 0))
    out, stats = program_sparse(crowded, shared, x, 0)
    assert float(stats["dropped"]) == 0
    assert float(stats["rows_here"]) == T * K
    assert close(out, reference_sparse(bench, crowded, shared, x, 0))


# -- rotary per kind ----------------------------------------------------------

def test_yarn_frequencies_and_partial_rotation_by_hand():
    """Head of 8, half of it rotated (two pairs), base 100, YaRN factor 4
    over an original length of 16, beta_fast 2, beta_slow 1, attention
    factor 1.2.  By hand: unscaled frequencies 100^(0) = 1 and 100^(-1/2) =
    0.1; the pair that turns twice over 16 positions is 4 ln(16 / 4 pi) / (2
    ln 100) = 0.105 -> floor 0, the one that turns once 4 ln(16 / 2 pi) / (2
    ln 100) = 0.406 -> ceil 1; ramp (0, 1): pair 0 keeps 1, pair 1 becomes
    0.1 / 4 = 0.025.  The row [1..8] at positions 0..3: pairs (1, 2) and
    (3, 4) turned by p and 0.025 p and scaled by 1.2, (5, 6, 7, 8) as they
    are."""
    spec = tfm.RopeSpec(theta=100.0, rotary_share=0.5, yarn_factor=4.0,
                        yarn_original_len=16, yarn_beta_fast=2.0,
                        yarn_beta_slow=1.0, attention_factor=1.2)
    np.testing.assert_allclose(spec.inv_freq(8), [1.0, 0.025], rtol=1e-12)
    plain = tfm.RopeSpec(theta=100.0, rotary_share=0.5)
    np.testing.assert_allclose(plain.inv_freq(8), [1.0, 0.1], rtol=1e-12)
    x = jnp.tile(jnp.arange(1.0, 9.0), (1, 1, 4, 1))        # (B, H, S, D)
    got = np.asarray(tfm.rotary(x, jnp.arange(4), 100.0, spec))[0, 0]
    want = np.array([
        [1.2, 2.4, 3.6, 4.8],
        [-1.371168, 2.306491, 3.478888, 4.888491],
        [-2.68169, 0.092405, 3.355601, 4.973926],
        [-1.526679, -2.206638, 3.230217, 5.056253]])
    np.testing.assert_allclose(got[:, :4], want, atol=2e-6)
    np.testing.assert_array_equal(got[:, 4:], np.tile([5., 6., 7., 8.],
                                                      (4, 1)))
    # a plain spec at the model's base is the historical rotation
    model = tfm.TransformerConfig(rope_theta=100.0, attn_kinds=("global",) * 4,
                                  rope_by_kind=(("global",
                                                 tfm.RopeSpec(theta=100.0)),))
    assert model.rope("global") is None


def test_the_published_yarn_ramp():
    """Laguna-XS.2's global layers: 32 rotated pairs at base 500,000, factor
    64 over 4,096 positions, beta_fast 64, beta_slow 1: pairs 0-5 keep their
    frequency, pairs 16-31 have it divided by 64, a linear ramp between
    (low 5, high 16: 64 ln(4096 / (2 pi b)) / (2 ln 500000) = 5.66 and
    15.80)."""
    spec = tfm.RopeSpec(theta=5e5, rotary_share=0.5, yarn_factor=64.0,
                        yarn_original_len=4096, yarn_beta_fast=64.0,
                        yarn_beta_slow=1.0,
                        attention_factor=1.4158883083359672)
    inv = spec.inv_freq(128)
    base = 5e5 ** (-np.arange(32) / 32.0)
    assert inv.shape == (32,)
    np.testing.assert_allclose(inv[:6], base[:6], rtol=1e-12)
    np.testing.assert_allclose(inv[16:], base[16:] / 64, rtol=1e-12)
    np.testing.assert_allclose(inv[10], base[10] * (1 - 5 / 11)
                               + base[10] / 64 * 5 / 11, rtol=1e-12)


# -- what stays as it was, and what refuses the new -------------------------------

# sha256 of the lowered text of SmallThinker's tiny train step
# (``test_moe_window.TINY`` through ``program.build_trainer``), first taken at
# the parent of the PR that brought the mechanisms above (commit a2698a2): with
# the new configuration fields at their defaults that program is today's, as
# the dense model's is (``test_moe_window.DENSE_STEP``).  Moved by PR 34, by
# ``ops/attention.py`` alone (e5035883... before): the flash kernels read a
# tile's liveness off one predicate and the global layer's index maps hold a
# dead step on the nearest live tile; the kernels' bodies are the parent's.
ROUTED_STEP = "c74d38eb79b320a5b5d501b76f7e3b5078a3ac2cb6188c0f844ae3c8a16dc5bc"


def test_the_routed_models_step_program_is_unchanged(bench):
    import families
    from test_moe_window import HP as hp, TINY

    cell = {"family": families.load("moe_window_gqa"), "config_file": TINY,
            "mix": {"trainer": {**hp, "compute_dtype": "float32",
                                "loss_impl": "dense", "remat": "none"}}}
    trainer = bench["program"].build_trainer(cell, jax.devices()[:1], seed=3)
    assert lm.step_metric_names(trainer.cfg.model) == lm.MOE_METRICS
    tok = jnp.zeros((2, 128), jnp.int32)
    text = lm.make_lm_train_step(trainer.cfg, trainer.mesh).lower(
        trainer.params, trainer.opt_state, tok, tok).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == ROUTED_STEP


DROPLESS = {"n_experts": 8, "moe_top_k": 2, "moe_dropless": True}


@pytest.mark.parametrize("change,named", [
    ({**DROPLESS, "moe_scoring": "sigmoid"}, "scores by sigmoid"),
    ({**DROPLESS, "moe_shared_ff": 16}, "shared expert"),
    ({**DROPLESS, "n_dense_layers": 1, "d_ff_dense": 64},
     "leading dense layers"),
    ({"attn_kinds": ("global", "window"), "attn_window": 8,
      "heads_by_kind": (("window", 4),)}, "head count per attention kind"),
    ({"attn_kinds": ("global", "global"), "rope_by_kind": (
        ("global", tfm.RopeSpec(rotary_share=0.5)),)},
     "rotary settings per attention kind"),
    ({"attn_gate": True}, "gate on the attention output"),
])
def test_decode_and_serving_refuse_each_new_mechanism_by_name(change, named):
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                                n_heads=2, head_dim=16, d_ff=32, **change)
    params = tfm.init(jax.random.key(0), cfg)
    with pytest.raises(NotImplementedError, match=named):
        generate(params, jnp.zeros((1, 4), jnp.int32), jax.random.key(0),
                 cfg=cfg, max_new=2)
    with pytest.raises(NotImplementedError, match=named):
        ContinuousBatcher(params, cfg, slots=2, max_len=32)


@pytest.mark.parametrize("change,named", [
    ({"moe_scoring": "sigmoid"}, "dropless"),
    ({"moe_shared_ff": 16}, "dropless"),
    ({"n_dense_layers": 1}, "dropless"),
    ({**DROPLESS, "moe_scoring": "tanh"}, "moe_scoring"),
    ({**DROPLESS, "moe_score_scale": 2.5}, "moe_score_scale"),
    ({**DROPLESS, "n_dense_layers": 3}, "n_dense_layers"),
    ({"heads_by_kind": (("window", 4),)}, "heads_by_kind"),
    ({"n_kv_heads": 2, "attn_kinds": ("global", "window"), "attn_window": 4,
      "heads_by_kind": (("window", 3),)}, "not divisible"),
])
def test_a_configuration_that_cannot_be_is_refused(change, named):
    with pytest.raises(ValueError, match=named):
        tfm.TransformerConfig(n_layers=2, n_heads=2, **change)


def test_rotary_settings_that_cannot_be_are_refused():
    with pytest.raises(ValueError, match="rotary_share"):
        tfm.RopeSpec(rotary_share=0.0)
    with pytest.raises(ValueError, match="yarn_original_len"):
        tfm.RopeSpec(yarn_factor=4.0)


def test_every_kinds_heads_must_divide_over_tp(bench):
    model = bench["family"].program.model_config(bench["cfg"])
    dense = dataclasses.replace(
        model, moe_dropless=False, n_experts=0, moe_scoring="softmax",
        moe_score_scale=1.0, moe_shared_ff=0, n_dense_layers=0,
        d_ff_dense=None, moe_experts_held=None, tie_embeddings=True)
    with pytest.raises(ValueError, match="n_heads 6 must divide over tp=4"):
        lm.validate_lm_cfg(lm.LMTrainConfig(model=dense, tp=4, dp=1))
    with pytest.raises(ValueError, match="tp=2"):     # the dropless layer's
        lm.validate_lm_cfg(lm.LMTrainConfig(model=model, tp=2, dp=1))


def test_the_new_counters_reach_telemetry_by_name(bench, tmp_path):
    from distributed_pytorch_tpu.utils import telemetry

    trainer = tiny_trainer(bench)
    telemetry.enable(str(tmp_path), rank=0)
    try:
        trainer.train_step(*batch(bench))
    finally:
        telemetry.disable()     # flushes the step's deferred gauges
    (_, records), = telemetry.read_run(str(tmp_path))
    gauges = {r["name"]: r["value"] for r in records if r["type"] == "gauge"}
    assert gauges["moe.dropped"] == 0 and gauges["moe.rows_here"] > 0
    # random gates average a half; four picks of sixteen sigmoid scores
    assert 0.3 < gauges["attn.gate_mean"] < 0.7
    assert 2 < gauges["moe.score_sum_mean"] < 4


@pytest.mark.parametrize("window", [None, 48, 300])
def test_the_references_blocked_attention_is_plain_attention(bench, window):
    """The family's reference takes attention in blocks of query rows (a
    scan; K/V at their own head count, padded in front under a window):
    three blocks of 256 rows against the whole score matrix at once, value
    and gradients."""
    fam = bench["family"].reference
    s, h, kv, d = 3 * fam.Q_BLOCK, 6, 2, 16
    q, k, v = (jax.random.normal(key, shape) for key, shape in zip(
        jax.random.split(jax.random.key(4), 3),
        ((s, h, d), (s, kv, d), (s, kv, d))))

    def plain(q, k, v):
        k, v = jnp.repeat(k, h // kv, 1), jnp.repeat(v, h // kv, 1)
        sc = jnp.einsum("qhd,khd->hqk", q, k) / d ** 0.5
        i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
        seen = (i >= j) if window is None else (i >= j) & (j > i - window)
        p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    def summed(f):
        return lambda *a: jnp.sum(f(*a) * jnp.cos(jnp.arange(s * h * d)
                                                  ).reshape(s, h, d))

    with jax.default_matmul_precision("highest"):
        assert close(fam.attention(q, k, v, window), plain(q, k, v))
        for g, w in zip(
                jax.grad(summed(lambda *a: fam.attention(*a, window)),
                         (0, 1, 2))(q, k, v),
                jax.grad(summed(plain), (0, 1, 2))(q, k, v)):
            assert close(g, w)
