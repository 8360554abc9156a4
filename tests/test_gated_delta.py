"""The mechanisms a linear/full-attention hybrid needs on the training path
(Qwen3-Next's block): the gated delta rule in chunks and the causal
convolution before it (``ops/gated_delta.py``), a linear-attention layer
kind, an element-wise output gate from the second half of each head's query
projection, QK-norm, norm scales applied as ``1 + w``, and a sigmoid gate on
the shared expert -- each against plain code, at small sizes on the CPU with
seeded weights.

The chunked form's oracle is the recurrence token by token; the model's is
the benchmark's reference for the family
(``benchmarks/families/moe_gdn_hybrid/reference.py``), which takes the delta
rule token by token too and imports nothing of the program.  The decay is
tried at three settings: the benchmark's own init (``A_log``, ``dt_bias``
near one), upstream's (``A = U(1, 16)``, ``dt_bias = 1``) and a long memory
(``A = 1e-3``) under which the state carried between chunks is most of the
output, so a fault in that carry cannot hide.
"""

import dataclasses
import hashlib
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_tpu import lm
from distributed_pytorch_tpu.generate import generate
from distributed_pytorch_tpu.models import transformer as tfm
from distributed_pytorch_tpu.ops import gated_delta as gd
from distributed_pytorch_tpu.ops.nn import masked_ce
from distributed_pytorch_tpu.serve import ContinuousBatcher

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
TOL = 2e-5
HP = {"lr": 3e-4, "weight_decay": 0.1, "b1": 0.9, "b2": 0.95,
      "grad_clip": 1.0}


def close(a, b, tol=TOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b).max() <= tol * max(np.abs(b).max(), 0.1)


# -- the delta rule ----------------------------------------------------------------

def decay_inputs(setting, key, shape):
    """(A_log, dt_bias) per value head and the decay inputs a per token."""
    ka, kb, kc = jax.random.split(key, 3)
    h = shape[-1]
    a = jax.random.normal(kc, shape)
    if setting == "shared":     # the benchmark's plain scales, near one
        return (1 + 0.1 * jax.random.normal(ka, (h,)),
                1 + 0.1 * jax.random.normal(kb, (h,)), a)
    if setting == "upstream":
        return jnp.log(jax.random.uniform(ka, (h,), minval=1.0, maxval=16.0)
                       ), jnp.ones((h,)), a
    return jnp.full((h,), math.log(1e-3)), jnp.ones((h,)), a    # long memory


def rule_inputs(setting, t, b=1, h=3, dk=16, dv=8, seed=0):
    keys = jax.random.split(jax.random.key(seed), 5)
    l2 = lambda x: x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
    q = l2(jax.random.normal(keys[0], (b, t, h, dk))) / math.sqrt(dk)
    k = l2(jax.random.normal(keys[1], (b, t, h, dk)))
    v = jax.random.normal(keys[2], (b, t, h, dv))
    a_log, dt_bias, a = decay_inputs(setting, keys[3], (b, t, h))
    g = -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias)
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (b, t, h)))
    return q, k, v, g, beta


def summed(fn, dv=8):
    return lambda *a: jnp.sum(fn(*a)[0] * jnp.cos(jnp.arange(dv)))


@pytest.mark.parametrize("setting", ["shared", "upstream", "long"])
@pytest.mark.parametrize("t", [128, 150])
def test_chunked_rule_is_the_recurrence(setting, t):
    """Chunks of 64 against the recurrence token by token, output, final
    state and every input's gradient; 150 positions are two chunks and a
    padded third."""
    args = rule_inputs(setting, t)
    with jax.default_matmul_precision("highest"):
        want, want_s = gd.gated_delta_recurrent(*args)
        got, got_s, norm_max = gd.gated_delta_chunked(*args)
        assert got.shape == want.shape and close(got, want)
        assert close(got_s, want_s)
        assert float(norm_max) >= float(jnp.max(jnp.sqrt(jnp.sum(
            want_s ** 2, (-2, -1))))) * (1 - 1e-5)
        for g, w in zip(
                jax.grad(summed(gd.gated_delta_chunked), range(5))(*args),
                jax.grad(summed(gd.gated_delta_recurrent), range(5))(*args)):
            assert close(g, w, 1e-4)


def test_the_long_memory_case_lives_on_the_carried_state(monkeypatch):
    """With A = 1e-3 the state carried into a chunk is most of its output:
    zero it and the chunked form is far from the recurrence (under the
    shared init it still moves the chunk's first positions)."""
    args = rule_inputs("long", 192)
    want, _ = gd.gated_delta_recurrent(*args)
    carry = gd._carry
    monkeypatch.setattr(gd, "_carry", lambda s, x: carry(0.0 * s, x))
    cut, _, _ = gd.gated_delta_chunked(*args)
    late = np.s_[:, 64:]
    assert not close(cut[late], want[late], 0.1)
    shared = rule_inputs("shared", 192)
    cut, _, _ = gd.gated_delta_chunked(*shared)
    assert not close(cut[:, 64:66], gd.gated_delta_recurrent(*shared)[0]
                     [:, 64:66], 1e-3)


@pytest.mark.parametrize("setting", ["shared", "upstream", "long"])
@pytest.mark.parametrize("t", [128, 150])
def test_the_kernels_are_the_recurrence(setting, t):
    """The two kernels (interpret mode) against the recurrence token by
    token: output, final state, the largest state norm and every input's
    gradient, through the output and the final state alike."""
    args = rule_inputs(setting, t)
    w = jax.random.normal(jax.random.key(9), (1, 3, 16, 8))

    def through_both(fn):
        def loss(*a):
            o, s = fn(*a)[:2]
            return summed(lambda *_: (o,))() + jnp.sum(s * w)
        return loss

    with jax.default_matmul_precision("highest"):
        want, want_s = gd.gated_delta_recurrent(*args)
        got, got_s, norm_max = gd.gated_delta(*args)
        assert got.shape == want.shape and close(got, want)
        assert close(got_s, want_s)
        _, chunked_s, chunked_max = gd.gated_delta_chunked(*args)
        assert abs(float(norm_max) - float(chunked_max)) <= 1e-5 * float(
            chunked_max)
        for g, w_ in zip(
                jax.grad(through_both(gd.gated_delta), range(5))(*args),
                jax.grad(through_both(gd.gated_delta_recurrent), range(5))(
                    *args)):
            assert close(g, w_, 1e-4)


def test_the_kernels_long_memory_lives_on_the_carried_state(monkeypatch):
    """Under a long memory the forward kernel with the state entering each
    chunk forced to zero is far from the recurrence after the first
    chunk."""
    args = rule_inputs("long", 192)
    want, _ = gd.gated_delta_recurrent(*args)
    assert close(gd.gated_delta(*args)[0], want)
    kernel = gd._fwd_kernel

    def cut(q, k, v, g, b, o, s_out, *rest):
        s_out[...] = jnp.zeros_like(s_out)
        kernel(q, k, v, g, b, o, s_out, *rest)

    monkeypatch.setattr(gd, "_fwd_kernel", cut)
    got = gd.gated_delta(*args)[0]
    assert close(got[:, :64], want[:, :64])
    assert not close(got[:, 64:], want[:, 64:], 0.1)


def test_the_kernels_at_the_cells_widths_are_the_chunked_form():
    """Key and value heads of 128, each of 8 key heads serving two of 16
    value heads (the kernels take 8 value heads a pass in float32, so the
    key heads are read and their gradients summed a pass at a time), three
    chunks: the kernels, handed q and k at the key heads' count, against
    the XLA chunked form of q and k repeated, output, final state, the
    largest state norm and every input's gradient."""
    q, k, v, g, beta = rule_inputs("upstream", 192, h=8, dk=128, dv=128)
    v = jnp.concatenate([v, -v[..., ::-1]], axis=2)
    g, beta = (jnp.concatenate([x, x[:, ::-1]], axis=2) for x in (g, beta))
    args = q, k, v, g, beta

    def repeated(q, k, *rest):
        return gd.gated_delta_chunked(jnp.repeat(q, 2, axis=2),
                                      jnp.repeat(k, 2, axis=2), *rest)

    with jax.default_matmul_precision("highest"):
        for a, b in zip(gd.gated_delta(*args), repeated(*args)):
            assert close(a, b)
        for a, b in zip(
                jax.grad(summed(gd.gated_delta, 128), range(5))(*args),
                jax.grad(summed(repeated, 128), range(5))(*args)):
            assert a.shape == b.shape and close(a, b, 1e-4)


def test_the_kernels_inverse_holds_where_a_chunks_keys_are_alike():
    """The inverse of ``I + A`` the kernels take, against float64: where
    every key of a chunk is one key (beta 0.99, no decay) or keys share
    most of their direction, the powers of A reach 1e13, and a sum of them
    (the unipotent product) would cancel its own digits away; the inverse
    from blocks along the diagonal is as close as a float32 solve."""
    rng = np.random.default_rng(0)
    base = rng.normal(size=128)
    keys = [np.tile(base, (64, 1)),
            base + 0.3 * rng.normal(size=(64, 128)),
            rng.normal(size=(64, 128))]
    a = []
    for k in keys:
        k = k / np.linalg.norm(k, axis=1, keepdims=True)
        a.append(np.tril(0.99 * (k @ k.T), -1))
    a = np.stack(a).astype(np.float32)
    assert np.abs(np.linalg.matrix_power(a[0].astype(np.float64), 16)
                  ).max() > 1e12
    got = np.asarray(gd._unit_lower_inverse(jnp.asarray(a)), np.float64)
    want = np.linalg.inv(np.eye(64) + a.astype(np.float64))
    for g, w in zip(got, want):
        assert np.abs(g - w).max() <= 1e-6 * np.abs(w).max()


def test_a_bfloat16_operand_against_the_state_keeps_float32s_precision():
    """The kernels' products at float32's precision where one operand is
    bfloat16 (the WY factor ``w`` against the state, ``Q e^G`` against a
    cotangent): the float32 operand in three bfloat16 pieces gives the
    product to float32's rounding, either operand first; one bfloat16
    pass would be a thousand times further off."""
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(4, 64, 128)), jnp.bfloat16)
    s = jnp.asarray(10 * rng.normal(size=(4, 128, 128)), jnp.float32)
    want = np.einsum("hik,hkj->hij", np.asarray(a, np.float64),
                     np.asarray(s, np.float64))
    scale = np.abs(want).max()
    for got in (gd._mm(a, s, 2, 1), jnp.swapaxes(gd._mm(s, a, 1, 2), 1, 2)):
        assert np.abs(np.asarray(got, np.float64) - want).max() < 1e-6 * scale
    one_pass = np.asarray(gd._mm1(a, s, 2, 1, jnp.bfloat16), np.float64)
    assert np.abs(one_pass - want).max() > 1e-4 * scale


def test_the_kernels_refuse_widths_the_chip_cannot_tile():
    args = rule_inputs("shared", 64)
    with pytest.raises(ValueError, match="multiples of 128"):
        gd.gated_delta(*args, interpret=False)
    q, k, v, g, beta = args
    with pytest.raises(ValueError, match="3 value heads cannot share 2"):
        gd.gated_delta(q[:, :, :2], k[:, :, :2], v, g, beta)


def test_causal_conv_by_hand():
    """Two channels, three taps: position t reads t-2, t-1, t with zeros
    before 0, the last tap on the current position."""
    x = jnp.array([[[1., 10.], [2., 20.], [3., 30.], [4., 40.]]])
    w = jnp.array([[1., 0.], [10., 1.], [100., 2.]])
    want = [[100., 20.], [210., 50.], [321., 80.], [432., 110.]]
    np.testing.assert_allclose(gd.causal_conv(x, w)[0], want)


# -- the model against the family's reference -----------------------------------

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

    fam = families.load("moe_gdn_hybrid")
    with open(os.path.join(BENCH, "configs", "qwen3-next-80b-a3b.json")) as f:
        cfg = {**json.load(f), **fam.weights.tiny, "moe_first_expert": 8}
    yield {"family": fam, "reference": reference, "weights": weights,
           "program": program, "checks": checks, "cfg": cfg}
    sys.path.remove(BENCH)


def tiny_trainer(bench, remat="none"):
    cell = {"family": bench["family"], "config_file": bench["cfg"],
            "mix": {"trainer": {**HP, "compute_dtype": "float32",
                                "loss_impl": "dense", "remat": remat}}}
    return bench["program"].build_trainer(cell, jax.devices()[:1], seed=3)


def batch(bench, seed=0):
    tok = np.random.default_rng(seed).integers(
        0, bench["cfg"]["vocab_size"], (2, 128)).astype(np.int32)
    return tok, np.roll(tok, -1, 1)


def test_the_tiny_model_has_every_mechanism(bench):
    model = bench["family"].program.model_config(bench["cfg"])
    assert model.attn_kinds == ("linear", "linear", "linear", "global")
    assert all(model.is_moe_layer(i) for i in range(4))
    assert model.rope("global").rotary_share == 0.25
    assert (model.attn_gate, model.attn_gate_form, model.qk_norm,
            model.norm_offset, model.moe_shared_gate) == (
        True, "element", True, 1.0, True)
    params = tfm.init(jax.random.key(0), model)
    want = bench["weights"].make_params(bench["family"], 7, bench["cfg"])
    assert jax.tree.structure(params) == jax.tree.structure(want)
    assert [a.shape for a in jax.tree.leaves(params)] == [
        a.shape for a in jax.tree.leaves(want)]
    specs = tfm.shard_specs(model)
    assert jax.tree.structure(specs, is_leaf=lambda x: not isinstance(
        x, dict)) == jax.tree.structure(params)
    # zero-centred scales start at 0 (1 as applied); the gated norm's at 1
    assert float(jnp.abs(params["layer0"]["attn_norm"]).max()) == 0
    assert float(params["layer0"]["attn_linear"]["norm"].min()) == 1
    assert params["layer3"]["attn_global"]["wq"].shape == (64, 4, 64)
    assert params["layer0"]["attn_linear"]["w_qkvz"].shape == (
        64, 2 * 32 + 2 * 64)


@pytest.mark.parametrize("remat", ["none", "full"])
def test_whole_model_step_agrees_with_the_reference(bench, remat):
    """Three linear layers and a full one, the router over experts 8-11 of
    16, the gated shared expert, through ``LMTrainer``'s step (flash
    attention, the chunked delta rule): the loss, the first gradient as
    AdamW gets it and the parameters' change, leaf by leaf, as the
    benchmark compares them; the counters in ``last_metrics``."""
    fam, ref, prog = bench["family"], bench["reference"], bench["program"]
    trainer = tiny_trainer(bench, remat)
    start = bench["weights"].make_params(fam, 7, bench["cfg"])
    prog.reset_trainer(trainer, jax.tree.map(jnp.copy, start))
    tok, tgt = batch(bench)
    loss = float(trainer.train_step(tok, tgt))
    names = lm.step_metric_names(trainer.cfg.model)
    assert names == lm.MOE_METRICS + (
        "attn.gate_mean", "moe.shared_gate_mean", "gdn.decay_mean",
        "gdn.beta_mean", "gdn.state_norm_max")
    met = dict(zip(names, np.asarray(trainer.last_metrics)[2:]))
    assert met["moe.dropped"] == 0 and 0 < met["moe.rows_here"] < 4 * 256 * 4
    for name in ("attn.gate_mean", "moe.shared_gate_mean", "gdn.decay_mean",
                 "gdn.beta_mean"):
        assert 0 < met[name] < 1, name
    assert met["gdn.state_norm_max"] > 0
    mine = {"losses": [loss],
            "grad_norms": np.asarray(ref.leaf_norms(prog.adam_first_moment(
                trainer.opt_state))) / (1 - HP["b1"]),
            "delta_norms": np.asarray(ref.diff_norms(trainer.params, start))}
    theirs = ref.with_delta_norms(
        ref.train_steps(fam.reference, jax.tree.map(jnp.copy, start),
                        [(tok, tgt)], bench["cfg"], HP), start)
    numbers = bench["checks"].train_numbers(mine, theirs)
    assert all(v < 1e-4 for v in numbers.values()), numbers


def _linear(params, i):
    return params[f"layer{i}"]["attn_linear"]


def test_the_plain_attention_path_agrees_leaf_by_leaf(bench):
    """``attn_impl="reference"``: the loss and every leaf's gradient against
    the reference's, and the comparison is not blind to the new mechanisms:
    each planted wrong moves the logits a thousand tolerances."""
    fam, ref = bench["family"], bench["reference"]
    cfg = bench["cfg"]
    model = fam.program.model_config(cfg)
    params = bench["weights"].make_params(fam, 7, cfg)
    tok, tgt = (jnp.asarray(a[:1]) for a in batch(bench))

    def loss_of(p, model=model):
        logits = tfm.apply(p, tok, cfg=model, attn_impl="reference")
        return masked_ce(logits, tgt)[0] / tgt.size

    def layer_with(i, kind, **leaves):
        lp = params[f"layer{i}"]
        return {**params, f"layer{i}": {**lp, kind: {**lp[kind], **leaves}}}

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
        wq = params["layer3"]["attn_global"]["wq"]
        conv = _linear(params, 0)["conv"]
        wrong = {
            "element gate stuck": (model, layer_with(
                3, "attn_global", wq=wq.at[..., 32:].set(0.0))),
            "no QK-norm": (dataclasses.replace(model, qk_norm=False), {
                **params, "layer3": {**params["layer3"], "attn_global": {
                    k: v for k, v in params["layer3"]["attn_global"].items()
                    if not k.endswith("_norm")}}}),
            "plain norm scales": (dataclasses.replace(model, norm_offset=0.0),
                                  params),
            "ungated shared expert": (dataclasses.replace(
                model, moe_shared_gate=False), params),
            "conv reads ahead": (model, layer_with(
                0, "attn_linear", conv=conv[::-1])),
            "beta stuck at a half": (model, layer_with(
                0, "attn_linear",
                w_ba=_linear(params, 0)["w_ba"].at[:, :4].set(0.0))),
        }
        for what, (other, tree) in wrong.items():
            off = tfm.apply(tree, tok, cfg=other, attn_impl="reference")
            assert not close(off, logits, 1e-2), what


def test_sixteen_shares_and_one_shared_expert_add_up_to_the_uncut_layer(
        bench):
    """Sixteen chips hold 4 of the 64 experts each and the mixer, the
    shared expert and its gate whole: each share's layer through the
    program's ``block``, less what every share computes alike (the same
    layer with its held experts adding nothing), summed, plus that common
    part once, is the uncut reference's whole layer."""
    fam = bench["family"]
    cfg = {**bench["cfg"], "moe_router_width": 64, "num_experts": 64,
           "moe_first_expert": 0}
    uncut = bench["weights"].make_params(fam, 5, cfg)["layer0"]
    x = jax.random.normal(jax.random.key(2), (96, cfg["hidden_size"]))

    def share(first, held=4, routed=True):
        moe = {k: (v if k == "router" else v[first:first + held])
               for k, v in uncut["moe"].items()}
        if not routed:
            moe["w_down"] = 0.0 * moe["w_down"]
        return {**uncut, "moe": moe}

    def program(lp, first):
        model = fam.program.model_config(
            {**cfg, "num_experts": 4, "moe_first_expert": first})
        with jax.default_matmul_precision("highest"):
            out, _, stats = tfm.block(
                lp, x[None], cfg=model, is_moe=True, pos=jnp.arange(96),
                kind="linear", attn_impl="reference", with_stats=True)
        return out[0], stats

    common = program(share(0, routed=False), 0)[0]
    parts = [program(share(first), first) for first in range(0, 64, 4)]
    with jax.default_matmul_precision("highest"):
        want = fam.reference.layer(uncut, x, jnp.arange(96), cfg, None)
    assert close(sum(out - common for out, _ in parts) + common, want)
    assert sum(float(st["rows_here"]) for _, st in parts) == 96 * 4
    assert all(float(st["dropped"]) == 0 for _, st in parts)


# -- the new norms and gates, by hand ------------------------------------------------

def test_zero_centred_norm_element_gate_and_qk_norm_by_hand():
    """One full-attention layer, one head of 2 dimensions, one KV head, no
    rotary (a share that rotates none is refused, so positions are all 0),
    two positions, the identity for every projection but ``wo`` (zeros:
    the layer's output is then x + o only through the hand-checked value).

    x = [[3, 4], [0, 2]]; norm scales stored w = [0, 0.5] (applied 1 + w):
    n1 = x / rms(x) * [1, 1.5]: rms [3.5355, 1.4142] -> [[0.8485, 1.6971],
    [0, 2.1213]].  q = k = v = n1 (identity); the gate half of wq is the
    identity too, so gate = n1.  QK-norm with w = [0, 0] rescales each
    row of q and k to rms 1: q0 = k0 = [0.7071, 1.4142] / 1.1180 =
    [0.6325, 1.2649], q1 = k1 = [0, 1.4142].  Position 0 sees only itself:
    o0 = v0 * sigmoid(gate0) = [0.8485 * 0.7003, 1.6971 * 0.8451] =
    [0.5942, 1.4342].  Position 1: scores q1.k0 / sqrt 2 = 1.2649,
    q1.k1 / sqrt 2 = 1.4142, softmax [0.4627, 0.5373]; attention out =
    0.4627 v0 + 0.5373 v1 = [0.3926, 1.9251]; times sigmoid(gate1) =
    [0.5, 0.8930] -> [0.1963, 1.7191]."""
    model = tfm.TransformerConfig(
        vocab_size=8, d_model=2, n_layers=1, n_heads=1, n_kv_heads=1,
        head_dim=2, attn_kinds=("global_nope",), attn_gate=True,
        attn_gate_form="element", qk_norm=True, norm_offset=1.0,
        norm_eps=0.0)
    eye = jnp.eye(2)
    ap = {"wq": jnp.concatenate([eye, eye], 1).reshape(2, 1, 4),
          "wk": eye.reshape(2, 1, 2), "wv": eye.reshape(2, 1, 2),
          "wo": eye.reshape(1, 2, 2), "q_norm": jnp.zeros(2),
          "k_norm": jnp.zeros(2)}
    w = jnp.array([0.0, 0.5])
    x = jnp.array([[[3.0, 4.0], [0.0, 2.0]]])
    with jax.default_matmul_precision("highest"):
        o, stats = tfm._softmax_attention(
            ap, tfm.rms_norm(x, w + 1.0, 0.0), cfg=model, pos=jnp.arange(2),
            kind="global_nope", attn_impl="reference", seq_axis=None,
            seq_layout="contiguous",
            tp_axis=None, proj2d=None, save_attn=False,
            norm=lambda y, s: tfm.rms_norm(y, s + 1.0, 0.0))
        lp = {"attn_norm": w, "mlp_norm": w, "attn_global_nope": ap,
              "w_gate": jnp.zeros((2, 4)), "w_up": jnp.zeros((2, 4)),
              "w_down": jnp.zeros((4, 2))}
        out, _ = tfm.block(lp, x, cfg=model, is_moe=False, pos=jnp.arange(2),
                           kind="global_nope", attn_impl="reference")
    want = np.array([[0.5942, 1.4342], [0.1963, 1.7191]])
    np.testing.assert_allclose(o[0], want, atol=2e-4)
    np.testing.assert_allclose(out[0], np.asarray(x[0]) + want, atol=2e-4)
    gates = [0.7003, 0.8451, 0.5, 0.8930]
    assert abs(float(stats["gate_mean"]) - np.mean(gates)) < 1e-4


def test_the_shared_experts_gate_by_hand():
    """One token x = [1, 1] (rms 1, norm scales 0 + 1), every expert and
    the shared one the same SwiGLU whose output is known, the gate w_sg =
    [1, 1]: sigmoid(2) = 0.8808 of the shared expert's output is added."""
    model = tfm.TransformerConfig(
        vocab_size=8, d_model=2, n_layers=1, n_heads=1, head_dim=2, d_ff=1,
        attn_kinds=("linear",), linear_k_heads=1, linear_v_heads=1,
        linear_k_dim=2, linear_v_dim=2, n_experts=2, moe_top_k=1,
        moe_dropless=True, moe_experts_held=2, moe_shared_ff=1,
        moe_shared_gate=True, norm_offset=1.0, norm_eps=1e-6)
    params = tfm.init(jax.random.key(0), model)["layer0"]
    ones = {"w_gate": jnp.ones((2, 1)), "w_up": jnp.ones((2, 1)),
            "w_down": jnp.ones((1, 2))}
    lp = {**params, "attn_linear": {**params["attn_linear"],
                                    "w_out": jnp.zeros((2, 2))},
          "moe": {**params["moe"], "w_down": jnp.zeros((2, 1, 2))},
          "shared": {**ones, "w_sg": jnp.ones((2, 1))}}
    x = jnp.ones((1, 1, 2))
    out, _, stats = tfm.block(lp, x, cfg=model, is_moe=True, pos=jnp.arange(1),
                              kind="linear", attn_impl="reference",
                              with_stats=True)
    swiglu = 2.0 * jax.nn.sigmoid(2.0) * 2.0    # silu(2) * 2 a unit of down
    gate = float(jax.nn.sigmoid(2.0))
    np.testing.assert_allclose(out[0, 0], 1.0 + gate * swiglu, rtol=1e-5)
    assert abs(float(stats["shared_gate_mean"]) - gate) < 1e-6


# -- what stays as it was, and what refuses the new ---------------------------------

# sha256 of the lowered text of Laguna-XS.2's tiny train step (the family's
# ``tiny`` through ``program.build_trainer``, float32, remat none), taken at
# the parent of the PR that brought the mechanisms above (commit 4464356):
# the attention part of ``block`` moved into ``_softmax_attention`` and the
# program is the parent's.
LAGUNA_STEP = "b7b4dfc0c3bf4387df3aa29b07035b71cdd837be2647a6d35c962b2ac581fc80"


def test_the_shared_gate_models_step_program_is_unchanged(bench):
    import families

    fam = families.load("moe_shared_window_gqa")
    with open(os.path.join(BENCH, "configs", "laguna-xs.2.json")) as f:
        cfg = {**json.load(f), **fam.weights.tiny, "moe_first_expert": 8}
    cell = {"family": fam, "config_file": cfg,
            "mix": {"trainer": {**HP, "compute_dtype": "float32",
                                "loss_impl": "dense", "remat": "none"}}}
    trainer = bench["program"].build_trainer(cell, jax.devices()[:1], seed=3)
    tok = jnp.zeros((2, 128), jnp.int32)
    text = lm.make_lm_train_step(trainer.cfg, trainer.mesh).lower(
        trainer.params, trainer.opt_state, tok, tok).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == LAGUNA_STEP


LINEAR = {"attn_kinds": ("linear", "global"), "linear_k_heads": 1,
          "linear_v_heads": 2, "linear_k_dim": 16, "linear_v_dim": 16}


@pytest.mark.parametrize("change,named", [
    (LINEAR, "linear-attention layers"),
    ({"attn_gate": True, "attn_gate_form": "element"}, "element-wise gate"),
    ({"qk_norm": True}, "QK-norm"),
    ({"norm_offset": 1.0}, "norm_offset"),
    ({"n_experts": 4, "moe_top_k": 2, "moe_dropless": True,
      "moe_shared_ff": 16, "moe_shared_gate": True}, "gate on the shared"),
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
    ({"attn_gate_form": "element"}, "attn_gate_form"),
    ({"attn_gate": True, "attn_gate_form": "row"}, "attn_gate_form"),
    ({"moe_shared_gate": True}, "moe_shared_gate"),
    ({**LINEAR, "linear_v_heads": 3, "linear_k_heads": 2}, "linear_k_heads"),
])
def test_a_configuration_that_cannot_be_is_refused(change, named):
    with pytest.raises(ValueError, match=named):
        tfm.TransformerConfig(n_layers=2, n_heads=2, **change)


@pytest.mark.parametrize("knob", [{"sp": 2}, {"tp": 2}, {"ep": 2},
                                  {"matmul_dtype": "int8"}])
def test_linear_layers_refuse_what_has_no_exchange(knob):
    model = tfm.TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                                  n_heads=2, head_dim=16, n_experts=4,
                                  **LINEAR)
    with pytest.raises(ValueError, match="linear-attention layers"):
        lm.validate_lm_cfg(lm.LMTrainConfig(model=model, dp=1, **knob))


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
    # random gates average a half; the decay of these weights is small
    assert 0.3 < gauges["attn.gate_mean"] < 0.7
    assert 0.3 < gauges["moe.shared_gate_mean"] < 0.7
    assert 0 < gauges["gdn.decay_mean"] < 0.5
    assert 0.3 < gauges["gdn.beta_mean"] < 0.7
    assert gauges["gdn.state_norm_max"] > 0


def test_the_references_delta_rule_is_the_recurrence(bench):
    """The reference's own delta rule (token by token, checkpointed
    segments of 64) against ``gated_delta_recurrent``, at a length that is
    no whole number of segments, value and gradients."""
    fam = bench["family"].reference
    q, k, v, g, beta = rule_inputs("upstream", 150)

    def mine(*a):       # (S, H, D): the reference's layout, one row
        return fam.delta_rule(*(x[0] for x in a))

    def theirs(*a):
        return gd.gated_delta_recurrent(*a)[0][0]

    with jax.default_matmul_precision("highest"):
        assert close(mine(q, k, v, g, beta), theirs(q, k, v, g, beta))
        for a, b in zip(jax.grad(summed(lambda *a: (mine(*a),)), range(5))(
                q, k, v, g, beta),
                jax.grad(summed(lambda *a: (theirs(*a),)), range(5))(
                q, k, v, g, beta)):
            assert close(a, b, 1e-4)
