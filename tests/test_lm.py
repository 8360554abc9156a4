"""3-D-parallel LM trainer tests (lm.py).

The core claim: the training trajectory is invariant to how the mesh is cut
— (dp, sp, tp) of (1,1,1), (2,2,2), (1,4,2) must produce the same losses and
parameters (same seed, same data), exercising ring attention, Megatron TP
psums, and the autodiff-fused DP/SP gradient sync together.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_tpu.lm import (
    IGNORE, LMTrainConfig, LMTrainer, masked_ce)


def _data(b=4, s=256, vocab=1024):
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, vocab, (b, s)).astype(np.int32)
    targets = np.roll(tokens, -1, axis=1).astype(np.int32)
    targets[:, -1] = IGNORE
    return tokens, targets


_BASE_RUN_CACHE: dict = {}


def _base_run(steps=3):
    """The (1,1,1) baseline trajectory the mesh-layout tests compare
    against, computed ONCE per suite process (ROADMAP wall-time policy:
    consolidate same-shape LMTrainer builds — this run repeated
    identically per parametrization before round 5)."""
    if "traj" not in _BASE_RUN_CACHE:
        from distributed_pytorch_tpu.models import transformer as tfm
        model = tfm.TransformerConfig(vocab_size=256, d_model=128,
                                      n_layers=2, n_heads=2, head_dim=64,
                                      d_ff=256)
        tokens, targets = _data(s=128, vocab=256)
        tr = LMTrainer(LMTrainConfig(model=model, compute_dtype=None))
        losses = [float(tr.train_step(tokens, targets))
                  for _ in range(steps)]
        _BASE_RUN_CACHE["traj"] = (
            model, tokens, targets, losses,
            jax.tree.map(np.asarray, tr.params))
    return _BASE_RUN_CACHE["traj"]


@pytest.mark.parametrize("dp,sp,tp", [(2, 2, 2), (1, 4, 2)])
def test_trajectory_invariant_to_mesh_layout(dp, sp, tp):
    # Small explicit model: the invariance property is dimension-independent
    # and VGG/LM-tiny-sized compiles dominate one-core suite time.
    model, tokens, targets, base_losses, base_params = _base_run()
    cfg = LMTrainConfig(model=model, dp=dp, sp=sp, tp=tp,
                        compute_dtype=None)
    tr = LMTrainer(cfg)
    losses = [float(tr.train_step(tokens, targets)) for _ in range(3)]
    np.testing.assert_allclose(losses, base_losses, rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(base_params),
                    jax.tree.leaves(jax.tree.map(np.asarray, tr.params))):
        # atol absorbs Adam's amplification of f32 reduction-order noise on
        # near-zero gradient entries
        np.testing.assert_allclose(b, a, rtol=1e-4, atol=5e-4)


def test_loss_falls():
    from distributed_pytorch_tpu.models import transformer as tfm
    model = tfm.TransformerConfig(vocab_size=256, d_model=128, n_layers=2,
                                  n_heads=2, head_dim=64, d_ff=256)
    tokens, targets = _data(b=2, s=128, vocab=256)
    tr = LMTrainer(LMTrainConfig(model=model, dp=2, sp=2, tp=2,
                                 compute_dtype=None))
    losses = [float(tr.train_step(tokens, targets)) for _ in range(6)]
    assert losses[-1] < losses[0], losses
    assert np.isfinite(losses).all()


def test_masked_ce_ignores_padding():
    logits = jnp.zeros((2, 4, 8))
    targets = jnp.array([[1, 2, IGNORE, IGNORE], [3, IGNORE, IGNORE, IGNORE]])
    ce, n = masked_ce(logits, targets)
    assert int(n) == 3
    np.testing.assert_allclose(float(ce) / int(n), np.log(8), rtol=1e-6)


def test_mesh_size_mismatch_raises():
    with pytest.raises(AssertionError, match="devices"):
        from distributed_pytorch_tpu.lm import make_lm_mesh
        cfg = LMTrainConfig(dp=2, sp=2, tp=2)
        mesh = make_lm_mesh(LMTrainConfig(dp=1, sp=1, tp=2))
        LMTrainer(cfg, mesh=mesh)


def test_bf16_compute_trains():
    tokens, targets = _data(b=2, s=128)
    tr = LMTrainer(LMTrainConfig(dp=1, sp=2, tp=1, compute_dtype="bfloat16"))
    loss = float(tr.train_step(tokens, targets))
    assert np.isfinite(loss)


def test_pipeline_parallel_matches_dense():
    """GPipe over 'pipe' (and composed with dp) must reproduce the dense
    single-device trajectory exactly (same loss mean over microbatches)."""
    from distributed_pytorch_tpu.models import transformer as tfm

    model = tfm.TransformerConfig(vocab_size=256, d_model=128, n_layers=4,
                                  n_heads=2, head_dim=64, d_ff=256)
    tokens, targets = _data(b=8, s=64, vocab=256)
    runs = {}
    for name, kw in {"base": dict(), "pp4": dict(pp=4),
                     "dp2pp2": dict(dp=2, pp=2)}.items():
        cfg = LMTrainConfig(model=model, compute_dtype=None, **kw)
        tr = LMTrainer(cfg)
        runs[name] = [float(tr.train_step(tokens, targets))
                      for _ in range(3)]
    np.testing.assert_allclose(runs["pp4"], runs["base"], rtol=1e-5)
    np.testing.assert_allclose(runs["dp2pp2"], runs["base"], rtol=1e-5)


def test_pipeline_split_merge_roundtrip():
    from distributed_pytorch_tpu.models import transformer as tfm
    from distributed_pytorch_tpu.parallel import pipeline as pp

    model = tfm.TransformerConfig(vocab_size=128, d_model=64, n_layers=4,
                                  n_heads=1, head_dim=64)
    params = tfm.init(jax.random.key(0), model)
    stages, shared = pp.split_layer_params(params, model, 2)
    merged = pp.merge_layer_params(stages, shared, model)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(merged)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_moe_lm_mesh_parity_and_training():
    """MoE transformer: expert-parallel trajectory == single device (CE
    only — per-group aux means differ by construction), and training with
    the aux on reduces the loss."""
    from distributed_pytorch_tpu.models import transformer as tfm

    model = tfm.TransformerConfig(vocab_size=512, d_model=128, n_layers=2,
                                  n_heads=4, head_dim=32, n_experts=4,
                                  capacity_factor=8.0)  # no drops => parity
    tokens, targets = _data(b=4, s=64, vocab=512)
    runs = {}
    for name, kw in {"base": dict(), "ep4": dict(tp=4),
                     "3d": dict(dp=2, sp=2, tp=2)}.items():
        cfg = LMTrainConfig(model=model, compute_dtype=None, aux_coef=0.0,
                            **kw)
        tr = LMTrainer(cfg)
        runs[name] = [float(tr.train_step(tokens, targets))
                      for _ in range(3)]
    np.testing.assert_allclose(runs["ep4"], runs["base"], rtol=1e-5)
    np.testing.assert_allclose(runs["3d"], runs["base"], rtol=1e-5)

    tr = LMTrainer(LMTrainConfig(model=model, compute_dtype=None, tp=4))
    losses = [float(tr.train_step(tokens, targets)) for _ in range(5)]
    assert losses[-1] < losses[0]


def test_pp_with_sp_matches_dense_oracle():
    """pp x sp composition (round 2): ring attention inside pipeline
    stages over a (data, pipe, seq) mesh follows the dense single-device
    trajectory exactly (same seed, same data, f32)."""
    from distributed_pytorch_tpu.models import transformer as tfm

    model = tfm.TransformerConfig(vocab_size=128, d_model=128, n_layers=2,
                                  n_heads=2, head_dim=64, d_ff=256)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 128, (8, 128)).astype(np.int32)
    targets = np.roll(tokens, -1, 1).astype(np.int32)
    targets[:, -1] = IGNORE

    losses = {}
    for name, kw in (("dense", dict(dp=1)),
                     ("pp2sp2", dict(dp=1, pp=2, sp=2, microbatches=4)),
                     ("pp2sp2dp2", dict(dp=2, pp=2, sp=2, microbatches=2))):
        tr = LMTrainer(LMTrainConfig(model=model, compute_dtype=None, **kw))
        losses[name] = [float(tr.train_step(tokens, targets))
                        for _ in range(2)]
    np.testing.assert_allclose(losses["pp2sp2"], losses["dense"], rtol=2e-4)
    np.testing.assert_allclose(losses["pp2sp2dp2"], losses["dense"],
                               rtol=2e-4)


def test_fsdp_shards_params_and_matches_dense():
    """ZeRO-3 (fsdp): params/optimizer sharded over 'data', trajectory
    identical to plain DP, checkpoint round-trips, composes with tp."""
    from distributed_pytorch_tpu.models import transformer as tfm

    model = tfm.TransformerConfig(vocab_size=512, d_model=128, n_layers=2,
                                  n_heads=4, head_dim=32)
    tokens, targets = _data(b=8, s=128, vocab=512)
    runs = {}
    for name, kw in {"dp4": dict(dp=4), "fsdp4": dict(dp=4, fsdp=True),
                     "fsdp4tp2": dict(dp=4, tp=2, fsdp=True)}.items():
        cfg = LMTrainConfig(model=model, compute_dtype=None, **kw)
        tr = LMTrainer(cfg)
        runs[name] = ([float(tr.train_step(tokens, targets))
                       for _ in range(3)], tr)
    np.testing.assert_allclose(runs["fsdp4"][0], runs["dp4"][0], rtol=1e-5)
    np.testing.assert_allclose(runs["fsdp4tp2"][0], runs["dp4"][0],
                               rtol=1e-5)
    # local shard is 1/dp of the global embed; adam mu shards identically
    tr = runs["fsdp4"][1]
    emb = tr.params["embed"]
    assert emb.addressable_shards[0].data.shape[0] == emb.shape[0] // 4
    mu = tr.opt_state[1][0].mu["embed"]
    assert mu.addressable_shards[0].data.shape[0] == mu.shape[0] // 4


def test_fsdp_overlap_streams_gathers_and_is_bitwise():
    """Streaming ZeRO-3 (round 8, overlap=True): per-layer-group weight
    gathers at the transformer's boundary hook.  Two pins: (a) the
    trajectory — params AND optimizer state — is BITWISE identical to the
    all-at-once gather over a multi-step run (same ops, moved); (b) the
    compiled program actually streams: with overlap the all_gathers are
    interleaved between matmuls, without it every gather precedes the
    first matmul of the step (utils/debug.py op_schedule)."""
    from distributed_pytorch_tpu.lm import make_lm_mesh, make_lm_train_step
    from distributed_pytorch_tpu.lm import make_optimizer as lm_opt
    from distributed_pytorch_tpu.models import transformer as tfm
    from distributed_pytorch_tpu.utils import debug as dbg

    model = tfm.TransformerConfig(vocab_size=256, d_model=128, n_layers=2,
                                  n_heads=2, head_dim=64, d_ff=256)
    tokens, targets = _data(b=8, s=64, vocab=256)

    def run(overlap):
        cfg = LMTrainConfig(model=model, dp=4, fsdp=True, overlap=overlap,
                            compute_dtype=None)
        tr = LMTrainer(cfg)
        for _ in range(3):
            tr.train_step(tokens, targets)
        return jax.tree.map(lambda x: np.array(x, copy=True),
                            (tr.params, tr.opt_state))

    base, over = run(False), run(True)
    for a, b in zip(jax.tree.leaves(base), jax.tree.leaves(over)):
        np.testing.assert_array_equal(a, b)

    def gather_positions(overlap):
        cfg = LMTrainConfig(model=model, dp=4, fsdp=True, overlap=overlap,
                            compute_dtype=None)
        step = make_lm_train_step(cfg, make_lm_mesh(cfg))
        params = tfm.init(jax.random.key(0), model)
        opt = lm_opt(cfg).init(params)
        sched = dbg.op_schedule(step, params, opt, tokens, targets)
        comp = [i for i, r in enumerate(sched) if r["kind"] == "compute"]
        gathers = [i for i, r in enumerate(sched)
                   if r["prim"] == "all_gather"]
        assert gathers, "fsdp step lost its gathers"
        return sum(1 for i in gathers if comp[0] < i < comp[-1])

    assert gather_positions(False) == 0      # all-at-once, pre-backbone
    assert gather_positions(True) >= model.n_layers  # streamed per group


def test_lm_overlap_validation():
    """overlap=True streams ZeRO-3 gathers and/or the factored-mesh DCN
    sync points; with NEITHER fsdp nor dcn_size > 1 there is nothing to
    stream (the data-axis cotangent psums already sit at use sites) and
    it must refuse, not silently no-op.  The round-8 overlap+dcn refusal
    is GONE (round 9): the streamed two-level sync composes — with and
    without fsdp."""
    from distributed_pytorch_tpu.lm import validate_lm_cfg
    with pytest.raises(ValueError, match="fsdp"):
        validate_lm_cfg(LMTrainConfig(dp=4, overlap=True))
    # round 9: the previously-raising compositions are now valid configs
    validate_lm_cfg(LMTrainConfig(dp=4, dcn_size=2, fsdp=True,
                                  overlap=True))
    validate_lm_cfg(LMTrainConfig(dp=4, dcn_size=2, overlap=True))
    validate_lm_cfg(LMTrainConfig(dp=4, dcn_size=2, grad_accum=2,
                                  fsdp=True, overlap=True))
    # ... but dcn + grad_accum WITHOUT fsdp still refuses: the one
    # post-accumulation exchange sits outside the backward, so overlap
    # would be a silent no-op there
    with pytest.raises(ValueError, match="fsdp"):
        validate_lm_cfg(LMTrainConfig(dp=4, dcn_size=2, grad_accum=2,
                                      overlap=True))


@pytest.mark.parametrize("fsdp", [False, True])
def test_lm_dcn_overlap_streams_and_is_bitwise(fsdp):
    """Streaming two-level DCN sync (round 9): with ``overlap=True`` on
    the factored (dcn, data) mesh, the whole-tree ``_dcn_sync_point``
    becomes one per-layer-group sync point each.  Three pins:

    (a) BITWISE trajectory equality — params AND optimizer state — over
        a multi-step run vs the whole-tree path (the two-level reduction
        is elementwise, so regrouping changes no sums; same ops, moved);
    (b) the compiled program actually streams: >= 2 non-scalar dcn-axis
        collectives land STRICTLY BETWEEN backward matmuls under overlap
        (``min_bytes`` excludes the scalar loss psums that legitimately
        cross 'dcn' mid-graph), while the whole-tree path emits every
        non-scalar dcn collective after the final matmul;
    (c) zero EXTRA compiles: the streamed step's compile count equals
        the whole-tree path's, and it reaches steady state (no
        marker-induced retrace on later steps).
    """
    from distributed_pytorch_tpu.lm import make_lm_mesh, make_lm_train_step
    from distributed_pytorch_tpu.lm import make_optimizer as lm_opt
    from distributed_pytorch_tpu.models import transformer as tfm
    from distributed_pytorch_tpu.utils import debug as dbg

    model = tfm.TransformerConfig(vocab_size=256, d_model=64, n_layers=2,
                                  n_heads=2, head_dim=32, d_ff=128)
    tokens, targets = _data(b=4, s=64, vocab=256)

    compiles = {}

    def run(overlap):
        cfg = LMTrainConfig(model=model, dp=4, dcn_size=2, fsdp=fsdp,
                            overlap=overlap, compute_dtype=None)
        tr = LMTrainer(cfg)
        for _ in range(3):
            tr.train_step(tokens, targets)
        if hasattr(tr.step_fn, "_cache_size"):
            compiles[overlap] = tr.step_fn._cache_size()
        return jax.tree.map(lambda x: np.array(x, copy=True),
                            (tr.params, tr.opt_state))

    base, over = run(False), run(True)
    for a, b in zip(jax.tree.leaves(base), jax.tree.leaves(over)):
        np.testing.assert_array_equal(a, b)
    # the per-group markers cost no extra compiles over the whole-tree
    # path (both reach the same steady state by step 3)
    if compiles:
        assert compiles[True] == compiles[False], compiles

    def dcn_schedule(overlap):
        cfg = LMTrainConfig(model=model, dp=4, dcn_size=2, fsdp=fsdp,
                            overlap=overlap, compute_dtype=None)
        step = make_lm_train_step(cfg, make_lm_mesh(cfg))
        params = tfm.init(jax.random.key(0), model)
        opt = lm_opt(cfg).init(params)
        sched = dbg.op_schedule(step, params, opt, jnp.asarray(tokens),
                                jnp.asarray(targets))
        return sched

    # scalar loss/aux/token-count psums cross 'dcn' mid-graph by design;
    # the gradient-sync pins look only at non-scalar payloads
    dbg.assert_overlap_schedule(dcn_schedule(True), axes=("dcn",),
                                min_interleaved=2, min_bytes=65)
    dbg.assert_post_backward_schedule(dcn_schedule(False), axes=("dcn",),
                                      min_bytes=65)


def test_two_level_sync_bucket_split_is_bitwise():
    """The grad-accumulation path's post-scan sync streams per ~bucket
    (round 9): splitting a spec group into buckets changes NOTHING —
    the two-level reduction is elementwise — while the program carries
    one shard-sized dcn psum PER BUCKET (the pipelineable layout)."""
    from distributed_pytorch_tpu.lm import _two_level_sync, make_lm_mesh
    from distributed_pytorch_tpu.models import transformer as tfm
    from distributed_pytorch_tpu.utils.compat import shard_map
    from jax.sharding import PartitionSpec as P

    model = tfm.TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                                  n_heads=2, head_dim=16)
    mesh = make_lm_mesh(LMTrainConfig(model=model, dp=4, dcn_size=2))
    grads = {"a": jnp.arange(2100, dtype=jnp.float32),
             "b": jnp.ones((3000,), jnp.float32)}
    specs = {"a": P(), "b": P()}
    axes = ("dcn", "data", "expert", "seq", "model")

    def f(g):
        g = jax.tree.map(
            lambda x: jax.lax.pcast(x, axes, to="varying"), g)
        mono = _two_level_sync(g, specs)
        bucketed = _two_level_sync(g, specs, bucket_bytes=4096)
        return jax.tree.map(lambda x, y: jnp.max(jnp.abs(x - y)),
                            mono, bucketed)

    diffs = jax.jit(shard_map(f, mesh=mesh, in_specs=(P(),),
                              out_specs=P(), check_vma=False))(grads)
    for k, d in diffs.items():
        assert float(d) == 0.0, (k, float(d))

    # program shape: the bucketed sync carries one dcn psum per bucket
    # (two here: the 3000-leaf bucket, then the 2100-leaf one), each
    # shard-sized — vs ONE for the monolithic group
    import re

    def dcn_payloads(fn):
        jaxpr = str(jax.make_jaxpr(shard_map(
            fn, mesh=mesh, in_specs=(P(),), out_specs=P(),
            check_vma=False))(grads))
        sizes = []
        for ln in jaxpr.splitlines():
            if "psum" in ln and "'dcn'" in ln:
                for dims in re.findall(r"f32\[([\d,]+)\]", ln):
                    n = int(np.prod([int(d) for d in dims.split(",")]))
                    if n > 1:
                        sizes.append(n)
        return sorted(sizes)

    def mono(g):
        g = jax.tree.map(
            lambda x: jax.lax.pcast(x, axes, to="varying"), g)
        return _two_level_sync(g, specs)

    def bucketed(g):
        g = jax.tree.map(
            lambda x: jax.lax.pcast(x, axes, to="varying"), g)
        return _two_level_sync(g, specs, bucket_bytes=4096)

    assert dcn_payloads(mono) == [-(-5100 // 2)]
    assert dcn_payloads(bucketed) == sorted(
        [-(-2100 // 2), -(-3000 // 2)])


def test_fsdp_checkpoint_roundtrip(tmp_path):
    from distributed_pytorch_tpu.models import transformer as tfm

    model = tfm.TransformerConfig(vocab_size=512, d_model=128, n_layers=2,
                                  n_heads=2, head_dim=64)
    tokens, targets = _data(b=4, s=128, vocab=512)
    cfg = LMTrainConfig(model=model, compute_dtype=None, dp=4, fsdp=True)
    a = LMTrainer(cfg)
    a.train_step(tokens, targets)
    a.save_checkpoint(str(tmp_path))
    b = LMTrainer(cfg)
    assert b.maybe_restore(str(tmp_path)) == 1
    la = float(a.train_step(tokens, targets))
    lb = float(b.train_step(tokens, targets))
    np.testing.assert_allclose(lb, la, rtol=1e-6)


def test_evaluate_and_lr_schedule():
    """Held-out eval returns finite loss/ppl consistent with exp(loss);
    warmup schedule starts near zero so early steps barely move params."""
    from distributed_pytorch_tpu.models import transformer as tfm
    from distributed_pytorch_tpu.lm import make_schedule

    model = tfm.TransformerConfig(vocab_size=512, d_model=128, n_layers=2,
                                  n_heads=2, head_dim=64)
    tokens, targets = _data(b=4, s=128, vocab=512)
    tr = LMTrainer(LMTrainConfig(model=model, compute_dtype=None,
                                 dp=2, sp=2, tp=2))
    tr.train_step(tokens, targets)
    m = tr.evaluate([(tokens, targets)])
    assert np.isfinite(m["loss"]) and m["tokens"] == 4 * 127
    np.testing.assert_allclose(m["ppl"], np.exp(m["loss"]), rtol=1e-5)

    sched = make_schedule(LMTrainConfig(lr=1e-3, warmup_steps=10,
                                        decay_steps=100))
    assert float(sched(0)) < 1e-4
    np.testing.assert_allclose(float(sched(10)), 1e-3, rtol=1e-5)
    assert float(sched(100)) < 2e-4  # decayed toward min_lr_ratio * lr


def test_pp_with_tp_composes():
    """dp=2 x pp=2 x tp=2: the pipeline's stage bodies run Megatron psums;
    losses must match the dense single-device trajectory."""
    from distributed_pytorch_tpu.models import transformer as tfm

    tokens, targets = _data(b=8, s=128)
    model = tfm.TransformerConfig(vocab_size=1024, d_model=256, n_layers=4,
                                  n_heads=2)
    losses = {}
    for name, kw in {"base": dict(dp=1),
                     "pp_tp": dict(dp=2, pp=2, tp=2)}.items():
        cfg = LMTrainConfig(model=model, compute_dtype=None, **kw)
        tr = LMTrainer(cfg)
        losses[name] = [float(tr.train_step(tokens, targets))
                        for _ in range(3)]
    np.testing.assert_allclose(losses["base"], losses["pp_tp"],
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("kw", [dict(dp=1, pp=2, interleave=2),
                                dict(dp=2, pp=2, tp=2, interleave=2)])
def test_interleaved_pipeline_matches_dense(kw):
    """Interleaved (virtual-stage) schedule: same losses as single device,
    including composed with dp/tp and a microbatch count not divisible by
    the wave size."""
    from distributed_pytorch_tpu.models import transformer as tfm

    tokens, targets = _data(b=12, s=128)  # 12 mbs default: M=2*pp -> set 3
    model = tfm.TransformerConfig(vocab_size=1024, d_model=256, n_layers=4,
                                  n_heads=2)
    losses = {}
    for name, run_kw in {"base": dict(dp=1),
                         "ipp": dict(microbatches=3, **kw)}.items():
        cfg = LMTrainConfig(model=model, compute_dtype=None, **run_kw)
        tr = LMTrainer(cfg)
        losses[name] = [float(tr.train_step(tokens, targets))
                        for _ in range(3)]
    np.testing.assert_allclose(losses["base"], losses["ipp"],
                               rtol=2e-4, atol=2e-4)


def test_interleave_split_merge_roundtrip():
    from distributed_pytorch_tpu.models import transformer as tfm
    from distributed_pytorch_tpu.parallel import pipeline as pp

    cfg = tfm.TransformerConfig(vocab_size=64, d_model=64, n_layers=8,
                                n_heads=1, head_dim=64)
    params = tfm.init(jax.random.key(0), cfg)
    stages, shared = pp.split_layer_params(params, cfg, 2, interleave=2)
    # leaf shape: (n_stages, interleave, per_chunk, ...)
    assert jax.tree.leaves(stages)[0].shape[:3] == (2, 2, 2)
    back = pp.merge_layer_params(stages, shared, cfg)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_pp_block_remat_bounds_activation_memory():
    """O(pp * mb) memory (round 2): the block-rematted tick scan (default)
    must compile to substantially less temp memory than the flat O(num_ticks)
    scan at a microbatch-heavy config, with an identical loss."""
    from distributed_pytorch_tpu.models import transformer as tfm

    model = tfm.TransformerConfig(vocab_size=128, d_model=128, n_layers=2,
                                  n_heads=2, head_dim=64, d_ff=256)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 128, (32, 128)).astype(np.int32)
    targets = np.roll(tokens, -1, 1).astype(np.int32)
    targets[:, -1] = IGNORE

    def build(remat):
        cfg = LMTrainConfig(model=model, compute_dtype=None, dp=1, pp=2,
                            microbatches=16, pp_remat_block=remat)
        tr = LMTrainer(cfg)
        lowered = tr.step_fn.lower(tr.params, tr.opt_state,
                                   jnp.asarray(tokens), jnp.asarray(targets))
        stats = lowered.compile().memory_analysis()
        return stats.temp_size_in_bytes, tr

    flat_bytes, tr_flat = build(None)
    blocked_bytes, tr_blocked = build(0)
    # 17 saved tick carries vs ~9 block carries + one in-flight block; the
    # non-activation temp dilutes the ratio — 1.4x is a conservative floor
    # (measured 1.8x at this config).
    assert blocked_bytes * 1.4 < flat_bytes, (blocked_bytes, flat_bytes)
    l_flat = float(tr_flat.train_step(tokens, targets))
    l_blocked = float(tr_blocked.train_step(tokens, targets))
    assert abs(l_flat - l_blocked) < 1e-5


def test_pp_with_uniform_moe_matches_dense_oracle():
    """pp x MoE (round 2): a uniformly-MoE stack (moe_every=1) pipelines;
    with one microbatch the whole batch routes together, so the CE
    trajectory matches the dense path exactly (aux off: per-microbatch
    routing makes aux means non-comparable by construction, as in the
    expert-parallel parity test).  Alternating stacks remain a validated
    error."""
    from distributed_pytorch_tpu.models import transformer as tfm

    model = tfm.TransformerConfig(vocab_size=128, d_model=128, n_layers=2,
                                  n_heads=2, head_dim=64, d_ff=256,
                                  n_experts=4, moe_every=1,
                                  capacity_factor=8.0)  # no drops => parity
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 128, (8, 64)).astype(np.int32)
    targets = np.roll(tokens, -1, 1).astype(np.int32)
    targets[:, -1] = IGNORE

    # pp2 runs aux_coef ON: with one microbatch the whole batch routes
    # together, so the pipeline's aux reduction (psum over 'pipe' /
    # n_micro + pmean) is exactly comparable to the dense path — pinning
    # the aux scaling, not just the CE.  pp2 x tp2 compares CE only
    # (aux off): each tp rank routes its own token slice, so per-slice
    # aux means differ from full-batch routing by construction (as in the
    # expert-parallel parity test).
    losses = {}
    for name, kw, coef in (("dense", dict(dp=1), 0.01),
                           ("pp2", dict(pp=2, microbatches=1), 0.01),
                           ("dense-noaux", dict(dp=1), 0.0),
                           ("pp2tp2", dict(pp=2, tp=2, microbatches=1),
                            0.0)):
        tr = LMTrainer(LMTrainConfig(model=model, compute_dtype=None,
                                     aux_coef=coef, **kw))
        losses[name] = [float(tr.train_step(tokens, targets))
                        for _ in range(2)]
    np.testing.assert_allclose(losses["pp2"], losses["dense"], rtol=1e-5)
    np.testing.assert_allclose(losses["pp2tp2"], losses["dense-noaux"],
                               rtol=1e-5)

    # aux on + real microbatching: trains and improves
    tr = LMTrainer(LMTrainConfig(model=model, compute_dtype=None,
                                 aux_coef=0.01, dp=2, pp=2, microbatches=2))
    ls = [float(tr.train_step(tokens, targets)) for _ in range(4)]
    assert np.isfinite(ls).all() and ls[-1] < ls[0]

    # alternating dense/MoE stacks still cannot pipeline
    alt = tfm.TransformerConfig(vocab_size=128, d_model=128, n_layers=2,
                                n_heads=2, head_dim=64, d_ff=256,
                                n_experts=4, moe_every=2)
    with pytest.raises(ValueError, match="uniform"):
        LMTrainer(LMTrainConfig(model=alt, compute_dtype=None, pp=2))


def test_pp_trained_params_merge_and_decode():
    """The pp workflow closes end-to-end: train with pipeline parallelism,
    merge the stage-stacked params back to the dense layout
    (pp.merge_layer_params), and decode with generate() — the documented
    bridge, since per-token pp decode would pay a full stage-ring bubble
    per token (decode shards over 'model', not 'pipe')."""
    from distributed_pytorch_tpu import generate as gen
    from distributed_pytorch_tpu.models import transformer as tfm
    from distributed_pytorch_tpu.parallel import pipeline as pp

    model = tfm.TransformerConfig(vocab_size=128, d_model=128, n_layers=2,
                                  n_heads=2, head_dim=64, d_ff=256)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 128, (8, 64)).astype(np.int32)
    targets = np.roll(tokens, -1, 1).astype(np.int32)
    targets[:, -1] = IGNORE

    tr = LMTrainer(LMTrainConfig(model=model, compute_dtype=None, dp=2,
                                 pp=2, microbatches=2))
    losses = [float(tr.train_step(tokens, targets)) for _ in range(3)]
    assert losses[-1] < losses[0]

    dense = pp.merge_layer_params(
        jax.tree.map(np.asarray, tr.params["stages"]),
        jax.tree.map(np.asarray, tr.params["shared"]), model)
    # Oracle: the merged params' dense-path CE must equal the pp trainer's
    # own next-step loss (computed from the same pre-update params; the
    # dense model has no experts, so the aux term is zero) — a scrambled
    # layer order would fail this, not just produce in-range tokens.
    logits = tfm.apply(dense, jnp.asarray(tokens), cfg=model,
                       attn_impl="reference")
    ce, n = masked_ce(logits, jnp.asarray(targets))
    dense_loss = float(ce) / int(n)
    pp_loss = float(tr.train_step(tokens, targets))
    assert abs(dense_loss - pp_loss) < 1e-4, (dense_loss, pp_loss)

    out = gen.generate(dense, jnp.asarray(tokens[:1, :8]),
                       jax.random.key(0), cfg=model, max_new=8,
                       temperature=0.0, decode_kernel=False)
    assert out.shape == (1, 16)


def test_pp_evaluate_matches_dense_oracle():
    """evaluate() with pp>1 (VERDICT round-2 #2): held-out eval runs through
    the pipeline forward and must match the dense single-device oracle, and
    keep matching after a pp training step moves the params."""
    from distributed_pytorch_tpu.models import transformer as tfm

    model = tfm.TransformerConfig(vocab_size=256, d_model=128, n_layers=4,
                                  n_heads=2, head_dim=64, d_ff=256)
    tokens, targets = _data(b=8, s=64, vocab=256)

    dense = LMTrainer(LMTrainConfig(model=model, compute_dtype=None))
    pp2 = LMTrainer(LMTrainConfig(model=model, compute_dtype=None,
                                  dp=2, pp=2))
    m_dense = dense.evaluate([(tokens, targets)])
    m_pp = pp2.evaluate([(tokens, targets)])
    assert m_pp["tokens"] == m_dense["tokens"] == 8 * 63
    np.testing.assert_allclose(m_pp["loss"], m_dense["loss"], rtol=1e-5)

    # after a training step the params differ from init; trajectories are
    # identical (test_pipeline_parallel_matches_dense), so eval must be too
    dense.train_step(tokens, targets)
    pp2.train_step(tokens, targets)
    np.testing.assert_allclose(pp2.evaluate([(tokens, targets)])["loss"],
                               dense.evaluate([(tokens, targets)])["loss"],
                               rtol=1e-5)


def test_pp_sp_evaluate_matches_dense_oracle():
    """pp x sp eval: the zigzag ring inside pipeline stages, forward-only."""
    from distributed_pytorch_tpu.models import transformer as tfm

    model = tfm.TransformerConfig(vocab_size=128, d_model=64, n_layers=2,
                                  n_heads=2, head_dim=32, d_ff=128)
    tokens, targets = _data(b=4, s=128, vocab=128)
    dense = LMTrainer(LMTrainConfig(model=model, compute_dtype=None))
    ppsp = LMTrainer(LMTrainConfig(model=model, compute_dtype=None,
                                   pp=2, sp=2, microbatches=2))
    np.testing.assert_allclose(ppsp.evaluate([(tokens, targets)])["loss"],
                               dense.evaluate([(tokens, targets)])["loss"],
                               rtol=1e-5)


def test_dedicated_expert_axis_parity():
    """EP x TP (VERDICT round-2 #6): experts on their own 'expert' mesh
    axis with each expert's FFN tp-sharded.  All layouts must reproduce
    the single-device trajectory (ample capacity, aux off), including the
    full (data, expert, model) composition at n=8."""
    from distributed_pytorch_tpu.models import transformer as tfm

    model = tfm.TransformerConfig(vocab_size=512, d_model=128, n_layers=2,
                                  n_heads=4, head_dim=32, n_experts=4,
                                  capacity_factor=8.0)
    tokens, targets = _data(b=4, s=64, vocab=512)
    runs = {}
    for name, kw in {"base": dict(), "ep4": dict(ep=4),
                     "ep2tp2": dict(ep=2, tp=2),
                     "dp2ep2tp2": dict(dp=2, ep=2, tp=2)}.items():
        cfg = LMTrainConfig(model=model, compute_dtype=None, aux_coef=0.0,
                            **kw)
        tr = LMTrainer(cfg)
        assert tr.mesh.axis_names == ("data", "expert", "seq", "model")
        runs[name] = [float(tr.train_step(tokens, targets))
                      for _ in range(3)]
    for name in ("ep4", "ep2tp2", "dp2ep2tp2"):
        np.testing.assert_allclose(runs[name], runs["base"], rtol=1e-5,
                                   err_msg=name)
    # expert weights are genuinely expert-sharded on the 8-device mesh
    tr = LMTrainer(LMTrainConfig(model=model, compute_dtype=None,
                                 dp=2, ep=2, tp=2))
    spec = tr.params["layer1"]["moe"]["w_gate"].sharding.spec
    assert spec[0] == "expert" and spec[2] == "model", spec
    losses = [float(tr.train_step(tokens, targets)) for _ in range(5)]
    assert losses[-1] < losses[0]


def test_ep_validation():
    from distributed_pytorch_tpu.models import transformer as tfm

    dense = tfm.TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                                  n_heads=2, head_dim=16)
    with pytest.raises(ValueError, match="requires an MoE model"):
        LMTrainer(LMTrainConfig(model=dense, ep=2))
    moe = tfm.TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                                n_heads=2, head_dim=16, n_experts=4,
                                moe_every=1)
    with pytest.raises(ValueError, match="do not shard"):
        LMTrainer(LMTrainConfig(model=moe, ep=3))
    with pytest.raises(ValueError, match="does not compose"):
        LMTrainer(LMTrainConfig(model=moe, ep=2, pp=2))


def test_dcn_factored_lm_matches_flat_dp():
    """Multislice LM (cfg.dcn_size): the (dcn, data)-factored mesh with
    the explicit two-level gradient sync reproduces the flat-dp
    trajectory to f32 noise — including composition with sp and tp."""
    from distributed_pytorch_tpu.models import transformer as tfm
    model = tfm.TransformerConfig(vocab_size=256, d_model=128, n_layers=2,
                                  n_heads=2, head_dim=64, d_ff=256)
    tokens, targets = _data(s=128, vocab=256)
    runs = {}
    for name, kw in {"flat": dict(dp=4),
                     "dcn2x2": dict(dp=4, dcn_size=2),
                     "dcn2x2_ov": dict(dp=4, dcn_size=2, overlap=True),
                     "dcn2x1_sp2_tp2": dict(dp=2, dcn_size=2, sp=2,
                                            tp=2)}.items():
        tr = LMTrainer(LMTrainConfig(model=model, compute_dtype=None, **kw))
        runs[name] = [float(tr.train_step(tokens, targets))
                      for _ in range(3)]
    np.testing.assert_allclose(runs["dcn2x2"], runs["flat"], rtol=2e-5)
    # streaming per-group sync points (round 9): same trajectory as the
    # whole-tree point, hence as flat dp
    np.testing.assert_allclose(runs["dcn2x2_ov"], runs["dcn2x2"],
                               rtol=0, atol=0)
    np.testing.assert_allclose(runs["dcn2x1_sp2_tp2"], runs["flat"],
                               rtol=2e-5)
    # eval runs on the factored mesh too
    tr = LMTrainer(LMTrainConfig(model=model, compute_dtype=None,
                                 dp=4, dcn_size=2))
    out = tr.evaluate([(tokens, targets)])
    assert np.isfinite(out["loss"])


def test_dcn_payload_is_shard_sized_lm():
    """The LM analog of the VGG strategy's DCN-payload pin (VERDICT
    round-3 weak #4): on the (dcn, data)-factored LM mesh, the ONLY
    non-scalar collective crossing 'dcn' in the whole grad step is the
    explicit shard-sized psum — ceil(P / ici) floats, not the full
    parameter count.  The round-3 story relied on XLA lowering a flat
    psum hierarchically; this makes the payload a program property."""
    import re

    from distributed_pytorch_tpu.lm import (
        _make_grad_step, _spec_axes, make_lm_mesh, param_specs)
    from distributed_pytorch_tpu.models import transformer as tfm

    model = tfm.TransformerConfig(vocab_size=256, d_model=64, n_layers=2,
                                  n_heads=2, head_dim=32, d_ff=128)
    cfg = LMTrainConfig(model=model, compute_dtype=None, dp=4, dcn_size=2)
    mesh = make_lm_mesh(cfg)
    grad_step = _make_grad_step(cfg, mesh)
    tr = LMTrainer(cfg, mesh=mesh)
    ici = cfg.dp // cfg.dcn_size
    # the sync groups leaves by sharded axes (one flat vector each);
    # expected dcn payloads = ceil(group param count / ici) per group
    groups: dict = {}
    for leaf, spec in zip(jax.tree.leaves(tr.params),
                          jax.tree.leaves(param_specs(cfg))):
        key = frozenset(_spec_axes(spec))
        groups[key] = groups.get(key, 0) + leaf.size
    want = sorted(-(-g // ici) for g in groups.values())
    n_params = sum(groups.values())

    tokens, targets = _data(b=4, s=64, vocab=256)
    jaxpr = str(jax.make_jaxpr(grad_step)(
        tr.params, jnp.asarray(tokens), jnp.asarray(targets),
        jnp.float32(1.0), jnp.float32(0.0)))
    dcn_lines = [ln for ln in jaxpr.splitlines()
                 if "psum" in ln and "'dcn'" in ln]
    assert dcn_lines, jaxpr[:800]
    sized = []
    for ln in dcn_lines:
        # ANY dtype and rank (a regression reintroducing a full-payload
        # cotangent psum would carry the leaf's natural multi-dim shape)
        for dims in re.findall(r"\w+\[([\d,]+)\]", ln):
            size = int(np.prod([int(d) for d in dims.split(",")]))
            if size > 1:
                sized.append(size)
    # the only non-scalar dcn crossings are the shard-sized per-group
    # reductions — total DCN payload ~= P/ici, not the full P
    assert sorted(sized) == want, (sized, want)
    assert sum(sized) < n_params, (sum(sized), n_params)


def test_dcn_grad_accum_single_exchange():
    """grad_accum x dcn_size accumulates LOCAL grads and syncs once:
    the trajectory matches both the unaccumulated factored run and the
    flat-dp accumulated run to f32 noise, and the jaxpr carries exactly
    ONE set of shard-sized dcn psums (one per spec group) — not A."""
    import re

    from distributed_pytorch_tpu.lm import (
        _make_accum_grad_step, _spec_axes, make_lm_mesh, param_specs)
    from distributed_pytorch_tpu.models import transformer as tfm

    model = tfm.TransformerConfig(vocab_size=256, d_model=64, n_layers=2,
                                  n_heads=2, head_dim=32, d_ff=128)
    tokens, targets = _data(b=8, s=64, vocab=256)
    runs = {}
    for name, kw in {"flat_a2": dict(dp=4, grad_accum=2),
                     "dcn_a1": dict(dp=4, dcn_size=2),
                     "dcn_a2": dict(dp=4, dcn_size=2,
                                    grad_accum=2)}.items():
        tr = LMTrainer(LMTrainConfig(model=model, compute_dtype=None,
                                     aux_coef=0.0, **kw))
        runs[name] = [float(tr.train_step(tokens, targets))
                      for _ in range(3)]
    np.testing.assert_allclose(runs["dcn_a2"], runs["dcn_a1"], rtol=2e-5)
    np.testing.assert_allclose(runs["dcn_a2"], runs["flat_a2"], rtol=2e-5)

    # payload pin: ONE dcn exchange per step in the accumulated program
    cfg = LMTrainConfig(model=model, compute_dtype=None, dp=4,
                        dcn_size=2, grad_accum=2)
    mesh = make_lm_mesh(cfg)
    accum = _make_accum_grad_step(cfg, mesh)
    tr = LMTrainer(cfg, mesh=mesh)
    groups: dict = {}
    for leaf, spec in zip(jax.tree.leaves(tr.params),
                          jax.tree.leaves(param_specs(cfg))):
        key = frozenset(_spec_axes(spec))
        groups[key] = groups.get(key, 0) + leaf.size
    ici = cfg.dp // cfg.dcn_size
    want = sorted(-(-g // ici) for g in groups.values())
    micro = jnp.asarray(tokens).reshape(2, 4, -1)
    jaxpr = str(jax.make_jaxpr(accum)(
        tr.params, micro, jnp.asarray(targets).reshape(2, 4, -1),
        jnp.float32(1.0), jnp.float32(0.0)))
    sized = []
    for ln in jaxpr.splitlines():
        if "psum" in ln and "'dcn'" in ln:
            for dims in re.findall(r"\w+\[([\d,]+)\]", ln):
                size = int(np.prod([int(d) for d in dims.split(",")]))
                if size > 1:
                    sized.append(size)
    assert sorted(sized) == want, (sized, want)


def test_dcn_validation():
    from distributed_pytorch_tpu.models import transformer as tfm
    model = tfm.TransformerConfig(vocab_size=256, d_model=64, n_layers=2,
                                  n_heads=2, head_dim=32, d_ff=128)
    with pytest.raises(ValueError, match="does not factor"):
        LMTrainer(LMTrainConfig(model=model, dp=4, dcn_size=3))
    with pytest.raises(ValueError, match="does not compose with pp"):
        LMTrainer(LMTrainConfig(model=model, dp=2, pp=2, dcn_size=2))


def test_dcn_fsdp_composes_and_keeps_shard_payload():
    """FSDP x multislice (round-4 missing #4): ZeRO-3 partitions over the
    SLICE-LOCAL 'data' axis while 'dcn' carries one shard-sized gradient
    psum per step — the trajectory matches flat dp, params are genuinely
    data-sharded, and the jaxpr pins the DCN payload at FSDP-shard size
    (the fsdp analog of test_dcn_payload_is_shard_sized_lm)."""
    import re

    from distributed_pytorch_tpu.lm import (
        _make_grad_step, _spec_axes, make_lm_mesh, param_specs)
    from distributed_pytorch_tpu.models import transformer as tfm

    model = tfm.TransformerConfig(vocab_size=256, d_model=64, n_layers=2,
                                  n_heads=2, head_dim=32, d_ff=128)
    tokens, targets = _data(b=4, s=64, vocab=256)
    runs = {}
    for name, kw in {"flat": dict(dp=4),
                     "dcn_fsdp": dict(dp=4, dcn_size=2, fsdp=True)}.items():
        tr = LMTrainer(LMTrainConfig(model=model, compute_dtype=None, **kw))
        runs[name] = [float(tr.train_step(tokens, targets))
                      for _ in range(3)]
    np.testing.assert_allclose(runs["dcn_fsdp"], runs["flat"], rtol=2e-5)

    cfg = LMTrainConfig(model=model, compute_dtype=None, dp=4,
                        dcn_size=2, fsdp=True)
    mesh = make_lm_mesh(cfg)
    tr = LMTrainer(cfg, mesh=mesh)
    ici = cfg.dp // cfg.dcn_size
    # params genuinely shard over the slice-local 'data' axis
    emb_spec = tr.params["embed"].sharding.spec
    assert "data" in _spec_axes(emb_spec), emb_spec
    # expected dcn payloads: the ZeRO shard itself for fsdp leaves
    # (per-leaf psum — the gather transpose already reduce-scattered),
    # ceil(group/ici) for the two-level groups of unsharded leaves
    want, groups, n_params = [], {}, 0
    for leaf, spec in zip(jax.tree.leaves(tr.params),
                          jax.tree.leaves(param_specs(cfg))):
        axes = _spec_axes(spec)
        n_params += leaf.size
        if "data" in axes:
            want.append(leaf.size // ici)
        else:
            key = frozenset(axes)
            groups[key] = groups.get(key, 0) + leaf.size
    want = sorted(want + [-(-g // ici) for g in groups.values()])
    assert want, "model has no fsdp-shardable leaf"

    grad_step = _make_grad_step(cfg, mesh)
    jaxpr = str(jax.make_jaxpr(grad_step)(
        tr.params, jnp.asarray(tokens), jnp.asarray(targets),
        jnp.float32(1.0), jnp.float32(0.0)))
    sized = []
    for ln in jaxpr.splitlines():
        if "psum" in ln and "'dcn'" in ln:
            for dims in re.findall(r"\w+\[([\d,]+)\]", ln):
                size = int(np.prod([int(d) for d in dims.split(",")]))
                if size > 1:
                    sized.append(size)
    assert sorted(sized) == want, (sorted(sized), want)
    assert sum(sized) < n_params, (sum(sized), n_params)


def test_train_steps_scan_matches_per_step_calls():
    """The K-step scan dispatch produces the identical trajectory to K
    train_step calls (same data, same init) — and works over the
    (data, expert, seq, model) mesh."""
    from distributed_pytorch_tpu.models import transformer as tfm

    model = tfm.TransformerConfig(vocab_size=256, d_model=64, n_layers=2,
                                  n_heads=2, head_dim=32, d_ff=128)
    rng = np.random.default_rng(3)
    K, b, s = 4, 4, 64
    toks = rng.integers(0, 256, (K, b, s)).astype(np.int32)
    tgts = np.roll(toks, -1, axis=2).astype(np.int32)
    tgts[:, :, -1] = IGNORE

    a = LMTrainer(LMTrainConfig(model=model, compute_dtype=None, dp=2, tp=2))
    per_step = [float(a.train_step(toks[i], tgts[i])) for i in range(K)]
    b_tr = LMTrainer(LMTrainConfig(model=model, compute_dtype=None,
                                   dp=2, tp=2))
    scanned = [float(x) for x in b_tr.train_steps(toks, tgts)]
    np.testing.assert_allclose(scanned, per_step, rtol=1e-6)
    jax.tree.map(
        lambda x, y: np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), atol=1e-6),
        a.params, b_tr.params)
    assert b_tr._step == K

    with pytest.raises(ValueError, match="train_steps"):
        LMTrainer(LMTrainConfig(model=model, compute_dtype=None,
                                pp=2)).train_steps(toks, tgts)


def test_grad_accum_exact_trajectory():
    """grad_accum=A produces the unaccumulated trajectory to float noise:
    microbatch grads normalize by the FULL batch's token count, so mask
    imbalance between microbatches reweights nothing.  Composes with
    dp x tp and with MoE aux (aux weight coef/A per microbatch)."""
    from distributed_pytorch_tpu.models import transformer as tfm

    model = tfm.TransformerConfig(vocab_size=256, d_model=64, n_layers=2,
                                  n_heads=2, head_dim=32, d_ff=128,
                                  n_experts=2, capacity_factor=8.0)
    rng = np.random.default_rng(5)
    b, s = 8, 64
    toks = rng.integers(0, 256, (b, s)).astype(np.int32)
    tgts = np.roll(toks, -1, axis=1).astype(np.int32)
    tgts[:, -1] = IGNORE
    # unequal masks per microbatch: pad the first rows' tails
    tgts[0, 40:] = IGNORE
    tgts[1, 20:] = IGNORE

    runs = {}
    for name, kw in {"a1": dict(), "a4": dict(grad_accum=4),
                     "a2_dp2tp2": dict(grad_accum=2, dp=2, tp=2)}.items():
        # aux off for the exactness claim: the MoE aux is a per-routing-
        # group statistic, and accumulation regroups (documented)
        tr = LMTrainer(LMTrainConfig(model=model, compute_dtype=None,
                                     aux_coef=0.0, **kw))
        runs[name] = [float(tr.train_step(toks, tgts)) for _ in range(3)]
    np.testing.assert_allclose(runs["a4"], runs["a1"], rtol=2e-5)
    np.testing.assert_allclose(runs["a2_dp2tp2"], runs["a1"], rtol=2e-5)
    # with aux ON the trajectories stay close (group statistics shift a
    # little, as with any dp/tp regrouping — not a correctness bug)
    aux_runs = {}
    for name, kw in {"a1": dict(), "a4": dict(grad_accum=4)}.items():
        tr = LMTrainer(LMTrainConfig(model=model, compute_dtype=None,
                                     aux_coef=0.01, **kw))
        aux_runs[name] = [float(tr.train_step(toks, tgts))
                          for _ in range(3)]
    np.testing.assert_allclose(aux_runs["a4"], aux_runs["a1"],
                               rtol=5e-3)

    with pytest.raises(ValueError, match="divisible into"):
        LMTrainer(LMTrainConfig(model=model, compute_dtype=None,
                                grad_accum=3)).train_step(toks, tgts)
    # grad_accum is validated everywhere it cannot apply (never dropped)
    with pytest.raises(ValueError, match="does not compose with pp"):
        LMTrainer(LMTrainConfig(model=model, compute_dtype=None,
                                pp=2, grad_accum=2))
    # ... including when the caller supplies the mesh (advisor regression,
    # round 3: an explicit mesh must not skip cfg validation — the pp step
    # builder never reads grad_accum, so accepting it would drop it)
    from distributed_pytorch_tpu.lm import make_lm_mesh
    dense = tfm.TransformerConfig(vocab_size=256, d_model=64, n_layers=2,
                                  n_heads=2, head_dim=32, d_ff=128)
    good = LMTrainConfig(model=dense, compute_dtype=None, pp=2)
    with pytest.raises(ValueError, match="does not compose with pp"):
        LMTrainer(LMTrainConfig(model=dense, compute_dtype=None,
                                pp=2, grad_accum=2),
                  mesh=make_lm_mesh(good))
    with pytest.raises(ValueError, match="does not implement gradient"):
        LMTrainer(LMTrainConfig(model=model, compute_dtype=None,
                                grad_accum=2)).train_steps(
            toks[None], tgts[None])
