"""Topology-aware sync autotuner (round 11, parallel/autotune.py):
calibration fit, profile cache, the chooser's decisions on fixed
synthetic profiles, the auto->named bitwise pins on both trainers, and
the LM int8-DCN error-feedback invariant."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_tpu.lm import LMTrainConfig, LMTrainer
from distributed_pytorch_tpu.models import transformer as tfm
from distributed_pytorch_tpu.parallel import autotune as at
from distributed_pytorch_tpu.parallel import strategies as strat
from distributed_pytorch_tpu.train import TrainConfig, Trainer


def _census(total_mb: float = 37.0) -> at.GradCensus:
    """A VGG11-shaped census: a few large conv-like leaves plus small
    bias-like ones, ~total_mb MB of f32."""
    per = int(total_mb * 1024 * 1024 / 4 / 8)
    sizes = [per, 64, per, 128, per, 256, per, 512,
             per, 512, per, 512, per, 512, per, 10]
    return at.GradCensus(tuple(
        at._SizedLeaf(s, np.dtype("float32")) for s in sizes))


# -- calibration fit --------------------------------------------------------


@pytest.mark.quick
def test_fit_alpha_beta_recovers_planted_model():
    """Synthesize observation times from a known (alpha, beta) over the
    calibration grid; the least-squares fit must recover both."""
    alpha, beta = 5e-5, 3e-9
    obs = []
    for algo in ("psum", "rs_ag", "ring"):
        for b in (256 << 10, 1 << 20, 4 << 20):
            launches, wire_per_byte = at._algo_factors(algo, 8)
            obs.append((launches, wire_per_byte * b,
                        alpha * launches + beta * wire_per_byte * b))
    link = at.fit_alpha_beta(obs)
    assert abs(link.alpha_s - alpha) / alpha < 1e-6
    assert abs(link.beta_s_per_byte - beta) / beta < 1e-6


@pytest.mark.quick
def test_algo_factors():
    """The analytic launch/wire factors the fit divides out: one fused
    launch for psum, two for rs+ag, n-1 chained hops for the ring."""
    assert at._algo_factors("psum", 8) == (1.0, 2.0 * 7 / 8)
    assert at._algo_factors("rs_ag", 8) == (2.0, 2.0 * 7 / 8)
    assert at._algo_factors("ring", 8) == (7.0, 7.0)
    with pytest.raises(ValueError):
        at._algo_factors("bogus", 8)


def test_calibrate_smoke_on_virtual_mesh():
    """A real (tiny-payload) calibration over the virtual factored mesh:
    non-negative fits for both links, raw observations recorded."""
    from distributed_pytorch_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(8, axis_names=("dcn", "ici"), axis_shape=(2, 4))
    prof = at.calibrate(mesh, payload_bytes=(64 << 10, 256 << 10),
                        algos=("psum", "rs_ag", "ring"), inner=2, reps=1)
    assert prof.version == at.PROFILE_VERSION
    assert prof.axes == {"dcn": 2, "ici": 4}
    for axis in ("dcn", "ici"):
        assert prof.links[axis].alpha_s >= 0
        assert prof.links[axis].beta_s_per_byte >= 0
        assert set(prof.measured[axis]) == {"psum", "rs_ag", "ring"}


# -- profile cache ----------------------------------------------------------


@pytest.mark.quick
def test_profile_cache_roundtrip_and_version_invalidation(tmp_path):
    """Save -> load reproduces the profile; a version-bumped file (or a
    topology mismatch) loads as None — stale profiles must trigger
    recalibration, never silently steer the chooser."""
    import json

    prof = at.synthetic_profile("fast_ici_slow_dcn", {"dcn": 2, "ici": 4})
    path = at.save_profile(prof, str(tmp_path))
    back = at.load_profile("synthetic", {"dcn": 2, "ici": 4},
                           str(tmp_path))
    assert back is not None
    assert back.links == prof.links and back.axes == prof.axes
    # topology mismatch: miss
    assert at.load_profile("synthetic", {"dcn": 2, "ici": 2},
                           str(tmp_path)) is None
    # version mismatch: invalidated
    with open(path) as f:
        d = json.load(f)
    d["version"] = at.PROFILE_VERSION + 1
    with open(path, "w") as f:
        json.dump(d, f)
    assert at.load_profile("synthetic", {"dcn": 2, "ici": 4},
                           str(tmp_path)) is None


@pytest.mark.quick
def test_get_profile_rejects_mismatch_and_unknown():
    prof = at.synthetic_profile("uniform", {"data": 8})
    with pytest.raises(ValueError, match="topology"):
        at.get_profile(prof, {"dcn": 2, "ici": 4})
    with pytest.raises(ValueError, match="neither"):
        at.get_profile("no_such_preset_or_file", {"data": 8})


# -- the chooser ------------------------------------------------------------


@pytest.mark.quick
def test_chooser_selects_expected_plan_per_profile():
    """The acceptance matrix: each fixed synthetic profile has one
    clearly optimal plan and the chooser finds it — two-level + int8 on
    a fast-ICI/slow-DCN gap, flat fused psum on uniform (launch-bound)
    and inverted (inner-link-bound) topologies, the int8+EF ring on one
    slow flat link, plain ddp on a fast flat link."""
    census = _census()
    fac = {"dcn": 2, "ici": 4}

    plan = at.choose_train_plan(
        census, at.synthetic_profile("fast_ici_slow_dcn", fac), dcn_size=2)
    assert (plan.strategy, plan.dcn_compress) == ("hierarchical", "int8")

    plan = at.choose_train_plan(
        census, at.synthetic_profile("uniform", fac), dcn_size=2)
    assert (plan.strategy, plan.dcn_compress) == ("ddp", None)

    plan = at.choose_train_plan(
        census, at.synthetic_profile("inverted", fac), dcn_size=2)
    assert plan.strategy == "ddp"

    plan = at.choose_train_plan(
        census, at.synthetic_profile("slow", {"data": 8}), dcn_size=1)
    assert plan.strategy == "quantized_ring_ef"

    plan = at.choose_train_plan(
        census, at.synthetic_profile("fast", {"data": 8}), dcn_size=1)
    assert plan.strategy == "ddp"


@pytest.mark.quick
def test_lm_chooser_decides_compression_from_the_link():
    """The LM side's tunables are the slow-hop compression and the
    bucket size (the algorithm is structurally the two-level
    reduction): a slow DCN picks int8+EF, uniform links keep the exact
    psum; a flat (dcn_size=1) config resolves to the no-op plan."""
    census = _census()
    axes = {"dcn": 2, "data": 2}
    plan = at.choose_lm_plan(
        census, at.synthetic_profile("fast_ici_slow_dcn", axes),
        dcn_size=2)
    assert (plan.strategy, plan.dcn_compress) == ("two_level_int8", "int8")
    plan = at.choose_lm_plan(
        census, at.synthetic_profile("uniform", axes), dcn_size=2)
    assert (plan.strategy, plan.dcn_compress) == ("two_level", None)
    plan = at.choose_lm_plan(
        census, at.synthetic_profile("fast", {"data": 8}), dcn_size=1)
    assert plan.strategy == "flat_autodiff_psum"
    assert plan.dcn_compress is None


@pytest.mark.quick
def test_chooser_is_deterministic_and_explainable():
    """Same census + same profile -> the identical plan (dataclass
    equality), with a printable per-axis table and a JSON-able
    summary — the 'explainable SyncPlan' contract."""
    census = _census()
    prof = at.synthetic_profile("fast_ici_slow_dcn", {"dcn": 2, "ici": 4})
    a = at.choose_train_plan(census, prof, dcn_size=2, overlap=True)
    b = at.choose_train_plan(census, prof, dcn_size=2, overlap=True)
    assert a == b
    table = a.table()
    assert "dcn" in table and "int8" in table and "ms" in table
    s = a.summary()
    assert s["strategy"] == "hierarchical"
    assert set(s["bytes_by_axis"]) == {"dcn", "ici"}
    import json
    json.dumps(s)  # must be JSON-able for the bench line


@pytest.mark.quick
def test_bucket_ladder_prefers_default_on_tiny_trees():
    """A census far under every ladder rung packs to one bucket at any
    size — the tie must resolve to the 25 MB torch-DDP default, so the
    chooser never moves a knob without a reason."""
    census = _census(total_mb=0.5)
    prof = at.synthetic_profile("fast_ici_slow_dcn", {"dcn": 2, "ici": 4})
    plan = at.choose_train_plan(census, prof, dcn_size=2, overlap=True)
    assert plan.bucket_mb == strat.BUCKET_CAP_MB


@pytest.mark.quick
def test_registry_rejects_auto_with_pointer():
    """'auto' is not a registry strategy — the error must say who
    resolves it."""
    with pytest.raises(ValueError, match="autotune"):
        strat.get("auto")


# -- auto -> named bitwise pins (the acceptance criterion) ------------------


def _vgg_data(steps=3, n=16):
    rng = np.random.default_rng(0)
    images = rng.integers(0, 256, (steps, n, 32, 32, 3)).astype(np.uint8)
    labels = rng.integers(0, 10, (steps, n)).astype(np.int32)
    return images, labels


def _assert_trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("profile,dcn_size,overlap", [
    ("fast_ici_slow_dcn", 2, False),   # -> hierarchical + int8
    ("fast_ici_slow_dcn", 2, True),    # -> hierarchical + int8, streamed
    ("slow", 1, False),                # -> quantized_ring_ef
    ("uniform", 2, False),             # -> flat ddp (ignores dcn factor)
])
def test_vgg_auto_bitwise_matches_resolved_named(profile, dcn_size,
                                                 overlap):
    """``strategy="auto"`` under a forced profile must train
    BITWISE-identically (params + optimizer state, multi-step) to the
    named strategy it resolves to — the plan only routes through
    existing pinned paths, it never forks them."""
    images, labels = _vgg_data()
    auto_cfg = TrainConfig(strategy="auto", model="TINY", batch_size=2,
                           dcn_size=dcn_size, overlap=overlap,
                           autotune_profile=profile, augment=False)
    tr_auto = Trainer(auto_cfg)
    named_cfg = TrainConfig(
        strategy=tr_auto.cfg.strategy, model="TINY", batch_size=2,
        dcn_size=tr_auto.cfg.dcn_size,
        dcn_compress=tr_auto.cfg.dcn_compress, overlap=overlap,
        overlap_bucket_mb=tr_auto.cfg.overlap_bucket_mb, augment=False)
    tr_named = Trainer(named_cfg)
    losses = {}
    for name, tr in (("auto", tr_auto), ("named", tr_named)):
        losses[name] = [float(tr.train_step(images[i], labels[i]))
                        for i in range(images.shape[0])]
    assert losses["auto"] == losses["named"]
    _assert_trees_equal(tr_auto.params, tr_named.params)
    _assert_trees_equal(tr_auto.opt_state, tr_named.opt_state)


def _lm_model():
    return tfm.TransformerConfig(vocab_size=128, d_model=128, n_layers=2,
                                 n_heads=2, head_dim=64, d_ff=256)


def _lm_data(steps=3, b=8, s=64):
    from distributed_pytorch_tpu.lm import IGNORE
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, 128, (steps, b, s)).astype(np.int32)
    targets = np.roll(tokens, -1, axis=2).astype(np.int32)
    targets[:, :, -1] = IGNORE
    return tokens, targets


@pytest.mark.parametrize("kw", [
    dict(dp=4, dcn_size=2, tp=2),
    dict(dp=4, dcn_size=2, tp=2, fsdp=True, overlap=True),
    dict(dp=4, dcn_size=2, tp=2, grad_accum=2),
])
def test_lm_auto_bitwise_matches_resolved_config(kw):
    """``LMTrainConfig(sync_plan="auto")`` under a forced profile trains
    bitwise-identically (params + Adam state, multi-step) to the
    explicit dcn_compress/bucket_mb config it resolves to — including
    the fsdp/dcn/overlap and grad-accumulation combos."""
    tokens, targets = _lm_data()
    auto = LMTrainer(LMTrainConfig(model=_lm_model(), compute_dtype=None,
                                   sync_plan="auto",
                                   autotune_profile="fast_ici_slow_dcn",
                                   **kw))
    assert auto.sync_plan is not None
    assert auto.cfg.dcn_compress == "int8"  # the slow-DCN profile's pick
    named = LMTrainer(LMTrainConfig(model=_lm_model(), compute_dtype=None,
                                    dcn_compress=auto.cfg.dcn_compress,
                                    bucket_mb=auto.cfg.bucket_mb, **kw))
    losses = {}
    for name, tr in (("auto", auto), ("named", named)):
        losses[name] = [float(tr.train_step(tokens[i], targets[i]))
                        for i in range(tokens.shape[0])]
    assert losses["auto"] == losses["named"]
    _assert_trees_equal(auto.params, named.params)
    _assert_trees_equal(auto.opt_state, named.opt_state)
    # the EF residual genuinely charged on both sides and carries equal
    assert float(np.abs(np.asarray(auto.sync_state)).max()) > 0
    _assert_trees_equal(auto.sync_state, named.sync_state)


# -- LM int8 DCN hop: numerics + the EF invariant ---------------------------


class TestLMInt8Dcn:
    """The round-11 sync-state channel: the LM train step's int8 DCN
    exchange with error-feedback residuals (the standing round-9
    follow-up, closed)."""

    def _mesh(self):
        from jax.sharding import Mesh
        return Mesh(np.array(jax.devices()[:8]).reshape(2, 4, 1, 1, 1),
                    ("dcn", "data", "expert", "seq", "model"))

    def test_two_level_sync_int8_ef_invariant(self):
        """EF bookkeeping is exact for BOTH bucket kinds: the delivered
        sum plus everything the residuals recorded equals the exact
        (uncompressed) sync — for a replicated-spec leaf (the two-level
        path: ICI shard exchanged over dcn) and an fsdp-spec leaf (the
        shard-sized direct ring).  Nothing is lost, only delayed one
        step."""
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from distributed_pytorch_tpu.lm import (_residual_total_len,
                                                _two_level_sync)
        from distributed_pytorch_tpu.utils.compat import shard_map

        rng = np.random.default_rng(5)
        # per-device values: leading dim 8 = one row per device
        w = rng.standard_normal((8, 97, 5)).astype(np.float32)
        z = rng.standard_normal((8, 300)).astype(np.float32)
        specs = {"w": P(), "z": P("data")}
        n_dcn, n_ici = 2, 4
        # leaf order: dict flatten order is ("w", "z")
        res_len = _residual_total_len(
            [np.zeros(w.shape[1:], np.float32),
             np.zeros(z.shape[1:], np.float32)],
            [specs["w"], specs["z"]], n_dcn, n_ici, None)
        res0 = np.zeros((8, res_len), np.float32)

        def run(g, r):
            out, new_r = _two_level_sync(g, specs, dcn_compress="int8",
                                         residual=r[0])
            # exact references
            exact_z = lax.psum(g["z"], "dcn")
            flat_w = g["w"].ravel()
            padded = jnp.pad(flat_w, (0, (-flat_w.size) % n_ici))
            shard = lax.psum_scatter(padded, "data",
                                     scatter_dimension=0, tiled=True)
            exact_w_shard = lax.psum(shard, "dcn")
            # residual layout: fsdp bucket (z) first, then the w group
            z_seg = n_dcn * strat.QuantizedRing()._chunk(g["z"].size,
                                                         n_dcn)
            res_z = new_r[:z_seg].reshape(n_dcn, -1)
            res_w = new_r[z_seg:].reshape(n_dcn, -1)
            # EF recovery: delivered + psum_dcn(residual rows) == exact
            rec_z = (out["z"].ravel()
                     + lax.psum(res_z, "dcn").reshape(-1)[:g["z"].size])
            err_z = jnp.max(jnp.abs(rec_z - exact_z.ravel()))
            sh = padded.size // n_ici
            me = lax.axis_index("data")
            out_w_flat = jnp.pad(out["w"].ravel().astype(jnp.float32),
                                 (0, (-flat_w.size) % n_ici))
            mine = lax.dynamic_slice(out_w_flat, (me * sh,), (sh,))
            dropped = lax.psum(res_w, "dcn").reshape(-1)[:sh]
            err_w = jnp.max(jnp.abs(mine + dropped - exact_w_shard))
            return out, new_r[None], err_z[None], err_w[None]

        spec_all = P(("dcn", "data", "expert", "seq", "model"))
        f = jax.jit(shard_map(
            run, mesh=self._mesh(),
            in_specs=({"w": spec_all, "z": spec_all}, spec_all),
            out_specs=({"w": spec_all, "z": spec_all}, spec_all,
                       spec_all, spec_all),
            check_vma=False))
        out, new_r, err_z, err_w = f({"w": w, "z": z}, jnp.asarray(res0))
        scale = max(np.abs(w).max(), np.abs(z).max())
        assert float(np.max(err_z)) < 1e-4 * scale * 8, np.max(err_z)
        assert float(np.max(err_w)) < 1e-4 * scale * 8, np.max(err_w)
        assert float(np.abs(np.asarray(new_r)).max()) > 0

    def test_trains_and_follows_exact_curve(self):
        """End-to-end through LMTrainer (stateful donated carry): the
        compressed trajectory follows the exact two-level one within
        int8 tolerance, with a live residual; the whole-tree and the
        streamed (fsdp+overlap) layouts both converge."""
        tokens, targets = _lm_data(steps=4)
        losses = {}
        for name, kw in (
                ("exact", dict()),
                ("int8", dict(dcn_compress="int8")),
                ("int8_streamed", dict(dcn_compress="int8", fsdp=True,
                                       overlap=True))):
            tr = LMTrainer(LMTrainConfig(model=_lm_model(), dp=4,
                                         dcn_size=2, tp=2,
                                         compute_dtype=None, **kw))
            losses[name] = [float(tr.train_step(tokens[i], targets[i]))
                            for i in range(4)]
            if name != "exact":
                assert float(
                    np.abs(np.asarray(tr.sync_state)).max()) > 0
        np.testing.assert_allclose(losses["int8"], losses["exact"],
                                   rtol=1e-2, atol=1e-2)
        np.testing.assert_allclose(losses["int8_streamed"],
                                   losses["exact"], rtol=1e-2, atol=1e-2)

    def test_sync_state_len_matches_streamed_and_whole_tree(self):
        """The residual sizing helper agrees with itself across layouts
        (streamed per-group vs whole-tree differ only in bucket
        grouping) and with the carry the trainer actually allocates."""
        from distributed_pytorch_tpu.lm import (lm_sync_state_len,
                                                make_lm_mesh)

        for kw in (dict(), dict(fsdp=True, overlap=True)):
            cfg = LMTrainConfig(model=_lm_model(), dp=4, dcn_size=2, tp=2,
                                compute_dtype=None, dcn_compress="int8",
                                **kw)
            mesh = make_lm_mesh(cfg)
            n = lm_sync_state_len(cfg, mesh)
            assert n > 0
            tr = LMTrainer(cfg, mesh=mesh)
            assert tr.sync_state.shape == (8, n)

    def test_refusals(self):
        """Compression needs a DCN hop and composes with neither
        pipeline scheduler; train_steps refuses the stateful carry."""
        from distributed_pytorch_tpu.lm import validate_lm_cfg
        with pytest.raises(ValueError, match="no DCN hop to compress"):
            validate_lm_cfg(LMTrainConfig(model=_lm_model(), dp=8,
                                          dcn_compress="int8"))
        with pytest.raises(ValueError, match="int8"):
            validate_lm_cfg(LMTrainConfig(model=_lm_model(), dp=4,
                                          dcn_size=2,
                                          dcn_compress="fp8"))
        with pytest.raises(ValueError, match="pipeline"):
            validate_lm_cfg(LMTrainConfig(
                model=tfm.TransformerConfig(vocab_size=128, d_model=128,
                                            n_layers=4, n_heads=2,
                                            head_dim=64, d_ff=256),
                dp=2, dcn_size=2, pp=2, dcn_compress="int8"))
        with pytest.raises(ValueError, match="sync_plan"):
            validate_lm_cfg(LMTrainConfig(model=_lm_model(),
                                          sync_plan="bogus"))
        tr = LMTrainer(LMTrainConfig(model=_lm_model(), dp=4, dcn_size=2,
                                     tp=2, compute_dtype=None,
                                     dcn_compress="int8"))
        tokens, targets = _lm_data(steps=1)
        with pytest.raises(ValueError, match="sync-state"):
            tr.train_steps(tokens, targets)


# -- predicted vs measured (the cost model's ground truth) ------------------


def test_predicted_bytes_match_inspector_on_emitted_programs():
    """The plan's per-axis operand-byte predictions must match the
    schedule inspector's measurements of the program the resolved
    trainer actually emits — ddp (flat) and hierarchical+int8
    (factored), within 10%."""
    from distributed_pytorch_tpu.train import make_multi_step
    from distributed_pytorch_tpu.utils import debug as dbg

    images, labels = _vgg_data(steps=1)
    for profile, dcn_size, expect in (
            ("uniform", 2, "ddp"),
            ("fast_ici_slow_dcn", 2, "hierarchical")):
        cfg = TrainConfig(strategy="auto", model="VGG11", batch_size=2,
                          dcn_size=dcn_size, autotune_profile=profile,
                          augment=False)
        tr = Trainer(cfg)
        assert tr.cfg.strategy == expect, tr.sync_plan.summary()
        img, lbl = tr._stage(images, labels)
        args = tr._args(img, lbl)
        if tr._multi_fn is None:
            tr._multi_fn = make_multi_step(tr.cfg, tr.strategy, tr.mesh,
                                           fault_sig=tr._fault_sig)
        sched = dbg.op_schedule(tr._multi_fn, *args)
        rows = dbg.assert_plan_bytes_match(tr.sync_plan, sched, rtol=0.1)
        assert rows, rows


# -- review hardening (round-11 code-review findings) -----------------------


def test_auto_refuses_ambiguous_and_premature_inputs():
    """auto owns the knobs it tunes: an explicit dcn_compress alongside
    auto raises on both trainers (silently overriding either way would
    lose someone's intent), and a caller-supplied mesh raises up front
    (resolution decides the topology — a pre-built mesh can disagree
    with the pick and would only die as a cryptic trace error)."""
    from distributed_pytorch_tpu.parallel.mesh import make_mesh

    with pytest.raises(ValueError, match="set one, not both"):
        Trainer(TrainConfig(strategy="auto", dcn_compress="int8",
                            autotune_profile="uniform", dcn_size=2))
    with pytest.raises(ValueError, match="mesh=None"):
        Trainer(TrainConfig(strategy="auto", autotune_profile="uniform"),
                mesh=make_mesh(8))
    with pytest.raises(ValueError, match="set one, not both"):
        LMTrainer(LMTrainConfig(model=_lm_model(), dp=4, dcn_size=2,
                                tp=2, sync_plan="auto",
                                dcn_compress="int8",
                                autotune_profile="uniform"))


def test_lm_auto_respects_pipeline_and_pinned_bucket():
    """sync_plan='auto' on a pipeline config must resolve to a plan the
    trainer can actually run (int8 needs the sync-state channel the
    pipeline paths lack — the chooser drops those candidates instead of
    picking a plan validate_lm_cfg would refuse), and an explicitly
    pinned bucket_mb constrains the ladder so the recorded prediction
    describes the executed config."""
    from distributed_pytorch_tpu.lm import validate_lm_cfg
    from distributed_pytorch_tpu.parallel import autotune as at2

    cfg = LMTrainConfig(
        model=tfm.TransformerConfig(vocab_size=128, d_model=128,
                                    n_layers=4, n_heads=2, head_dim=64,
                                    d_ff=256),
        dp=2, pp=2, microbatches=4,
        sync_plan="auto", autotune_profile="fast_ici_slow_dcn")
    resolved, plan = at2.resolve_lm_auto(cfg)
    assert resolved.dcn_compress is None  # int8 excluded, not refused
    validate_lm_cfg(resolved)             # the plan actually runs

    pinned = LMTrainConfig(model=_lm_model(), dp=4, dcn_size=2, tp=2,
                           bucket_mb=4.0, sync_plan="auto",
                           autotune_profile="fast_ici_slow_dcn")
    resolved, plan = at2.resolve_lm_auto(pinned)
    assert plan.bucket_mb == 4.0 and resolved.bucket_mb == 4.0
