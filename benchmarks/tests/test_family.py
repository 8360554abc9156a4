"""The seam is enough: a configuration brings its family's files.

A second family that lives under these tests (``families/moe_top2``: the
program's own top-2 routed block, its tree with ``moe`` leaves, a short
reference, counts) and a ``BENCHMARK.json`` made here with one serving cell
of it go through ``run.execute`` on the CPU with no edit to any file of
``benchmarks/``.  Beside it: the dense family makes the weights it made
before there were families, bit for bit; a family that lacks a name fails
with the contract's text.
"""

import hashlib
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import families
import harness
import program
import rehearse
import run
import weights
import work

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = (1 << 31) + 2626
# float32 serving on the CPU puts the reference's own first token first
LIMITS = {"limits": {"max_gap": {"limit": 1e-3}, "p90_gap": {"limit": 1e-4},
                     "wrong_length": {"limit": 0},
                     "prompt_altered": {"limit": 0}}}


@pytest.fixture
def moe_cell(monkeypatch):
    """``BENCHMARK.json`` with the chat cell's entries rewritten for one
    serving cell of the family under the tests: entries and new files."""
    monkeypatch.setattr(families, "ROOTS",
                        families.ROOTS + [os.path.join(HERE, "families")])
    v5e = work.peaks("TPU v5 lite")
    monkeypatch.setattr(work, "peaks", lambda kind: v5e)
    bench = harness.load_benchmark(left_out=True)
    bench["configs"] = [{
        "name": "moe-top2-tiny", "source": "the program's own routed block",
        "file": "benchmarks/tests/families/moe_top2_tiny.json",
        "reduced": [], "why": "a second family, to show the seam"}]
    bench["workloads"] = [{"name": "moe_serve_chat", "config": "moe-top2-tiny",
                           "traffic": "chat_open_loop", "chips": 1,
                           "why": "the chat mix on the routed block"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "lm_serve_chat" in m.get("workloads", ()):
            m["workloads"] = ["moe_serve_chat"]
    cell = rehearse.shrink(harness.find_cell("moe_serve_chat", bench))
    cell["limits"] = LIMITS
    return cell


def execute(cell, trace):
    return run.execute(cell, jax.devices("cpu")[:1], SEED, 3.0, trace,
                       time.perf_counter(), harness.CompileClock())


def test_a_second_family_runs_by_new_files_and_entries_alone(moe_cell):
    fam = moe_cell["family"]
    assert fam.name == "moe_top2" and fam.dir.startswith(HERE)
    tree = fam.weights.leaf_shapes(moe_cell["config_file"])
    assert "moe" in tree["layer1"] and "w_gate" in tree["layer0"]
    done = execute(moe_cell, trace=True)
    assert done["result"]["correct"], done["checks"]
    assert done["result"]["attempted"] > 0 and done["result"]["failed"] == 0
    assert set(done["checks"]) == set(LIMITS["limits"])
    # a reader reached the family's counts through ctx["work"]
    live = done["result"]["metrics"]["device.serve_kv_live_gb"]["value"]
    assert live > 0


def test_one_expert_zeroed_in_the_programs_copy_is_not_correct(
        moe_cell, monkeypatch):
    build = program.build_server

    def build_broken(cell, params, seed, **kw):
        moe = dict(params["layer1"]["moe"])
        moe["w_down"] = moe["w_down"].at[0].set(0.0)
        broken = {**params, "layer1": {**params["layer1"], "moe": moe}}
        return build(cell, broken, seed, **kw)

    monkeypatch.setattr(program, "build_server", build_broken)
    done = execute(moe_cell, trace=False)
    assert not done["result"]["correct"]
    assert not done["checks"]["max_gap"]["ok"]


def test_the_routed_familys_counts_are_its_own(moe_cell):
    cfg, w = moe_cell["config_file"], moe_cell["family"].work
    dense = families.load("dense_gqa").work
    d, f = 64, 128
    assert w.param_count(cfg) == dense.param_count(cfg) + 3 * 3 * d * f + 4 * d
    assert (w.decode_flops(cfg, 1, 0) - dense.decode_flops(cfg, 1, 0)
            == 2 * (3 * d * f + 4 * d))
    assert w.kernels["decode_attn"](cfg, context_tokens=10)["bytes"] == (
        10 * dense.kv_bytes_per_token(cfg))


# sha256 over every leaf's path and bytes of weights.make_params(seed, cfg,
# dtype) for benchmarks/configs/ernie-4.5-0.3b.json on the CPU, taken at the
# parent of the PR that brought the families (commit e6ed3eb)
PARENT_SUMS = {
    (2147483665, "float32"):
        "b815c83b73b952d1c6bd09d085e170de615954422b2c25c81ca3cdec64757bb8",
    (2147483665, "bfloat16"):
        "b3933eeca0e6c3d4b8a68902bca1a93340cd1c83347ed807fd9e19b8f916a54f",
    (7, "float32"):
        "8f922c18c3deafebdfdc575f87b1df27152e3f07c031c3584abda1d56d2c5c70",
}


@pytest.mark.parametrize("seed,dtype", sorted(PARENT_SUMS))
def test_the_dense_family_makes_the_weights_it_made_before(seed, dtype):
    cfg = harness.load_json(harness.HERE, "configs", "ernie-4.5-0.3b.json")
    tree = weights.make_params(families.of_config(cfg), seed, cfg,
                               jnp.dtype(dtype))
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == PARENT_SUMS[(seed, dtype)]


def family_dir(root, name, lacking=None):
    """A copy of the dense family under ``root``, one name left out."""
    src = families.load("dense_gqa").dir
    os.makedirs(root / name)
    for part in families.PARTS:
        text = open(os.path.join(src, part + ".py")).read()
        if lacking and lacking[0] == part:
            text = text.replace(lacking[1], "_not_" + lacking[1])
        (root / name / (part + ".py")).write_text(text)


@pytest.mark.parametrize("part,name", [("work", "kernels"),
                                       ("reference", "embed_backward"),
                                       ("program", "model_config"),
                                       ("weights", "leaf_shapes")])
def test_a_family_that_lacks_a_name_fails_with_the_contracts_text(
        tmp_path, monkeypatch, part, name):
    family_dir(tmp_path, "whole")
    family_dir(tmp_path, "lacking", (part, name))
    monkeypatch.setattr(families, "ROOTS", [str(tmp_path)])
    assert families.load("whole").work.kernels
    with pytest.raises(SystemExit) as e:
        families.load("lacking")
    assert f"{part}.py lacks {name}" in str(e.value)
    assert families.CONTRACT in str(e.value)


def test_a_configuration_without_a_family_or_with_an_unknown_one_fails():
    for cfg, said in (({"hidden_size": 8}, "names no \"family\""),
                      ({"family": "no_such_family"}, "no such directory")):
        with pytest.raises(SystemExit) as e:
            families.of_config(cfg, "x")
        assert said in str(e.value) and families.CONTRACT in str(e.value)


def test_no_reader_reaches_the_model_but_through_the_family():
    for kind in ("layer_metrics", "end_to_end"):
        for name in os.listdir(os.path.join(harness.HERE, kind)):
            if not name.endswith(".py"):
                continue
            with open(os.path.join(harness.HERE, kind, name)) as f:
                lines = f.read().split("\n")
            for module in ("work", "reference", "weights", "program"):
                assert f"import {module}" not in lines, (kind, name)
