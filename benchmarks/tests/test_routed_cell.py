"""The routed two-kind family's cell through the run the chip makes
(``rehearse.shrink``: the family's ``tiny``, whose 48-key window is shorter
than the rehearsal's 128-token rows, so the band is live): a sound run is
correct; with one held expert zeroed in the program's copy, under the int8
control and with half the batch left out it is not; its counts are its own.

Limits for that size on the CPU, set the way the chip's are: sound tiny runs
read ``grad_gap`` 0.004 to 0.010 and ``delta_gap`` 0.002 to 0.0034 (bfloat16
against float32); the int8 control reads ``grad_gap`` 0.09 to 0.16 and
``delta_gap`` 0.25 to 0.28, half the batch 0.50 to 0.63 and 0.18 to 0.21, one
zeroed expert ``grad_gap`` 0.11 to 0.21 and ``delta_gap`` over 100 (four
seeds each).  The loss does not tell int8 from bfloat16 at this size (sound
0.9e-5 to 2.4e-5, int8 3.4e-5 to 6.6e-5: under threefold) and has no limit
here, as in the cell's own limits.
"""

import time

import jax
import pytest

import checks
import harness
import program
import rehearse
import run
import run_train
import work

CELL = "smallthinker_train_8k"
LIMITS = {"limits": {"grad_gap": {"limit": 0.03},
                     "delta_gap": {"limit": 0.03}}}
SEED = (1 << 31) + 2828


@pytest.fixture(autouse=True)
def v5e_peaks(monkeypatch):
    v5e = work.peaks("TPU v5 lite")
    monkeypatch.setattr(work, "peaks", lambda kind: v5e)


@pytest.fixture
def cell():
    cell = rehearse.shrink(harness.find_cell(CELL))
    cell["limits"] = LIMITS
    return cell


def execute(cell, trace=False):
    return run.execute(cell, jax.devices("cpu")[:1], SEED, 1.0, trace,
                       time.perf_counter(), harness.CompileClock())


def test_a_sound_run_is_correct_and_reports_the_cells_metrics(cell):
    done = execute(cell, trace=True)
    assert done["result"]["correct"], done["checks"]
    assert set(done["checks"]) == set(LIMITS["limits"])
    tree = cell["family"].weights.leaf_shapes(cell["config_file"])
    assert "attn_global_nope" in tree["layer0"] and "lm_head" in tree
    assert all("attn_window" in tree[f"layer{i}"] for i in (1, 2, 3))
    # the whole step's share of the peak is read through the family's counts
    assert done["result"]["metrics"]["model.train_mfu_pct"]["value"] > 0


def test_one_held_expert_zeroed_in_the_programs_copy_is_not_correct(
        cell, monkeypatch):
    reset = program.reset_trainer

    def reset_broken(trainer, params):
        moe = dict(params["layer1"]["moe"])
        moe["w_down"] = moe["w_down"].at[0].set(0.0)
        reset(trainer, {**params, "layer1": {**params["layer1"],
                                             "moe": moe}})

    monkeypatch.setattr(program, "reset_trainer", reset_broken)
    done = execute(cell)
    assert not done["result"]["correct"]
    assert not done["checks"]["grad_gap"]["ok"]


@pytest.mark.parametrize("fault", [
    dict(quant="int8"),
    dict(grad_fault=lambda t, y: (t[:len(t) // 2], y[:len(y) // 2]))],
    ids=["control_int8", "half_batch"])
def test_the_control_and_half_a_batch_are_not_correct(cell, fault):
    devices = jax.devices("cpu")[:1]
    state = run_train.prepare(cell, devices, SEED, 1.0)
    firsts = run_train.first_steps(state, cell, SEED, 3, 3)
    ref = run_train.follow(cell, SEED, firsts["batches"], state["hp"])
    sound = checks.judge(checks.train_numbers(firsts["prog"], ref), LIMITS)
    assert sound[0], sound[1]
    faulted = run_train.follow(cell, SEED, firsts["batches"], state["hp"],
                               **fault)
    assert not checks.judge(checks.train_numbers(faulted, ref), LIMITS)[0]


def test_the_familys_counts_are_this_shares():
    cfg = harness.find_cell(CELL)["config_file"]
    w = harness.find_cell(CELL)["family"].work
    layer = 20_971_520 + 2560 * 64 + 16 * 5_898_240 + 2 * 2560
    assert w.param_count(cfg) == 4 * layer + 2 * 37_984 * 2560 + 2560
    # a token's six picks fall on the 16 held of 64 experts 1.5 times
    assert w.routed_rows(cfg, 8192) == {"picks": 49_152, "here": 12_288,
                                        "buffer": 49_152 + 17 * 512}
    # a query of a windowed layer sees 3,072.25 keys of an 8,192-token row
    assert w.mean_keys(8192, 4096) == 3072.25
    assert w.mean_keys(8192) == 4096.5 and w.mean_keys(128, 4096) == 64.5
    flops = w.train_flops_per_token(cfg, 8192)
    attn = 12 * (4096.5 + 3 * 3072.25) * 28 * 128
    assert flops == pytest.approx(
        6 * (4 * (20_971_520 + 163_840 + 1.5 * 5_898_240 + 5120)
             + 37_984 * 2560 + 2560) + attn)
    assert 1.8e9 < flops < 1.95e9
    grouped = w.kernels["grouped_ffn"](cfg, tokens=8192)
    assert grouped["flops"] == 4 * 18 * 12_288 * 2560 * 768
    flash = w.kernels["flash_attn"](cfg, rows=1, seq=8192)
    assert flash["flops"] == pytest.approx(attn * 8192)
