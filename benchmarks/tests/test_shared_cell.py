"""The shared-expert two-kind family's cell through the run the chip makes
(``rehearse.shrink``: the family's ``tiny``: the dense layer and a whole
period, two head counts, half of each global head rotated, a 48-key window
shorter than the rehearsal's 128-token rows): a sound run is correct; with
the shared expert or one held expert zeroed or the gate stuck in the
program's copy, under the int8 control and with half the batch left out it
is not; its counts are its own.

Limits for that size on the CPU, set the way the chip's are: sound tiny runs
read ``grad_gap`` 0.006 to 0.010 and ``delta_gap`` 0.003 to 0.005 (bfloat16
against float32, four seeds); the int8 control reads ``grad_gap`` 0.08 to
0.14 and ``delta_gap`` 0.3, half the batch 0.5 and 0.2, the shared expert
zeroed ``grad_gap`` 0.3 and ``delta_gap`` over 100, the gate stuck 0.5 and
over 100.  The loss does not tell int8 from bfloat16 at this size either
(``loss_gap`` 9e-5 to 4e-4 against sound runs' 7e-5 to 2.0e-4, five seeds);
half the batch left out moves it (9.0e-4 to 2.1e-3), and its limit lies
between those two readings, as the chip's does.
"""

import time

import jax
import pytest

import calibrate_one_row
import calibrate_shared_gate
import checks
import harness
import rehearse
import run
import run_train
import work

CELL = "laguna_train_8k"
LIMITS = {"limits": {"loss_gap": {"limit": 4.2e-4},
                     "grad_gap": {"limit": 0.03},
                     "delta_gap": {"limit": 0.03}}}
SEED = (1 << 31) + 3232


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
    assert "attn_global" in tree["layer0"] and "w_gate" in tree["layer0"]
    assert all("attn_window" in tree[f"layer{i}"] and "shared" in
               tree[f"layer{i}"] for i in (1, 2, 3))
    assert "attn_global" in tree["layer4"] and "lm_head" in tree
    assert tree["layer1"]["attn_window"]["wg"][0] == (64, 6)
    assert done["result"]["metrics"]["model.train_mfu_pct"]["value"] > 0
    # the cell lists the new reader; the CPU's trace has no kernel to read
    assert "kernel.flash_attn_window_roofline" in {
        m["name"] for m in cell["per_layer"]}
    assert "kernel.flash_attn_window_roofline" not in done["result"]["metrics"]


@pytest.mark.parametrize("fault", list(calibrate_shared_gate.FAULTS))
def test_a_fault_in_the_programs_copy_is_not_correct(cell, fault):
    """As ``calibrate_shared_gate.py`` plants them on the chip."""
    state = run_train.prepare(cell, jax.devices("cpu")[:1], SEED, 1.0)
    calibrate_one_row.TRAINER[0] = state["trainer"]
    firsts = run_train.first_steps(state, cell, SEED, 3, 3)
    ref = run_train.follow(cell, SEED, firsts["batches"], state["hp"])
    assert checks.judge(checks.train_numbers(firsts["prog"], ref), LIMITS)[0]
    numbers = calibrate_shared_gate.planted(
        cell, SEED, ref, calibrate_shared_gate.FAULTS[fault])
    correct, checked = checks.judge(numbers, LIMITS)
    assert not correct
    assert not checked["grad_gap"]["ok"] and not checked["delta_gap"]["ok"]


@pytest.mark.parametrize("fault", [
    dict(quant="int8"), dict(grad_fault=calibrate_one_row.half_row)],
    ids=["control_int8", "half_batch"])
def test_the_control_and_half_a_row_are_not_correct(cell, fault):
    state = run_train.prepare(cell, jax.devices("cpu")[:1], SEED, 1.0)
    firsts = run_train.first_steps(state, cell, SEED, 3, 3)
    ref = run_train.follow(cell, SEED, firsts["batches"], state["hp"])
    faulted = run_train.follow(cell, SEED, firsts["batches"], state["hp"],
                               **fault)
    correct, checked = checks.judge(checks.train_numbers(faulted, ref), LIMITS)
    assert not correct
    failed = {name for name, c in checked.items() if not c["ok"]}
    assert {"grad_gap", "delta_gap"} <= failed
    # the loss of the timed step is compared too: half a row moves it tenfold
    assert ("loss_gap" in failed) == ("grad_fault" in fault)


def test_the_cells_limits_compare_all_three_numbers():
    """The committed limits judge the loss, the first gradient and the
    parameters' change, each between its two readings."""
    limits = harness.find_cell(CELL)["limits"]["limits"]
    assert set(limits) == {"loss_gap", "grad_gap", "delta_gap"}
    for spec in limits.values():
        assert 3 * spec["lower"] <= spec["limit"] <= spec["upper"] / 1.5


def test_the_familys_counts_are_this_shares():
    found = harness.find_cell(CELL)
    cfg, w = found["config_file"], found["family"].work
    attn_global = 2 * 2048 * 6144 + 2 * 2048 * 1024 + 2048 * 48
    attn_window = 2 * 2048 * 8192 + 4_194_304 + 2048 * 64
    assert (attn_global, attn_window) == (29_458_432, 37_879_808)
    expert = shared = 3 * 2048 * 512
    beside = shared + 2048 * 256 + 2 * 2048     # shared, router, two norms
    layer0 = attn_global + 3 * 2048 * 8192 + 2 * 2048
    assert layer0 == 79_794_176
    assert attn_window + beside == 41_553_920
    assert w.param_count(cfg) == (
        layer0 + 3 * (attn_window + beside + 32 * expert)
        + attn_global + beside + 32 * expert + 2 * 12_544 * 2048 + 2048)
    assert w.param_count(cfg) == 691_623_936
    # a token's eight picks fall on the 32 held of 256 experts once
    assert w.param_count(cfg, active=True) == 275_863_552
    assert w.routed_rows(cfg, 8192) == {"picks": 65_536, "here": 8_192,
                                        "buffer": 81_920}
    # a query of a windowed layer sees 496.03 keys of an 8,192-token row
    assert w.mean_keys(8192, 512) == pytest.approx(496.03125)
    flops = w.train_flops_per_token(cfg, 8192)
    attn = 12 * 128 * (2 * 4096.5 * 48 + 3 * 496.03125 * 64)
    assert flops == pytest.approx(6 * 275_863_552 + attn)
    assert flops == 2_405_520_384.0      # 2.41 GFLOP a token
    grouped = w.kernels["grouped_ffn"](cfg, tokens=8192)
    assert grouped["flops"] == 4 * 18 * 8192 * 2048 * 512
    flash = w.kernels["flash_attn"](cfg, rows=1, seq=8192)
    assert flash["flops"] == pytest.approx(attn * 8192)
    window = w.kernels["flash_attn_window"](cfg, rows=1, seq=8192)
    assert window["flops"] == pytest.approx(
        12 * 128 * 3 * 496.03125 * 64 * 8192)
    assert window["bytes"] == 6 * 8192 * 128 * 2 * 3 * (64 + 8)
    assert w.window_kernel_operand(cfg, 1, 8192) == "[64,8192,128]"


# -- the new reader on a trace the chip recorded ----------------------------------

@pytest.fixture(scope="module")
def recorded():
    """Two steps of ``laguna_train_8k`` (``recorded_trace_laguna.json.gz``,
    cut from the traced run of PR 32) as a reader's ``ctx``."""
    import gzip
    import json
    import os

    import tracing

    here = os.path.dirname(os.path.abspath(__file__))
    with gzip.open(os.path.join(here, "recorded_trace_laguna.json.gz"),
                   "rt") as f:
        rec = json.load(f)
    cell = harness.find_cell(CELL)
    reduced = tracing.busy_and_idle(rec["trace"], rec["t0"], rec["t1"])
    reduced["t0"], reduced["t1"] = rec["t0"], rec["t1"]
    return {"trace": rec["trace"], "reduced": reduced, "cell": cell,
            "config": cell["config_file"], "work": cell["family"].work,
            "peak": work.peaks("TPU v5 lite"), "chips": 1, "rows": 1,
            "seq": 8192, "tokens_per_step": 8192}


def test_the_windowed_kernels_are_told_by_their_head_count(recorded):
    import routed_ops
    import tracing

    names = {tracing.op_key(n) for n, _, _ in
             routed_ops.readers.first_plane_ops(recorded)
             if routed_ops.is_flash(n)}
    # forward and fused backward at each kind's head count, nothing else
    assert {n.split(" ")[0] for n in names} == {"jvp__", "transpose_jvp___"}
    assert sorted(n.split("bf16")[1].split("]")[0] for n in names) == [
        "[48,8192,128", "[48,8192,128", "[64,8192,128", "[64,8192,128"]
    read = lambda m: harness.load_reader("layer_metrics", m)(recorded)
    window = read("kernel.flash_attn_window_roofline")
    both = read("kernel.flash_attn_by_name_roofline")
    # the windowed layers' kernels execute four times the counted pairs
    assert 12 < window < 18 and 33 < both < 40
    shape = recorded["work"].window_kernel_operand(recorded["config"], 1, 8192)
    secs_window = routed_ops.seconds(
        recorded, lambda n: routed_ops.is_flash(n) and shape in n)
    secs_all = routed_ops.seconds(recorded, routed_ops.is_flash)
    # three windowed layers of five take just under half the kernels' time
    assert 0.4 < secs_window / secs_all < 0.55
    assert 45 < read("kernel.grouped_ffn_roofline") < 52


def test_the_reader_finds_nothing_where_the_family_has_no_such_kernel(
        recorded):
    """On a family without ``window_kernel_operand`` (every other cell's)
    the reader leaves the metric out and does not raise."""
    other = harness.find_cell("smallthinker_train_8k")
    ctx = {**recorded, "work": other["family"].work,
           "config": other["config_file"]}
    reader = harness.load_reader("layer_metrics",
                                 "kernel.flash_attn_window_roofline")
    assert reader(ctx) is None
    # nor where both kinds have one head count: the names tell nothing apart
    level = {**recorded["config"], "heads_global": 64}
    assert recorded["work"].window_kernel_operand(level, 1, 8192) is None
    assert reader({**recorded, "config": level}) is None
