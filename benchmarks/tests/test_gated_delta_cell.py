"""The linear/full-attention hybrid's cell through the run the chip makes
(``rehearse.shrink``: the family's ``tiny``: linear, linear, linear, full;
two key heads serving four value heads of 16; 128-token rows, two chunks of
the delta rule): a sound run is correct; with a fault planted in the
program (``calibrate_gated_delta.py``'s, as it plants them on the chip),
under the int8 control and with half the row left out it is not; its counts
are its own.

Limits for that size on the CPU, set the way the chip's are, from five
seeds: sound tiny runs read ``loss_gap`` 3e-5 to 1.9e-4, ``grad_gap`` 0.16
to 0.37 (bfloat16 q, k and v through the l2 norm and the delta rule, four
layers deep at width 64: sixty times Laguna's tiny readings) and
``delta_gap`` 0.015 to 0.025; the int8 control ``delta_gap`` 0.25 to 0.29,
half the row 0.21 and ``loss_gap`` 1.4e-3 to 2.1e-3, each weight fault
``delta_gap`` over 1,000.  At this size ``grad_gap`` tells only the largest
faults apart, and ``delta_gap`` all of them.

The state carried between chunks is caught only where it carries: under the
benchmark's init a token's decay ``e^g`` is ~0.02-0.6, so the carry reaches a
chunk's first few positions and zeroing it moves the tiny run's numbers by a
few percent.  The test that plants it sets ``A_log`` to log(1e-3) in the
weights both sides are handed (a long memory: the carry is most of a
chunk's output) and judges by limits set from that regime's tiny readings
(``LONG_LIMITS``), where the fault fails all three.
"""

import math
import time

import jax
import pytest

import calibrate_gated_delta as cgd
import calibrate_one_row
import checks
import harness
import rehearse
import run
import run_train
import weights
import work

CELL = "qwen3next_train_8k"
LIMITS = {"limits": {"loss_gap": {"limit": 6e-4},
                     "grad_gap": {"limit": 0.6},
                     "delta_gap": {"limit": 0.08}}}
SEED = (1 << 31) + 3232
# under a long memory the sound tiny run reads 9.2e-5 / 0.0102 / 0.0044, the
# zeroed carry 4.2e-4 / 0.270 / 0.029: the bfloat16 gradients are sixty
# times closer to float32's than under the benchmark's fast decay (the gated
# norm divides by a head's output, which a fast decay leaves near nought)
LONG_LIMITS = {"limits": {"loss_gap": {"limit": 2e-4},
                          "grad_gap": {"limit": 0.05},
                          "delta_gap": {"limit": 0.012}}}


@pytest.fixture(autouse=True)
def v5e_peaks(monkeypatch):
    v5e = work.peaks("TPU v5 lite")
    monkeypatch.setattr(work, "peaks", lambda kind: v5e)


@pytest.fixture
def cell():
    cell = rehearse.shrink(harness.find_cell(CELL))
    cell["limits"] = LIMITS
    return cell


def firsts_and_ref(cell):
    state = run_train.prepare(cell, jax.devices("cpu")[:1], SEED, 1.0)
    calibrate_one_row.TRAINER[0] = state["trainer"]
    firsts = run_train.first_steps(state, cell, SEED, 3, 3)
    ref = run_train.follow(cell, SEED, firsts["batches"], state["hp"])
    return state, firsts, ref


def test_a_sound_run_is_correct_and_reports_the_cells_metrics(cell):
    done = run.execute(cell, jax.devices("cpu")[:1], SEED, 1.0, True,
                       time.perf_counter(), harness.CompileClock())
    assert done["result"]["correct"], done["checks"]
    assert set(done["checks"]) == set(LIMITS["limits"])
    tree = cell["family"].weights.leaf_shapes(cell["config_file"])
    assert all("attn_linear" in tree[f"layer{i}"] for i in (0, 1, 2))
    assert "attn_global" in tree["layer3"] and "lm_head" in tree
    assert tree["layer3"]["attn_global"]["wq"][0] == (64, 4, 64)
    assert tree["layer0"]["shared"]["w_sg"][0] == (64, 1)
    assert done["result"]["metrics"]["model.train_mfu_pct"]["value"] > 0
    # the cell lists the new readers; the CPU's trace has no device to read
    names = {m["name"] for m in cell["per_layer"]}
    assert {"kernel.gated_delta_roofline",
            "model.gated_delta_share_pct"} <= names
    assert "model.gated_delta_share_pct" not in done["result"]["metrics"]


@pytest.mark.parametrize("fault", list(cgd.FAULTS))
def test_a_fault_in_the_programs_copy_is_not_correct(cell, fault):
    _, firsts, ref = firsts_and_ref(cell)
    assert checks.judge(checks.train_numbers(firsts["prog"], ref), LIMITS)[0]
    numbers = cgd.planted(cell, SEED, ref, cgd.FAULTS[fault])
    correct, checked = checks.judge(numbers, LIMITS)
    assert not correct and not checked["delta_gap"]["ok"]


@pytest.mark.parametrize("fault", [
    dict(quant="int8"), dict(grad_fault=calibrate_one_row.half_row)],
    ids=["control_int8", "half_batch"])
def test_the_control_and_half_a_row_are_not_correct(cell, fault):
    state, firsts, ref = firsts_and_ref(cell)
    faulted = run_train.follow(cell, SEED, firsts["batches"], state["hp"],
                               **fault)
    correct, checked = checks.judge(checks.train_numbers(faulted, ref), LIMITS)
    failed = {name for name, c in checked.items() if not c["ok"]}
    assert not correct and "delta_gap" in failed
    # the loss of the timed step is compared too: half a row moves it tenfold
    assert ("loss_gap" in failed) == ("grad_fault" in fault)


def test_a_zeroed_carry_is_not_correct_where_the_state_carries(
        cell, monkeypatch):
    """A long memory (``A_log`` = log 1e-3 in every linear layer, in the
    weights both sides get): sound, correct; with the state carried into
    each chunk zeroed in the program (``calibrate_gated_delta.
    carry_zeroed``), not correct, by every limit of that regime."""
    make = weights.make_params

    def long_memory(family, seed, cfg, *a, **kw):
        params = make(family, seed, cfg, *a, **kw)
        for lp in params.values():
            if isinstance(lp, dict) and "attn_linear" in lp:
                lp["attn_linear"]["A_log"] = (
                    0.0 * lp["attn_linear"]["A_log"] + math.log(1e-3))
        return params

    monkeypatch.setattr(weights, "make_params", long_memory)
    monkeypatch.setattr(cgd, "CARRY_TRAINER", [None])
    _, firsts, ref = firsts_and_ref(cell)
    assert checks.judge(checks.train_numbers(firsts["prog"], ref),
                        LONG_LIMITS)[0]
    correct, checked = checks.judge(cgd.carry_zeroed(cell, SEED, ref),
                                    LONG_LIMITS)
    assert not correct
    assert not any(c["ok"] for c in checked.values()), checked


def test_the_cells_limits_compare_all_three_numbers():
    """The committed limits judge the loss, the first gradient and the
    parameters' change, each between its two readings with room on both
    sides."""
    limits = harness.find_cell(CELL)["limits"]["limits"]
    assert set(limits) == {"loss_gap", "grad_gap", "delta_gap"}
    for spec in limits.values():
        assert 2 * spec["lower"] <= spec["limit"] <= spec["upper"] / 1.5


def test_the_familys_counts_are_this_shares():
    found = harness.find_cell(CELL)
    cfg, w = found["config_file"], found["family"].work
    linear = 25_165_824 + 131_072 + 32_768 + 64 + 128 + 8_388_608
    full = 16_777_216 + 2_097_152 + 8_388_608 + 512
    beside = 1_048_576 + 3_145_728 + 2_048 + 4_096     # router, shared...
    expert = 3 * 2048 * 512
    assert (linear, full, beside) == (33_718_464, 27_263_488, 4_200_448)
    assert 3 * (linear + beside + 32 * expert) == 3 * 138_582_208
    assert full + beside + 32 * expert == 132_127_232
    assert w.param_count(cfg) == (
        3 * 138_582_208 + 132_127_232 + 2 * 18_992 * 2048 + 2048)
    assert w.param_count(cfg) == 625_667_136       # 10.01 GB at 16 B
    assert w.param_count({**cfg, "num_experts": 64}) == 1_028_320_320
    assert w.param_count({**cfg, "num_experts": 16}) == 424_340_544
    # a token's ten picks fall on the 32 held of 512 experts 0.625 times
    assert w.param_count(cfg, active=True) == 191_982_656
    assert w.routed_rows(cfg, 8192) == {"picks": 81_920, "here": 5_120,
                                        "buffer": 98_304}
    # per position and value head at chunks of 64: the triangle's four
    # products and three 128 x 128 state products
    macs = 32.5 * 128 + 31.5 * 128 + 32.5 * 256 + 32.5 * 128 + 3 * 128 * 128
    assert w.delta_rule_flops(cfg) == 2 * macs * 32 == 4_468_736
    flops = w.train_flops_per_token(cfg, 8192)
    assert flops == (6 * 191_982_656 + 12 * 4096.5 * 16 * 256
                     + 3 * 3 * 4_468_736)
    assert flops == 1_393_465_728.0      # 1.39 GFLOP a token
    delta = w.kernels["gated_delta"](cfg, rows=1, seq=8192)
    assert delta["flops"] == 3 * 3 * 4_468_736 * 8192
    # q, k at 16 key heads and v, o at 32 value heads in bfloat16, g and
    # beta in float32, the float32 state once a chunk of 64
    per_position = 2 * (2 * 2048 + 2 * 4096) + 4 * 64 + 4 * 32 * 128 * 128 / 64
    assert delta["bytes"] == 3 * 3 * per_position * 8192
    flash = w.kernels["flash_attn"](cfg, rows=1, seq=8192)
    assert flash["flops"] == 12 * 8192 * 4096.5 * 16 * 256
    grouped = w.kernels["grouped_ffn"](cfg, tokens=8192)
    assert grouped["flops"] == 4 * 18 * 5120 * 2048 * 512


# -- the new readers ----------------------------------------------------------------

def synthetic_ctx(ops):
    """A reader's ``ctx`` over one device's operations ``[(name, ns)]`` laid
    end to end inside one step program that holds a Pallas kernel."""
    found = harness.find_cell(CELL)
    events, t = [], 1000.0
    for name, ns in ops:
        events.append([name, t, float(ns)])
        t += ns
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit_step(1)", 1000.0, t - 1000.0]]},
        {"name": "XLA Ops", "events": events}]}]}
    reduced = {"t0": 0.0, "t1": t + 1000.0, "busy_s_per_device": [(t - 1000.0) * 1e-9]}
    return {"trace": trace, "reduced": reduced, "cell": found,
            "config": found["config_file"], "work": found["family"].work,
            "peak": work.peaks("TPU v5 lite"), "chips": 1, "rows": 1,
            "seq": 8192, "tokens_per_step": 8192}


def test_the_new_readers_tell_the_linear_layers_by_their_shapes():
    """The delta rule's operations by their result shapes, the convolution's
    by the row beside its channels; the flash kernels, the projections, the
    routed layer and the head by none of them."""
    ms = 1e6
    ctx = synthetic_ctx([
        ("%fusion.1 = f32[128,1,32,64,64] fusion", 2 * ms),          # squares
        ("%fusion.2 = bf16[128,1,32,64,128] fusion", 1 * ms),        # u, w
        ("%triangular-solve.3 = f32[128,1,32,64,256] triangular-solve", 3 * ms),
        ("%fusion.4 = f32[1,32,128,128] fusion", 2 * ms),            # a state
        ("%fusion.5 = bf16[1,8192,32,128] fusion", 1 * ms),          # q, k, v
        ("%fusion.6 = (f32[1,8192,32],f32[1,8192,32]) fusion", 1 * ms),
        ("%fusion.7 = bf16[1,8192,8192] fusion", 4 * ms),            # the conv
        ("%fusion.8 = bf16[1,8192,12288] fusion", 10 * ms),          # W_qkvz
        ("%custom-call.9 = bf16[1,16,8192,256] custom-call pallas", 20 * ms),
        ("%fusion.10 = f32[8192,18992] fusion", 6 * ms),             # the head
        ("%ragged-dot.11 = bf16[98304,512] custom-call pallas", 5 * ms),
        ("%fusion.12 = bf16[1,8192,2048] fusion", 45 * ms),
    ])
    read = lambda m: harness.load_reader("layer_metrics", m)(ctx)
    share = read("model.gated_delta_share_pct")
    assert share == pytest.approx(100 * 14 / 100)
    roofline = read("kernel.gated_delta_roofline")
    need = ctx["work"].kernels["gated_delta"](ctx["config"], rows=1, seq=8192)
    least = work.roofline_seconds(need["flops"], need["bytes"], ctx["peak"])
    assert least["bound"] == "memory"
    assert roofline == pytest.approx(100 * least["seconds"] / 10e-3)
    # the flash reader counts the flash kernel alone
    flash = ctx["work"].kernels["flash_attn"](ctx["config"], rows=1, seq=8192)
    least = work.roofline_seconds(flash["flops"], flash["bytes"], ctx["peak"])
    assert read("kernel.flash_attn_by_name_roofline") == pytest.approx(
        100 * least["seconds"] / 20e-3)


def test_the_new_readers_find_nothing_in_another_family():
    other = harness.find_cell("laguna_train_8k")
    ctx = {**synthetic_ctx([("%fusion.1 = f32[1,32,128,128] fusion", 1e6),
                            ("%custom-call.2 = bf16[1,48,8192,128] custom-call"
                             " pallas", 1e6)]),
           "work": other["family"].work, "config": other["config_file"]}
    for metric in ("kernel.gated_delta_roofline",
                   "model.gated_delta_share_pct"):
        assert harness.load_reader("layer_metrics", metric)(ctx) is None


@pytest.fixture(scope="module")
def recorded():
    """Two steps of ``qwen3next_train_8k`` (``recorded_trace_qwen3next.json.gz``,
    cut from the traced run of PR 36) as a reader's ``ctx``."""
    import gzip
    import json
    import os

    import tracing

    here = os.path.dirname(os.path.abspath(__file__))
    with gzip.open(os.path.join(here, "recorded_trace_qwen3next.json.gz"),
                   "rt") as f:
        rec = json.load(f)
    cell = harness.find_cell(CELL)
    reduced = tracing.busy_and_idle(rec["trace"], rec["t0"], rec["t1"])
    reduced["t0"], reduced["t1"] = rec["t0"], rec["t1"]
    return {"trace": rec["trace"], "reduced": reduced, "cell": cell,
            "config": cell["config_file"], "work": cell["family"].work,
            "peak": work.peaks("TPU v5 lite"), "chips": 1, "rows": 1,
            "seq": 8192, "tokens_per_step": 8192}


def test_the_readers_on_the_chips_trace(recorded):
    """On the chip's own trace: the delta rule's operations (the triangular
    solve is XLA's `custom-call` of `f32[128,1,32,1,64,64]`, no Pallas
    kernel) take about a third of the step's busy time with the
    convolution's, at ~5% of their least time; the flash reader sees the
    two flash kernels at 16 heads of 256 alone (the fused backward), and the
    routed layer's reader the grouped products and the layer's row work."""
    import routed_ops
    import tracing

    read = lambda m: harness.load_reader("layer_metrics", m)(recorded)
    assert 4.5 < read("kernel.gated_delta_roofline") < 5.5
    assert 35 < read("model.gated_delta_share_pct") < 42
    assert 60 < read("kernel.flash_attn_by_name_roofline") < 72
    assert 40 < read("kernel.grouped_ffn_roofline") < 46
    assert 4 < read("model.routed_share_pct") < 7
    flash = {tracing.op_key(n).split(" ")[0] for n, _, _ in
             routed_ops.readers.first_plane_ops(recorded)
             if routed_ops.is_flash(n)}
    assert flash == {"jvp__", "transpose_jvp___"}
    solve = [n for n, _, _ in routed_ops.readers.first_plane_ops(recorded)
             if n.endswith("= f32[128,1,32,1,64,64] custom-call")]
    assert solve and not any(tracing.is_kernel(n) for n in solve)
