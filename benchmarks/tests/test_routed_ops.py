"""The readers of a routed step on a trace the chip recorded: two steps of
``smallthinker_train_8k`` (``recorded_trace_routed.json.gz``, cut from a run
of PR 28).  What is pinned: which operations count as the routed layer's, as
grouped products and as flash attention, by the names and shapes the chip's
trace gives them, and the three metrics those make."""

import gzip
import json
import os

import pytest

import harness
import routed_ops
import tracing
import work

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def ctx():
    with gzip.open(os.path.join(HERE, "recorded_trace_routed.json.gz"),
                   "rt") as f:
        rec = json.load(f)
    cell = harness.find_cell("smallthinker_train_8k")
    reduced = tracing.busy_and_idle(rec["trace"], rec["t0"], rec["t1"])
    reduced["t0"], reduced["t1"] = rec["t0"], rec["t1"]
    return {"trace": rec["trace"], "reduced": reduced, "cell": cell,
            "config": cell["config_file"], "work": cell["family"].work,
            "peak": work.peaks("TPU v5 lite"), "chips": 1, "rows": 1,
            "seq": 8192, "tokens_per_step": 8192}


def read(metric, ctx):
    return harness.load_reader("layer_metrics", metric)(ctx)


def test_names_on_the_chips_trace_tell_the_kernels_apart(ctx):
    names = {tracing.op_key(n) for n, _, _ in routed_ops.readers.
             first_plane_ops(ctx) if tracing.is_kernel(n)}
    grouped = {n for n in names if routed_ops.is_grouped(n)}
    assert grouped == {
        "ragged-dot-none bf16[49152,768]", "ragged-dot-none bf16[49152,2560]",
        "ragged-dot-none bf16[16,2560,768]",
        "ragged-dot-none bf16[16,768,2560]",
        "ragged-dot-metadata (s32[17],s32[111],s32[111],s32[1])"}
    assert {n.split(" ")[0] for n in names - grouped} == {
        "jvp__", "transpose_jvp___"}


@pytest.mark.parametrize("name,routed", [
    ("%fusion.1 = bf16[49152,2560] fusion", True),          # a row gather
    ("%reshape.2 = bf16[8192,6,2560] reshape", True),       # the combine
    ("%sort.3 = (s32[49152],s32[49152]) sort", True),
    ("%ragged-dot-none.4 = bf16[16,768,2560] custom-call pallas", True),
    ("%fusion.5 = bf16[8192,2560] fusion", False),
    ("%fusion.6 = f32[8192,37984] fusion", False),
    ("%jvp__.7 = (bf16[28,8192,128],f32[28,8,8192]) custom-call pallas",
     False),
    ("%fusion.8 = f32[149152,2560] fusion", False),
    ("%fusion.9 = bf16[57856,768] fusion", True),           # the row buffer
])
def test_what_counts_as_the_routed_layer(name, routed):
    assert routed_ops.is_routed(name, 8192, 6, 57856) == routed


def test_the_three_metrics_of_the_recorded_steps(ctx):
    share = read("model.routed_share_pct", ctx)
    grouped = read("kernel.grouped_ffn_roofline", ctx)
    flash = read("kernel.flash_attn_by_name_roofline", ctx)
    # the routed layer is between a quarter and a third of these steps
    assert 25 < share < 35
    assert 30 < grouped < 50 and 35 < flash < 50
    # the two kernels' seconds add up to what the shared reader sums
    both = (routed_ops.seconds(ctx, routed_ops.is_grouped)
            + routed_ops.seconds(ctx, routed_ops.is_flash))
    assert both == pytest.approx(routed_ops.readers.kernel_seconds(ctx))
    # and the accepted flash reader, which sums both, would read low here
    low = read("kernel.flash_attn_roofline", ctx)
    assert low < 0.8 * flash


def test_a_program_without_the_routed_layer_reads_nothing(ctx):
    dense = dict(ctx, work=harness.find_cell("lm_train_4k")["family"].work)
    assert read("model.routed_share_pct", dense) is None
    assert read("kernel.grouped_ffn_roofline", dense) is None
