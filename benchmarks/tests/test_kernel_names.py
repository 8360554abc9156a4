"""The readers of the flash kernels by name, ``kernel.flash_fwd_roofline``
and ``kernel.flash_bwd_roofline``, on copies of the chip's recorded traces
whose flash kernels are renamed as a program with named kernels compiles
them: ``%jvp__`` (the forward) becomes ``%jvp_flash_fwd_``, and
``%transpose_jvp___`` (the backward) ``%transpose_jvp_flash_bwd__``; the
windowed layers' kernels take ``_window``, and of the two-kernel backward
the one whose result is a single array is ``flash_bwd_dq`` and the one whose
result is a pair ``flash_bwd_dkv``.  The same again with the names bare, as
a kernel compiled under ``checkpoint`` or in a loop's body is named.  The
recorded files are read as they are."""

import copy
import gzip
import json
import os
import re

import pytest

import harness
import kernel_names
import tracing
import work

HERE = os.path.dirname(os.path.abspath(__file__))

# cell -> (recorded file, rows a step, the combined reader of that cell)
RECORDED = {
    "lm_train_4k": ("recorded_trace.json.gz", 2, "kernel.flash_attn_roofline"),
    "laguna_train_8k": ("recorded_trace_laguna.json.gz", 1,
                        "kernel.flash_attn_by_name_roofline"),
    "qwen3next_train_8k": ("recorded_trace_qwen3next.json.gz", 1,
                           "kernel.flash_attn_by_name_roofline"),
}
FORMS = {
    "under_grad": lambda own, fwd: (f"jvp_{own}_" if fwd
                                    else f"transpose_jvp_{own}__"),
    "bare": lambda own, fwd: own,
}


def load(cell):
    """The recorded trace of ``cell`` as a reader's ``ctx``."""
    name, rows, _ = RECORDED[cell]
    with gzip.open(os.path.join(HERE, name), "rt") as f:
        rec = json.load(f)
    trace = rec["trace"]
    t0, t1 = ((rec["t0"], rec["t1"]) if "t0" in rec
              else tracing.window_of(trace))
    found = harness.find_cell(cell)
    seq = found["mix"]["seq_len"]
    return {"trace": trace, "reduced": {"t0": t0, "t1": t1}, "cell": found,
            "config": found["config_file"], "work": found["family"].work,
            "peak": work.peaks("TPU v5 lite"), "chips": 1, "rows": rows,
            "seq": seq, "tokens_per_step": rows * seq}


def own(name, window_shape):
    """What the kernel of an unnamed trace event is called today."""
    shape = name.partition(" = ")[2].split(" ")[0]
    windowed = window_shape is not None and window_shape in shape
    suffix = "_window" if windowed else ""
    if name.startswith("%jvp__."):
        return "flash_fwd" + suffix
    results = shape.count("[")
    return {1: "flash_bwd_dq", 2: "flash_bwd_dkv",
            3: "flash_bwd"}[results] + suffix


def renamed(ctx, form):
    """A copy of ``ctx`` whose flash kernels carry their names in ``form``."""
    trace = copy.deepcopy(ctx["trace"])
    operand = getattr(ctx["work"], "window_kernel_operand", None)
    window_shape = (operand(ctx["config"], ctx["rows"], ctx["seq"])
                    if operand else None)
    for plane in tracing.device_planes(trace):
        for event in tracing.line_events(plane, tracing.OPS_LINE):
            m = re.match(r"^%(jvp__|transpose_jvp___)(\.\d+ = .*)$", event[0])
            if m and tracing.is_kernel(event[0]):
                name = own(event[0], window_shape)
                event[0] = "%" + FORMS[form](name, m.group(1) == "jvp__") \
                    + m.group(2)
    return {**ctx, "trace": trace}


def read(metric, ctx):
    return harness.load_reader("layer_metrics", metric)(ctx)


def by_hand(ctx, unnamed_prefix, share):
    """The metric from the recorded events directly: the kernels' durations
    inside the window summed (no Pallas kernel nests in another), and the
    family's count at ``share``."""
    t0, t1 = ctx["reduced"]["t0"], ctx["reduced"]["t1"]
    ops = tracing.line_events(tracing.device_planes(ctx["trace"])[0],
                              tracing.OPS_LINE)
    secs = sum(d for n, s, d in ops if n.startswith(unnamed_prefix)
               and tracing.is_kernel(n) and s >= t0 and s + d <= t1) / 1e9
    mods = tracing.line_events(tracing.device_planes(ctx["trace"])[0],
                               tracing.MODULES_LINE)
    steps = sum(1 for _, s, d in mods if s >= t0 and s + d <= t1)
    need = ctx["work"].kernels["flash_attn"](ctx["config"], rows=ctx["rows"],
                                             seq=ctx["seq"])
    least = work.roofline_seconds(need["flops"], need["bytes"], ctx["peak"])
    assert least["bound"] == "compute"
    return 100 * steps * share * least["seconds"] / secs


@pytest.mark.parametrize("form", list(FORMS))
@pytest.mark.parametrize("cell", list(RECORDED))
def test_the_two_readers_on_renamed_copies_of_the_chips_traces(cell, form):
    ctx = load(cell)
    named = renamed(ctx, form)
    fwd = read("kernel.flash_fwd_roofline", named)
    bwd = read("kernel.flash_bwd_roofline", named)
    assert fwd == pytest.approx(by_hand(ctx, "%jvp__.", 2 / 6), rel=1e-9)
    assert bwd == pytest.approx(by_hand(ctx, "%transpose_jvp___.", 4 / 6),
                                rel=1e-9)
    assert 0 < fwd < 105 and 0 < bwd < 105
    # together they are the accepted combined reading of the same trace
    combined = read(RECORDED[cell][2], named)
    assert 1 / (1 / (3 * fwd) + 2 / (3 * bwd)) == pytest.approx(
        combined, rel=5e-3)
    # which the names do not move
    assert combined == read(RECORDED[cell][2], ctx)


@pytest.mark.parametrize("cell", list(RECORDED))
def test_the_unrenamed_traces_read_nothing(cell):
    ctx = load(cell)
    assert read("kernel.flash_fwd_roofline", ctx) is None
    assert read("kernel.flash_bwd_roofline", ctx) is None


def test_what_the_renamed_copies_hold():
    """The renaming gave each kernel the name the program gives it: the
    ``lm_train_4k`` trace, recorded with the two-kernel backward, the dQ and
    the dK/dV kernel, Laguna's windowed layers (64 heads over a 512 window)
    their ``_window`` kernels."""
    def names(cell):
        ctx = renamed(load(cell), "under_grad")
        return {kernel_names.own_name(n) for n, _, _ in
                tracing.line_events(tracing.device_planes(ctx["trace"])[0],
                                    tracing.OPS_LINE)
                if kernel_names.own_name(n).startswith("flash")}

    assert names("lm_train_4k") == {"flash_fwd", "flash_bwd_dq",
                                    "flash_bwd_dkv"}
    assert names("laguna_train_8k") == {"flash_fwd", "flash_bwd",
                                        "flash_fwd_window",
                                        "flash_bwd_window"}
    assert names("qwen3next_train_8k") == {"flash_fwd", "flash_bwd"}


@pytest.mark.parametrize("op,name", [
    ("%jvp_flash_fwd_.3 = (bf16[32,4096,128],f32[32,8,4096]) custom-call"
     " pallas", "flash_fwd"),
    ("%jvp_flash_fwd_window_.1 = (bf16[64,8192,128],f32[64,8,8192])"
     " custom-call pallas", "flash_fwd_window"),
    ("%transpose_jvp_flash_bwd__.5 = (bf16[1],bf16[1],bf16[1]) custom-call"
     " pallas", "flash_bwd"),
    ("%transpose_jvp_flash_bwd_dq__.2 = bf16[28,8192,128] custom-call pallas",
     "flash_bwd_dq"),
    ("%flash_bwd_window.9 = (bf16[1],bf16[1],bf16[1]) custom-call pallas",
     "flash_bwd_window"),
    ("%decode_attn_paged.4 = bf16[64,16,128] custom-call pallas",
     "decode_attn_paged"),
    ("%jvp__.7 = (bf16[28,8192,128],f32[28,8,8192]) custom-call pallas", ""),
    ("%transpose_jvp___.7 = bf16[28,8192,128] custom-call pallas", ""),
    ("%ragged-dot-none.4 = bf16[16,768,2560] custom-call pallas",
     "ragged-dot-none"),
    ("%flash_fwd.1 = bf16[8,128] fusion", ""),       # no Pallas kernel
])
def test_a_kernels_own_name(op, name):
    assert kernel_names.own_name(op) == name


def test_the_entries_list_every_training_cell():
    bench = harness.load_benchmark()
    train = [w["name"] for w in bench["workloads"]
             if harness.find_cell(w["name"], bench)["mix"]["kind"] == "train"]
    entries = {m["name"]: m for m in bench["per_layer"]}
    for metric in ("kernel.flash_fwd_roofline", "kernel.flash_bwd_roofline"):
        assert entries[metric]["workloads"] == train
        assert entries[metric]["layer"] == entries[
            "kernel.flash_attn_roofline"]["layer"]
