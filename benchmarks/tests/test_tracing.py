"""The reduction from a trace to numbers, on hand-built traces and on the
small trace recorded on the chip beside this file."""

import gzip
import json
import os

import pytest

import tracing

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1e6   # ns


def trace_of(ops, host=(), modules=(), device=0):
    ops = [(tracing.short_name(n), s, d) for n, s, d in ops]   # as read
    planes = [{"name": f"/device:TPU:{device}", "lines": [
        {"name": "XLA Ops", "events": [list(e) for e in ops]},
        {"name": "XLA Modules", "events": [list(e) for e in modules]}]}]
    if host:
        planes.append({"name": "/host:CPU", "lines": [
            {"name": "python3",
             "events": [["bench:" + n, s, d] for n, s, d in host]}]})
    return {"planes": planes}


def test_union_subtract_and_measure():
    u = tracing.union([(0, 4), (2, 6), (10, 12), (12, 13), (20, 20)])
    assert u == [[0, 6], [10, 13]]
    assert tracing.measure(u) == 9
    assert tracing.subtract([[0, 20]], u) == [[6, 10], [13, 20]]
    assert tracing.subtract(u, [[1, 2], [5, 11]]) == [[0, 1], [2, 5], [11, 13]]


def test_busy_is_the_union_inside_the_window_and_idle_the_rest():
    ops = [("fusion.1", 0, 4 * MS), ("fusion.2", 2 * MS, 4 * MS),
           ("copy.3", 8 * MS, 4 * MS)]
    r = tracing.busy_and_idle(trace_of(ops), 1 * MS, 11 * MS)
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx(0.008)      # [1,6] and [8,11]
    assert r["idle_share_max"] == pytest.approx(0.2)


def test_busy_is_averaged_over_devices_and_idle_is_the_worst_devices():
    t = trace_of([("a.1", 0, 10 * MS)])
    t["planes"] += trace_of([("a.1", 0, 5 * MS)], device=1)["planes"]
    r = tracing.busy_and_idle(t, 0, 10 * MS)
    assert r["busy_s_per_device"] == pytest.approx([0.010, 0.005])
    assert r["busy_s"] == pytest.approx(0.0075)
    assert r["idle_share_max"] == pytest.approx(0.5)


def test_op_totals_group_by_kind_and_shape_and_count_a_loop_once():
    ops = [("%while.9 = (s32[]) while(...)", 0, 10 * MS),
           ("%fusion.1 = bf16[64,128]{1,0} fusion(...)", 1 * MS, 2 * MS),
           ("%fusion.7 = bf16[64,128]{1,0} fusion(...)", 4 * MS, 2 * MS),
           ("%copy.2 = bf16[513,2,512,128]{3,2,1,0} copy(...)", 7 * MS, 3 * MS)]
    tot = tracing.op_totals(trace_of(ops), 0, 10 * MS)
    assert tot["fusion bf16[64,128]"] == pytest.approx(0.004)
    assert tot["copy bf16[513,2,512,128]"] == pytest.approx(0.003)
    assert tot["while (s32[])"] == pytest.approx(0.003)   # its self time only
    assert tracing.op_key("fusion.123") == "fusion"
    assert tracing.op_key("custom-call.4.1") == "custom-call"


def test_short_name_keeps_name_shape_and_opcode_and_marks_pallas_kernels():
    full = ('%body.180 = bf16[32,16,128]{2,1,0:T(8,128)(2,1)S(1)} custom-call('
            's32[32]{0:T(128)S(1)} %get-tuple-element.7452), '
            'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
    short = tracing.short_name(full)
    assert short == "%body.180 = bf16[32,16,128] custom-call pallas"
    assert tracing.is_kernel(short) and tracing.op_key(short) == "body bf16[32,16,128]"
    other = tracing.short_name(
        '%custom-call.2 = f32[8]{0} custom-call(f32[8]{0} %x), '
        'custom_call_target="ConcatBitcast"')
    assert not tracing.is_kernel(other)
    tup = tracing.short_name('%sort.1 = (f32[32,103424]{1,0}, s32[32,103424]{1,0}) '
                             'sort(%a, %b), dimensions={1}')
    assert tracing.op_key(tup) == "sort (f32[32,103424],s32[32,103424])"
    assert tracing.is_container(tracing.short_name("%while.3 = (s32[]) while(%t)"))
    assert tracing.short_name("bench:window") == "bench:window"


def test_gaps_are_named_by_the_innermost_span_over_their_middle():
    ops = [("a.1", 0, 2 * MS), ("a.2", 5 * MS, 1 * MS), ("a.3", 9 * MS, 1 * MS)]
    host = [("window", 0, 10 * MS), ("sched.step", 1 * MS, 5 * MS),
            ("loadgen.offer", 2.5 * MS, 2 * MS)]
    gaps = dict(tracing.idle_gaps(trace_of(ops, host), 0, 10 * MS))
    assert gaps == {"loadgen.offer": pytest.approx(0.003),
                    "_no_span_": pytest.approx(0.003)}
    assert tracing.window_of(trace_of(ops, host)) == (0, 10 * MS)


def test_exposed_collective_share_leaves_out_what_compute_hides():
    ops = [("%fusion.1 = f32[8]{0} fusion()", 0, 4 * MS),
           ("%all-reduce.1 = f32[8]{0} all-reduce()", 3 * MS, 3 * MS),
           ("%fusion.2 = f32[8]{0} fusion()", 8 * MS, 2 * MS)]
    share = tracing.exposed_collective_share(trace_of(ops), 0, 10 * MS)
    assert share == pytest.approx(0.2)              # [4,6] of 10 ms
    assert tracing.exposed_collective_share(trace_of(ops[:1]), 0, 10 * MS) is None


def test_module_runs_keeps_whole_runs_inside_the_window():
    mods = [("jit_step(1)", 0, 3 * MS), ("jit_step(1)", 4 * MS, 3 * MS),
            ("jit_other(2)", 8 * MS, 1 * MS), ("jit_step(1)", 9 * MS, 3 * MS)]
    runs = tracing.module_runs(trace_of([], modules=mods), 0, 10 * MS,
                               lambda n: "step" in n)
    assert [(s, d) for _, s, d in runs] == [(0, 3 * MS), (4 * MS, 3 * MS)]


RECORDED = os.path.join(HERE, "recorded_trace.json.gz")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_recorded_chip_trace_reduces_to_the_numbers_noted_beside_it():
    with gzip.open(RECORDED, "rt") as f:
        rec = json.load(f)
    trace, want = rec["trace"], rec["expected"]
    t0, t1 = tracing.window_of(trace)
    r = tracing.busy_and_idle(trace, t0, t1)
    assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0.0 < r["busy_s"] <= r["window_s"]
    bd = tracing.breakdown(trace, t0, t1)
    assert [k for k, _ in bd["device_ops"]][:3] == want["top_ops"]
    assert sum(v for _, v in bd["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s_per_device"][0], rel=1e-6)
