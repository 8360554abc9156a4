"""The readers of the program's own spans and counters, on a hand-built
plain trace and a hand-built counter dict: the device's idle time split by
the innermost span, and None wherever the program wrote nothing."""

import pytest

import harness
import program_spans
import tracing

MS = 1e6   # ns
NEW = ("sched.queue_wait_ms_mean", "sched.admit_to_first_ms_mean",
       "sched.chained_pct", "sched.unchained_admission_pct",
       "sched.idle_admit_pct", "sched.idle_sync_pct")


def read(metric: str, ctx: dict):
    return harness.load_reader("layer_metrics", metric)(ctx)


def trace_of(ops, host):
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [list(e) for e in ops]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3",
             "events": [["bench:" + n, s, d] for n, s, d in host]}]}]}


def ctx_of(trace, counters=None):
    t0, t1 = tracing.window_of(trace)
    reduced = tracing.busy_and_idle(trace, t0, t1)
    reduced.update(t0=t0, t1=t1)
    return {"trace": trace, "reduced": reduced, "counters": counters or {}}


# a 100 ms window: two blocks on the device, one step of the scheduler
# around each gap, the program's regions nested in the benchmark's step
OPS = [("%fusion.1 = bf16[8] fusion", 0, 30 * MS),       # idle 30..50
       ("%fusion.2 = bf16[8] fusion", 50 * MS, 30 * MS),  # idle 80..100
       ("%fusion.3 = bf16[8] fusion", 62 * MS, 2 * MS)]   # inside fusion.2
HOST = [("window", 0, 100 * MS),
        ("sched.step", 28 * MS, 24 * MS),
        ("serve.host_plan", 29 * MS, 5 * MS),     # covers 30..34, no middle
        ("serve.prefill", 34 * MS, 14 * MS),      # covers the gap's middle
        ("serve.first_token", 38 * MS, 6 * MS),   # ... innermost over it
        ("serve.dispatch", 48 * MS, 3 * MS),
        ("sched.step", 78 * MS, 21 * MS),
        ("serve.fetch", 79 * MS, 15 * MS),        # the middle of 80..100
        ("serve.host_parse", 94 * MS, 4 * MS)]


def test_idle_is_split_by_the_innermost_span_over_each_gaps_middle():
    trace = trace_of(OPS, HOST)
    by = program_spans.idle_by_span(trace, 0, 100 * MS)
    assert by == {"serve.first_token": pytest.approx(0.020),
                  "serve.fetch": pytest.approx(0.020)}
    ctx = ctx_of(trace)
    assert read("sched.idle_admit_pct", ctx) == pytest.approx(20.0)
    assert read("sched.idle_sync_pct", ctx) == pytest.approx(20.0)
    assert (read("sched.idle_admit_pct", ctx) + read("sched.idle_sync_pct", ctx)
            == pytest.approx(100.0 * ctx["reduced"]["idle_share_max"]))


def test_a_gap_under_no_program_span_stays_with_the_benchmarks_own():
    host = [h for h in HOST if h[0] not in ("serve.prefill",
                                            "serve.first_token")]
    ctx = ctx_of(trace_of(OPS, host))
    assert program_spans.idle_by_span(ctx["trace"], 0, 100 * MS) == {
        "sched.step": pytest.approx(0.020),
        "serve.fetch": pytest.approx(0.020)}
    assert read("sched.idle_admit_pct", ctx) == pytest.approx(0.0)
    assert read("sched.idle_sync_pct", ctx) == pytest.approx(20.0)


def test_span_readers_find_nothing_without_program_spans_or_a_trace():
    bare = [h for h in HOST if not h[0].startswith("serve.")]
    for ctx in (ctx_of(trace_of(OPS, bare)),          # a parent's trace
                ctx_of({"planes": []}),               # an empty trace
                {"trace": None, "counters": {}}):     # --trace 0
        assert read("sched.idle_admit_pct", ctx) is None
        assert read("sched.idle_sync_pct", ctx) is None


COUNTERS = {"admitted": 4.0, "queue_wait_s": 0.2, "first_tokens": 5.0,
            "admit_to_first_s": 0.4, "decode_dispatches": 50.0,
            "chained_dispatches": 30.0, "unchained_off": 6.0,
            "unchained_admitting": 1.0, "unchained_empty_slot": 8.0,
            "unchained_retire_unstaged": 3.0, "unchained_refill_pages": 0.0,
            "unchained_pool": 2.0}


def test_counter_readers_take_the_windows_deltas():
    ctx = {"counters": COUNTERS}
    assert read("sched.queue_wait_ms_mean", ctx) == pytest.approx(50.0)
    assert read("sched.admit_to_first_ms_mean", ctx) == pytest.approx(80.0)
    assert read("sched.chained_pct", ctx) == pytest.approx(60.0)
    assert read("sched.unchained_admission_pct", ctx) == pytest.approx(24.0)


def test_counter_readers_find_nothing_where_the_program_counts_nothing():
    parent = {"decode_dispatches": 50.0, "chained_dispatches": 30.0}
    assert read("sched.chained_pct", {"counters": parent}) == 60.0
    for metric in ("sched.queue_wait_ms_mean", "sched.admit_to_first_ms_mean",
                   "sched.unchained_admission_pct"):
        assert read(metric, {"counters": parent}) is None
    idle = dict(COUNTERS, admitted=0.0, first_tokens=0.0,
                decode_dispatches=0.0)
    for metric in NEW[:4]:
        assert read(metric, {"counters": idle}) is None


def test_the_new_metrics_are_the_chat_cells_and_no_other_cells():
    bench = harness.load_benchmark(left_out=True)
    for w in bench["workloads"]:
        cell = harness.find_cell(w["name"], bench)
        got = {m["name"] for m in cell["per_layer"]} & set(NEW)
        assert got == (set(NEW) if w["name"] == "lm_serve_chat" else set())
