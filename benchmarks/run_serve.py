"""A ``serve`` run: the program's server behind the benchmark's own load
generator and per-request clock.

One thread drives everything: requests that are due are submitted between
``step()`` calls, every token ``step()`` hands out is stamped on return, and
nothing is printed or written inside the window.  An ``open_loop`` mix
offers load on a schedule fixed in its file, starting ``ramp_seconds`` ahead
of the window so that the window opens in steady state; the window's
requests are those *due* in it.  A ``backlog`` mix keeps the queue
``depth`` requests deep throughout.
"""

from __future__ import annotations

import gc
import time

import jax.numpy as jnp
import numpy as np

import checks
import harness
import program
import reference
import tracing
import traffic
import weights
import work

DRAIN_LIMIT_S = 60.0   # an answer that comes later than this never came


class Book:
    """The benchmark's own clock per request (all times perf_counter)."""

    def __init__(self):
        self.req: dict[int, traffic.Request] = {}
        self.due: dict[int, float] = {}
        self.submitted: dict[int, float] = {}
        self.first: dict[int, float] = {}
        self.last: dict[int, float] = {}
        self.count: dict[int, int] = {}
        self.window_tokens = 0

    def submit(self, cb, r: traffic.Request, due_at: float) -> int:
        rid = cb.submit(r.prompt, r.max_new,
                        temperature=0.0 if r.greedy else None)
        self.req[rid], self.due[rid] = r, due_at
        self.submitted[rid] = time.perf_counter()
        self.count[rid] = 0
        return rid

    def stamp(self, out, now: float, in_window: bool) -> None:
        for rid, _tok in out:
            if rid not in self.first:
                self.first[rid] = now
            self.last[rid] = now
            self.count[rid] += 1
        if in_window:
            self.window_tokens += len(out)

    def finished(self, rid: int) -> bool:
        return self.count[rid] >= self.req[rid].max_new


def warm(cb, mix: dict, vocab: int) -> None:
    """Compile every shape the mix can reach: one request per reachable
    prompt bucket, then a staggered tail whose live count falls through
    every decode width.  Counted as set-up; checked afterwards."""
    rng = np.random.default_rng(0)
    widths = [int(w) for w in mix["warm_widths"]]
    for b in mix["warm_buckets"]:
        cb.submit(rng.integers(0, vocab, int(b)).astype(np.int32), 2)
    # and one greedy request: its first token is picked by another program
    cb.submit(rng.integers(0, vocab, 16).astype(np.int32), 2, temperature=0.0)
    while cb.pending():
        cb.step()
    # then fill the widest width and let the live count halve block by
    # block, so that a dispatch happens at every narrower width
    k = cb.steps_per_sync
    n, group, block = max(widths), max(widths) // 2, 1
    while n >= 1 and max(widths) > 1:
        for _ in range(max(group, 1)):
            cb.submit(rng.integers(0, vocab, 16).astype(np.int32),
                      k * block + 2)
        n, group, block = n - max(group, 1), group // 2, block + 1
    while cb.pending():
        cb.step()
    have = program.server_programs(cb)
    missing = ([w for w in widths if w not in have["decode_widths"]]
               + [b for b in mix["warm_buckets"]
                  if b not in have["prefill_buckets"]])
    if missing:
        raise RuntimeError(f"warm-up left shapes uncompiled: {missing}; "
                           f"have {have}")


def drive(cb, requests: traffic.Requests, mix: dict, seconds: float,
          book: Book, clock: harness.CompileClock,
          trace_out: dict | None) -> dict:
    """Ramp (set-up), window, drain.  Returns the window's bounds and the
    program's counters and the compile clock at both of its edges."""
    arr = mix["arrivals"]
    open_loop = arr["process"] == "open_loop"
    depth = int(arr.get("depth", 0))
    pending: list[traffic.Request] = []
    seg_k = 0

    def next_request() -> traffic.Request:
        nonlocal seg_k
        if not pending:
            pending.extend(requests.segment(seg_k))
            seg_k += 1
        return pending[0]

    if open_loop:
        # a schedule is known ahead: make it before the clock starts
        horizon = float(mix["ramp_seconds"]) + seconds
        while not pending or pending[-1].due <= horizon:
            pending.extend(requests.segment(seg_k))
            seg_k += 1
    start = time.perf_counter()
    t_open = start + float(mix["ramp_seconds"])
    t_close = t_open + seconds
    edge: dict = {}
    profiler = window_span = None

    def offer(now: float) -> None:
        if open_loop:
            while start + next_request().due <= min(now, t_close):
                r = pending.pop(0)
                book.submit(cb, r, start + r.due)
        else:
            while now < t_close and cb.queue_depth() < depth:
                next_request()
                book.submit(cb, pending.pop(0), now)

    def snapshot() -> dict:
        return {"counters": program.server_counters(cb),
                "compile": clock.snapshot()}

    opened = closed = False
    waiting: list[int] = []
    while True:
        now = time.perf_counter()
        if not opened and now >= t_open:
            opened = True
            gc.collect()
            gc.freeze()
            if trace_out is not None:
                profiler = tracing.capture(trace_out)
                profiler.__enter__()
                window_span = tracing.span("window")
                window_span.__enter__()
            # collecting garbage and starting the profiler are not the
            # server's time: the schedule moves with the window
            stall = time.perf_counter() - now
            start, t_open = start + stall, now + stall
            t_close, now = t_open + seconds, t_open
            edge["open"] = snapshot()
        if opened and not closed and now >= t_close:
            closed = True
            edge["close"] = snapshot()
            edge["t_close"] = t_close
            if trace_out is not None:
                window_span.__exit__(None, None, None)
                profiler.__exit__(None, None, None)
            waiting = [rid for rid, d in book.due.items()
                       if open_loop and t_open <= d < t_close]
        if closed:
            # late is late, not wrong: wait for each first token that is due
            waiting = [rid for rid in waiting if rid not in book.first]
            if not waiting or now > t_close + DRAIN_LIMIT_S:
                break
        with tracing.span("loadgen.offer"):
            offer(now)
        if cb.pending():
            with tracing.span("sched.step"):
                out = cb.step()
            t = time.perf_counter()
            book.stamp(out, t, opened and t < t_close)
        else:
            with tracing.span("loadgen.idle"):
                due = start + next_request().due if open_loop else now
                time.sleep(min(max(due - time.perf_counter(), 0.0), 0.001))
    edge["t_open"] = t_open
    return edge


def sample_for_check(book: Book, t_open: float, t_close: float, seed: int,
                     budget_tokens: int) -> list[int]:
    """Greedy requests that finished in the window: the longest of them,
    then others drawn from the seed until ``budget_tokens`` served tokens."""
    done = [rid for rid in book.first
            if book.req[rid].greedy and book.finished(rid)
            and t_open <= book.last[rid] < t_close]
    if not done:
        return []
    done.sort(key=lambda r: -(len(book.req[r].prompt) + book.req[r].max_new))
    rng = np.random.default_rng([int(seed), 11])
    rest = list(rng.permutation(done[1:]))
    picked, total = [done[0]], book.req[done[0]].max_new
    while rest and total < budget_tokens:
        rid = int(rest.pop(0))
        picked.append(rid)
        total += book.req[rid].max_new
    return picked


def served_of(cb, book: Book, rids: list[int]) -> list[dict]:
    """What the server says it served for each of ``rids``."""
    out = []
    for rid in rids:
        r, result = book.req[rid], np.asarray(cb.result(rid))
        out.append({"prompt": r.prompt, "tokens": result[len(r.prompt):],
                    "wanted_new": r.max_new, "result": result})
    return out


def check(cell: dict, params, served: list[dict],
          with_control: bool = False) -> list[dict]:
    """The reference over each sampled request's prompt with its served
    tokens.  ``served``: {"prompt", "tokens", "wanted_new", "result"}."""
    out = []
    for s in served:
        got = np.asarray(s["result"])
        n_prompt = len(s["prompt"])
        gaps = reference.served_gaps(cell["family"].reference, params,
                                     s["prompt"], s["tokens"],
                                     cell["config_file"], with_control)
        out.append({**gaps, "served": len(s["tokens"]),
                    "wanted_new": s["wanted_new"],
                    "prompt_ok": bool(np.array_equal(got[:n_prompt],
                                                     s["prompt"]))})
    return out


def run(cell: dict, devices, seed: int, seconds: float, trace: bool,
        t_start: float, clock: harness.CompileClock) -> dict:
    cfg, mix, dep = cell["config_file"], cell["mix"], cell["deployment"]
    phase = harness.Phases(clock, t_start)
    params = weights.make_params(cell["family"], seed, cfg,
                                 jnp.dtype(dep["dtype"]))
    cb = program.build_server(cell, params, seed)
    phase("weights and server")
    warm(cb, mix, cfg["vocab_size"])
    phase(f"warm-up of {len(mix['warm_buckets'])} prompt buckets and "
          f"{len(mix['warm_widths'])} decode widths")
    requests = traffic.Requests(mix, seed, cfg["vocab_size"])
    book = Book()
    traced: dict | None = {} if trace else None
    if trace:
        seconds = min(seconds, float(mix.get("trace_seconds", seconds)))

    # the ramp is set-up: the window opens ramp_seconds into the drive
    edge = drive(cb, requests, mix, seconds, book, clock, traced)
    t_open, t_close = edge["t_open"], edge["t_close"]
    phase(f"ramp of {mix['ramp_seconds']} s, window and drain")
    setup_s = t_open - t_start
    memory_peak = harness.memory_peak_bytes(devices)

    picked = sample_for_check(book, t_open, t_close, seed,
                              int(mix["check_tokens"]))
    served = served_of(cb, book, picked)
    c0, c1 = edge["open"]["counters"], edge["close"]["counters"]
    counters = {k: c1[k] - c0.get(k, 0.0) for k in c1}
    live_context = _live_context(book, t_open, t_close,
                                 int(dep["page_tokens"]))
    del cb
    gc.unfreeze()
    gc.collect()
    window_s = t_close - t_open
    harness.log(f"live in the window, mean: "
                f"{live_context['window_live_token_s'] / window_s:.0f} "
                f"context tokens in "
                f"{live_context['window_live_page_s'] / window_s:.1f} pages "
                f"of the pool's {int(dep['pool_pages']) - 1}; peak "
                f"{memory_peak / 1e9:.2f} GB counts the whole pool")

    t_ref = time.perf_counter()
    sampled = check(cell, params, served)
    numbers = (checks.serve_numbers(sampled) if sampled
               else {"max_gap": None})
    harness.log(f"reference read {sum(s['served'] for s in sampled)} served "
                f"tokens of {len(sampled)} requests in "
                f"{time.perf_counter() - t_ref:.1f} s; numbers {numbers}")
    correct, checked = checks.judge(numbers, cell["limits"])

    if mix["arrivals"]["process"] == "open_loop":
        # the window's requests are those due in it; one whose first token
        # never came has failed
        in_window = [rid for rid, d in book.due.items()
                     if t_open <= d < t_close]
        failed = [rid for rid in in_window if rid not in book.first]
    else:
        # a backlog has no due times: its requests are those it finished
        in_window = [rid for rid, t in book.last.items()
                     if book.finished(rid) and t_open <= t < t_close]
        failed = [rid for rid in in_window
                  if book.count[rid] != book.req[rid].max_new]
    ctx = {
        "kind": "serve", "cell": cell, "config": cfg, "mix": mix,
        "chips": len(devices), "peak": work.peaks(devices[0].device_kind),
        "work": cell["family"].work, "setup_s": setup_s,
        "window_s": t_close - t_open,
        "t_open": t_open, "t_close": t_close, "book": book,
        "window_requests": in_window,
        "counters": {**counters,
                     "compile_s": edge["open"]["compile"][0],
                     "inwindow_compiles": (edge["close"]["compile"][1]
                                           - edge["open"]["compile"][1]),
                     **live_context},
        "memory_peak_bytes": memory_peak,
        "trace": (traced or {}).get("trace"),
    }
    return {"ctx": ctx, "correct": correct and not failed, "checks": checked,
            "attempted": len(in_window), "failed": len(failed),
            "memory_peak": memory_peak}


def _live_context(book: Book, t_open: float, t_close: float,
                  page: int) -> dict:
    """Work the window's decode steps had to do, from the benchmark's own
    record: every token handed out in the window, with the context it
    attended.  Token times inside a block are spread evenly between the
    request's first and last stamps.  Also what the window held live: a
    request's context (prompt and tokens so far) counts from its first
    token to its last, in tokens and in the ``page``-token pages that hold
    them, each times the seconds it was held (a prompt being written before
    its first token is not seen from here, so this reads a little low)."""
    tokens = ctx_tokens = prompt_tokens = live_token_s = live_page_s = 0.0
    for rid, first in book.first.items():
        r, n = book.req[rid], book.count[rid]
        last = book.last[rid]
        if last < t_open or first >= t_close:
            continue
        dt = (last - first) / max(n - 1, 1)
        for j in range(n):
            t = first + dt * j
            if t_open <= t < t_close:
                held = len(r.prompt) + j + 1
                tokens += 1
                ctx_tokens += held
                if j < n - 1:   # freed with the last token
                    live_token_s += held * dt
                    live_page_s += -(-held // page) * dt
        if t_open <= first < t_close:
            prompt_tokens += len(r.prompt)
    return {"window_decode_tokens": tokens,
            "window_decode_context_tokens": ctx_tokens,
            "window_prompt_tokens": prompt_tokens,
            "window_live_token_s": live_token_s,
            "window_live_page_s": live_page_s}
