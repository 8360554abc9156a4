"""A ``train`` run: one trainer object, built and driven through its first
steps in set-up, handed to the measured window, then checked against the
plain reference once its state is freed.
"""

from __future__ import annotations

import gc
import time

import jax
import numpy as np

import checks
import harness
import program
import reference
import tracing
import traffic
import weights
import work

DEPTH = 2   # steps in flight: the host stays this far ahead of the device


def prepare(cell: dict, devices, seed: int, seconds: float,
            trainer=None) -> dict:
    """Build the corpus, the loader and the trainer (the object the window
    will drive).  Compiles nothing yet.  ``trainer``: one built before (the
    calibration follows many seeds with one compiled step)."""
    cfg, mix = cell["config_file"], cell["mix"]
    rows = int(mix["rows_per_chip"]) * len(devices)
    seq = int(mix["seq_len"])
    # enough distinct windows for the warm steps and a window at twice the
    # rate any chip has shown, so an epoch rarely repeats
    est_steps = int(mix["warm_steps"]) + int(
        2 * seconds * 40_000 * len(devices) / (rows * seq)) + 8
    corpus = traffic.train_corpus(seed, cfg["vocab_size"],
                                  est_steps * rows * seq + 1)
    batches, _ = program.train_loader(mix, corpus, rows, seed)
    if trainer is None:
        trainer = program.build_trainer(cell, devices, seed)
    return {"trainer": trainer, "batches": batches, "rows": rows, "seq": seq,
            "hp": hyperparams(mix)}


def hyperparams(mix: dict) -> dict:
    """The optimizer's settings the reference follows, from the mix."""
    return {k: float(mix["trainer"][k]) for k in
            ("lr", "weight_decay", "b1", "b2", "grad_clip")}


def first_steps(run: dict, cell: dict, seed: int, n_ref: int,
                n_warm: int) -> dict:
    """Drive the trainer from the seed's weights through ``n_warm`` steps by
    the window's own call and feed; keep what the reference will follow."""
    cfg, fam = cell["config_file"], cell["family"]
    trainer = run["trainer"]
    program.reset_trainer(trainer, weights.make_params(fam, seed, cfg))
    kept, losses, grad_norms, delta_norms = [], [], None, None
    for i in range(max(n_warm, n_ref)):
        tok, tgt = next(run["batches"])
        with tracing.span("trainer.step"):
            loss = trainer.train_step(tok, tgt)
        if i < n_ref:
            kept.append((np.array(tok), np.array(tgt)))
            losses.append(float(loss))
        if i == 0:
            mu = program.adam_first_moment(trainer.opt_state)
            grad_norms = np.asarray(reference.leaf_norms(mu)) / (
                1.0 - run["hp"]["b1"])
        if i == n_ref - 1:
            start = program.place_like(weights.make_params(fam, seed, cfg),
                                       trainer.params)
            delta_norms = np.asarray(
                reference.diff_norms(trainer.params, start))
            del start
    jax.block_until_ready(trainer.params)
    return {"batches": kept,
            "prog": {"losses": losses, "grad_norms": grad_norms,
                     "delta_norms": delta_norms}}


def window(run: dict, seconds: float) -> dict:
    """Steps for ``seconds``: dispatch until the deadline, then wait for
    what was dispatched; the window closes when the last step has
    finished, so every token counted was computed inside it."""
    trainer, batches = run["trainer"], run["batches"]
    inflight, done_at = [], []
    with tracing.span("window"):
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while time.perf_counter() < deadline:
            with tracing.span("input.next"):
                tok, tgt = next(batches)
            with tracing.span("trainer.step"):
                inflight.append(trainer.train_step(tok, tgt))
            if len(inflight) > DEPTH:
                with tracing.span("trainer.wait"):
                    jax.block_until_ready(inflight.pop(0))
                done_at.append(time.perf_counter())
        with tracing.span("trainer.drain"):
            for loss in inflight:
                jax.block_until_ready(loss)
                done_at.append(time.perf_counter())
        t1 = time.perf_counter()
    return {"t0": t0, "t1": t1, "window_s": t1 - t0, "steps": len(done_at),
            "done_at": done_at,
            "last_loss": float(inflight[-1]) if inflight else None}


def follow(cell: dict, seed: int, batches, hp: dict, **fault) -> dict:
    """The reference through the kept batches from the seed's weights:
    losses, first gradient norms, and the norms of the parameters' change.
    ``fault``: ``quant`` (the control's precision) or ``grad_fault``."""
    cfg, fam = cell["config_file"], cell["family"]
    done = reference.train_steps(fam.reference,
                                 weights.make_params(fam, seed, cfg), batches,
                                 cfg, hp, **fault)
    return reference.with_delta_norms(done,
                                      weights.make_params(fam, seed, cfg))


def check(cell: dict, seed: int, firsts: dict, hp: dict) -> dict:
    """The numbers compared: the program's first steps against the
    reference's."""
    return checks.train_numbers(firsts["prog"],
                                follow(cell, seed, firsts["batches"], hp))


def run(cell: dict, devices, seed: int, seconds: float, trace: bool,
        t_start: float, clock: harness.CompileClock) -> dict:
    cfg, mix = cell["config_file"], cell["mix"]
    phase = harness.Phases(clock, t_start)
    state = prepare(cell, devices, seed, seconds)
    phase("corpus, loader and trainer (the trainer makes weights of its own)")
    n_ref = int(mix["reference_steps"])
    firsts = first_steps(state, cell, seed, n_ref, int(mix["warm_steps"]))
    phase(f"weights and the first {mix['warm_steps']} steps")
    gc.collect()
    gc.freeze()
    compile_s, compiles0 = clock.snapshot()
    setup_s = time.perf_counter() - t_start

    traced: dict = {}
    if trace:
        seconds = min(seconds, float(mix.get("trace_seconds", seconds)))
        with tracing.capture(traced):
            win = window(state, seconds)
    else:
        win = window(state, seconds)
    compiles1 = clock.snapshot()[1]
    memory_peak = harness.memory_peak_bytes(devices)

    hp = state["hp"]
    tokens_per_step = state["rows"] * state["seq"]
    program.release_trainer(state["trainer"])
    state.clear()
    del state
    gc.unfreeze()
    gc.collect()
    t_ref = time.perf_counter()
    numbers = check(cell, seed, firsts, hp)
    harness.log(f"reference followed {n_ref} steps in "
                f"{time.perf_counter() - t_ref:.1f} s; numbers {numbers}")
    correct, checked = checks.judge(numbers, cell["limits"])

    peak = work.peaks(devices[0].device_kind)
    ctx = {
        "kind": "train", "cell": cell, "config": cfg, "mix": mix,
        "chips": len(devices), "peak": peak, "work": cell["family"].work,
        "setup_s": setup_s,
        "window_s": win["window_s"], "steps": win["steps"],
        "tokens_per_step": tokens_per_step, "done_at": win["done_at"],
        "t0": win["t0"], "rows": tokens_per_step // int(mix["seq_len"]),
        "seq": int(mix["seq_len"]),
        "counters": {"compile_s": compile_s,
                     "inwindow_compiles": compiles1 - compiles0},
        "memory_peak_bytes": memory_peak,
        "trace": traced.get("trace"),
    }
    return {"ctx": ctx, "correct": correct, "checks": checked,
            "attempted": win["steps"], "failed": 0,
            "memory_peak": memory_peak}
