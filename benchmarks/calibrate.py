#!/usr/bin/env python3
"""Read, on the chip, what the limits of a cell's output check are set from.

    python3 benchmarks/calibrate.py --workload <name> --seeds 12 --controls 3 \
        [--seconds 25] --out chiprun_out/<name>.calib.json

One process, one compiled program, many seeds (set-up is most of a run):

- the *lower* readings: sound runs of the program against the reference,
  ``--seeds`` seeds (training needs no measured window; serving a short one
  at the cell's own load);
- the *upper* readings, on the first ``--controls`` seeds: the control (the
  reference in int8 put in the program's place) and, for training, each
  fault the cell can have, planted in the reference put in its place
  (half of the batch left out; on several chips the exchange left out, so
  that a chip follows its own rows only).  A state left unchanged reads
  ``delta_gap`` = 1 by construction and needs no run.

Every control and fault is also passed through ``checks.judge`` against the
limits the cell has committed (``judged`` in its row): ``correct`` has to
come out false there, and the command ends non-zero where one comes out
true.  ``--judge <readings.json>`` does that for readings recorded earlier,
without a chip.  ``--witness`` (serving) looks into seeds that read far
off: it checks every greedy request the window finished, says where in
each the widest gaps lie, and has the program's plainest path (a second
server: batch prefill, no refill inside blocks, no chaining, no compaction)
serve the same prompts alone, read by the same reference.  ``--option``
builds the server under test with one option changed, to pin a fault down.

It writes every reading as JSON; a person sets ``limits/<workload>.json``
from them (above the largest lower reading, below the smallest upper one,
with more of the room above the lower) and copies both into ``PERF.md``.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))


def fault_readings(cell, seed, batches, hp, ref) -> dict:
    """What the control and each fault the cell can have read against the
    reference ``ref``: the reference itself, faulted, put in the program's
    place."""
    import checks
    import run_train

    mix = cell["mix"]
    per_chip = int(mix["rows_per_chip"])
    faults = {"control_int8": dict(quant="int8"),
              "half_batch": dict(grad_fault=lambda t, y: (
                  t[:len(t) // 2], y[:len(y) // 2]))}
    if int(cell["chips"]) > 1:
        # a chip that follows its own rows only
        faults["no_exchange"] = dict(grad_fault=lambda t, y: (
            t[:per_chip], y[:per_chip]))
    return {name: checks.train_numbers(
        run_train.follow(cell, seed, batches, hp, **kw), ref)
        for name, kw in faults.items()}


UPPER_KEYS = ("control_int8", "half_batch", "no_exchange", "altered_token")


def judge_upper(row: dict, limits: dict) -> dict:
    """``checks.judge`` over each control and fault reading of ``row``
    against the committed ``limits``: {name: {"correct", "failed"}}.  One
    altered token reads the program's own numbers with ``max_gap`` at the
    least that any altered position gives."""
    import checks

    out = {}
    for name in UPPER_KEYS:
        if name not in row:
            continue
        numbers = dict(row[name])
        if name == "altered_token":
            numbers = {**row["program"],
                       "max_gap": max(row["program"]["max_gap"],
                                      numbers["min_gap"])}
        elif "max_gap" in numbers:   # the control serves what it was given
            numbers = {"wrong_length": 0.0, "prompt_altered": 0.0, **numbers}
            if "p90_gap" not in numbers and "control_gap_quantiles" in row:
                numbers["p90_gap"] = row["control_gap_quantiles"][1]
        correct, checked = checks.judge(numbers, limits)
        out[name] = {"correct": correct,
                     "failed": [k for k, c in checked.items() if not c["ok"]]}
    return out


def report_judged(rows: list[dict], limits: dict) -> int:
    """Adds ``judged`` to every row; how many controls or faults came out
    correct (none may)."""
    import harness

    wrong = 0
    for row in rows:
        row["judged"] = judge_upper(row, limits)
        for name, j in row["judged"].items():
            wrong += j["correct"]
            harness.log(f"seed {row['seed']} {name}: correct "
                        f"{str(j['correct']).lower()}, fails {j['failed']}")
    return wrong


def faults_only_train(cell, seeds) -> list[dict]:
    """The upper readings of a training cell without its program: the
    control and the faults are the reference against itself, so one chip
    reads them for a cell of any number of chips (``--faults-only``)."""
    import numpy as np

    import harness
    import program
    import run_train
    import traffic

    cfg, mix = cell["config_file"], cell["mix"]
    rows = int(mix["rows_per_chip"]) * int(cell["chips"])
    n_ref = int(mix["reference_steps"])
    hp = run_train.hyperparams(mix)
    out = []
    for seed in seeds:
        corpus = traffic.train_corpus(
            seed, cfg["vocab_size"],
            (n_ref + 2) * rows * int(mix["seq_len"]) + 1)
        feed, _ = program.train_loader(mix, corpus, rows, seed)
        batches = [tuple(np.array(a) for a in next(feed))
                   for _ in range(n_ref)]
        ref = run_train.follow(cell, seed, batches, hp)
        row = {"seed": seed, **fault_readings(cell, seed, batches, hp, ref)}
        harness.log(json.dumps(row))
        out.append(row)
    return out


def calibrate_train(cell, devices, seeds, n_controls) -> list[dict]:
    import numpy as np

    import checks
    import harness
    import program
    import run_train

    n_ref = int(cell["mix"]["reference_steps"])
    trainer, out = None, []
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        state = run_train.prepare(cell, devices, seed, 1.0, trainer=trainer)
        trainer = state["trainer"]
        firsts = run_train.first_steps(state, cell, seed, n_ref, n_ref)
        program.release_trainer(trainer)
        gc.collect()
        hp = state["hp"]
        ref = run_train.follow(cell, seed, firsts["batches"], hp)
        row = {"seed": seed, "program": checks.train_numbers(firsts["prog"],
                                                             ref),
               "grad_norm_median": float(np.median(ref["grad_norms"])),
               "losses": ref["losses"]}
        if i < n_controls:
            row.update(fault_readings(cell, seed, firsts["batches"], hp, ref))
        row["seconds"] = time.perf_counter() - t0
        harness.log(json.dumps(row))
        out.append(row)
    return out


def request_detail(s: dict, gaps, tag: str = "") -> dict:
    """Where in one request the widest gaps lie (index among its served
    tokens; the position in its context is the prompt's length more)."""
    import numpy as np

    g = np.asarray(gaps)
    top = np.argsort(-g)[:6]
    return {f"{tag}max_gap": float(g.max()),
            f"{tag}p99_gap": float(np.percentile(g, 99)),
            f"{tag}nonzero": int((g > 0).sum()),
            f"{tag}over_0.05": int((g > 0.05).sum()),
            f"{tag}widest_at": [[int(i), float(g[i])] for i in sorted(top)
                                if g[i] > 0.05]}


def witness_serve(cell, kept: list[dict]) -> None:
    """The program's plainest path serves each seed's checked prompts
    alone; the same reference reads what it served.  Adds ``plain_*`` to
    the rows' ``per_request`` and ``plain_path`` to the rows."""
    import jax.numpy as jnp
    import numpy as np

    import checks
    import harness
    import program
    import run_serve
    import weights

    cfg, dep = cell["config_file"], cell["deployment"]
    plain = None
    for k in kept:
        params = weights.make_params(cell["family"], k["row"]["seed"], cfg,
                                     jnp.dtype(dep["dtype"]))
        if plain is None:
            plain = program.build_server(cell, params, k["row"]["seed"],
                                         inblock_refill=False, overlap=False,
                                         compact_tail=False)
        plain.params = params
        rids = [plain.submit(s["prompt"], s["wanted_new"], temperature=0.0)
                for s in k["served"]]
        while plain.pending():
            plain.step()
        again = []
        for rid, s in zip(rids, k["served"]):
            result = np.asarray(plain.result(rid))
            again.append({"prompt": s["prompt"], "result": result,
                          "tokens": result[len(s["prompt"]):],
                          "wanted_new": s["wanted_new"]})
        sampled = run_serve.check(cell, params, again)
        k["row"]["plain_path"] = checks.serve_numbers(sampled)
        for s, a, g, d in zip(k["served"], again, sampled,
                              k["row"]["per_request"]):
            n = min(len(s["tokens"]), len(a["tokens"]))
            differ = np.nonzero(np.asarray(s["tokens"][:n])
                                != np.asarray(a["tokens"][:n]))[0]
            d.update(request_detail(a, g["gaps"], "plain_"))
            d["first_token_that_differs"] = (int(differ[0]) if len(differ)
                                             else None)
        harness.log(json.dumps(k["row"]))


def calibrate_serve(cell, devices, seeds, n_controls, seconds,
                    witness=False, options=None) -> list[dict]:
    import jax.numpy as jnp
    import numpy as np

    import checks
    import harness
    import program
    import run_serve
    import traffic
    import weights

    cfg, mix, dep = cell["config_file"], cell["mix"], cell["deployment"]
    clock = harness.CompileClock()
    cb, out, kept = None, [], []
    if witness:     # every greedy request the window finished, not a sample
        mix = {**mix, "check_tokens": 10 ** 9}
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        params = weights.make_params(cell["family"], seed, cfg,
                                     jnp.dtype(dep["dtype"]))
        if cb is None:
            cb = program.build_server(cell, params, seed, **(options or {}))
            run_serve.warm(cb, mix, cfg["vocab_size"])
        cb.params = params
        book = run_serve.Book()
        edge = run_serve.drive(cb, traffic.Requests(mix, seed,
                                                    cfg["vocab_size"]),
                               mix, seconds, book, clock, None)
        gc.unfreeze()
        picked = run_serve.sample_for_check(book, edge["t_open"],
                                            edge["t_close"], seed,
                                            int(mix["check_tokens"]))
        served = run_serve.served_of(cb, book, picked)
        while cb.pending():     # empty the server for the next seed
            cb.step()
        sampled = run_serve.check(cell, params, served,
                                  with_control=i < n_controls)
        row = {"seed": seed, "program": checks.serve_numbers(sampled),
               "checked_tokens": int(sum(s["served"] for s in sampled)),
               "checked_requests": len(sampled),
               "inwindow_compiles": (edge["close"]["compile"][1]
                                     - edge["open"]["compile"][1])}
        gaps = np.concatenate([s["gaps"] for s in sampled])
        row["gap_quantiles"] = [float(np.quantile(gaps, q))
                                for q in (0.5, 0.9, 0.99, 1.0)]
        row["nonzero_gaps"] = int((gaps > 0).sum())
        row["per_request"] = [
            {"prompt": int(len(s["prompt"])), "served": int(len(s["tokens"])),
             **request_detail(s, g["gaps"])}
            for s, g in zip(served, sampled)]
        if witness:
            kept.append({"row": row, "served": served})
        if i < n_controls:
            cg = np.concatenate([s["control_gaps"] for s in sampled])
            row["control_int8"] = {"max_gap": float(cg.max()),
                                   "p90_gap": float(np.percentile(cg, 90))}
            ag = np.concatenate([s["altered_gaps"] for s in sampled])
            row["altered_token"] = {"min_gap": float(ag.min()),
                                    "p10_gap": float(np.percentile(ag, 10))}
            row["control_gap_quantiles"] = [float(np.quantile(cg, q))
                                            for q in (0.5, 0.9, 0.99, 1.0)]
        row["seconds"] = time.perf_counter() - t0
        harness.log(json.dumps(row))
        out.append(row)
    if witness and not options:     # with options the run is the witness
        del cb      # its pool makes room for the plain server's
        gc.collect()
        witness_serve(cell, kept)
    return out


def judge_recorded(path: str, workload: str | None) -> int:
    """``--judge``: readings recorded earlier against the limits committed
    now.  Ends non-zero where a control or a fault comes out correct."""
    import harness

    with open(path) as f:
        recorded = json.load(f)
    cell = harness.find_cell(workload or recorded["workload"])
    harness.log(f"{path} against limits/{cell['name']}.json")
    return 1 if report_judged(recorded["rows"], cell["limits"]) else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload")
    ap.add_argument("--judge", metavar="READINGS",
                    help="judge recorded readings against the committed "
                         "limits (of --workload, or the file's own); no chip")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--seed-list", default=None,
                    help="these seeds, comma-separated, and not a series")
    ap.add_argument("--witness", action="store_true",
                    help="serving: every finished greedy request, where its "
                         "widest gaps lie, and the plain path on the same "
                         "prompts")
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=(1 << 31) + 1000)
    ap.add_argument("--option", action="append", default=[],
                    metavar="NAME=JSON",
                    help="serving, to pin a fault down: build the server "
                         "with this option besides what the deployment "
                         "states (inblock_refill=false)")
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--out")
    ap.add_argument("--faults-only", action="store_true",
                    help="a training cell's control and faults alone, on "
                         "one chip whatever the cell's chips")
    args = ap.parse_args(argv)
    import harness

    if args.judge:
        return judge_recorded(args.judge, args.workload)
    if not args.workload or not args.out:
        ap.error("--workload and --out are required")
    cell = harness.find_cell(args.workload)
    devices = harness.require_devices(1 if args.faults_only
                                      else int(cell["chips"]))
    harness.enable_compile_cache()
    seeds = ([int(x) for x in args.seed_list.split(",")] if args.seed_list
             else [args.first_seed + 7919 * i for i in range(args.seeds)])
    if args.faults_only:
        rows = faults_only_train(cell, seeds)
    elif cell["mix"]["kind"] == "train":
        rows = calibrate_train(cell, devices, seeds, args.controls)
    else:
        rows = calibrate_serve(cell, devices, seeds, args.controls,
                               args.seconds, args.witness,
                               {k: json.loads(v) for k, v in
                                (o.split("=", 1) for o in args.option)})
    wrong = report_judged(rows, cell["limits"])
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"workload": args.workload, "rows": rows}, f, indent=1)
    if wrong:
        harness.log(f"{wrong} control or fault reading(s) came out correct")
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
