"""The comparison that decides ``correct``: the numbers compared, and the
judgement of each against a limit of its own.

The limits are data: ``benchmarks/limits/<workload>.json`` holds, for each
number compared, the limit and the readings it was set from (``PERF.md``
gives the same).  A number the run can compute but that has no limit in the
file is printed with the others on standard error and not judged.
"""

from __future__ import annotations

import numpy as np


def worst_leaf_gap(prog, ref, skip=None) -> float:
    """The worst leaf's gap between the program's norm and the
    reference's (not the norm of their difference), against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger, since some gradients are all but zero."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    floor = np.median(ref)
    gap = np.abs(prog - ref) / np.maximum(ref, floor)
    if skip is not None:
        gap = gap[~np.asarray(skip)]
    return float(gap.max())


def still_leaves(ref_grad_norms) -> np.ndarray:
    """Leaves whose gradient is nought to rounding in the reference (under
    a thousandth of the median leaf's): under Adam they move by round-off
    alone, so they are left out of the parameters' change."""
    g = np.asarray(ref_grad_norms, np.float64)
    return g < 1e-3 * np.median(g)


def train_numbers(prog: dict, ref: dict) -> dict:
    """``prog``/``ref``: {"losses", "grad_norms", "delta_norms"} of the
    first steps.  ``loss_gap``: the worst step's relative loss gap;
    ``grad_gap``: the first gradient as the optimizer gets it, worst leaf;
    ``delta_gap``: the parameters' change over those steps, worst leaf that
    the reference's gradient moves."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    n = min(len(lp), len(lr))
    return {
        "loss_gap": float(np.max(np.abs(lp[:n] - lr[:n]) / np.abs(lr[:n]))),
        "grad_gap": worst_leaf_gap(prog["grad_norms"], ref["grad_norms"]),
        "delta_gap": worst_leaf_gap(prog["delta_norms"], ref["delta_norms"],
                                    skip=still_leaves(ref["grad_norms"])),
    }


def serve_numbers(sampled: list[dict]) -> dict:
    """``sampled``: per checked request {"gaps", "served", "wanted_new",
    "prompt_ok"}, the gaps by which each served token's logit lies below
    the reference's best.  ``max_gap``: the widest of them, which an altered
    token moves; ``p90_gap``: their 90th percentile, which a lower precision
    moves (the widest gap of some hundreds of tokens is an extreme and
    swings: bfloat16 and int8 overlap there, and differ tenfold in the
    bulk); the two counts are exact."""
    gaps = np.concatenate([np.asarray(s["gaps"]) for s in sampled])
    return {
        "max_gap": float(gaps.max()),
        "p90_gap": float(np.percentile(gaps, 90)),
        "wrong_length": float(sum(s["served"] != s["wanted_new"]
                                  for s in sampled)),
        "prompt_altered": float(sum(not s["prompt_ok"] for s in sampled)),
    }


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit", "ok"}}) over the numbers that
    have a limit; a missing or non-finite number fails."""
    checks = {}
    for name, spec in limits.get("limits", {}).items():
        value = numbers.get(name)
        limit = float(spec["limit"])
        ok = (value is not None and np.isfinite(value) and value <= limit)
        checks[name] = {"value": None if value is None else float(value),
                        "limit": limit, "ok": bool(ok)}
    return bool(checks) and all(c["ok"] for c in checks.values()), checks
