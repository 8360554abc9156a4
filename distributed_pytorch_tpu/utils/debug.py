"""Training-consistency checkers: the race/desync detection the reference lacks.

The reference has no sanitizers at all (SURVEY.md section 5): its collectives
are synchronous so ordering races are avoided by construction, but nothing
ever *verifies* the data-parallel invariants — and its manual variants do
silently violate one (per-rank BatchNorm stats drift, SURVEY.md 2.3).  On TPU
the failure modes shift (non-deterministic reduction orders, desynced
replicated state after a bad host-side update, NaN-poisoned grads); this
module makes them checkable:

- ``replica_desync(tree)``: bitwise-compare every device copy of replicated
  arrays — the DP invariant torch DDP enforces by broadcast; a mismatch means
  a desync bug (or a non-replicated sharding sneaking into training state);
- ``check_determinism(fn, *args)``: run a compiled step twice from identical
  inputs and compare results bitwise — catches nondeterministic kernels or
  host-side state leaking into a supposedly pure step;
- ``assert_finite(tree)``: NaN/Inf scan over a pytree (grad/param health);
- the SCHEDULE INSPECTOR (round 8): ``op_schedule`` linearizes a compiled
  step's jaxpr into equation order — the order XLA receives the program,
  which the backward-overlap machinery (parallel/strategies.OverlapSync)
  manipulates — and ``collective_stats`` / ``assert_overlap_schedule`` /
  ``assert_post_backward_schedule`` prove whether gradient-sync
  collectives are interleaved between backward matmuls (overlap=True) or
  clustered after the backward drains (the historical post-backward
  shape).  ``hlo_collective_counts`` counts collectives in lowered
  (Stable)HLO text for the bench tables.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import numpy as np

PyTree = Any

# Compute ops a training step's forward/backward is made of (VGG steps are
# convolution-dominated, LM steps dot_general-dominated).
COMPUTE_PRIMS = frozenset({"dot_general", "conv_general_dilated"})
# Cross-device collectives (pmean lowers to psum+div, reduce-scatter to
# psum_scatter, so these cover every strategy's wire ops).
COLLECTIVE_PRIMS = frozenset({
    "psum", "psum2", "pmax", "pmin", "ppermute", "pshuffle", "all_gather",
    "all_to_all", "psum_scatter", "reduce_scatter",
})
# jax 0.9.0 binds psum and the two-level gather-back under the names of
# their vma-typed forms; the schedule keeps the names the pins use.
_PRIM_ALIASES = {"psum_invariant": "psum",
                 "all_gather_invariant": "all_gather"}


def _eqn_axes(eqn) -> tuple:
    """The mesh axis names a collective equation runs over (normalized to
    a flat tuple; empty for non-collectives)."""
    axes = eqn.params.get("axes", None)
    if axes is None:
        axes = eqn.params.get("axis_name", None)
    if axes is None:
        return ()
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    flat: list = []
    for a in axes:
        if isinstance(a, (tuple, list)):
            flat.extend(a)
        else:
            flat.append(a)
    return tuple(flat)


def _eqn_bytes(eqn) -> int:
    """Total operand payload of an equation (per device, per execution of
    its enclosing jaxpr) — the collective's wire cost proxy."""
    total = 0
    for v in eqn.invars:
        aval = getattr(v, "aval", None)
        if aval is not None and hasattr(aval, "shape"):
            total += int(np.prod(aval.shape, dtype=np.int64) or 1) * \
                jax.dtypes.canonicalize_dtype(aval.dtype).itemsize
    return total


def _sub_jaxprs(eqn):
    """Nested jaxprs of call-like equations (pjit/scan/while/cond/
    shard_map/remat/custom_* ...), in parameter order."""
    for val in eqn.params.values():
        vals = val if isinstance(val, (list, tuple)) else [val]
        for s in vals:
            inner = getattr(s, "jaxpr", None)
            if inner is not None and hasattr(inner, "eqns"):
                yield inner
            elif hasattr(s, "eqns"):
                yield s


def jaxpr_schedule(jaxpr) -> list[dict]:
    """Flatten a (closed) jaxpr into equation order, recursing into nested
    jaxprs in place, and record every compute/collective op as
    ``{"kind": "compute"|"collective", "prim": name, "axes": tuple,
    "bytes": int, "trips": int}``.  Equation order is the order
    autodiff/transposition emitted the program and the order XLA receives
    it — the thing the overlap sync points exist to restructure.

    A scan body appears ONCE in the schedule (its per-iteration sequence
    is the repeating unit), but ``trips`` carries the product of the
    enclosing scan lengths, so per-execution accounting (the ring
    strategies' 2(n-1) ppermute hops live in scans) sums ``bytes *
    trips`` — see ``collective_stats``'s ``bytes_executed``.  ``while``
    bodies have no static trip count and keep the enclosing multiplier
    (an undercount; none of the train steps use while-loop collectives).
    """
    sched: list[dict] = []

    def walk(j, trips: int):
        for eqn in j.eqns:
            name = eqn.primitive.name
            name = _PRIM_ALIASES.get(name, name)
            if name in COMPUTE_PRIMS:
                sched.append({"kind": "compute", "prim": name,
                              "axes": (), "bytes": _eqn_bytes(eqn),
                              "trips": trips})
            elif name in COLLECTIVE_PRIMS:
                sched.append({"kind": "collective", "prim": name,
                              "axes": _eqn_axes(eqn),
                              "bytes": _eqn_bytes(eqn), "trips": trips})
            inner = trips
            if name == "scan":
                inner = trips * int(eqn.params.get("length", 1))
            for sub in _sub_jaxprs(eqn):
                walk(sub, inner)

    walk(jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr, 1)
    return sched


def op_schedule(fn: Callable, *args, **kwargs) -> list[dict]:
    """``jaxpr_schedule`` of ``fn(*args, **kwargs)`` (fn may be jitted or
    shard_mapped; nothing is executed — args can be ShapeDtypeStructs)."""
    return jaxpr_schedule(jax.make_jaxpr(fn)(*args, **kwargs))


def collective_stats(sched: list[dict], axes=None,
                     min_bytes: int = 0) -> dict:
    """Interleaving statistics for the collectives in a schedule.

    ``axes``: restrict to collectives touching ANY of these mesh axes
    (e.g. ("data",) for the data-parallel gradient sync; None = all).
    ``min_bytes``: drop collectives below this operand payload — the
    LM steps psum scalar loss/token-count values over the batch axes
    mid-graph, and a gradient-sync interleaving pin must not count a
    4-byte loss reduction as overlapped sync traffic.

    Returns counts over the STATIC schedule: ``total`` collectives,
    ``interleaved`` (compute BOTH before and after — emitted strictly
    between matmuls), ``tail`` (no compute after — the post-backward
    cluster), ``bytes`` (summed operand payload, each scan body once) and
    ``compute`` (compute-op count); plus the PER-EXECUTION accounting
    ``executions`` / ``bytes_executed`` (scan-trip-weighted — the honest
    wire totals when collectives ride a scan, e.g. the int8 ring's
    ppermute hops)."""
    if axes is not None:
        axes = set(axes)
    compute_idx = [i for i, r in enumerate(sched) if r["kind"] == "compute"]
    first_c = compute_idx[0] if compute_idx else None
    last_c = compute_idx[-1] if compute_idx else None
    total = interleaved = tail = executions = 0
    nbytes = nbytes_exec = 0
    for i, r in enumerate(sched):
        if r["kind"] != "collective":
            continue
        if axes is not None and not (axes & set(r["axes"])):
            continue
        if r["bytes"] < min_bytes:
            continue
        total += 1
        nbytes += r["bytes"]
        trips = r.get("trips", 1)
        executions += trips
        nbytes_exec += r["bytes"] * trips
        if last_c is None or i > last_c:
            tail += 1
        elif first_c is not None and i > first_c:
            interleaved += 1
    return {"total": total, "interleaved": interleaved, "tail": tail,
            "bytes": nbytes, "compute": len(compute_idx),
            "executions": executions, "bytes_executed": nbytes_exec}


def per_axis_collective_stats(sched: list[dict],
                              min_bytes: int = 0) -> dict[str, dict]:
    """``collective_stats`` split BY MESH AXIS: one stats dict per axis
    name appearing in the schedule ({'dcn': ..., 'ici': ...} for the
    factored-mesh strategies), so wire accounting can attribute traffic
    to the link that carries it — cross-slice DCN bytes separately from
    within-slice ICI bytes (scripts/bench_strategies.py's per-axis
    columns; the measurement behind two_level_psum's |grads|/ici claim).
    A collective running over several axes at once (a flat psum over
    ('data', 'expert')) counts toward EACH of them — per-axis rows are
    attribution, not a partition, and need not sum to the total."""
    axes = sorted({a for r in sched if r["kind"] == "collective"
                   for a in r["axes"]})
    return {a: collective_stats(sched, axes=(a,), min_bytes=min_bytes)
            for a in axes}


def per_hop_collective_stats(sched: list[dict],
                             min_bytes: int = 0) -> dict[str, dict]:
    """``collective_stats`` split BY HOP — one row per (mesh-axes,
    primitive) pair, keyed ``"axis:prim"`` in the routing grammar's
    spirit (``parallel/routing``): a 3-hop routed sync traces as e.g.
    ``{"ici:psum_scatter": ..., "dcn:ppermute": ..., "wan:ppermute":
    ..., "ici:all_gather": ...}``, so each hop of a ``HopPlan`` is
    attributable separately even when two hops share a mesh axis (the
    reduce-scatter and the all-gather of the same bracket).  A
    collective spanning several axes at once keys them joined with
    ``"+"`` (``"data+expert:psum"``) — the same joint-axis spelling the
    route grammar uses for flat plans.  Stats fields match
    ``collective_stats`` (round 20, the per-hop side of
    ``plan_bytes_vs_schedule``)."""
    compute_idx = [i for i, r in enumerate(sched) if r["kind"] == "compute"]
    first_c = compute_idx[0] if compute_idx else None
    last_c = compute_idx[-1] if compute_idx else None
    out: dict[str, dict] = {}
    for i, r in enumerate(sched):
        if r["kind"] != "collective" or r["bytes"] < min_bytes:
            continue
        key = "+".join(sorted(r["axes"])) + ":" + r["prim"]
        row = out.setdefault(key, {
            "total": 0, "interleaved": 0, "tail": 0, "bytes": 0,
            "compute": len(compute_idx), "executions": 0,
            "bytes_executed": 0})
        trips = r.get("trips", 1)
        row["total"] += 1
        row["bytes"] += r["bytes"]
        row["executions"] += trips
        row["bytes_executed"] += r["bytes"] * trips
        if last_c is None or i > last_c:
            row["tail"] += 1
        elif first_c is not None and i > first_c:
            row["interleaved"] += 1
    return out


def amortized_axis_bytes(entries, steps: int,
                         min_bytes: int = 0, *,
                         by_hop: bool = False) -> dict[str, float]:
    """Per-axis wire bytes PER STEP of a multi-program step family:
    ``entries`` is an iterable of ``(sched, multiplicity)`` pairs — each
    jaxpr schedule weighted by how many times it runs over a ``steps``-
    step window — and the result sums each axis's scan-trip-weighted
    ``bytes_executed`` across them, divided by ``steps``.

    This is the round-18 measurement behind the local-SGD claim: a
    ``sync_every=H`` trainer runs the LOCAL schedule H times and the
    boundary-EXCHANGE schedule once per window, so
    ``amortized_axis_bytes([(local, H), (exchange, 1)], H)`` gives the
    honest dcn-axis bytes/step to compare against the per-step path's
    ``amortized_axis_bytes([(step, 1)], 1)`` — the ~1/H scaling pin
    (tests/test_localsgd.py, the __graft_entry__ dryrun leg).

    ``by_hop=True`` (round 20) keys the result per HOP instead of per
    axis (``per_hop_collective_stats``'s ``"axis:prim"`` keys) — the
    3-axis-mesh accounting that keeps routed ``HopPlan`` predictions
    checkable hop-by-hop against emitted programs."""
    split = per_hop_collective_stats if by_hop else per_axis_collective_stats
    totals: dict[str, float] = {}
    for sched, mult in entries:
        for axis, stats in split(sched, min_bytes=min_bytes).items():
            totals[axis] = (totals.get(axis, 0.0)
                            + float(stats["bytes_executed"]) * mult)
    return {a: b / float(steps) for a, b in totals.items()}


def assert_overlap_schedule(sched: list[dict], axes=("data",),
                            min_interleaved: int = 2,
                            min_bytes: int = 0) -> dict:
    """Assert the overlap property: at least ``min_interleaved``
    ``axes``-collectives sit STRICTLY BETWEEN compute ops (backward
    matmuls run after them — the latency-hiding scheduler has something
    to overlap).  ``min_bytes`` excludes scalar loss reductions (see
    collective_stats).  Returns the stats for reporting."""
    stats = collective_stats(sched, axes=axes, min_bytes=min_bytes)
    if stats["interleaved"] < min_interleaved:
        raise ConsistencyError(
            f"expected >= {min_interleaved} {tuple(axes)}-collectives "
            f"interleaved between compute ops, found "
            f"{stats['interleaved']} (of {stats['total']}; {stats}) — "
            f"the collectives are not overlapped with backward compute")
    return stats


def assert_post_backward_schedule(sched: list[dict],
                                  axes=("data",),
                                  min_bytes: int = 0) -> dict:
    """Assert the historical post-backward shape: every ``axes``-collective
    comes AFTER the last compute op (all-at-the-end; nothing for the
    scheduler to overlap).  ``min_bytes`` excludes the scalar loss
    reductions that legitimately sit mid-graph (see collective_stats)."""
    stats = collective_stats(sched, axes=axes, min_bytes=min_bytes)
    if stats["interleaved"] != 0 or stats["tail"] != stats["total"]:
        raise ConsistencyError(
            f"expected all {tuple(axes)}-collectives after the final "
            f"compute op, got {stats}")
    return stats


# Lowered-HLO collective opcodes (canonical name -> regex matching the op
# DEFINITION site — opcode immediately followed by its operand list — in
# both classic HLO (`all-reduce(...)`) and StableHLO
# (`"stablehlo.all_reduce"(...)` / `stablehlo.all_reduce(...)`) text;
# value references like `%all-reduce.1` never match).
_HLO_COLLECTIVES = {
    "all-reduce": r"all[-_]reduce\"?\(",
    "collective-permute": r"collective[-_]permute\"?\(",
    "all-gather": r"all[-_]gather\"?\(",
    "reduce-scatter": r"reduce[-_]scatter\"?\(",
    "all-to-all": r"all[-_]to[-_]all\"?\(",
}


def hlo_collective_counts(hlo_text: str) -> dict[str, int]:
    """Count collective ops in lowered (Stable)HLO text
    (``jit(f).lower(...).as_text()``), keyed by canonical opcode plus a
    ``"total"`` — the bench tables' HLO collective-count column
    (scripts/bench_strategies.py)."""
    import re

    counts = {canon: len(re.findall(pat, hlo_text))
              for canon, pat in _HLO_COLLECTIVES.items()}
    counts = {k: v for k, v in counts.items() if v}
    counts["total"] = sum(counts.values())
    return counts


class ConsistencyError(AssertionError):
    """A data-parallel training invariant was violated."""


# Route-grammar hop operations (parallel/routing.Hop.describe()'s part
# after the ":", bracket suffix stripped) -> the jaxpr primitives that
# hop lowers to.
_HOP_OP_PRIMS = {
    "rs": ("psum_scatter", "reduce_scatter"),
    "slice": (),            # local dynamic_slice — no collective
    "ag": ("all_gather",),
    "psum": ("psum", "psum2"),
    "ring": ("ppermute",),
    # round 21: the expert dispatch/combine exchange ('expert:a2a@bits'
    # hops) lowers to all_to_all at every wire width — the quantized
    # payload+scale concat rides the same primitive
    "a2a": ("all_to_all",),
}


def plan_bytes_vs_schedule(plan, sched: list[dict], *,
                           min_bytes: int = 1024,
                           by_hop: bool = False) -> dict[str, dict]:
    """Predicted-vs-measured wire accounting for an autotuner SyncPlan
    (parallel/autotune.py) against a traced step's schedule: for each
    axis the plan predicts traffic on, pair its ``predicted_bytes``
    (operand-payload, scan-trip-weighted — the same accounting as
    ``collective_stats``'s ``bytes_executed``) with the measured
    ``bytes_executed`` of that axis's collectives (``min_bytes`` filters
    the scalar loss/health reductions, as everywhere).  Returns
    ``{axis: {"predicted": int, "measured": int, "ratio": float}}`` —
    the cost model's ground-truth check (round 11).

    ``by_hop=True`` (round 20) compares the plan's ``per_hop`` rows
    instead (route-model plans only — ``plan.per_hop`` must be
    populated): each hop label (``"dcn:ring[int4+ef]"``) is matched to
    the measured ``per_hop_collective_stats`` rows for its axis and the
    primitives that hop kind lowers to, so a 3-axis routed sync is
    checkable hop-by-hop, not just axis-by-axis.  Hops predicting no
    bytes (a ``slice`` reduce-scatter, a degraded size-1 tier) are
    skipped, same as zero-byte axes."""
    if by_hop:
        measured_hops = per_hop_collective_stats(sched, min_bytes=min_bytes)
        out: dict[str, dict] = {}
        for hp in getattr(plan, "per_hop", ()) or ():
            if hp.predicted_bytes <= 0:
                continue
            axis, _, op = hp.axis.partition(":")
            # strip both tag syntaxes: 'ring[int4+ef]' and 'a2a@int8'
            prims = _HOP_OP_PRIMS.get(
                op.split("[", 1)[0].split("@", 1)[0], ())
            measured = sum(
                measured_hops.get(f"{axis}:{p}", {}).get("bytes_executed", 0)
                for p in prims)
            out[hp.axis] = {"predicted": int(hp.predicted_bytes),
                            "measured": int(measured),
                            "ratio": measured / hp.predicted_bytes}
        return out
    per_axis = per_axis_collective_stats(sched, min_bytes=min_bytes)
    out = {}
    for ap in plan.per_axis:
        if ap.predicted_bytes <= 0:
            continue
        measured = per_axis.get(ap.axis, {}).get("bytes_executed", 0)
        out[ap.axis] = {"predicted": int(ap.predicted_bytes),
                        "measured": int(measured),
                        "ratio": measured / ap.predicted_bytes}
    return out


def assert_plan_bytes_match(plan, sched: list[dict], *, rtol: float = 0.5,
                            min_bytes: int = 1024) -> dict[str, dict]:
    """Assert every axis the plan predicts traffic on measures within
    ``rtol`` relative tolerance of the prediction — the autotuner's
    cost model is only trustworthy while its byte predictions track the
    emitted program (the measured side may run slightly over: the
    schedule also carries non-sync collectives like BN-buffer
    broadcasts above ``min_bytes``).  Returns the comparison rows."""
    rows = plan_bytes_vs_schedule(plan, sched, min_bytes=min_bytes)
    if not rows:
        raise ConsistencyError(
            f"plan {plan.strategy!r} predicts no per-axis traffic to "
            f"check (per_axis={plan.per_axis!r})")
    bad = {a: r for a, r in rows.items()
           if abs(r["ratio"] - 1.0) > rtol}
    if bad:
        raise ConsistencyError(
            f"predicted per-axis bytes diverge from the measured "
            f"schedule beyond rtol={rtol}: {bad} (all rows: {rows})")
    return rows


def _leaf_paths(tree: PyTree):
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    for path, leaf in leaves:
        yield jax.tree_util.keystr(path), leaf


def replica_desync(tree: PyTree, *, atol: float = 0.0) -> list[str]:
    """Paths of replicated leaves whose per-device copies disagree.

    Replicated training state (params, optimizer state) must be identical on
    every device — the invariant the reference maintains by same-seed
    construction plus grad sync (SURVEY.md 2.3) and torch DDP by broadcast.
    Leaves that are genuinely sharded (no device holds the full value) are
    skipped; only the replicated ones are comparable.
    """
    bad = []
    for path, leaf in _leaf_paths(tree):
        if not isinstance(leaf, jax.Array) or not hasattr(leaf, "sharding"):
            continue
        shards = leaf.addressable_shards
        if len(shards) < 2:
            continue
        if shards[0].data.shape != leaf.shape:
            continue  # sharded, not replicated: nothing to cross-check
        ref = np.asarray(shards[0].data)
        for s in shards[1:]:
            other = np.asarray(s.data)
            if atol == 0.0:
                ok = np.array_equal(ref, other, equal_nan=True)
            else:
                ok = np.allclose(ref, other, atol=atol, rtol=0.0,
                                 equal_nan=True)
            if not ok:
                bad.append(path)
                break
    return bad


def assert_replicas_in_sync(tree: PyTree, *, atol: float = 0.0,
                            what: str = "training state") -> None:
    bad = replica_desync(tree, atol=atol)
    if bad:
        raise ConsistencyError(
            f"{what} desynced across replicas at {len(bad)} leaves: "
            f"{bad[:5]}{'...' if len(bad) > 5 else ''}")


def check_determinism(fn: Callable[..., PyTree], *args,
                      runs: int = 2) -> None:
    """Run ``fn(*args)`` ``runs`` times and require bitwise-identical outputs.

    ``fn`` must be pure (a compiled step re-invoked on the SAME inputs —
    donation must be off, or pass fresh copies).  Catches nondeterministic
    reductions and host-side state leaking into the step.
    """
    outs = [jax.tree.map(np.asarray, fn(*args)) for _ in range(runs)]
    ref = outs[0]
    for i, out in enumerate(outs[1:], start=2):
        mism = []
        for (path, a), (_, b) in zip(_leaf_paths(ref), _leaf_paths(out)):
            if not np.array_equal(np.asarray(a), np.asarray(b),
                                  equal_nan=True):
                mism.append(path)
        if mism:
            raise ConsistencyError(
                f"run {i} differs from run 1 at {len(mism)} leaves: "
                f"{mism[:5]}{'...' if len(mism) > 5 else ''}")


def assert_finite(tree: PyTree, *, what: str = "pytree") -> None:
    """Raise if any leaf contains NaN/Inf (grad/param health check)."""
    bad = []
    for path, leaf in _leaf_paths(tree):
        arr = np.asarray(leaf)
        if np.issubdtype(arr.dtype, np.floating) and not np.isfinite(
                arr).all():
            bad.append(path)
    if bad:
        raise ConsistencyError(
            f"{what} has non-finite values at {len(bad)} leaves: "
            f"{bad[:5]}{'...' if len(bad) > 5 else ''}")
