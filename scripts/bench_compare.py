#!/usr/bin/env python
"""Compare bench JSONs with per-key direction + threshold rules — the
CI-able perf gate over the BENCH_r*.json trajectory.

    python scripts/bench_compare.py OLD.json NEW.json
    python scripts/bench_compare.py --trajectory BENCH_r*.json
    python scripts/bench_compare.py OLD.json NEW.json --across-hosts

Accepts either bench.py's raw JSON or the driver's BENCH_r*.json
wrapper (``{"parsed": {...}}``).  Exit status: 0 clean, 1 when any
gated key regressed.  The rule table is seeded from the measured
round-3..14 figures in BASELINE.md: throughput/MFU/speedup keys must
not drop more than their tolerance, latency keys must not rise more
than theirs, and ``telemetry_overhead_pct`` is held to the round-13
acceptance CEILING (<= 2%) rather than a relative band — a near-zero
baseline (-0.15% measured) makes any relative rule meaningless.

Cross-host comparisons do not gate by default: the ``meta`` block
(round 15) stamps platform/device, and a v5e-vs-CPU delta is a host
change, not a regression.  ``--across-hosts`` overrides (e.g. for a
same-pod-type fleet where hostnames differ).

Deliberately jax-free / stdlib-only: it must run in CI and on a laptop
against JSONs rsync'd off a pod.
"""

from __future__ import annotations

import argparse
import json
import sys

# key -> (direction, relative tolerance).  "higher" keys gate when
# new < old * (1 - tol); "lower" keys when new > old * (1 + tol).
# Tolerances widen with each key's measured run-to-run noise
# (BASELINE.md): medians-of-windows sit near ±3-5%, p95s and
# fault-path wall-clocks swing harder on a contended host.
RULES: dict[str, tuple[str, float]] = {
    "value": ("higher", 0.10),
    "vs_baseline": ("higher", 0.10),
    "mfu": ("higher", 0.10),
    "calib_tflops": ("higher", 0.10),
    "train_overlap_speedup": ("higher", 0.10),
    "train_dcn_overlap_speedup": ("higher", 0.10),
    "train_autotune_speedup": ("higher", 0.10),
    "elastic_recovery_ms": ("lower", 0.25),
    "lm_tokens_per_sec_per_chip": ("higher", 0.10),
    "lm_mfu": ("higher", 0.10),
    "lm_large_tokens_per_sec_per_chip": ("higher", 0.10),
    "lm_large_mfu": ("higher", 0.10),
    "decode_ms_per_token": ("lower", 0.15),
    "decode_ms_per_token_p95": ("lower", 0.25),
    "serving_tokens_per_sec": ("higher", 0.15),
    "serving_tokens_per_sec_p95": ("higher", 0.25),
    "serving_overlap_speedup": ("higher", 0.10),
    "serving_slot_step_utilization": ("higher", 0.10),
    "serving_emitted_per_slot_step": ("higher", 0.10),
    "fleet_tokens_per_sec": ("higher", 0.15),
    "fleet_prefix_hit_rate": ("higher", 0.10),
    "fleet_handoff_ms": ("lower", 0.50),
    # round 16: int4 wire bytes are deterministic accounting (inspector-
    # measured), so the band is tight; the q8-gather A/B is a wall-clock
    # median like the other speedups.
    "train_dcn_int4_bytes_per_step": ("lower", 0.02),
    "lm_q8_gather_speedup": ("higher", 0.10),
    # round 17: the accountant's predicted footprints are deterministic
    # shape arithmetic (census-verified), so the bands are tight — a
    # move means the model/stack changed, not noise.
    "lm_ce_peak_activation_bytes": ("lower", 0.02),
    "lm_remat_saved_bytes": ("higher", 0.02),
    # round 18: the windowed dcn payload is deterministic inspector
    # accounting like the int4 bytes (tight band); the local-SGD A/B
    # is a wall-clock median like the other speedups.
    "train_localsgd_speedup": ("higher", 0.10),
    "train_dcn_bytes_per_step_windowed": ("lower", 0.02),
    # round 19: heartbeat round-trip over the unix-socket RPC — wide
    # band (sub-ms values are scheduler-noise dominated) plus an
    # absolute ceiling below so the tax stays decisively under a
    # decode step
    "fleet_rpc_overhead_ms": ("lower", 0.50),
    # round 20: routed hop-graph wire bytes per step — deterministic
    # (schedule-inspector payload accounting, no timing noise), same
    # tight band as the round-16 dcn-int4 byte key
    "train_routed_bytes_per_step": ("lower", 0.02),
    # round 21: quantized MoE dispatch — all_to_all wire bytes and the
    # int8/f32 wire ratio are deterministic schedule-inspector payload
    # accounting (no timing noise), same tight band as the routed and
    # dcn-int4 byte keys
    "moe_a2a_bytes_per_step": ("lower", 0.02),
    "moe_a2a_dispatch_ratio": ("lower", 0.02),
    # round 22: DiLoCo WAN leg — the measured boundary-exchange bytes
    # per optimizer step and the chooser's predicted WAN-hop figure are
    # both deterministic accounting (inspector payloads / alpha-beta
    # pricing of a fixed census), same tight band as the other byte
    # keys; the plain-vs-outer wall-clock is a median like the other
    # speedups (~1.0x expected — the outer step is off the wire)
    "wan_diloco_speedup": ("higher", 0.10),
    "wan_diloco_bytes_per_opt_step": ("lower", 0.02),
    "wan_bytes_per_opt_step_predicted": ("lower", 0.02),
}

# absolute ceilings: gate on the NEW value alone (acceptance bounds,
# not ratios — see module docstring)
ABS_CEILINGS: dict[str, float] = {
    "telemetry_overhead_pct": 2.0,  # round-13 acceptance bound
    # round-16 bound: int8-vs-bf16 teacher-forced argmax flips on the
    # corpus-trained byte-LM (measured 0.004-0.013 across model sizes,
    # concentrated at |top1-top2| < 0.05 near-ties; the kernel-vs-XLA
    # int8 pair is bitwise equal, pinned at zero by tests/test_lowbit.py)
    "lm_int8_matmul_fliprate": 0.02,
    # round-17 bound: the remat/chunked step may spend recompute for its
    # memory saving, but a step more than 35% slower than dense/no-remat
    # is spending more than full recomputation should cost (measured
    # ~5-25% on the CPU mesh depending on the rung)
    "lm_remat_step_overhead_pct": 35.0,
    # round-19 bound: one framed RPC round-trip (heartbeat median) must
    # stay well under a single decode step (~10 ms on the CPU mesh) —
    # measured ~0.1-0.3 ms over unix sockets
    "fleet_rpc_overhead_ms": 5.0,
    # round-21 bound: the round-16 flip-rate methodology applied to
    # int8 expert DISPATCH (teacher-forced argmax flips, f32 vs int8
    # dispatch at identical params) — measured 0.000 on the ep=2 CPU
    # mesh at d_model=256 (rowwise scales track token magnitude, so
    # the perturbation sits well under near-tie width)
    "moe_router_flip_rate": 0.02,
}


def load_bench(path: str) -> dict:
    """One bench result: bench.py's raw JSON, or the driver wrapper's
    ``parsed`` block (meta rides inside ``parsed`` there too)."""
    with open(path) as f:
        data = json.load(f)
    if "parsed" in data and isinstance(data["parsed"], dict):
        data = data["parsed"]
    if not isinstance(data, dict) or "metric" not in data:
        raise ValueError(f"{path!r} is not a bench JSON "
                         f"(no 'metric' key)")
    return data


def hosts_comparable(old: dict, new: dict) -> tuple[bool, str]:
    """Same platform + device kind?  Legacy JSONs without a meta block
    (pre-round-15) compare as before — there is nothing to refuse on."""
    mo, mn = old.get("meta"), new.get("meta")
    if not mo or not mn:
        return True, "no meta (legacy JSON) — comparing unconditionally"
    for field in ("platform", "device_kind"):
        if mo.get(field) != mn.get(field):
            return False, (f"{field} differs: {mo.get(field)!r} -> "
                           f"{mn.get(field)!r}")
    return True, ""


def compare(old: dict, new: dict) -> list[dict]:
    """Judge every rule key present in BOTH results (None = the gate
    was skipped that round and cannot be judged).  Each row:
    {key, old, new, direction, tolerance, ratio, regressed}."""
    rows: list[dict] = []
    for key, (direction, tol) in RULES.items():
        ov, nv = old.get(key), new.get(key)
        if not isinstance(ov, (int, float)) or not isinstance(
                nv, (int, float)):
            continue
        if ov == 0:
            ratio = None
            regressed = (nv < 0) if direction == "higher" else (nv > 0)
        else:
            ratio = nv / ov
            regressed = (ratio < 1 - tol if direction == "higher"
                         else ratio > 1 + tol)
        rows.append({"key": key, "old": ov, "new": nv,
                     "direction": direction, "tolerance": tol,
                     "ratio": ratio, "regressed": regressed})
    for key, ceiling in ABS_CEILINGS.items():
        nv = new.get(key)
        if not isinstance(nv, (int, float)):
            continue
        rows.append({"key": key, "old": old.get(key), "new": nv,
                     "direction": "ceiling", "tolerance": ceiling,
                     "ratio": None, "regressed": nv > ceiling})
    return rows


def print_rows(rows: list[dict]) -> None:
    print(f"  {'key':<34} {'old':>12} {'new':>12} {'change':>8} "
          f"{'gate':>16} {'verdict':>10}")
    for r in rows:
        old_s = (f"{r['old']:g}" if isinstance(r["old"], (int, float))
                 else "-")
        chg = (f"{(r['ratio'] - 1) * 100:+.1f}%"
               if r["ratio"] is not None else "-")
        if r["direction"] == "ceiling":
            gate = f"<= {r['tolerance']:g}"
        else:
            sign = "-" if r["direction"] == "higher" else "+"
            gate = (f"{r['direction']} {sign}"
                    f"{r['tolerance'] * 100:.0f}%")
        verdict = "REGRESSED" if r["regressed"] else "ok"
        print(f"  {r['key']:<34} {old_s:>12} {r['new']:>12g} "
              f"{chg:>8} {gate:>16} {verdict:>10}")


def run_pair(old_path: str, new_path: str, *,
             across_hosts: bool) -> int:
    old, new = load_bench(old_path), load_bench(new_path)
    print(f"{old_path} -> {new_path}")
    comparable, why = hosts_comparable(old, new)
    if why:
        print(f"  note: {why}")
    rows = compare(old, new)
    print_rows(rows)
    regressions = [r for r in rows if r["regressed"]]
    if regressions and not comparable and not across_hosts:
        print(f"  {len(regressions)} would-be regression(s) NOT gated: "
              f"hosts differ (use --across-hosts to enforce)")
        return 0
    if regressions:
        print(f"  {len(regressions)} regression(s)")
        return len(regressions)
    return 0


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(
        description="bench-JSON perf gate (direction + threshold per "
                    "key; exit 1 on regression)")
    p.add_argument("benches", nargs="+",
                   help="two bench JSONs (old new), or with "
                        "--trajectory a whole BENCH_r*.json sequence")
    p.add_argument("--trajectory", action="store_true",
                   help="compare every consecutive pair in order "
                        "instead of exactly two files")
    p.add_argument("--across-hosts", action="store_true",
                   help="gate regressions even when meta says "
                        "platform/device changed")
    args = p.parse_args(argv)

    if args.trajectory:
        if len(args.benches) < 2:
            p.error("--trajectory needs at least two JSONs")
        pairs = list(zip(args.benches, args.benches[1:]))
    else:
        if len(args.benches) != 2:
            p.error("need exactly OLD.json NEW.json "
                    "(or --trajectory for a sequence)")
        pairs = [(args.benches[0], args.benches[1])]

    total = 0
    for i, (a, b) in enumerate(pairs):
        if i:
            print()
        total += run_pair(a, b, across_hosts=args.across_hosts)
    return 1 if total else 0


if __name__ == "__main__":
    sys.exit(main())
