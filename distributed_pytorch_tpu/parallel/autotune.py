"""Topology-aware gradient-sync autotuner (round 11).

PRs 4-10 built every sync mechanism the dp x fsdp x tp x pp lattice
needs — reverse-topo bucket plans, in-backward sync points, two-level
(ici, dcn) streaming, int8-on-the-DCN-hop with error feedback — but
every knob was hand-picked: fixed 25 MB buckets, one global strategy
string, compression only where a human wired it.  DynamiQ (compressed
multi-hop all-reduce) and "The Big Send-off" (PAPERS.md) both show the
right algorithm/compression choice is a function of the LINK, not of
the model; this module closes the loop:

1. **Calibration** (``calibrate``): per mesh axis, time a small ladder
   of real collectives — ``psum``, reduce-scatter + all-gather, and a
   ppermute ring — at 3-4 payload sizes, then least-squares fit an
   alpha-beta cost model per link (``LinkModel``: launch latency
   ``alpha_s`` + inverse bandwidth ``beta_s_per_byte``), using each
   algorithm's analytic launch/wire factors so all observations
   constrain one (alpha, beta) pair.  Round 16 adds one quantize/
   dequantize round-trip to the same pass (``quant_s_per_byte``): the
   compute a compressed hop spends to earn its wire saving, so the
   chooser stops recommending compression on hosts where quantize
   compute eats the win (the round-11 CPU 0.71x mischoice).  Profiles cache to a versioned
   repo-local JSON (like the XLA compile cache; ``save_profile`` /
   ``load_profile``; a version mismatch invalidates silently), and
   deterministic synthetic profiles (``synthetic_profile``) are
   injectable for CPU tests.

2. **Plan choosing** (``choose_train_plan`` / ``choose_lm_plan``):
   given the grad-tree byte census (the same ``make_bucket_plan``
   packing the strategies execute) and a fitted profile, pick the
   bucket size, the ring-vs-tree-vs-two-level algorithm, and per-hop
   compression (none / int8+EF / int4+EF) by minimizing predicted
   step-sync
   time, emitting an explainable ``SyncPlan`` (predicted ms + operand
   bytes per axis, printable table).  The chooser is a pure function
   of (census, profile, config flags) — deterministic given a fixed
   profile (test-pinned).

3. **Resolution** (``resolve_train_auto`` / ``resolve_lm_auto``):
   ``TrainConfig(strategy="auto")`` / ``LMTrainConfig(sync_plan=
   "auto")`` resolve to the NAMED strategies/knobs the framework
   already ships, so the chosen plan routes through the existing
   (bitwise-pinned) paths unchanged: ``strategy="auto"`` under a
   forced profile trains bitwise-identically to the named strategy it
   resolves to.

Cost model (documented so the numbers are auditable; O = operand bytes
per device, n = axis size, a/b = the link's alpha/beta):

- ``psum`` (all-reduce, modeled bandwidth-optimal): a + 2*O*(n-1)/n*b
- ``psum_scatter`` (reduce-scatter):                a +   O*(n-1)/n*b
- ``all_gather`` of an O-byte shard:                a + O*(n-1)*b
- ``ppermute`` of an O-byte payload:                a + O*b

Wire accounting (``AxisPlan.predicted_bytes``) is OPERAND-PAYLOAD,
scan-trip-weighted — deliberately the same accounting as the schedule
inspector's ``bytes_executed`` (utils/debug.py), so predictions are
cross-checkable against measurements (``debug.assert_plan_bytes_match``,
scripts/bench_strategies.py's predicted-vs-measured table).
"""

from __future__ import annotations

import dataclasses
import json
import os
from dataclasses import dataclass, field
from typing import Any

import numpy as np

from . import strategies as strat
from ..utils import telemetry

# 2 since round 16: the cost model gained the quantize-compute term, so
# a version-1 profile (no quant_s_per_byte) would cost compression the
# optimistic old way — the exact mischoice this round fixes.  The cache
# version bump forces recalibration instead of silently steering.
# 3 since round 17: the memory chooser (choose_lm_memory_plan) prices
# remat/chunked-CE rungs with the device's calibrated
# recompute-seconds-per-byte; a version-2 profile has no such term and
# would cost rematerialization as free.
# 4 since round 20: profiles carry the routed-plan era's fields — the
# concurrent-calibration record (``concurrent`` block in ``measured``
# plus ``concurrent_delta_pct``) and the 3-level preset vocabulary
# ('wan' joins 'dcn' as a link role) — and the route chooser
# (choose_sync_plan) prices hop-graphs from the same per-axis fits.  A
# version-3 profile predates the busy-MXU calibration option and the
# wan role; the version gate recalibrates instead of silently steering
# (regression-tested in tests/test_routing.py).
# 5 since round 21: calibration times an all-to-all ladder rung per
# axis (the expert-dispatch collective, wire factor (n-1)/n) and the
# MoE dispatch chooser (choose_moe_plan) prices dispatch bit-widths
# from the same per-axis fits.  A version-4 profile's alpha-beta fit
# never saw an all-to-all observation; the version gate recalibrates
# instead of silently steering (regression-tested in tests/test_a2a.py).
PROFILE_VERSION = 5

# Bucket-size candidates (MB).  25 first: the torch-DDP default wins
# ties (strict-improvement argmin), so the chooser only moves off it
# when the profile actually says so.
BUCKET_LADDER_MB = (25.0, 4.0, 100.0)

# int8 ring per-hop payload factor: chunk int8 bytes + one f32 scale per
# 256-element row = chunk * (1 + 4/(4*256)) relative to chunk elements.
_RING_BLOCK = 256
_INT8_ROW_OVERHEAD = 1.0 + 1.0 / 64.0  # (1 int8 + 4/256 scale bytes)/elem
# int4 (round 16): two nibbles per int8 lane halve the chunk payload;
# the per-row f32 scale rides at full width either way.
_INT4_ROW_OVERHEAD = 0.5 + 1.0 / 64.0  # (0.5 packed + 4/256 scale)/elem

# Quantize-COMPUTE f32 passes per chunk element per ring hop: every hop
# dequantizes the incoming chunk and requantizes the outgoing one (2
# full f32 passes); the int4 rung adds the nibble pack/unpack pair on
# top.  Charged at the link's calibrated ``quant_s_per_byte`` — this is
# the term whose absence produced the round-11 CPU mischoice (predicted
# win, measured 0.71x: the wire saving was real, the quantize compute
# that paid for it was not in the model).
_QUANT_PASSES = {"int8": 2.0, "int4": 4.0}


# ---------------------------------------------------------------------------
# profiles


@dataclass(frozen=True)
class LinkModel:
    """Alpha-beta-quant cost model of one mesh-axis link: a collective
    costs ``launches * alpha_s + wire_bytes * beta_s_per_byte +
    quant_bytes * quant_s_per_byte`` seconds, where ``quant_bytes`` is
    the f32 traffic a compressed hop pushes through quantize/dequantize
    (and, at int4, nibble pack/unpack) on the way to the wire.  The
    quant term (round 16) is calibrated from the same pass as alpha/
    beta; it defaults to 0.0 only for hand-built profile dicts — cached
    profiles without it are version-1 and recalibrate (PROFILE_VERSION
    bump)."""

    alpha_s: float
    beta_s_per_byte: float
    quant_s_per_byte: float = 0.0


@dataclass
class TopologyProfile:
    """Fitted per-axis link models for one mesh topology.

    ``axes`` preserves mesh order (outer first); ``measured`` carries the
    raw calibration observations (axis -> algo -> payload-bytes -> s) for
    auditability; ``source`` records provenance ("calibrated",
    "synthetic:<preset>", "cache:<path>").

    ``recompute_s_per_byte`` (round 17, version 3) is the DEVICE's cost
    of re-producing one activation byte under rematerialization —
    calibrated from a jitted transformer-shaped forward in the same pass
    as alpha/beta/quant, and charged by the memory chooser against the
    bytes ``utils.memacct.predict_recompute_bytes`` says a remat/chunked
    rung re-runs.  Like ``quant_s_per_byte`` it defaults to 0.0 only for
    hand-built dicts; cached profiles without it are stale and
    recalibrate (version gate).

    ``concurrent_delta_pct`` (round 20, version 4) records how much the
    quantize rate degraded when calibration ran against a background
    matmul stream (``calibrate(concurrent=True)`` — link fits that
    reflect a busy MXU instead of an idle device); ``None`` means the
    profile was calibrated idle.  Hand-built dicts default it; cached
    profiles without the field are version-3 and recalibrate."""

    version: int
    device_kind: str
    axes: dict[str, int]
    links: dict[str, LinkModel]
    source: str = "calibrated"
    measured: dict = field(default_factory=dict)
    recompute_s_per_byte: float = 0.0
    concurrent_delta_pct: float | None = None

    def key(self) -> str:
        """Cache-file key: device kind + topology (axis names x sizes)."""
        topo = "-".join(f"{a}{s}" for a, s in self.axes.items())
        kind = "".join(c if c.isalnum() else "_" for c in self.device_kind)
        return f"{kind}_{topo}"

    def to_json(self) -> dict:
        return {"version": self.version, "device_kind": self.device_kind,
                "axes": dict(self.axes),
                "links": {a: {"alpha_s": l.alpha_s,
                              "beta_s_per_byte": l.beta_s_per_byte,
                              "quant_s_per_byte": l.quant_s_per_byte}
                          for a, l in self.links.items()},
                "source": self.source, "measured": self.measured,
                "recompute_s_per_byte": self.recompute_s_per_byte,
                "concurrent_delta_pct": self.concurrent_delta_pct}

    @classmethod
    def from_json(cls, d: dict) -> "TopologyProfile":
        return cls(version=int(d["version"]),
                   device_kind=d["device_kind"],
                   axes={a: int(s) for a, s in d["axes"].items()},
                   links={a: LinkModel(float(l["alpha_s"]),
                                       float(l["beta_s_per_byte"]),
                                       # pre-round-16 profiles have no
                                       # quant term: load, cost it free
                                       float(l.get("quant_s_per_byte",
                                                   0.0)))
                          for a, l in d["links"].items()},
                   source=d.get("source", "cache"),
                   measured=d.get("measured", {}),
                   recompute_s_per_byte=float(
                       d.get("recompute_s_per_byte", 0.0)),
                   # pre-round-20 profiles never calibrated busy: None
                   concurrent_delta_pct=d.get("concurrent_delta_pct"))


# Deterministic synthetic profiles for CPU tests and the dryrun: each
# preset maps the requested axes onto fixed (alpha, beta) pairs by ROLE
# ('dcn' = the cross-slice slow hop; every other axis is a fast intra-
# slice link).  The numbers are chosen so each preset has one clearly
# optimal plan (test-pinned in tests/test_autotune.py):
#
# - uniform:           equal medium links, launch-latency-dominated ->
#                      the flat fused psum (fewest launches) wins.
# - fast_ici_slow_dcn: ~400x bandwidth gap -> two-level + int8 on the
#                      scarce hop (the DynamiQ design point).  int8, NOT
#                      int4: at 0.5 GB/s the int4 rung's halved wire
#                      (saves ~2 ns/elem) no longer pays for its doubled
#                      quantize passes (~1.6 ns/elem extra at the preset
#                      quant rate) plus the 16x-coarser rounding — the
#                      quant term keeps the ladder honest.
# - inverted:          the INNER link is the bottleneck -> two-level
#                      buys nothing (its reduce-scatter/gather ride the
#                      slow link either way); flat psum wins on launches.
# - slow:              one slow flat link -> the int8+EF ring (true
#                      per-hop wire compression) wins.
# - fast:              one fast flat link -> plain fused psum wins.
# - wan_dcn:           a WAN-grade cross-site hop (~0.05 GB/s, round
#                      16): wire is 10x scarcer than fast_ici_slow_dcn,
#                      so halving it dominates the extra quantize
#                      passes -> two-level + int4+EF on the slow hop.
# - quant_bound:       same 0.5 GB/s DCN hop but a quantize throughput
#                      of ~0.5 GB/s (a host-bound mesh, e.g. the CPU
#                      mesh of BASELINE round 11 that measured 0.71x on
#                      a predicted win): quantize compute eats the wire
#                      saving -> the chooser DECLINES compression.
_QUANT = 2e-10  # ~5 GB/s quantize/dequantize throughput (accelerator)
# ~5 GB/s of re-produced activation bytes: the synthetic presets' stand-
# in for the calibrated recompute rate (same order as _QUANT — both are
# device compute, not wire)
_RECOMPUTE_SYNTH = 2e-10
_FAST = LinkModel(alpha_s=1e-6, beta_s_per_byte=5e-12,     # ~200 GB/s
                  quant_s_per_byte=_QUANT)
_SLOW = LinkModel(alpha_s=1e-5, beta_s_per_byte=2e-9,      # ~0.5 GB/s
                  quant_s_per_byte=_QUANT)
_WAN = LinkModel(alpha_s=1e-5, beta_s_per_byte=2e-8,       # ~0.05 GB/s
                 quant_s_per_byte=_QUANT)
_SLOW_QUANT_BOUND = LinkModel(alpha_s=1e-5, beta_s_per_byte=2e-9,
                              quant_s_per_byte=2e-9)  # ~0.5 GB/s quant
_MEDIUM_HIGH_ALPHA = LinkModel(alpha_s=2e-4, beta_s_per_byte=1e-11,
                               quant_s_per_byte=_QUANT)
SYNTHETIC_PRESETS = {
    "uniform": lambda axis: _MEDIUM_HIGH_ALPHA,
    "fast_ici_slow_dcn": lambda axis: _SLOW if axis == "dcn" else _FAST,
    "inverted": lambda axis: _FAST if axis == "dcn" else _SLOW,
    "slow": lambda axis: LinkModel(alpha_s=2e-6, beta_s_per_byte=2e-9,
                                   quant_s_per_byte=_QUANT),
    "fast": lambda axis: _MEDIUM_HIGH_ALPHA,
    "wan_dcn": lambda axis: _WAN if axis == "dcn" else _FAST,
    "quant_bound": lambda axis: (_SLOW_QUANT_BOUND if axis == "dcn"
                                 else _FAST),
    # round 20: the ≥3-level mesh the route chooser searches — fast ICI
    # within a slice, a datacenter-grade DCN tier across slices, and a
    # WAN-grade cross-site tier above that.  The optimal plan is a
    # NESTED 3-hop route (ici:rs → dcn:rs → wan:ring[int4+ef] → dcn:ag
    # → ici:ag): the wan exchange rides a payload already divided by
    # BOTH faster axes, and at 0.05 GB/s halving its wire dominates the
    # extra quantize passes (test-pinned in tests/test_routing.py).
    "ici_dcn_wan": lambda axis: (_WAN if axis == "wan"
                                 else _SLOW if axis == "dcn" else _FAST),
}


def synthetic_profile(preset: str, axes: dict[str, int]) -> TopologyProfile:
    """A deterministic profile for ``axes`` from a named preset — the CPU
    tests' injection point (no device timing anywhere)."""
    try:
        link_of = SYNTHETIC_PRESETS[preset]
    except KeyError:
        raise ValueError(
            f"unknown synthetic profile {preset!r}; presets: "
            f"{sorted(SYNTHETIC_PRESETS)}") from None
    return TopologyProfile(
        version=PROFILE_VERSION, device_kind="synthetic",
        axes=dict(axes), links={a: link_of(a) for a in axes},
        source=f"synthetic:{preset}",
        recompute_s_per_byte=_RECOMPUTE_SYNTH)


# ---------------------------------------------------------------------------
# profile cache (repo-local, versioned — the XLA-compile-cache shape)


def profile_cache_dir() -> str:
    env = os.environ.get("JAX_GRAFT_AUTOTUNE_CACHE")
    if env:
        return env
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, ".autotune_cache")


def save_profile(profile: TopologyProfile,
                 cache_dir: str | None = None) -> str:
    d = cache_dir or profile_cache_dir()
    os.makedirs(d, exist_ok=True)
    path = os.path.join(d, f"profile_{profile.key()}.json")
    with open(path, "w") as f:
        json.dump(profile.to_json(), f, indent=1, sort_keys=True)
    return path


def load_profile(device_kind: str, axes: dict[str, int],
                 cache_dir: str | None = None) -> TopologyProfile | None:
    """Cached profile for this (device kind, topology), or None on a miss
    OR a version/topology mismatch — a stale profile must trigger
    recalibration, never silently steer the chooser."""
    key = TopologyProfile(PROFILE_VERSION, device_kind, dict(axes), {}).key()
    path = os.path.join(cache_dir or profile_cache_dir(),
                        f"profile_{key}.json")
    if not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            d = json.load(f)
    except (OSError, ValueError):
        return None
    if int(d.get("version", -1)) != PROFILE_VERSION:
        return None
    p = TopologyProfile.from_json(d)
    if p.axes != dict(axes):
        return None
    p.source = f"cache:{path}"
    return p


# ---------------------------------------------------------------------------
# calibration


def _algo_factors(algo: str, n: int) -> tuple[float, float]:
    """(launches, wire-bytes-per-payload-byte) of one calibration
    collective over an n-way axis — the analytic factors the fit divides
    out so every (algo, size) observation constrains ONE (alpha, beta)."""
    if algo == "psum":
        return 1.0, 2.0 * (n - 1) / n
    if algo == "rs_ag":  # psum_scatter + all_gather
        return 2.0, 2.0 * (n - 1) / n
    if algo == "ring":   # n-1 chained full-payload ppermute hops
        return float(n - 1), float(n - 1)
    if algo == "a2a":    # all-to-all: each device keeps its own 1/n block
        return 1.0, float(n - 1) / n
    raise ValueError(f"unknown calibration algorithm {algo!r}")


def fit_alpha_beta(observations: list[tuple[float, float, float]]
                   ) -> LinkModel:
    """Least-squares fit of ``t = alpha*L + beta*W`` over observations
    ``(launches L, wire_bytes W, seconds t)``; both coefficients clamped
    non-negative (a negative latency/bandwidth fit is noise)."""
    A = np.asarray([[l, w] for l, w, _ in observations], np.float64)
    t = np.asarray([s for _, _, s in observations], np.float64)
    coef, *_ = np.linalg.lstsq(A, t, rcond=None)
    alpha = float(max(coef[0], 1e-12))
    beta = float(max(coef[1], 1e-15))
    return LinkModel(alpha_s=alpha, beta_s_per_byte=beta)


def _time_axis_collective(mesh, axis: str, payload_bytes: int, algo: str,
                          *, inner: int = 4, reps: int = 2) -> float:
    """Measured seconds per execution of one ``algo`` collective over
    ``axis`` at ``payload_bytes`` (f32 payload), best-of-``reps`` of an
    ``inner``-deep data-chained loop (the bench.py chained-window
    discipline: the chain defeats CSE, one fetch ends the window)."""
    import time

    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from ..utils.compat import shard_map

    n = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    elems = max(payload_bytes // 4, _RING_BLOCK)
    elems += (-elems) % n  # rs_ag needs an n-divisible payload
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(x):
        if algo == "psum":
            return lax.psum(x, axis) * (1.0 / n)
        if algo == "rs_ag":
            s = lax.psum_scatter(x, axis, scatter_dimension=0, tiled=True)
            return lax.all_gather(s, axis, axis=0, tiled=True) * (1.0 / n)
        if algo == "a2a":  # the expert-dispatch permutation (round 21)
            y = lax.all_to_all(x.reshape(n, elems // n), axis,
                               split_axis=0, concat_axis=0, tiled=False)
            return y.reshape(elems)
        acc = x
        for _ in range(n - 1):  # ring: chained full-payload hops
            acc = lax.ppermute(acc, axis, perm)
        return acc

    def chained(x):
        for _ in range(inner):
            x = body(x)
            x = lax.optimization_barrier(x)
        return x

    fn = jax.jit(shard_map(
        chained, mesh=mesh,
        in_specs=(P(),), out_specs=P(),
        # the ring assembles a ppermute result: replicated by
        # construction (value-preserving permutation of identical
        # payloads), not provably — calibration is measurement-only
        check_vma=False))
    x = jnp.full((elems,), 1.0 / inner, jnp.float32)
    np.asarray(fn(x))  # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(x)
        out.block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best / inner


def _time_quantize(payload_bytes: int = 4 << 20, *,
                   reps: int = 3) -> float:
    """Seconds per f32 byte of ONE quantize-or-dequantize pass on the
    default device: time a jitted per-row symmetric int8 round-trip
    (the ring hops' exact compute shape) over a ``payload_bytes``
    buffer, best-of-``reps``, and divide by the two passes' f32 bytes.
    This is the round-16 calibration of ``LinkModel.quant_s_per_byte``
    — measured on the same pass as alpha/beta so the chooser can weigh
    wire saved against quantize compute spent on THIS host."""
    import time

    import jax
    import jax.numpy as jnp

    elems = max(payload_bytes // 4, _RING_BLOCK)
    elems += (-elems) % _RING_BLOCK

    @jax.jit
    def roundtrip(x):
        rows = x.reshape(-1, _RING_BLOCK)
        scale = jnp.maximum(
            jnp.max(jnp.abs(rows), axis=1, keepdims=True), 1e-30) / 127.0
        q = jnp.clip(jnp.round(rows / scale), -127, 127).astype(jnp.int8)
        return (q.astype(jnp.float32) * scale).reshape(x.shape)

    x = jnp.linspace(-1.0, 1.0, elems, dtype=jnp.float32)
    np.asarray(roundtrip(x))  # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        roundtrip(x).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best / (2.0 * elems * 4.0)


def _time_recompute(*, rows: int = 2048, width: int = 512,
                    reps: int = 3) -> float:
    """Seconds per activation byte RE-produced by a rematerialized
    forward on the default device: time a jitted transformer-flavored
    chain (matmul -> silu-gate -> matmul, the block's recompute shape)
    and divide by the intermediate bytes it materializes.  The round-17
    calibration of ``TopologyProfile.recompute_s_per_byte`` — the
    ``_time_quantize`` precedent, aimed at memory instead of wire: the
    memory chooser weighs activation bytes saved against THIS host's
    cost of re-running the forward that re-creates them."""
    import time

    import jax
    import jax.numpy as jnp

    @jax.jit
    def fwd(x, w1, w2):
        g = x @ w1                  # rows x (4*width)
        a = jax.nn.silu(g) * g      # two more rows x (4*width)
        return a @ w2               # rows x width

    k = jax.random.PRNGKey(0)
    x = jax.random.normal(k, (rows, width), jnp.float32)
    w1 = jax.random.normal(jax.random.fold_in(k, 1),
                           (width, 4 * width), jnp.float32) * 0.02
    w2 = jax.random.normal(jax.random.fold_in(k, 2),
                           (4 * width, width), jnp.float32) * 0.02
    produced = (3 * rows * 4 * width + rows * width) * 4  # f32 bytes
    np.asarray(fwd(x, w1, w2))  # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fwd(x, w1, w2).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best / produced


class _BackgroundMatmul:
    """A host thread that keeps dispatching a jitted matmul chain on the
    default device while calibration times its ladders — the round-20
    busy-MXU stream.  Context manager: enter starts the stream, exit
    joins it.  Dispatch is async (one ``block_until_ready`` per chain of
    8), so the device queue stays occupied without the host thread
    monopolizing the GIL."""

    def __init__(self, dim: int = 512):
        import threading

        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, args=(dim,),
                                        daemon=True)

    def _run(self, dim: int) -> None:
        import jax
        import jax.numpy as jnp

        @jax.jit
        def chain(x):
            for _ in range(8):
                x = x @ x * (1.0 / dim)
            return x

        x = jnp.full((dim, dim), 1.0 / dim, jnp.float32)
        x = chain(x)
        x.block_until_ready()  # compile outside the timed window
        while not self._stop.is_set():
            x = chain(x)
            x.block_until_ready()

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()
        return False


def calibrate(mesh, *, payload_bytes=(256 << 10, 1 << 20, 4 << 20),
              algos=("psum", "rs_ag", "ring", "a2a"),
              inner: int = 4, reps: int = 2,
              concurrent: bool = False) -> TopologyProfile:
    """Fit a ``TopologyProfile`` by timing real collectives per axis of
    ``mesh`` (the calibration pass), plus one quantize/dequantize
    round-trip for the compute half of the compressed-hop cost (shared
    across axes — it runs on the device, not the link).  Axes of size 1
    get a zero-cost link (nothing ever crosses them).  The ladder's
    fourth rung (round 21) is the all-to-all — the expert-dispatch
    permutation, wire factor ``(n-1)/n`` — so the same (alpha, beta)
    fit also prices MoE dispatch (``choose_moe_plan``).

    ``concurrent=True`` (round 20) runs the quantize ladder and the
    per-axis collective ladders against a background matmul stream
    (``_BackgroundMatmul``), so the fits reflect a BUSY device — the
    regime the sync actually runs in (collectives compete with backward
    compute for the same cores/MXU).  The idle quantize rate is always
    measured first; the busy-vs-idle delta lands in
    ``concurrent_delta_pct`` and ``measured['concurrent']`` (recorded in
    BASELINE round 20)."""
    import contextlib
    import time

    import jax

    t0 = time.perf_counter()
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    quant_idle = _time_quantize()
    recompute = _time_recompute()
    stream = _BackgroundMatmul() if concurrent else contextlib.nullcontext()
    concurrent_delta = None
    with stream:
        quant = _time_quantize() if concurrent else quant_idle
        if concurrent:
            concurrent_delta = (quant / quant_idle - 1.0) * 100.0
        links: dict[str, LinkModel] = {}
        measured: dict[str, dict] = {"quantize_s_per_byte": quant,
                                     "recompute_s_per_byte": recompute}
        if concurrent:
            measured["concurrent"] = {
                "quantize_s_per_byte_idle": quant_idle,
                "quantize_s_per_byte_busy": quant,
                "delta_pct": concurrent_delta}
        for axis, n in sizes.items():
            if n < 2:
                links[axis] = LinkModel(alpha_s=0.0, beta_s_per_byte=0.0)
                continue
            obs: list[tuple[float, float, float]] = []
            raw: dict[str, dict] = {}
            for algo in algos:
                raw[algo] = {}
                for b in payload_bytes:
                    t = _time_axis_collective(mesh, axis, b, algo,
                                              inner=inner, reps=reps)
                    launches, wire_per_byte = _algo_factors(algo, n)
                    obs.append((launches, wire_per_byte * b, t))
                    raw[algo][str(b)] = t
            links[axis] = dataclasses.replace(fit_alpha_beta(obs),
                                              quant_s_per_byte=quant)
            measured[axis] = raw
    tel = telemetry.active()
    if tel is not None:
        # calibration on the unified timeline (round 13): when, how
        # long, and which links it fitted
        tel.span_at("autotune_calibrate", t0, time.perf_counter() - t0,
                    phase="autotune", axes=sizes,
                    links={a: {"alpha_s": l.alpha_s,
                               "beta_s_per_byte": l.beta_s_per_byte,
                               "quant_s_per_byte": l.quant_s_per_byte}
                           for a, l in links.items()})
    return TopologyProfile(
        version=PROFILE_VERSION,
        device_kind=getattr(jax.devices()[0], "device_kind", "cpu"),
        axes=sizes, links=links,
        source="calibrated:concurrent" if concurrent else "calibrated",
        measured=measured, recompute_s_per_byte=recompute,
        concurrent_delta_pct=concurrent_delta)


def get_profile(spec, axes: dict[str, int], *, cache_dir: str | None = None,
                calibrate_kwargs: dict | None = None) -> TopologyProfile:
    """Resolve a profile for ``axes`` from ``spec``:

    - a ``TopologyProfile``: used as-is (axes must match — a forced
      profile for the wrong topology would silently mis-steer);
    - a synthetic preset name (``SYNTHETIC_PRESETS``);
    - a path to a profile JSON (version/axes-checked, loudly);
    - ``None``: the cached profile for this (device kind, topology), or
      a fresh calibration over a throwaway mesh, saved back to the cache.
    """
    if isinstance(spec, TopologyProfile):
        if spec.axes != dict(axes):
            raise ValueError(
                f"injected profile is for topology {spec.axes}, the config "
                f"needs {dict(axes)} — refusing to choose from the wrong "
                f"links")
        return spec
    if isinstance(spec, str):
        if spec in SYNTHETIC_PRESETS:
            return synthetic_profile(spec, axes)
        if os.path.exists(spec):
            with open(spec) as f:
                d = json.load(f)
            if int(d.get("version", -1)) != PROFILE_VERSION:
                raise ValueError(
                    f"profile {spec} has version {d.get('version')}, this "
                    f"build needs {PROFILE_VERSION} — recalibrate")
            p = TopologyProfile.from_json(d)
            if p.axes != dict(axes):
                raise ValueError(
                    f"profile {spec} is for topology {p.axes}, the config "
                    f"needs {dict(axes)}")
            return p
        raise ValueError(
            f"autotune profile {spec!r} is neither a synthetic preset "
            f"({sorted(SYNTHETIC_PRESETS)}) nor an existing profile file")
    import jax

    kind = getattr(jax.devices()[0], "device_kind", "cpu")
    cached = load_profile(kind, axes, cache_dir)
    if cached is not None:
        return cached
    from .mesh import make_mesh

    n = int(np.prod(list(axes.values())))
    mesh = make_mesh(n, axis_names=tuple(axes),
                     axis_shape=tuple(axes.values()))
    prof = calibrate(mesh, **(calibrate_kwargs or {}))
    save_profile(prof, cache_dir)
    return prof


# ---------------------------------------------------------------------------
# grad census


# the ONE shapes-only stand-in for bucket planning (defined next to
# make_bucket_plan; lm.py's EF-residual sizing shares it)
_SizedLeaf = strat.SizedLeaf


@dataclass(frozen=True)
class GradCensus:
    """Byte census of a gradient pytree: per-leaf (element count, dtype)
    in flatten order — everything the bucket planner and the cost model
    need, nothing device-resident."""

    leaves: tuple[_SizedLeaf, ...]

    @property
    def total_bytes(self) -> int:
        return sum(l.size * l.dtype.itemsize for l in self.leaves)

    @property
    def n_leaves(self) -> int:
        return len(self.leaves)

    def bucket_plan(self, bucket_bytes: int) -> list[int]:
        """Per-bucket byte sizes under the REAL reverse-topo packing
        (the one plan every strategy shares)."""
        plan = strat.make_bucket_plan(list(self.leaves), bucket_bytes)
        return [sum(self.leaves[i].size * self.leaves[i].dtype.itemsize
                    for i in b) for b in plan]


def grad_census(tree) -> GradCensus:
    """Census of ``tree`` (arrays OR ShapeDtypeStructs, e.g. from
    ``jax.eval_shape`` — no device work)."""
    import jax

    leaves = jax.tree.leaves(tree)
    return GradCensus(tuple(
        _SizedLeaf(int(np.prod(l.shape, dtype=np.int64) or 1),
                   np.dtype(l.dtype)) for l in leaves))


# ---------------------------------------------------------------------------
# the cost model


@dataclass(frozen=True)
class AxisPlan:
    """One mesh axis' share of a candidate plan: the algorithm label,
    launch count, predicted operand-payload bytes per step (the
    inspector-comparable number), and predicted milliseconds."""

    axis: str
    algorithm: str
    launches: int
    predicted_bytes: int
    predicted_ms: float


@dataclass(frozen=True)
class SyncPlan:
    """The chooser's output: a resolved named strategy + knobs, with the
    prediction that justified it.  ``predicted_ms`` is the EXPOSED
    per-step sync time (wire hidden under backward compute is
    discounted when ``overlap``); ``per_axis`` carries the raw totals.

    ``sync_every`` (round 18) is the chosen local-SGD window: the slow
    hop runs once per ``sync_every`` steps, so ``predicted_ms`` is the
    AMORTIZED per-step figure (dcn term divided by the window) when the
    interval is > 1; ``per_axis`` stays per-EXCHANGE so the dcn row
    remains comparable to the inspector's boundary-step bytes.

    ``route`` (round 20) is the declarative hop-graph this plan
    executes, in ``parallel/routing`` grammar (e.g. ``ici:rs →
    dcn:ring[int8+ef] → ici:ag``) — attached to every 2-level plan the
    legacy choosers emit and to the routed plans ``choose_sync_plan``
    searches; ``per_hop`` carries one cost row per hop (AxisPlan with
    the hop label in ``axis``) for plans priced by the route model."""

    strategy: str
    bucket_mb: float
    dcn_compress: str | None
    dcn_size: int
    overlap: bool
    predicted_ms: float
    per_axis: tuple[AxisPlan, ...]
    profile_source: str
    census_bytes: int
    sync_every: int = 1
    route: str = ""
    per_hop: tuple = ()
    # Round 22 (the WAN/DiLoCo dimension): ``outer_opt`` is the chooser's
    # boundary-update recommendation (None = plain mean; "nesterov" when
    # a ≥3-tier route widened an interval — wide windows want outer
    # momentum, the measured convergence-band claim);
    # ``interval_by_hop`` records the per-tier sync interval assignment
    # as sorted (axis, H) pairs — ``sync_every`` stays the BASE (minimum)
    # interval, what the trainer's window cadence compiles to, and the
    # slower tiers' wider H map to per-slice windows.
    outer_opt: str | None = None
    interval_by_hop: tuple = ()

    def axis(self, name: str) -> AxisPlan | None:
        for ap in self.per_axis:
            if ap.axis == name:
                return ap
        return None

    def summary(self) -> dict:
        """Compact JSON-able form (the bench's train_autotune_plan)."""
        out = {"strategy": self.strategy, "bucket_mb": self.bucket_mb,
               "dcn_compress": self.dcn_compress,
               "dcn_size": self.dcn_size, "overlap": self.overlap,
               "sync_every": self.sync_every,
               "predicted_ms": round(self.predicted_ms, 4),
               "profile": self.profile_source,
               "bytes_by_axis": {ap.axis: ap.predicted_bytes
                                 for ap in self.per_axis}}
        if self.route:
            out["route"] = self.route
        if self.per_hop:
            out["bytes_by_hop"] = {hp.axis: hp.predicted_bytes
                                   for hp in self.per_hop}
        if self.outer_opt is not None:
            out["outer_opt"] = self.outer_opt
        if self.interval_by_hop:
            out["interval_by_hop"] = dict(self.interval_by_hop)
        return out

    def table(self) -> str:
        """Printable explanation: one row per axis + the decision line."""
        lines = [f"SyncPlan: strategy={self.strategy} "
                 f"bucket={self.bucket_mb:g}MB "
                 f"dcn_compress={self.dcn_compress or 'none'} "
                 f"overlap={self.overlap} "
                 f"sync_every={self.sync_every} "
                 f"predicted {self.predicted_ms:.3f} ms/step "
                 f"(grads {self.census_bytes / 1e6:.2f} MB, "
                 f"profile {self.profile_source})",
                 "| axis | algorithm | launches | MB/step | ms |",
                 "|---|---|---|---|---|"]
        for ap in self.per_axis:
            lines.append(
                f"| {ap.axis} | {ap.algorithm} | {ap.launches} | "
                f"{ap.predicted_bytes / 1e6:.2f} | "
                f"{ap.predicted_ms:.3f} |")
        if self.route:
            lines.append(f"route: {self.route}")
        if self.interval_by_hop:
            lines.append("intervals: " + ", ".join(
                f"{a}:H={h}" for a, h in self.interval_by_hop)
                + (f" (outer_opt={self.outer_opt})"
                   if self.outer_opt else ""))
        for hp in self.per_hop:
            lines.append(
                f"|   hop {hp.axis} | {hp.algorithm} | {hp.launches} | "
                f"{hp.predicted_bytes / 1e6:.2f} | "
                f"{hp.predicted_ms:.3f} |")
        return "\n".join(lines)


def _ring_chunk_elems(elems: int, n: int) -> int:
    """The int8 ring's block-aligned per-device chunk (strategies.
    QuantizedRing._chunk) for an ``elems``-element flat vector."""
    return -(-elems // (n * _RING_BLOCK)) * _RING_BLOCK


def _quant_ring_bytes(elems: int, n: int, compress: str = "int8"
                      ) -> tuple[int, int, int]:
    """(executed ppermute operand bytes, launches, quantize-compute f32
    bytes) of one ``QuantizedRing._ring_sum`` over an n-way axis: the
    reduce-scatter and all-gather scans each run n-1 trips of one
    quantized-chunk ppermute (int8 lanes, or nibble-packed int4 at half
    width) plus one f32 row-scale ppermute.  The third number is the
    f32 traffic through quantize/dequantize (+ pack/unpack at int4)
    those hops cost in COMPUTE — charged at the link's
    ``quant_s_per_byte``."""
    if n < 2:
        return 0, 0, 0
    chunk = _ring_chunk_elems(elems, n)
    overhead = (_INT4_ROW_OVERHEAD if compress == "int4"
                else _INT8_ROW_OVERHEAD)
    hops = 2 * (n - 1)
    return (hops * int(chunk * overhead), hops,
            int(hops * chunk * 4 * _QUANT_PASSES[compress]))


def _two_level_axis_costs(bucket_elems: list[int], n_ici: int, n_dcn: int,
                          compress: str | None) -> dict[str, tuple]:
    """Per-axis (operand bytes, launches, wire bytes, quantize-compute
    bytes) of the two-level reduction over the given f32 bucket element
    counts: reduce-scatter over the fast axis, shard exchange over the
    slow one (stock psum or the int8/int4 ring), gather back
    (all_gather_invariant)."""
    ici_bytes = ici_wire = dcn_bytes = dcn_wire = dcn_quant = 0
    ici_launch = dcn_launch = 0
    for e in bucket_elems:
        padded = e + (-e) % max(n_ici, 1)
        shard = padded // max(n_ici, 1)
        if n_ici > 1:
            # psum_scatter operand: the padded full vector
            ici_bytes += padded * 4
            ici_wire += padded * 4 * (n_ici - 1) // n_ici
            ici_launch += 1
            ici_bytes += shard * 4       # all_gather of the shard
            ici_wire += shard * 4 * (n_ici - 1)
            ici_launch += 1
        if n_dcn > 1:
            if compress in ("int8", "int4"):
                b, l, q = _quant_ring_bytes(shard, n_dcn, compress)
                dcn_bytes += b
                dcn_wire += b
                dcn_launch += l
                dcn_quant += q
            else:
                dcn_bytes += shard * 4
                dcn_wire += 2 * shard * 4 * (n_dcn - 1) // n_dcn
                dcn_launch += 1
    return {"ici": (ici_bytes, ici_launch, ici_wire, 0),
            "dcn": (dcn_bytes, dcn_launch, dcn_wire, dcn_quant)}


def predict_named(name: str, census: GradCensus, profile: TopologyProfile,
                  *, bucket_mb: float = strat.BUCKET_CAP_MB,
                  dcn_compress: str | None = None,
                  overlap: bool = False) -> dict | None:
    """Predicted cost of running ``name`` (a registry strategy, or
    'none') for this census on this profile: ``{"ms_total", "ms_exposed",
    "per_axis": [AxisPlan, ...]}``; None for strategies the model does
    not cover.  ``ms_exposed`` discounts wire time hidden under backward
    compute when ``overlap`` (all but one bucket's wire hides — the
    exposed tail + every launch), and is what the chooser minimizes;
    ``ms_total`` is the undiscounted sum (what a post-backward step
    serializes — scripts/bench_strategies.py's predicted_ms column)."""
    bucket_bytes = int(bucket_mb * 1024 * 1024)
    B = census.total_bytes
    nl = census.n_leaves
    axes = list(profile.axes.items())
    links = profile.links

    def axis_plan(axis, algo, launches, op_bytes, wire, n, qbytes=0):
        link = links[axis]
        ms = (launches * link.alpha_s + wire * link.beta_s_per_byte
              + qbytes * link.quant_s_per_byte) * 1e3
        return AxisPlan(axis=axis, algorithm=algo, launches=int(launches),
                        predicted_bytes=int(op_bytes), predicted_ms=ms)

    per_axis: list[AxisPlan] = []
    n_buckets = 1
    can_overlap = name in ("ddp", "bucketed", "quantized",
                           "quantized_ring", "quantized_ring_ef",
                           "hierarchical")

    if name == "none":
        per_axis = []
    elif name in ("ddp", "bucketed", "all_reduce", "quantized",
                  "gather_scatter_symmetric", "gather_scatter",
                  "quantized_ring", "quantized_ring_ef"):
        # flat strategies: one emitted axis ('data'); on a factored
        # profile the payload crosses EVERY link at full width, so the
        # time sums the per-link costs while the operand bytes stay one
        # row (the emitted program has one axis).
        if name == "ddp":
            algo, op_bytes, launches, wire_f = "flat fused psum", B, 1, 2.0
        elif name == "bucketed":
            sizes = census.bucket_plan(bucket_bytes)
            n_buckets = len(sizes)
            algo, op_bytes, launches, wire_f = ("flat bucketed psum", B,
                                                n_buckets, 2.0)
        elif name == "all_reduce":
            algo, op_bytes, launches, wire_f = ("per-leaf sequential psum",
                                                B, nl, 2.0)
        elif name == "quantized":
            # pmax (scalar) + int32 psum per leaf: full-width wire
            algo, op_bytes, launches, wire_f = ("per-leaf int32 psum", B,
                                                2 * nl, 2.0)
        elif name == "gather_scatter_symmetric":
            # all_gather(leaf) + psum(leaf) per leaf
            algo, op_bytes, launches, wire_f = ("all_gather + masked psum",
                                                2 * B, 2 * nl, 3.0)
        elif name == "gather_scatter":
            n_tot = int(np.prod([s for _, s in axes]))
            algo = "rank-0 gather/scatter (ppermute)"
            op_bytes = 2 * (n_tot - 1) * B
            launches = 2 * (n_tot - 1) * nl
            wire_f = 2.0 * (n_tot - 1)
        else:  # the int8 rings
            sizes = census.bucket_plan(bucket_bytes)
            n_buckets = len(sizes)
            n_tot = int(np.prod([s for _, s in axes]))
            op_bytes = launches = qb = 0
            for b in sizes:
                bb, ll, qq = _quant_ring_bytes(b // 4, n_tot)
                op_bytes += bb
                launches += ll
                qb += qq
            algo = "int8 ring reduce-scatter/all-gather"
            wire_f = None  # wire == operand bytes for ppermute payloads
        # time: cross every link of the profile at the strategy's width
        ms = 0.0
        for axis, n in axes:
            if n < 2:
                continue
            link = links[axis]
            if wire_f is None:
                wire = op_bytes
            elif name == "gather_scatter":
                wire = 2.0 * (np.prod([s for _, s in axes]) - 1) * B
            else:
                wire = wire_f / 2.0 * 2.0 * B * (n - 1) / n
            ms += (launches * link.alpha_s
                   + wire * link.beta_s_per_byte) * 1e3
        if name in ("quantized_ring", "quantized_ring_ef"):
            # quantize COMPUTE happens once per hop on the device, not
            # per link crossed — charge it once, at the rate of the
            # slowest active quantizer
            ms += qb * max((links[a].quant_s_per_byte
                            for a, s in axes if s > 1), default=0.0) * 1e3
        emitted = "data" if len(axes) > 1 or axes[0][0] == "data" \
            else axes[0][0]
        per_axis = [AxisPlan(axis=emitted, algorithm=algo,
                             launches=int(launches),
                             predicted_bytes=int(op_bytes),
                             predicted_ms=ms)]
    elif name == "hierarchical":
        # the two-level reduction: slow hop is the 'dcn' axis, the fast
        # hop is whatever inner axis the profile carries ('ici' on the
        # VGG factored mesh, 'data' on the LM multislice mesh)
        sizes = {a: s for a, s in axes}
        fast = next((a for a, _ in axes if a != "dcn"), "ici")
        n_dcn, n_fast = sizes.get("dcn", 1), sizes.get(fast, 1)
        if overlap or dcn_compress in ("int8", "int4"):
            bucket_elems = [b // 4 for b in census.bucket_plan(bucket_bytes)]
        else:
            # the post-backward plain path flattens the WHOLE tree once
            bucket_elems = [B // 4]
        n_buckets = len(bucket_elems)
        costs = _two_level_axis_costs(bucket_elems, n_fast, n_dcn,
                                      dcn_compress)
        for axis, row in (("dcn", costs["dcn"]), (fast, costs["ici"])):
            ob, la, wi, qb = row
            algo = (f"{dcn_compress} ring exchange" if axis == "dcn"
                    and dcn_compress in ("int8", "int4") else
                    "shard-sized psum" if axis == "dcn" else
                    "reduce-scatter + gather")
            per_axis.append(axis_plan(axis, algo, la, ob, wi,
                                      sizes.get(axis, 1), qbytes=qb))
    else:
        return None

    ms_total = sum(ap.predicted_ms for ap in per_axis)
    launch_ms = sum(ap.launches * links.get(
        ap.axis, links[axes[0][0]]).alpha_s for ap in per_axis) * 1e3 \
        if per_axis else 0.0
    if len(axes) > 1 and per_axis and per_axis[0].axis == "data":
        # flat-on-factored: the launch term crossed every link above
        launch_ms = sum(per_axis[0].launches * links[a].alpha_s
                        for a, s in axes if s > 1) * 1e3
    wire_ms = ms_total - launch_ms
    if overlap and can_overlap and n_buckets > 0:
        # all but the last bucket's wire hides under backward compute
        ms_exposed = launch_ms + wire_ms / n_buckets
    else:
        ms_exposed = ms_total
    return {"ms_total": ms_total, "ms_exposed": ms_exposed,
            "per_axis": per_axis, "n_buckets": n_buckets}


# ---------------------------------------------------------------------------
# the route model (round 20): price hop-graphs, not strategy names


def _axis_parts(axis: str, sizes: dict) -> list[tuple[str, int]]:
    """Constituent (link, size) pairs of a hop axis.  Route enumeration
    writes joint axes as 'a+b' (a flat collective over a factored mesh
    crosses every constituent link); single axes pass through."""
    return [(a, int(sizes.get(a, 1))) for a in axis.split("+")]


def price_route(route, census: GradCensus, profile: TopologyProfile, *,
                bucket_mb: float = strat.BUCKET_CAP_MB,
                overlap: bool = False,
                intervals: dict[str, int] | None = None) -> dict:
    """Predicted cost of executing ``route`` (a ``routing.HopPlan``) for
    this census on this profile — the hop-graph generalization of
    ``predict_named``: each hop is priced with its axis' LinkModel
    alpha-beta fit plus the quantize-compute term of ring hops, payloads
    divided by every enclosing reduce-scatter.  Returns ``{"ms_total",
    "ms_exposed", "per_axis", "per_hop", "n_buckets"}`` where
    ``per_hop`` has one AxisPlan per hop (labelled ``axis:algo`` in
    route grammar) and ``per_axis`` aggregates hop rows per mesh axis —
    the inspector-comparable accounting ``plan_bytes_vs_schedule``
    cross-checks.

    ``intervals`` (round 22) prices PER-HOP local-SGD windows: a hop on
    axis ``a`` with ``intervals[a] = H`` runs once per H optimizer
    steps, so its bytes/launch-ms/wire-ms/quantize-ms rows are divided
    by H — the returned figures become amortized per-OPTIMIZER-STEP
    costs (the predicted WAN bytes/optimizer-step table the round-22
    bench pins).  Launch counts stay per-exchange.  Default None is the
    round-20 per-step accounting, untouched."""
    links = profile.links
    sizes = profile.axes
    bucket_bytes = int(bucket_mb * 1024 * 1024)
    if route.compressed or overlap:
        bucket_elems = [b // 4 for b in census.bucket_plan(bucket_bytes)]
    else:
        # the post-backward plain path flattens the whole tree once
        bucket_elems = [census.total_bytes // 4]
    n_buckets = len(bucket_elems)
    # per hop: [op_bytes, launches, launch_ms, wire_ms, quant_ms]
    acc = [[0, 0, 0.0, 0.0, 0.0] for _ in route.hops]
    for e0 in bucket_elems:
        e = e0
        stack: list[tuple[int, int]] = []
        for hi, hop in enumerate(route.hops):
            parts = _axis_parts(hop.axis, sizes)
            n = int(np.prod([ni for _, ni in parts]))
            active = [(a, ni) for a, ni in parts if ni > 1]
            if hop.kind == "a2a":
                raise ValueError(
                    "a2a hops are activation collectives priced by "
                    "choose_moe_plan (capacity census), not by the "
                    "gradient-bucket pricer")
            if hop.kind == "rs":
                padded = e + (-e) % max(n, 1)
                if n > 1 and hop.algorithm == "scatter" and active:
                    acc[hi][0] += padded * 4
                    acc[hi][1] += 1
                    acc[hi][2] += sum(links[a].alpha_s
                                      for a, _ in active) * 1e3
                    acc[hi][3] += sum(
                        padded * 4 * (ni - 1) / ni
                        * links[a].beta_s_per_byte
                        for a, ni in active) * 1e3
                # 'slice' is free: the value is already replicated
                stack.append((padded, n))
                e = padded // max(n, 1)
            elif hop.kind == "exchange":
                if not active:
                    continue  # degraded tier: nothing crosses
                if hop.bits == "f32":
                    acc[hi][0] += e * 4
                    acc[hi][1] += 1
                    acc[hi][2] += sum(links[a].alpha_s
                                      for a, _ in active) * 1e3
                    acc[hi][3] += sum(
                        2 * e * 4 * (ni - 1) / ni
                        * links[a].beta_s_per_byte
                        for a, ni in active) * 1e3
                else:
                    b, l, q = _quant_ring_bytes(e, n, hop.bits)
                    acc[hi][0] += b
                    acc[hi][1] += l
                    acc[hi][2] += l * sum(links[a].alpha_s
                                          for a, _ in active) * 1e3
                    # ppermute payloads cross every constituent link
                    acc[hi][3] += b * sum(links[a].beta_s_per_byte
                                          for a, _ in active) * 1e3
                    acc[hi][4] += q * max(links[a].quant_s_per_byte
                                          for a, _ in active) * 1e3
            else:  # 'ag'
                padded, n2 = stack.pop()
                if n2 > 1 and active:
                    acc[hi][1] += 1
                    acc[hi][2] += sum(links[a].alpha_s
                                      for a, _ in active) * 1e3
                    acc[hi][0] += e * 4
                    acc[hi][3] += sum(
                        e * 4 * (ni - 1)
                        * links[a].beta_s_per_byte
                        for a, ni in active) * 1e3
                e = padded
    if intervals:
        # amortize each hop's per-exchange cost over its window: H
        # optimizer steps share one exchange on this tier (launch
        # counts stay per-exchange — they describe the boundary
        # program, not the per-step average)
        for hi, hop in enumerate(route.hops):
            h = intervals.get(hop.axis, 1)
            if h > 1:
                ob, la, lm, wm, qm = acc[hi]
                acc[hi] = [ob / h, la, lm / h, wm / h, qm / h]
    per_hop: list[AxisPlan] = []
    by_axis: dict[str, list[float]] = {}
    for hop, (ob, la, lm, wm, qm) in zip(route.hops, acc):
        ms = lm + wm + qm
        per_hop.append(AxisPlan(
            axis=hop.describe(), algorithm=f"{hop.kind}/{hop.algorithm}",
            launches=int(la), predicted_bytes=int(ob), predicted_ms=ms))
        row = by_axis.setdefault(hop.axis, [0, 0, 0.0, []])
        row[0] += int(ob)
        row[1] += int(la)
        row[2] += ms
        row[3].append(hop.describe().split(":", 1)[1])
    per_axis = [AxisPlan(axis=a, algorithm="+".join(r[3]),
                         launches=int(r[1]), predicted_bytes=int(r[0]),
                         predicted_ms=r[2])
                for a, r in by_axis.items()]
    ms_total = sum(hp.predicted_ms for hp in per_hop)
    launch_ms = sum(a[2] for a in acc)
    if overlap and n_buckets > 0:
        # all but the last bucket's wire hides under backward compute
        ms_exposed = launch_ms + (ms_total - launch_ms) / n_buckets
    else:
        ms_exposed = ms_total
    return {"ms_total": ms_total, "ms_exposed": ms_exposed,
            "per_axis": per_axis, "per_hop": per_hop,
            "n_buckets": n_buckets}


def _route_label(name: str, compress: str | None,
                 profile: TopologyProfile) -> str:
    """The route-grammar description of a NAMED strategy choice — how
    the legacy choosers' outputs read as hop-graphs (the 2-level plans
    are literally executed through ``parallel/routing`` now)."""
    axes = list(profile.axes)
    flat = "+".join(axes) if len(axes) > 1 else (axes[0] if axes
                                                 else "data")
    x = f"ring[{compress}+ef]" if compress else "psum"
    if name == "hierarchical":
        fast = next((a for a in axes if a != "dcn"), "ici")
        if "dcn" in profile.axes:
            return f"{fast}:rs → dcn:{x} → {fast}:ag"
        return f"{fast}:rs → {fast}:ag"
    if name.startswith("two_level"):
        return f"data:rs → dcn:{x} → data:ag"
    if name in ("ddp", "bucketed", "flat_autodiff_psum"):
        return f"{flat}:psum"
    if name in ("quantized_ring", "quantized_ring_ef"):
        return f"{flat}:ring[int8+ef]"
    return ""


def choose_sync_plan(census: GradCensus, profile: TopologyProfile, *,
                     ladder: tuple = BUCKET_LADDER_MB,
                     overlap: bool = False,
                     max_sync_every: int = 1,
                     steps_per_loop: int | None = None) -> SyncPlan:
    """The route chooser (round 20): enumerate every hop-graph over the
    profile's axes (``routing.enumerate_routes`` — flat, every 2-level
    split, and the nested/sequential 3-level shapes on ≥3-level meshes,
    each at every slow-hop precision), price each with
    ``price_route`` at every ladder bucket size, and return the
    cheapest as an explainable routed ``SyncPlan`` (``route`` +
    ``per_hop`` populated).  Axes are ordered fastest→slowest by fitted
    inverse bandwidth, so 'nested' always reduces over the cheap links
    first.  Candidate order breaks exact ties toward the simpler route
    (enumeration emits flat, then 2-level, then 3-level).  Local-SGD
    amortization (``max_sync_every``) widens the window against the
    SLOWEST tier's hop cost — the 3-level generalization of round 18's
    dcn rule.  Deterministic given a profile (test-pinned on
    ``uniform``/``wan_dcn``/``ici_dcn_wan``)."""
    from . import routing

    fast_first = tuple(sorted(
        profile.axes,
        key=lambda a: (profile.links[a].beta_s_per_byte,
                       profile.links[a].alpha_s, a)))
    slowest = fast_first[-1]
    best: SyncPlan | None = None
    for route in routing.enumerate_routes(fast_first):
        for mb in ladder:
            pred = price_route(route, census, profile, bucket_mb=mb,
                               overlap=overlap)
            ring_bits = [h.bits for h in route.hops
                         if h.kind == "exchange" and h.bits != "f32"]
            plan = SyncPlan(
                strategy="routed", bucket_mb=mb,
                dcn_compress=ring_bits[-1] if ring_bits else None,
                dcn_size=profile.axes.get("dcn", 1), overlap=overlap,
                predicted_ms=pred["ms_exposed"],
                per_axis=tuple(pred["per_axis"]),
                profile_source=profile.source,
                census_bytes=census.total_bytes,
                route=route.describe(), per_hop=tuple(pred["per_hop"]))
            if max_sync_every > 1 and len(fast_first) >= 3:
                # round 22: ≥3-tier meshes price the interval PER HOP
                # (dcn H × wan H), with the outer-opt recommendation
                plan = _route_intervals(
                    plan, route, census, profile, max_sync_every,
                    overlap=overlap, fast_first=fast_first,
                    align=steps_per_loop)
            else:
                plan = _interval_for(plan, max_sync_every,
                                     align=steps_per_loop,
                                     slow_axis=slowest)
            if best is None or plan.predicted_ms < best.predicted_ms - 1e-12:
                best = plan
    assert best is not None
    _emit_plan(best, side="routed")
    return best


# ---------------------------------------------------------------------------
# the MoE dispatch chooser (round 21)


def _a2a_row_bytes(d: int, bits: str) -> float:
    """Wire bytes one d-element f32 token row occupies on the expert
    all-to-all at ``bits`` — the routed executor's exact format: f32 is
    full-width; int8/int4 ship the quantized lanes (nibble pairs at
    int4) plus the row's f32 scale bitcast onto the same row."""
    if bits == "f32":
        return 4.0 * d
    if bits == "int8":
        return d + 4.0
    if bits == "int4":
        return d / 2.0 + 4.0
    raise ValueError(f"unknown dispatch bits {bits!r}")


@dataclass(frozen=True)
class MoePlan:
    """The MoE dispatch chooser's explainable output: which wire width
    the expert all-to-alls run at, why (every candidate priced in
    ``per_bits``), and the predicted wire bytes the accounting
    inspectors (``plan_bytes_vs_schedule(by_hop=True)``) hold the
    compiled program to.  ``sync_every`` exists for inspector API parity
    with :class:`SyncPlan` (dispatch runs every step)."""

    dispatch_bits: str
    axis: str
    predicted_ms: float
    per_bits: tuple = ()         # one priced AxisPlan row per candidate
    per_hop: tuple = ()          # the chosen row(s), inspector-comparable
    per_axis: tuple = ()         # alias of per_hop (axis-level view)
    profile_source: str = ""
    dispatch_bytes: int = 0      # per-step wire bytes at the chosen width
    sync_every: int = 1
    route: str = ""              # 'expert:a2a@<bits>'

    def summary(self) -> dict:
        return {
            "dispatch_bits": self.dispatch_bits, "axis": self.axis,
            "predicted_ms": round(self.predicted_ms, 4),
            "dispatch_bytes": self.dispatch_bytes, "route": self.route,
            "profile_source": self.profile_source,
            "bytes_by_bits": {p.axis: p.predicted_bytes
                              for p in self.per_bits},
            "ms_by_bits": {p.axis: round(p.predicted_ms, 4)
                           for p in self.per_bits},
        }

    def table(self) -> str:
        rows = ["| dispatch | wire bytes/step | predicted ms |",
                "|---|---|---|"]
        for p in self.per_bits:
            pick = (" ←" if p.axis.rsplit("@", 1)[1] == self.dispatch_bits
                    else "")
            rows.append(f"| {p.axis} | {p.predicted_bytes} | "
                        f"{p.predicted_ms:.4f}{pick} |")
        return "\n".join(rows)


def choose_moe_plan(profile: TopologyProfile, *, axis: str, tokens: int,
                    d_model: int, n_experts: int,
                    capacity_factor: float = 2.0, top_k: int = 1,
                    bits_options: tuple = ("f32", "int8"),
                    a2a_per_step: int = 4) -> MoePlan:
    """Price the expert dispatch/combine all-to-alls over ``profile``'s
    ``axis`` link at every candidate wire width and return the cheapest
    as an explainable :class:`MoePlan` (round 21).

    The census is the MoE layer's own capacity arithmetic: each step
    moves the full ``(E, C, D)`` buffer — ``E * C`` rows of
    ``_a2a_row_bytes(d_model, bits)`` with ``C = min(max(1, ceil(T *
    top_k * capacity_factor / E)), T)`` — once per all-to-all, and a
    train step issues ``a2a_per_step`` of them (dispatch + combine
    forward, their transposes backward: 4 per MoE layer; pass 2 to
    price a forward-only program, or scale by the MoE layer count).
    Cost per width follows the calibrated alpha-beta-quant fit:
    ``launches * alpha + wire_bytes * (n-1)/n * beta`` plus — for
    compressed widths — the quantize/dequantize passes over the f32
    payload at the link's ``quant_s_per_byte``, priced at the actual
    width via ``_QUANT_PASSES`` (the round-11 lesson: the wire saving
    is only real if the compute that buys it is in the model).  f32
    wins exact ties (strict-improvement argmin, candidate order) —
    the chooser declines compression on quantize-bound links
    (``quant_bound`` preset) and fast uniform meshes, and takes int8 on
    slow/WAN expert links (matrix pinned in tests/test_a2a.py).  int4
    stays OUT of the default ladder — its routed-token flip rate has
    not cleared the 0.02 gate at small d_model — pass
    ``bits_options=("f32", "int8", "int4")`` to let the pricer consider
    it."""
    import math

    if axis not in profile.axes:
        raise ValueError(
            f"profile has no {axis!r} axis (axes: "
            f"{sorted(profile.axes)}) — calibrate the mesh the experts "
            f"actually shard over")
    n = int(profile.axes[axis])
    link = profile.links[axis]
    cap = min(max(1, math.ceil(tokens * top_k * capacity_factor
                               / n_experts)), tokens)
    rows = n_experts * cap
    wire_factor = (n - 1) / n if n > 1 else 0.0
    per_bits: list[AxisPlan] = []
    for bits in bits_options:
        payload = rows * _a2a_row_bytes(d_model, bits)
        launch_ms = link.alpha_s * 1e3 * a2a_per_step
        wire_ms = (payload * wire_factor * link.beta_s_per_byte
                   * 1e3 * a2a_per_step)
        quant_ms = 0.0
        if bits != "f32":
            quant_ms = (rows * d_model * 4.0 * _QUANT_PASSES[bits]
                        * link.quant_s_per_byte * 1e3 * a2a_per_step)
        per_bits.append(AxisPlan(
            axis=f"{axis}:a2a@{bits}", algorithm="a2a",
            launches=a2a_per_step,
            predicted_bytes=int(payload * a2a_per_step),
            predicted_ms=launch_ms + wire_ms + quant_ms))
    best = per_bits[0]
    for cand in per_bits[1:]:
        if cand.predicted_ms < best.predicted_ms - 1e-12:
            best = cand
    bits = best.axis.rsplit("@", 1)[1]
    # per_hop speaks the PROFILE's (mesh) axis name so the inspector can
    # match the compiled program's collectives; ``route`` speaks the
    # declarative grammar ('expert' tier) like every HopPlan.
    plan = MoePlan(
        dispatch_bits=bits, axis=axis, predicted_ms=best.predicted_ms,
        per_bits=tuple(per_bits), per_hop=(best,), per_axis=(best,),
        profile_source=profile.source, dispatch_bytes=best.predicted_bytes,
        route=f"expert:a2a@{bits}")
    _emit_plan(plan, side="moe")
    return plan


# ---------------------------------------------------------------------------
# the chooser


def _mk_plan(name, pred, *, bucket_mb, dcn_compress, dcn_size, overlap,
             profile, census) -> SyncPlan:
    return SyncPlan(
        strategy=name, bucket_mb=bucket_mb, dcn_compress=dcn_compress,
        dcn_size=dcn_size, overlap=overlap,
        predicted_ms=pred["ms_exposed"],
        per_axis=tuple(pred["per_axis"]),
        profile_source=profile.source, census_bytes=census.total_bytes,
        route=_route_label(name, dcn_compress, profile))


def _route_intervals(plan: SyncPlan, route, census: GradCensus,
                     profile: TopologyProfile, max_sync_every: int, *,
                     overlap: bool, fast_first: tuple,
                     align: int | None = None) -> SyncPlan:
    """Per-TIER interval assignment for ≥3-level routes (round 22, the
    WAN generalization of ``_interval_for``): walking tiers
    fastest→slowest, each slow tier's window H doubles (powers of 2,
    monotone — a slower tier never syncs more often than a faster one)
    while its amortized per-step cost still dominates everything that
    runs more often, then the route re-prices with
    ``price_route(intervals=...)`` so the candidate competes on the
    amortized figure.  The plan's ``sync_every`` becomes the BASE
    (minimum assigned) interval — the trainer's compiled boundary
    cadence — with the wider tiers recorded in ``interval_by_hop`` (the
    per-slice-window recommendation), and ``outer_opt`` set to
    "nesterov": a widened window wants the DiLoCo outer step (the
    measured wider-window-at-matched-quality band,
    tests/test_diloco.py).  ``per_axis`` stays per-exchange, like
    ``_interval_for``."""
    if max_sync_every <= 1:
        return plan
    axis_ms = {ap.axis: ap.predicted_ms for ap in plan.per_axis}
    intervals: dict[str, int] = {}
    h_floor = 1
    for i, a in enumerate(fast_first):
        if i == 0 or axis_ms.get(a, 0.0) <= 0.0:
            continue
        faster = sum(axis_ms[b] / intervals.get(b, 1)
                     for b in fast_first[:i] if b in axis_ms)
        h = h_floor
        while (2 * h <= max_sync_every
               and (align is None or align % (2 * h) == 0)
               and axis_ms[a] / h > faster):
            h *= 2
        if h > 1:
            intervals[a] = h
            h_floor = h
    if not intervals:
        return plan
    pred = price_route(route, census, profile, bucket_mb=plan.bucket_mb,
                       overlap=overlap, intervals=intervals)
    return dataclasses.replace(
        plan, sync_every=min(intervals.values()),
        predicted_ms=pred["ms_exposed"],
        per_hop=tuple(pred["per_hop"]),
        interval_by_hop=tuple(sorted(intervals.items())),
        outer_opt="nesterov")


def _interval_for(plan: SyncPlan, max_sync_every: int,
                  *, align: int | None = None,
                  slow_axis: str = "dcn") -> SyncPlan:
    """Attach the local-SGD interval dimension (round 18) to a candidate
    plan: widen the window H (powers of 2, up to ``max_sync_every``)
    while the slow hop's AMORTIZED cost still dominates the per-step
    fast-hop cost — once dcn/H drops at or below the ici term, further
    widening shrinks an already-subdominant term while the staleness
    risk keeps growing, so the admission rule stops there.  Plans
    without a dcn row (flat strategies, single-slice meshes) never
    widen: local-SGD windows only attach to the two-level family
    (``strategies.require_sync_window``).  ``align`` (the VGG trainer's
    ``steps_per_loop``) constrains H to divide it, so every compiled
    dispatch ends on a window boundary.  ``predicted_ms`` becomes the
    amortized per-step figure; the per-axis rows stay per-exchange."""
    if max_sync_every <= 1:
        return plan
    dcn = plan.axis(slow_axis)
    if dcn is None or dcn.predicted_ms <= 0.0:
        return plan
    ici_ms = sum(ap.predicted_ms for ap in plan.per_axis
                 if ap.axis != slow_axis)
    h = 1
    while (2 * h <= max_sync_every
           and (align is None or align % (2 * h) == 0)
           and dcn.predicted_ms / h > ici_ms):
        h *= 2
    if h == 1:
        return plan
    # the raw dcn row now bills once per H steps; the exposed figure
    # keeps whatever overlap discount the base prediction already took,
    # minus the amortized share of the slow hop
    amortized = max(plan.predicted_ms
                    - dcn.predicted_ms * (1.0 - 1.0 / h), 0.0)
    return dataclasses.replace(plan, sync_every=h, predicted_ms=amortized)


def choose_train_plan(census: GradCensus, profile: TopologyProfile, *,
                      dcn_size: int = 1, overlap: bool = False,
                      max_sync_every: int = 1,
                      steps_per_loop: int | None = None,
                      ladder: tuple = BUCKET_LADDER_MB) -> SyncPlan:
    """Pick the VGG trainer's sync plan: flat fused psum (``ddp``) vs
    bucketed psum vs the int8+EF ring on flat topologies; flat psum vs
    two-level (``hierarchical``) with an optional int8 or int4 DCN hop
    on factored ones — each at every ``ladder`` bucket size — by
    minimum predicted exposed sync time.  Pure function of its arguments
    (deterministic given a profile; candidate order breaks exact ties
    toward the simpler plan).  A caller with a pinned bucket size
    passes a one-rung ladder so the recorded prediction describes the
    config that will actually run.

    ``max_sync_every`` (round 18, default 1 so relaxation stays opt-in)
    lets the two-level candidates amortize their slow hop over a
    local-SGD window (``_interval_for``): candidates compete on the
    AMORTIZED per-step figure, so a windowed hierarchical plan can beat
    the flat psum a per-step comparison would have picked."""
    factored = dcn_size > 1 and "dcn" in profile.axes
    default_mb = float(ladder[0])
    candidates: list[tuple[str, str | None, float]] = []
    if factored:
        candidates.append(("ddp", None, default_mb))
        for mb in ladder:
            candidates.append(("hierarchical", None, mb))
            candidates.append(("hierarchical", "int8", mb))
            candidates.append(("hierarchical", "int4", mb))
        if overlap:
            for mb in ladder:
                candidates.append(("bucketed", None, mb))
    else:
        candidates.append(("ddp", None, default_mb))
        for mb in ladder:
            candidates.append(("bucketed", None, mb))
            candidates.append(("quantized_ring_ef", None, mb))
    best: SyncPlan | None = None
    for name, compress, mb in candidates:
        pred = predict_named(name, census, profile, bucket_mb=mb,
                             dcn_compress=compress, overlap=overlap)
        if pred is None:
            continue
        plan = _mk_plan(name, pred, bucket_mb=mb, dcn_compress=compress,
                        dcn_size=dcn_size if name == "hierarchical" else 1,
                        overlap=overlap, profile=profile, census=census)
        if name == "hierarchical":
            plan = _interval_for(plan, max_sync_every,
                                 align=steps_per_loop)
        if best is None or plan.predicted_ms < best.predicted_ms - 1e-12:
            best = plan
    assert best is not None
    return best


def choose_lm_plan(census: GradCensus, profile: TopologyProfile, *,
                   dcn_size: int = 1, overlap: bool = False,
                   grad_accum: int = 1, allow_compress: bool = True,
                   max_sync_every: int = 1,
                   ladder: tuple = BUCKET_LADDER_MB) -> SyncPlan:
    """Pick the LM trainer's sync knobs.  The LM data-axis algorithm is
    structurally fixed (autodiff cotangent psums on flat meshes, the
    explicit two-level reduction when ``dcn_size > 1``); what the
    profile decides is the slow-hop compression (none vs int8+EF vs
    int4+EF — ``allow_compress=False`` removes the compressed
    candidates for configs whose step has no sync-state channel, e.g.
    the pipeline paths) and the streaming bucket size.  Deterministic
    given a profile.

    Stated approximation: leaves are costed as if they all ride the
    grouped two-level path; under fsdp the shard-sized leaves skip the
    ici reduce-scatter/gather and ring the shard directly over dcn —
    same dcn magnitude, slightly overstated ici bytes (the per-axis
    BYTE cross-check in debug.assert_plan_bytes_match is scoped to the
    VGG programs, where the prediction is exact).

    ``max_sync_every`` (round 18) admits local-SGD windows on the
    two-level candidates (``_interval_for`` — default 1, opt-in), so a
    WAN-grade dcn hop can amortize over H local steps instead of being
    paid per step."""
    if dcn_size <= 1 or "dcn" not in profile.axes:
        pred = predict_named("ddp", census, profile, overlap=overlap)
        plan = _mk_plan("flat_autodiff_psum", pred,
                        bucket_mb=float(ladder[0]),
                        dcn_compress=None, dcn_size=1, overlap=overlap,
                        profile=profile, census=census)
        return plan
    best: SyncPlan | None = None
    for compress in ((None, "int8", "int4") if allow_compress else (None,)):
        for mb in ladder:
            pred = predict_named("hierarchical", census, profile,
                                 bucket_mb=mb, dcn_compress=compress,
                                 overlap=overlap and grad_accum == 1)
            plan = _mk_plan(
                "two_level" if compress is None
                else f"two_level_{compress}",
                pred, bucket_mb=mb, dcn_compress=compress,
                dcn_size=dcn_size, overlap=overlap,
                profile=profile, census=census)
            plan = _interval_for(plan, max_sync_every)
            if best is None or plan.predicted_ms < best.predicted_ms - 1e-12:
                best = plan
    assert best is not None
    return best


# ---------------------------------------------------------------------------
# the memory chooser (round 17): activation bytes vs recompute seconds


# Rung order = preference under exact price ties: no knob before either
# knob, the streamed head before block remat (it spends one logits
# recompute for a V-sized saving), selective before full (it keeps the
# flash kernel's work).
MEMORY_RUNGS = (
    ("none", "dense"),
    ("none", "chunked"),
    ("selective", "dense"),
    ("selective", "chunked"),
    ("full", "dense"),
    ("full", "chunked"),
)


@dataclass(frozen=True)
class MemoryPlan:
    """The memory chooser's output: which (remat, loss_impl) rung and
    microbatch to run, with the prediction that justified it.
    ``predicted_bytes`` is the accountant's per-microbatch activation
    footprint (utils.memacct — census-verified); ``recompute_ms`` is the
    per-step compute the rung spends re-producing activations, at the
    profile's calibrated rate; ``considered`` carries every rung
    evaluated at the chosen microbatch (auditability, printable via
    ``table()``)."""

    remat: str
    loss_impl: str
    microbatch: int
    n_micro: int
    predicted_bytes: int
    budget_bytes: int
    recompute_ms: float
    profile_source: str
    considered: tuple = ()

    def summary(self) -> dict:
        """Compact JSON-able form (the bench's lm_memory_plan shape)."""
        return {"remat": self.remat, "loss_impl": self.loss_impl,
                "microbatch": self.microbatch, "n_micro": self.n_micro,
                "predicted_bytes": self.predicted_bytes,
                "budget_bytes": self.budget_bytes,
                "recompute_ms": round(self.recompute_ms, 4),
                "profile": self.profile_source}

    def table(self) -> str:
        """Printable explanation: the decision line + one row per rung
        evaluated at the chosen microbatch."""
        lines = [f"MemoryPlan: remat={self.remat} "
                 f"loss_impl={self.loss_impl} "
                 f"microbatch={self.microbatch} (x{self.n_micro}) "
                 f"predicted {self.predicted_bytes / 1e6:.2f} MB of "
                 f"{self.budget_bytes / 1e6:.2f} MB budget, "
                 f"recompute {self.recompute_ms:.3f} ms/step "
                 f"(profile {self.profile_source})",
                 "| remat | loss_impl | MB | recompute ms | fits |",
                 "|---|---|---|---|---|"]
        for remat, li, act, ms, fits in self.considered:
            lines.append(f"| {remat} | {li} | {act / 1e6:.2f} | "
                         f"{ms:.3f} | {'yes' if fits else 'no'} |")
        return "\n".join(lines)


def choose_lm_memory_plan(model, profile: TopologyProfile, *,
                          batch: int, seq: int,
                          memory_budget_bytes: int,
                          dtype_bytes: int = 4,
                          tp: int = 1, sp: int = 1) -> MemoryPlan:
    """Pick the LM trainer's activation-memory knobs: the largest
    microbatch (descending divisors of ``batch``) at which ANY
    (remat, loss_impl) rung's predicted activation footprint
    (``utils.memacct.predict_activation_bytes``) fits
    ``memory_budget_bytes``, then the cheapest fitting rung by
    recompute price — ``predict_recompute_bytes`` charged at the
    profile's calibrated ``recompute_s_per_byte`` (the
    ``quant_s_per_byte`` precedent: both sides of the trade in
    seconds).  Microbatch outranks rung because splitting the batch
    serializes accumulation steps — re-running a forward is cheaper
    than running the whole step twice.  Pure function of its arguments
    (deterministic given a profile; rung order breaks exact ties toward
    the simpler knob).  Refuses loudly when even the smallest
    microbatch at the thriftiest rung overflows the budget."""
    if memory_budget_bytes <= 0:
        raise ValueError(
            f"memory_budget_bytes must be positive, got "
            f"{memory_budget_bytes}")
    from ..utils import memacct

    rate = profile.recompute_s_per_byte
    floor_bytes = None
    for m in sorted((m for m in range(1, batch + 1) if batch % m == 0),
                    reverse=True):
        n_micro = batch // m
        rows = []
        for remat, li in MEMORY_RUNGS:
            act = memacct.predict_activation_bytes(
                model, batch=m, seq=seq, remat=remat, loss_impl=li,
                dtype_bytes=dtype_bytes, tp=tp, sp=sp)
            rec = memacct.predict_recompute_bytes(
                model, batch=m, seq=seq, remat=remat, loss_impl=li,
                dtype_bytes=dtype_bytes, tp=tp, sp=sp)
            ms = rec * n_micro * rate * 1e3
            rows.append((remat, li, act, ms, act <= memory_budget_bytes))
        floor_bytes = min(r[2] for r in rows) if floor_bytes is None \
            else min(floor_bytes, min(r[2] for r in rows))
        fitting = [(r[3], i, r) for i, r in enumerate(rows) if r[4]]
        if not fitting:
            continue
        _, _, (remat, li, act, ms, _) = min(fitting)
        plan = MemoryPlan(
            remat=remat, loss_impl=li, microbatch=m, n_micro=n_micro,
            predicted_bytes=act, budget_bytes=memory_budget_bytes,
            recompute_ms=ms, profile_source=profile.source,
            considered=tuple(rows))
        tel = telemetry.active()
        if tel is not None:
            tel.event("memory_plan", phase="autotune", side="lm",
                      **plan.summary())
        return plan
    raise ValueError(
        f"no (remat, loss_impl, microbatch) configuration fits "
        f"memory_budget_bytes={memory_budget_bytes}: even microbatch=1 "
        f"under remat='full' + loss_impl='chunked' needs "
        f"{floor_bytes} predicted activation bytes "
        f"(model d={model.d_model} L={model.n_layers} "
        f"V={model.vocab_size}, seq={seq}) — raise the budget, shorten "
        f"the sequence, or shard the model further")


# ---------------------------------------------------------------------------
# config resolution (the ``strategy="auto"`` / ``sync_plan="auto"`` entry)


def train_topology_axes(dcn_size: int, n_devices: int) -> dict[str, int]:
    """The link topology a TrainConfig describes: ``dcn_size > 1`` (and
    divisible) factors the fleet into Mesh(('dcn', 'ici')); otherwise
    one flat 'data' link."""
    if dcn_size > 1 and n_devices % dcn_size == 0 and n_devices > dcn_size:
        return {"dcn": dcn_size, "ici": n_devices // dcn_size}
    return {"data": n_devices}


def resolve_train_auto(cfg, *, num_devices: int | None = None):
    """Resolve ``TrainConfig(strategy="auto")``: calibrate-or-load the
    profile (``cfg.autotune_profile`` injects one), census the model's
    grad tree, choose, and return ``(resolved_cfg, SyncPlan)`` — the
    resolved config names an existing strategy plus its knobs, so the
    Trainer routes through the bitwise-pinned named paths unchanged."""
    import jax

    from ..models import vgg

    if cfg.dcn_compress is not None:
        raise ValueError(
            "strategy='auto' resolves dcn_compress itself; an explicit "
            "dcn_compress alongside auto is ambiguous — set one, not "
            "both (a named strategy honors the explicit knob)")
    if cfg.sync_every != 1:
        raise ValueError(
            "strategy='auto' resolves sync_every itself (within "
            "max_sync_every); an explicit sync_every alongside auto is "
            "ambiguous — pin the strategy to pin the window")
    if cfg.outer_opt is not None:
        raise ValueError(
            "strategy='auto' resolves the boundary update itself; an "
            "explicit outer_opt alongside auto is ambiguous — pin the "
            "strategy to pin the outer optimizer")
    n = num_devices if num_devices is not None else len(jax.devices())
    if n < 2:
        plan = SyncPlan(strategy="none", bucket_mb=float(strat.BUCKET_CAP_MB),
                        dcn_compress=None, dcn_size=1, overlap=False,
                        predicted_ms=0.0, per_axis=(),
                        profile_source="single-device", census_bytes=0)
        _emit_plan(plan, side="train")
        return dataclasses.replace(cfg, strategy="none", overlap=False,
                                   dcn_compress=None), plan
    census = grad_census(jax.eval_shape(
        lambda k: vgg.init(k, cfg.model)[0], jax.random.key(0)))
    axes = train_topology_axes(cfg.dcn_size, n)
    profile = get_profile(cfg.autotune_profile, axes)
    # an explicitly pinned bucket size constrains the ladder, so the
    # recorded prediction describes the config that actually runs
    ladder = (BUCKET_LADDER_MB if cfg.overlap_bucket_mb is None
              else (float(cfg.overlap_bucket_mb),))
    # local-SGD windows only run on the non-overlapped window builder
    # (require_sync_window): with overlap on, the interval stays 1
    plan = choose_train_plan(census, profile,
                             dcn_size=axes.get("dcn", 1),
                             overlap=cfg.overlap,
                             max_sync_every=(1 if cfg.overlap
                                             else cfg.max_sync_every),
                             steps_per_loop=cfg.steps_per_loop,
                             ladder=ladder)
    resolved = dataclasses.replace(
        cfg, strategy=plan.strategy,
        dcn_size=plan.dcn_size if plan.strategy == "hierarchical"
        else cfg.dcn_size,
        dcn_compress=plan.dcn_compress,
        sync_every=plan.sync_every,
        outer_opt=plan.outer_opt,
        overlap_bucket_mb=(cfg.overlap_bucket_mb
                           if cfg.overlap_bucket_mb is not None
                           else plan.bucket_mb))
    _emit_plan(plan, side="train")
    return resolved, plan


def _emit_plan(plan: "SyncPlan", *, side: str) -> None:
    """The chosen SyncPlan on the unified timeline (round 13): the
    explainable decision — strategy/bucket/compression + predicted ms —
    as one 'autotune' event, so a run's telemetry records WHY its sync
    path looks the way it does."""
    tel = telemetry.active()
    if tel is not None:
        tel.event("sync_plan", phase="autotune", side=side,
                  **plan.summary())


def lm_topology_axes(cfg) -> dict[str, int]:
    """The LM config's data-sync links: the factored (dcn, data) pair on
    multislice configs, one flat 'data' link otherwise.  (tp/sp/ep axes
    carry activation traffic the sync chooser does not own.)"""
    if cfg.dcn_size > 1:
        return {"dcn": cfg.dcn_size, "data": cfg.dp // cfg.dcn_size}
    return {"data": max(cfg.dp, 1)}


def resolve_lm_auto(cfg):
    """Resolve ``LMTrainConfig(sync_plan="auto")`` into explicit
    ``dcn_compress`` / ``bucket_mb`` knobs (the LM side's tunables);
    returns ``(resolved_cfg, SyncPlan)``."""
    import jax

    from ..models import transformer as tfm

    if cfg.dcn_compress is not None:
        raise ValueError(
            "sync_plan='auto' resolves dcn_compress itself; an explicit "
            "dcn_compress alongside auto is ambiguous — set one, not "
            "both (drop sync_plan to pin the knob by hand)")
    if cfg.sync_every != 1:
        raise ValueError(
            "sync_plan='auto' resolves sync_every itself (within "
            "max_sync_every); an explicit sync_every alongside auto is "
            "ambiguous — drop sync_plan to pin the window by hand")
    if cfg.outer_opt is not None:
        raise ValueError(
            "sync_plan='auto' resolves the boundary update itself; an "
            "explicit outer_opt alongside auto is ambiguous — drop "
            "sync_plan to pin the outer optimizer by hand")
    census = grad_census(jax.eval_shape(
        lambda k: tfm.init(k, cfg.model), jax.random.key(0)))
    axes = lm_topology_axes(cfg)
    profile = get_profile(cfg.autotune_profile, axes)
    # windows require the windowed step family: no pipeline, no grad
    # accumulation (require_sync_window) — gate the interval dimension
    # rather than choose a plan the trainer would then refuse
    windowable = (cfg.pp == 1 and cfg.grad_accum == 1
                  and cfg.dcn_size > 1)
    plan = choose_lm_plan(
        census, profile, dcn_size=cfg.dcn_size, overlap=cfg.overlap,
        grad_accum=cfg.grad_accum,
        # the pipeline step has no sync-state channel (validate_lm_cfg
        # rejects dcn_compress there): keep int8 out of the candidates
        # instead of choosing a plan the trainer would then refuse
        allow_compress=cfg.pp == 1,
        max_sync_every=cfg.max_sync_every if windowable else 1,
        ladder=(BUCKET_LADDER_MB if cfg.bucket_mb is None
                else (float(cfg.bucket_mb),)))
    resolved = dataclasses.replace(
        cfg, sync_plan=None, dcn_compress=plan.dcn_compress,
        sync_every=plan.sync_every,
        outer_opt=plan.outer_opt,
        bucket_mb=cfg.bucket_mb if cfg.bucket_mb is not None
        else plan.bucket_mb)
    _emit_plan(plan, side="lm")
    return resolved, plan


def resolve_lm_route(cfg):
    """Resolve ``LMTrainConfig(sync_route=...)`` — the hand-pinned
    routed surface (round 21, the round-20 follow-up) — into the
    explicit knobs the LM sync machinery executes; returns
    ``(resolved_cfg, HopPlan)``.

    The same resolve-to-named-knobs mechanism as ``sync_plan='auto'``:
    parse the route (``routing.parse_route``), refuse what the trainer
    cannot run (``strategies.require_lm_route`` — wrong shapes for this
    topology, pp, combining with auto or an explicit dcn_compress),
    and translate the dcn hop's wire format into ``dcn_compress``.
    Round 20 already rebuilt ``_two_level_sync`` on
    ``routing.execute``, so the accepted routes ARE the programs the
    explicit knobs compile — a routed config trains BITWISE-identically
    to the config it names (parser + equivalence pinned in
    tests/test_a2a.py)."""
    from . import routing
    from .strategies import require_lm_route

    plan = routing.parse_route(cfg.sync_route)
    require_lm_route(plan, dcn=cfg.dcn_size > 1, pp=cfg.pp > 1,
                     dcn_compress=cfg.dcn_compress,
                     sync_plan=cfg.sync_plan)
    ring_bits = [h.bits for h in plan.hops
                 if h.kind == "exchange" and h.bits != "f32"]
    resolved = dataclasses.replace(
        cfg, sync_route=None,
        dcn_compress=ring_bits[0] if ring_bits else None)
    tel = telemetry.active()
    if tel is not None:
        tel.event("sync_plan", phase="autotune", side="lm_route",
                  route=plan.describe(),
                  dcn_compress=ring_bits[0] if ring_bits else None)
    return resolved, plan
