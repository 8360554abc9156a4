"""Declarative multi-hop collective routing (round 20).

Rounds 9/13/16/18 each hand-built one point on the communication
lattice — ``two_level_psum``, the int8/int4+EF DCN rings, the
hierarchical local-SGD window exchange — as separate ``_two_level_*``
code paths.  This module replaces the family with ONE compiler: a
collective is a declarative :class:`HopPlan`, an ordered graph of
topology hops, each hop independently choosing

  * **algorithm** — ``psum`` / reduce-scatter+all-gather (``rs``/``ag``
    pair) / chained-ppermute ``ring`` (the compressed exchange);
  * **bits** — ``f32`` / ``int8`` / ``int4`` on ring exchanges;
  * **EF-residual placement** — ``ef=True`` threads an error-feedback
    residual segment through a compressed hop.

``execute`` compiles a plan into exactly the op sequence the hand-built
strategies emitted, so the 2-level routes below are **bitwise ≡** the
round-9/16 implementations (same jaxpr collective census, same EF
invariant ``delivered + psum(residuals) ≡ exact sum`` at every hop
boundary — tests/test_routing.py pins both, and the existing
strategy/LM suites keep pinning the refactored callers):

  * ``hierarchical``                    → ``ici:rs → dcn:psum → ici:ag``
  * ``hierarchical + dcn_compress``     → ``ici:rs → dcn:ring[int8+ef] → ici:ag``
  * LM ``_two_level_sync`` fsdp bucket  → ``dcn:psum`` (leaf mode) or
    ``dcn:ring[bits+ef]``
  * local-SGD ``window_exchange``       → ``ici:slice → dcn:… → ici:ag``

and ≥3-level meshes route for free by nesting, e.g. the WAN plan the
autotuner's ``choose_sync_plan`` picks on the ``ici_dcn_wan`` preset::

    ici:rs → dcn:rs → wan:ring[int4+ef] → dcn:ag → ici:ag

Grammar (``HopPlan.validate``): ``rs``/``ag`` hops pair LIFO like
brackets (each ``ag`` gathers the innermost open ``rs`` axis);
``exchange`` hops act on the current shard anywhere between them; a
mesh axis appears at most once per role.  Re-quantization across hop
boundaries (a ring hop feeding another ring hop) adds one quantization
noise term per compressed hop — modeled in the autotuner's quantize
cost and curve-pinned by the routing tests.

The module is deliberately free of autotune imports (autotune imports
*us* to enumerate and price routes); it leans on
``strategies.QuantizedRing`` for the wire format so the int4 nibble
packing and per-256-row scale layout stay single-sourced.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax

from . import strategies as _strat

PyTree = Any

_BITS = ("f32", "int8", "int4")
_KINDS = ("rs", "exchange", "ag", "a2a")

# The expert-dispatch exchange (kind 'a2a', round 21) runs only over
# the dedicated expert tier: it permutes whole (device, expert, slot)
# token buffers, which is meaningful for exactly one mesh role.  The
# executor (`execute_a2a`) accepts any mesh axis NAME at call time —
# ops/moe.py binds whatever the caller's expert axis is called — but a
# declarative ROUTE must say 'expert' so plans stay topology-tier
# statements like every other hop.
_A2A_AXIS = "expert"


@dataclass(frozen=True)
class Hop:
    """One edge of a sync route.

    kind       'rs' (reduce-scatter over ``axis``), 'exchange'
               (all-reduce of the current shard over ``axis``), 'ag'
               (all-gather back over ``axis`` — must close the matching
               'rs'), or 'a2a' (the expert dispatch/combine all-to-all,
               round 21 — a pure permutation, not a reduction).
    axis       mesh axis name the hop runs over ('expert' for a2a hops
               in declarative routes; :func:`execute_a2a` rebinds the
               concrete mesh axis at call time).
    algorithm  rs: 'scatter' (``psum_scatter``) or 'slice' (take the
               static ``axis_index`` chunk — free when the value is
               already replicated over ``axis``, the local-SGD window
               case).  exchange: 'psum' (one XLA all-reduce) or 'ring'
               (chained-ppermute quantized ring).  ag: 'gather'.
               a2a: 'alltoall' (one ``lax.all_to_all``).
    bits       wire precision of a ring exchange or a2a hop ('f32'
               psum/rs/ag hops are always full-width).
    ef         thread an error-feedback residual through this ring hop
               (consumes/refills one residual segment in plan order).
               Never legal on a2a hops: the all-to-all compresses
               *activations*, whose error leaves the program with the
               step — there is no persistent ledger to feed back into.
    """

    kind: str
    axis: str
    algorithm: str = ""
    bits: str = "f32"
    ef: bool = False

    def __post_init__(self):
        defaults = {"rs": "scatter", "exchange": "psum", "ag": "gather",
                    "a2a": "alltoall"}
        if self.kind not in _KINDS:
            raise ValueError(f"hop kind must be one of {_KINDS}, "
                             f"got {self.kind!r}")
        if not self.algorithm:
            object.__setattr__(self, "algorithm", defaults[self.kind])
        allowed = {"rs": ("scatter", "slice"),
                   "exchange": ("psum", "ring"),
                   "ag": ("gather",),
                   "a2a": ("alltoall",)}[self.kind]
        if self.algorithm not in allowed:
            raise ValueError(
                f"{self.kind} hop over {self.axis!r}: algorithm must be "
                f"one of {allowed}, got {self.algorithm!r}")
        if self.bits not in _BITS:
            raise ValueError(f"bits must be one of {_BITS}, "
                             f"got {self.bits!r}")
        if self.bits != "f32" and not (self.kind == "a2a"
                                       or (self.kind == "exchange"
                                           and self.algorithm == "ring")):
            raise ValueError(
                f"bits={self.bits!r} requires a ring exchange or a2a "
                f"hop; {self.kind}/{self.algorithm} over {self.axis!r} "
                f"is always full-width")
        if self.kind == "a2a" and self.ef:
            raise ValueError(
                f"ef=True on the a2a hop over {self.axis!r}: the "
                f"all-to-all compresses activations, not gradient "
                f"partial sums — quantization error leaves with the "
                f"step, so there is no residual ledger to thread "
                f"(ef is a ring-exchange contract)")
        if self.ef and self.bits == "f32":
            raise ValueError(
                f"ef=True requires a compressed (int8/int4) ring hop; "
                f"the f32 hop over {self.axis!r} drops no bits")

    def describe(self) -> str:
        if self.kind == "rs":
            return (f"{self.axis}:rs" if self.algorithm == "scatter"
                    else f"{self.axis}:slice")
        if self.kind == "ag":
            return f"{self.axis}:ag"
        if self.kind == "a2a":
            return f"{self.axis}:a2a@{self.bits}"
        if self.algorithm == "psum":
            return f"{self.axis}:psum"
        tag = self.bits + ("+ef" if self.ef else "")
        return f"{self.axis}:ring[{tag}]"


@dataclass(frozen=True)
class HopPlan:
    """An ordered, validated hop graph — the declarative sync route."""

    hops: tuple = field(default_factory=tuple)

    def __post_init__(self):
        object.__setattr__(self, "hops", tuple(self.hops))
        self.validate()

    def validate(self) -> None:
        if not self.hops:
            raise ValueError("a HopPlan needs at least one hop")
        stack: list[str] = []
        seen_rs: set[str] = set()
        seen_x: set[str] = set()
        seen_a2a: set[str] = set()
        for hop in self.hops:
            if not isinstance(hop, Hop):
                raise ValueError(f"plan entries must be Hop, got {hop!r}")
            if hop.kind == "rs":
                if hop.axis in seen_rs:
                    raise ValueError(
                        f"axis {hop.axis!r} reduce-scattered twice — each "
                        f"axis gets at most one rs/ag pair")
                seen_rs.add(hop.axis)
                stack.append(hop.axis)
            elif hop.kind == "ag":
                if not stack:
                    raise ValueError(
                        f"ag over {hop.axis!r} with no open rs — rs/ag "
                        f"pair LIFO like brackets")
                if stack[-1] != hop.axis:
                    raise ValueError(
                        f"ag over {hop.axis!r} must close the innermost "
                        f"open rs ({stack[-1]!r}); rs/ag pair LIFO")
                stack.pop()
            elif hop.kind == "a2a":
                if hop.axis != _A2A_AXIS:
                    raise ValueError(
                        f"a2a over {hop.axis!r}: the all-to-all is the "
                        f"expert-dispatch exchange and routes only over "
                        f"the {_A2A_AXIS!r} tier (reduce/gather hops "
                        f"cover every other axis role)")
                if stack:
                    raise ValueError(
                        f"a2a over {hop.axis!r} inside the open rs "
                        f"bracket over {stack!r} — the dispatch "
                        f"exchange permutes whole (expert, slot) token "
                        f"buffers and cannot run on a scattered shard")
                if hop.axis in seen_a2a:
                    raise ValueError(
                        f"axis {hop.axis!r} carries two a2a hops — one "
                        f"a2a hop describes BOTH directions (dispatch "
                        f"and combine ride the same wire format)")
                seen_a2a.add(hop.axis)
            else:
                if hop.axis in seen_x:
                    raise ValueError(
                        f"axis {hop.axis!r} exchanged twice — one "
                        f"exchange hop per axis")
                if hop.axis in stack:
                    raise ValueError(
                        f"exchange over {hop.axis!r} while its rs is "
                        f"still open — an axis is either scattered or "
                        f"exchanged, not both")
                seen_x.add(hop.axis)
        if stack:
            raise ValueError(
                f"unclosed rs over {stack!r} — every rs needs a "
                f"matching ag")
        # NOTE an exchange-free plan is legal: scatter-rs + ag IS the
        # all-reduce over that axis (local_sync's within-slice route).

    # -- derived properties (the strategy-protocol flags) ------------------

    @property
    def compressed(self) -> bool:
        return any(h.bits != "f32" for h in self.hops)

    @property
    def stateful(self) -> bool:
        """Plans with an EF hop carry a residual (the round-9
        quantized_ring_ef sync-state contract)."""
        return any(h.ef for h in self.hops)

    @property
    def vma_opaque(self) -> bool:
        """Ring hops assemble their result from ppermute payloads —
        replicated by construction, not by proof (the round-9 escape
        hatch); slice-rs hops consume replication the type system can't
        see either."""
        return any(h.algorithm in ("ring", "slice") for h in self.hops)

    def axes(self) -> tuple:
        out = []
        for h in self.hops:
            if h.axis not in out:
                out.append(h.axis)
        return tuple(out)

    def describe(self) -> str:
        return " → ".join(h.describe() for h in self.hops)

    def exchange_hops(self) -> tuple:
        return tuple(h for h in self.hops if h.kind == "exchange")

    def mesh_axes(self) -> tuple:
        """The plan's mesh axis names ordered SLOWEST (outermost) first —
        the tier order a ``Mesh`` for this route is built with: exchange
        axes in reverse plan order (the last exchange is the outermost
        tier a sequential route climbs to), then reduce-scatter axes in
        reverse bracket order (the first-opened rs is the innermost
        shard axis).  ``two_level_route('ici', 'dcn')`` → ``('dcn',
        'ici')`` — the trainer's factored-mesh axis order."""
        ex: list = []
        for h in reversed(self.hops):
            if h.kind == "exchange" and h.axis not in ex:
                ex.append(h.axis)
        rs: list = []
        for h in self.hops:
            if h.kind == "rs" and h.axis not in ex and h.axis not in rs:
                rs.append(h.axis)
        # rs axes collected fastest-first (open order); flip to slow->fast
        return tuple(ex + list(reversed(rs)))


# -- route constructors ----------------------------------------------------

def flat_route(axis: str, *, bits: str = "f32", ef: bool = False) -> HopPlan:
    """Single-hop all-reduce over ``axis`` (the ddp / quantized_ring
    point of the lattice)."""
    if bits == "f32":
        return HopPlan((Hop("exchange", axis),))
    return HopPlan((Hop("exchange", axis, algorithm="ring", bits=bits,
                        ef=ef),))


def two_level_route(fast: str, slow: str | None, *,
                    compress: str | None = None,
                    rs_algorithm: str = "scatter") -> HopPlan:
    """The round-9 hierarchical route: reduce-scatter over the fast
    axis, exchange the shard over the slow one (plain psum, or a
    compressed+EF ring under ``compress``), gather back.  ``slow=None``
    degrades to the within-slice route (local_sync)."""
    hops: list[Hop] = [Hop("rs", fast, algorithm=rs_algorithm)]
    if slow is not None:
        if compress is None:
            hops.append(Hop("exchange", slow))
        else:
            hops.append(Hop("exchange", slow, algorithm="ring",
                            bits=compress, ef=True))
    hops.append(Hop("ag", fast))
    return HopPlan(tuple(hops))


def nested_route(axes: tuple, *, compress: str | None = None) -> HopPlan:
    """N-level nested route, fastest axis first: rs down every axis but
    the last, exchange the innermost shard over the slowest axis, gather
    back out.  ``nested_route(('ici','dcn','wan'), compress='int4')`` is
    the ISSUE's example ``ici:rs → dcn:rs → wan:ring[int4+ef] → dcn:ag →
    ici:ag``."""
    if len(axes) < 2:
        return flat_route(axes[0],
                          bits=compress or "f32",
                          ef=compress is not None)
    fast, slow = list(axes[:-1]), axes[-1]
    hops = [Hop("rs", a) for a in fast]
    if compress is None:
        hops.append(Hop("exchange", slow))
    else:
        hops.append(Hop("exchange", slow, algorithm="ring", bits=compress,
                        ef=True))
    hops.extend(Hop("ag", a) for a in reversed(fast))
    return HopPlan(tuple(hops))


def sequential_route(fast: str, slows: tuple,
                     bits_by_axis: dict | None = None) -> HopPlan:
    """One rs/ag pair over ``fast`` with a CHAIN of shard exchanges over
    each slow axis in order (``ici:rs → dcn:… → wan:… → ici:ag``) —
    the re-quantizing multi-hop shape: each compressed exchange
    re-quantizes the previous hop's delivered sum, so noise accumulates
    one term per compressed hop (modeled by the autotuner, curve-pinned
    by tests/test_routing.py)."""
    bits_by_axis = bits_by_axis or {}
    hops = [Hop("rs", fast)]
    for ax in slows:
        bits = bits_by_axis.get(ax, "f32")
        if bits == "f32":
            hops.append(Hop("exchange", ax))
        else:
            hops.append(Hop("exchange", ax, algorithm="ring", bits=bits,
                            ef=True))
    hops.append(Hop("ag", fast))
    return HopPlan(tuple(hops))


def parse_route(route: str) -> HopPlan:
    """Inverse of :meth:`HopPlan.describe`: parse a route string
    (``"ici:rs → dcn:ring[int4+ef] → ici:ag"``; a plain ``"->"``
    separator is accepted too — CLI flags shouldn't require typing an
    arrow glyph) back into a validated ``HopPlan``.  The grammar is
    exactly what ``describe()`` emits: per hop ``axis:op`` with op one
    of ``rs`` / ``slice`` / ``ag`` / ``psum`` /
    ``ring[int8|int4[+ef]]`` / ``a2a@f32|int8|int4`` (the expert
    dispatch exchange — ``expert:a2a@int8`` is the quantized-dispatch
    route, round 21)."""
    hops = []
    for part in route.replace("->", "→").split("→"):
        part = part.strip()
        if not part:
            raise ValueError(f"empty hop in route {route!r}")
        axis, sep, op = part.partition(":")
        if not sep or not axis or not op:
            raise ValueError(
                f"hop {part!r} in route {route!r} is not 'axis:op'")
        if op == "rs":
            hops.append(Hop("rs", axis))
        elif op == "slice":
            hops.append(Hop("rs", axis, algorithm="slice"))
        elif op == "ag":
            hops.append(Hop("ag", axis))
        elif op == "psum":
            hops.append(Hop("exchange", axis))
        elif op.startswith("ring[") and op.endswith("]"):
            tag = op[len("ring["):-1]
            bits, _, ef = tag.partition("+")
            if ef not in ("", "ef"):
                raise ValueError(f"bad ring tag {tag!r} in hop {part!r}")
            hops.append(Hop("exchange", axis, algorithm="ring",
                            bits=bits, ef=ef == "ef"))
        elif op.startswith("a2a@"):
            hops.append(Hop("a2a", axis, bits=op[len("a2a@"):]))
        else:
            raise ValueError(
                f"unknown hop op {op!r} in route {route!r} (want rs, "
                f"slice, ag, psum, ring[int8|int4[+ef]], or "
                f"a2a@f32|int8|int4)")
    return HopPlan(tuple(hops))


def enumerate_routes(axes: tuple, *,
                     compress_options: tuple = (None, "int8", "int4"),
                     ) -> list[HopPlan]:
    """Every candidate route over ``axes`` (fastest → slowest) the
    autotuner prices: the flat joint exchange, every 2-level split, and
    — at ≥3 axes — the nested and sequential 3-level shapes, each at
    every slow-hop precision.  Pure structure: pricing lives in
    autotune (``choose_sync_plan``)."""
    routes: list[HopPlan] = []
    joint = axes[0] if len(axes) == 1 else tuple(axes)
    # flat: one exchange over the joint axis tuple (a flat psum over a
    # multi-axis tuple is what strategy='ddp' emits on a factored mesh)
    if isinstance(joint, str):
        for c in compress_options:
            routes.append(flat_route(joint, bits=c or "f32",
                                     ef=c is not None))
        return routes
    routes.append(HopPlan((Hop("exchange", "+".join(axes)),)))
    # 2-level: rs/ag over a fast prefix (flattened), exchange the rest
    for split in range(1, len(axes)):
        fast = axes[:split]
        slow = axes[split:]
        fast_name = "+".join(fast)
        slow_name = "+".join(slow)
        for c in compress_options:
            routes.append(two_level_route(fast_name, slow_name,
                                          compress=c))
    if len(axes) >= 3:
        for c in compress_options:
            routes.append(nested_route(axes, compress=c))
        # sequential: compress only the slowest hop, or the two slowest
        for c in compress_options:
            if c is None:
                routes.append(sequential_route(axes[0], axes[1:]))
            else:
                routes.append(sequential_route(
                    axes[0], axes[1:], {axes[-1]: c}))
                routes.append(sequential_route(
                    axes[0], axes[1:],
                    {a: c for a in axes[1:]}))
    return routes


# -- residual sizing (the EF sync-state contract) --------------------------

def _elems_after(plan: HopPlan, upto: int, total: int,
                 sizes: dict) -> int:
    """Flat-vector length entering hop ``upto`` of ``plan`` for a
    ``total``-element bucket — each enclosing rs divides (after padding
    to a multiple), exchanges keep the length."""
    elems = total
    for h in plan.hops[:upto]:
        if h.kind == "rs":
            elems = -(-elems // sizes[h.axis])
        elif h.kind == "ag":
            elems = elems * sizes[h.axis]
    return elems


def residual_len(plan: HopPlan, total: int, sizes: dict) -> int:
    """Total EF-residual length one ``total``-element bucket needs under
    ``plan``: each EF ring hop over axis n contributes ``n * ring._chunk``
    of the shard length entering that hop — exactly the round-9
    ``Hierarchical._segments`` / lm ``_bucket_residual_len`` arithmetic
    (``_chunk`` is bits-independent, so the layout is stable across
    int8/int4)."""
    ring = _strat.QuantizedRing()
    out = 0
    for i, h in enumerate(plan.hops):
        if h.kind == "exchange" and h.ef:
            n = sizes[h.axis]
            out += n * ring._chunk(_elems_after(plan, i, total, sizes), n)
    return out


# -- the executor ----------------------------------------------------------

def execute(plan: HopPlan, tree: PyTree, *,
            scale: float | None = None,
            residuals: list | None = None,
            overrides: dict | None = None,
            concat: bool = True):
    """Compile ``plan`` into the executed sync of ``tree`` (a bucket).

    Reproduces the hand-built op sequences exactly — concatenate to one
    f32 vector, pad/scatter per rs hop, exchange, gather, slice back to
    ``total``, apply ``scale``, split to leaf shapes/dtypes — so routed
    2-level plans are bitwise ≡ ``two_level_psum`` (the strategy suites
    pin this transitively; tests/test_routing.py pins it directly).

    ``residuals``: list of EF residual segments, consumed in plan order
    by ``ef=True`` ring hops (lengths per :func:`residual_len`).
    ``overrides``: ``{axis: shard -> summed_shard}`` replaces that
    axis's exchange hop body — the hook the legacy ``dcn_reduce``
    callers (Hierarchical's n_dcn==1 degrade, LM's capture closures)
    plug into.  ``concat=False`` (single plain-psum exchange plans
    only) syncs the leaves as one multi-operand psum without
    flattening — the LM fsdp bucket's per-leaf-vma path.

    Returns ``(synced_tree, new_residuals)`` where ``new_residuals`` is
    the list of refilled EF segments (empty for stateless plans).
    """
    overrides = overrides or {}
    leaves, treedef = jax.tree.flatten(tree)
    if not concat:
        if (len(plan.hops) != 1
                or plan.hops[0].kind != "exchange"
                or plan.hops[0].algorithm != "psum"):
            raise ValueError(
                "concat=False supports only a single plain-psum "
                f"exchange plan, got {plan.describe()!r}")
        synced = lax.psum(leaves, plan.hops[0].axis)
        return jax.tree.unflatten(treedef, synced), []

    flat = jnp.concatenate(
        [g.ravel().astype(jnp.float32) for g in leaves])
    total = flat.size
    cur = flat
    stack: list[tuple[str, int, int]] = []  # (axis, padded_size, n)
    res_iter = iter(residuals or [])
    new_res: list = []
    for hop in plan.hops:
        if hop.kind == "rs":
            n = lax.axis_size(hop.axis)
            padded = jnp.pad(cur, (0, (-cur.size) % n))
            if hop.algorithm == "scatter":
                cur = lax.psum_scatter(padded, hop.axis,
                                       scatter_dimension=0, tiled=True)
            else:  # 'slice': value already replicated over hop.axis
                me = lax.axis_index(hop.axis)
                chunk = padded.size // n
                cur = lax.dynamic_slice(padded, (me * chunk,), (chunk,))
            stack.append((hop.axis, padded.size, n))
        elif hop.kind == "exchange":
            if hop.axis in overrides:
                cur = overrides[hop.axis](cur)
            elif hop.algorithm == "psum":
                cur = lax.psum(cur, hop.axis)
            else:  # quantized ring at hop.bits (+EF when hop.ef)
                n = lax.axis_size(hop.axis)
                ring = _strat.QuantizedRing(
                    bits=4 if hop.bits == "int4" else 8)
                res = next(res_iter) if hop.ef else None
                cur, err_rows = ring._ring_sum(cur, hop.axis, n,
                                               residual=res)
                if hop.ef:
                    new_res.append(err_rows.ravel())
        else:  # 'ag'
            axis, padded_size, n = stack.pop()
            assert axis == hop.axis, "validated plan cannot mismatch"
            cur = _strat._all_gather_inv(cur, hop.axis, axis=0, tiled=True)
    summed = cur[:total]
    if scale is not None:
        summed = summed * scale
    out, offset = [], 0
    for g in leaves:
        out.append(summed[offset:offset + g.size]
                   .reshape(g.shape).astype(g.dtype))
        offset += g.size
    return jax.tree.unflatten(treedef, out), new_res


# -- the expert all-to-all executor (round 21) -----------------------------

def _a2a_quant_exchange(x: jax.Array, axis: str, bits: str) -> jax.Array:
    """One quantized ``lax.all_to_all`` over ``axis`` of a device-major
    ``(n, ...)`` buffer: symmetric rowwise quantization over the last
    (feature) dim — int8 lanes, or the ``QuantizedRing`` nibble packing
    at int4 — with each row's f32 scale bitcast to 4 int8 lanes and
    CONCATENATED onto its payload row, so the scales ride the *same*
    exchange.  One collective either way, same census as f32; the wire
    carries ``d + 4`` (int8) or ``d/2 + 4`` (int4) bytes per d-element
    f32 row instead of ``4d``."""
    levels = 127.0 if bits == "int8" else 7.0
    d = x.shape[-1]
    if bits == "int4" and d % 2:
        raise ValueError(
            f"int4 a2a nibble-packs feature pairs; the trailing (model) "
            f"dim must be even, got {d}")
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(
        jnp.max(jnp.abs(xf), axis=-1, keepdims=True) / levels, 1e-30)
    q = jnp.clip(jnp.round(xf / scale), -levels, levels).astype(jnp.int8)
    if bits == "int4":
        ring = _strat.QuantizedRing(bits=4)
        q = ring._pack(q).reshape(q.shape[:-1] + (d // 2,))
    srows = lax.bitcast_convert_type(scale[..., 0], jnp.int8)  # (..., 4)
    wire = lax.all_to_all(jnp.concatenate([q, srows], axis=-1), axis,
                          split_axis=0, concat_axis=0, tiled=False)
    q_out, s_out = wire[..., :-4], wire[..., -4:]
    scale_out = lax.bitcast_convert_type(s_out, jnp.float32)[..., None]
    if bits == "int4":
        ring = _strat.QuantizedRing(bits=4)
        q_out = ring._unpack(q_out, q_out.shape[:-1] + (d,))
    return (q_out.astype(jnp.float32) * scale_out).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _a2a_wire_q(x: jax.Array, axis: str, bits: str) -> jax.Array:
    return _a2a_quant_exchange(x, axis, bits)


def _a2a_wire_q_fwd(x, axis, bits):
    return _a2a_quant_exchange(x, axis, bits), None


def _a2a_wire_q_bwd(axis, bits, _res, g):
    # all_to_all(split=0, concat=0) is its own transpose — a symmetric
    # block permutation — so the cotangent rides the SAME quantized
    # wire: both directions of the dispatch move low-bit bytes, and
    # quant→dequant is straight-through (activation compression; the
    # round-16 flip-rate gate, not an EF ledger, bounds the damage).
    return (_a2a_quant_exchange(g, axis, bits),)


_a2a_wire_q.defvjp(_a2a_wire_q_fwd, _a2a_wire_q_bwd)


def execute_a2a(hop: Hop, x: jax.Array, *, direction: str,
                axis: str | None = None) -> jax.Array:
    """Execute one direction of the expert all-to-all hop on an MoE
    exchange buffer — the ONE executor both ``ops/moe.py`` directions
    route through (round 21).

    ``direction='dispatch'`` takes the router's ``(E, C, D)`` capacity
    buffer and returns the expert-major ``(E/n, n*C, D)`` buffer each
    device's local experts consume; ``direction='combine'`` is the exact
    inverse trip for the expert outputs.  At ``bits='f32'`` the emitted
    op sequence is literally the hand-built one (reshape → all_to_all →
    moveaxis → reshape), so the routed path is bitwise ≡ and census-≡
    the pre-round-21 ``ops/moe.py``; at int8/int4 the wire payload is
    rowwise-quantized with scales on the same exchange (see
    :func:`_a2a_quant_exchange`) and the backward pass compresses the
    cotangent's wire identically via a ``custom_vjp``.

    ``axis`` rebinds the concrete mesh axis at call time (plans say
    'expert'; the caller's mesh may say 'model' or anything else).
    """
    if hop.kind != "a2a":
        raise ValueError(
            f"execute_a2a wants an a2a hop, got {hop.describe()!r}")
    ax = axis or hop.axis
    n = lax.axis_size(ax)

    def wire(v):
        if hop.bits == "f32":
            return lax.all_to_all(v, ax, split_axis=0, concat_axis=0,
                                  tiled=False)
        return _a2a_wire_q(v, ax, hop.bits)

    if direction == "dispatch":
        e, cap, d = x.shape
        x = wire(x.reshape(n, e // n, cap, d))
        return jnp.moveaxis(x, 0, 1).reshape(e // n, n * cap, d)
    if direction == "combine":
        e_local, ncap, d = x.shape
        x = wire(jnp.moveaxis(x.reshape(e_local, n, ncap // n, d), 1, 0))
        return x.reshape(n * e_local, ncap // n, d)
    raise ValueError(
        f"direction must be 'dispatch' or 'combine', got {direction!r}")


# -- the routed strategy (plug-in protocol, parallel/strategies.py) --------

class RoutedSync:
    """A gradient-sync strategy that executes an arbitrary
    :class:`HopPlan` — the first-class surface for routed plans the
    autotuner's ``choose_sync_plan`` emits (2-level routes keep running
    through ``hierarchical``, whose internals now delegate here too).

    ``axis_map`` renames plan axes to mesh axes at call time (the plan
    speaks topology tiers — 'ici'/'dcn'/'wan' — the mesh speaks whatever
    the trainer named its axes).  Stateless plans drop into the plain
    strategy protocol; EF plans follow the round-9 stateful contract
    (``state_segments``/``init_state``/``(grads, state) -> (synced,
    state)``)."""

    name = "routed"
    needs_mesh = True
    supports_overlap = True

    def __init__(self, plan: HopPlan, *, scale_to_mean: bool = True,
                 bucket_mb: float = _strat.BUCKET_CAP_MB,
                 n_by_axis: dict | None = None):
        self.plan = plan
        self.scale_to_mean = scale_to_mean
        self.bucket_bytes = int(bucket_mb * 1024 * 1024)
        self.stateful = plan.stateful
        self.vma_opaque = plan.vma_opaque
        # mesh axis order the trainer's make_mesh recipe needs (slow
        # tier first — Hierarchical.axes' contract); n_by_axis is the
        # static per-axis extent map trace-free sizing (init_state /
        # state_segments with an int replica count) resolves through —
        # the Trainer binds it from the mesh it builds
        self.axes = plan.mesh_axes()
        self.n_by_axis = dict(n_by_axis) if n_by_axis else None

    def _sizes(self) -> dict:
        return {h.axis: lax.axis_size(h.axis) for h in self.plan.hops}

    def _static_sizes(self, n_by_axis) -> dict:
        if not isinstance(n_by_axis, dict):
            # the round-9 stateful-strategy contract passes the total
            # replica count; the per-axis split comes from the bound map
            if self.n_by_axis is None:
                raise ValueError(
                    "RoutedSync needs its per-axis sizes to size EF "
                    "state from a replica count: pass n_by_axis={axis: "
                    "n} at construction (or call with a dict)")
            n_by_axis = self.n_by_axis
        return {h.axis: int(n_by_axis[h.axis]) for h in self.plan.hops}

    def _scale(self, sizes: dict) -> float | None:
        if not self.scale_to_mean:
            return None
        n = 1
        for ax in self.plan.axes():
            n *= sizes[ax]
        return 1.0 / n

    # -- EF sync-state contract (round 9) ------------------------------

    def state_segments(self, leaves: list, n_by_axis) -> list[int]:
        sizes = self._static_sizes(n_by_axis)
        return [residual_len(self.plan,
                             sum(leaves[i].size for i in b), sizes)
                for b in _strat.make_bucket_plan(leaves,
                                                 self.bucket_bytes)]

    def init_state(self, params: PyTree, n_by_axis) -> jax.Array:
        if not self.stateful:
            return jnp.zeros((0,), jnp.float32)
        leaves = jax.tree.leaves(params)
        return jnp.zeros(
            (sum(self.state_segments(leaves, n_by_axis)),), jnp.float32)

    def sync_bucket(self, leaves: list, residual: jax.Array | None = None):
        sizes = self._sizes()
        res_list = None
        if self.stateful:
            # one residual segment per EF hop, split in plan order
            segs, off = [], 0
            total = sum(int(g.size) for g in leaves)
            ring = _strat.QuantizedRing()
            for i, h in enumerate(self.plan.hops):
                if h.kind == "exchange" and h.ef:
                    n = sizes[h.axis]
                    ln = n * ring._chunk(
                        _elems_after(self.plan, i, total, sizes), n)
                    segs.append(residual[off:off + ln])
                    off += ln
            res_list = segs
        synced, new_res = execute(self.plan, leaves,
                                  scale=self._scale(sizes),
                                  residuals=res_list)
        if not self.stateful:
            return synced
        return synced, (jnp.concatenate(new_res) if new_res
                        else jnp.zeros((0,), jnp.float32))

    def __call__(self, grads: PyTree, axis=None,
                 sync_state: jax.Array | None = None):
        # ``axis`` is the strategy-protocol slot (the trainer passes its
        # data axes); the plan is the authority on which axes each hop
        # runs over, so it is accepted and ignored
        del axis
        leaves, treedef = jax.tree.flatten(grads)
        out: list = [None] * len(leaves)
        if not self.stateful:
            for b in _strat.make_bucket_plan(leaves, self.bucket_bytes):
                synced = self.sync_bucket([leaves[i] for i in b])
                for i, s in zip(b, synced):
                    out[i] = s
            return jax.tree.unflatten(treedef, out)
        sizes = self._sizes()
        new_parts, offset = [], 0
        for b in _strat.make_bucket_plan(leaves, self.bucket_bytes):
            total = sum(int(leaves[i].size) for i in b)
            seg = residual_len(self.plan, total,
                               {a: sizes[a] for a in self.plan.axes()})
            synced, new_r = self.sync_bucket(
                [leaves[i] for i in b],
                sync_state[offset:offset + seg])
            offset += seg
            new_parts.append(new_r)
            for i, s in zip(b, synced):
                out[i] = s
        return (jax.tree.unflatten(treedef, out),
                jnp.concatenate(new_parts) if new_parts
                else jnp.zeros((0,), jnp.float32))
