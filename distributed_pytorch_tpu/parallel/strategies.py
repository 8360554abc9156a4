"""Gradient-synchronization strategies as grad-pytree transforms.

The reference implements each strategy as a distinct copy-pasted script whose
only real delta is ~15 lines between ``loss.backward()`` and
``optimizer.step()`` (SURVEY.md section 0).  Here each strategy is a pure
function ``grads -> synced_grads`` executed *inside* the compiled, shard_mapped
train step, over the named mesh axis:

- ``none``       — identity; the single-process baseline (reference main.py).
- ``all_reduce`` — per-tensor mean via psum, kept sequential with explicit
                   optimization barriers (reference main_all_reduce.py:45-48:
                   34 sequential blocking all_reduces per step).
- ``gather_scatter`` — per-tensor ppermute-to-rank-0 -> mean -> ppermute-out,
                   sequential (reference main_gather.py:42-59: two network
                   crossings per tensor, ALL traffic through rank 0).  This is
                   the deliberately-naive parameter-server baseline, slow for
                   the reference's reason (device 0 is the bandwidth hotspot).
- ``gather_scatter_symmetric`` — same semantics via all_gather + masked psum:
                   no rank-0 hotspot; the ICI-friendly re-expression.
- ``ddp``        — one whole-pytree pmean; XLA's latency-hiding scheduler
                   provides the bucketing/overlap that torch DDP implements in
                   C++ autograd hooks (reference main_ddp.py:137).
- ``bucketed``   — explicit DDP-style gradient bucketing: leaves flattened and
                   packed into ~25 MB buckets, one psum per bucket (torch
                   DDP's default bucket_cap_mb=25), making the overlap
                   measurable and XLA's fusion explicit.

Why barriers: torch dispatches 34 *eager* collectives; XLA would otherwise
fuse them into one — dissolving exactly the contrast these baselines exist to
measure (SURVEY.md section 7.3 "preserving naivety on purpose").  Each leaf's
collective is data-chained to the previous leaf's result with
``lax.optimization_barrier`` so the schedule stays sequential.
"""

from __future__ import annotations

from typing import Any, Callable, Protocol

import jax
import jax.numpy as jnp
from jax import lax

from ..utils.compat import all_gather_invariant as _all_gather_inv

PyTree = Any

BUCKET_CAP_MB = 25  # torch DDP default bucket size


class Strategy(Protocol):
    """A stateless gradient-sync strategy: a pure grad-pytree transform.

    Calling convention (the train step's contract, train.py scan body):
    ``synced = strategy(grads, axis)`` with ``axis`` the mesh axis name the
    collective runs over (a TUPLE of names for factored-axis strategies —
    see ``Hierarchical.axes``), or None outside a mesh ('none' only).

    Optional attributes the trainer consults:

    - ``vma_opaque``: result is replicated by construction but not provably
      so (ppermute-assembled) — the step compiles with ``check_vma=False``
      and re-verifies replication dynamically after each fresh compile.
    - ``axes``: factored mesh axis names this strategy needs.
    - ``supports_overlap`` + ``sync_bucket``: the strategy can run as
      in-backward bucket collectives (``OverlapSync``; train.py
      ``overlap=True``).
    """

    name: str
    needs_mesh: bool

    def __call__(self, grads: PyTree, axis: str) -> PyTree: ...


class StatefulStrategy(Protocol):
    """A gradient-sync strategy carrying per-device state between steps
    (error-feedback residuals).  The train step calls it as

        ``synced, new_state = strategy(grads, axis, sync_state)``

    (train.py scan body), threading ``sync_state`` through the K-step scan
    carry next to BN state; ``init_state(params, n_axis)`` builds the
    per-device zero state (the Trainer stacks it with a leading device
    axis).  Stateless strategies thread a zero-size dummy through the same
    carry slot and are called with the two-argument form above — the
    ``stateful`` attribute (True here, absent/False on ``Strategy``) is
    what selects the calling convention.
    """

    name: str
    needs_mesh: bool
    stateful: bool

    def init_state(self, params: PyTree, n_axis: int) -> jax.Array: ...

    def __call__(self, grads: PyTree, axis: str,
                 sync_state: jax.Array) -> tuple[PyTree, jax.Array]: ...


class SizedLeaf:
    """The two attributes ``make_bucket_plan`` reads (``size`` and
    ``dtype.itemsize``), without a device array — the shared stand-in
    for planning buckets from shapes alone (the autotuner's census,
    lm.py's EF-residual sizing).  Lives here, next to the planner whose
    contract it mirrors, so a change to the planner's leaf requirements
    has ONE stand-in to update."""

    __slots__ = ("size", "dtype")

    def __init__(self, size: int, dtype):
        import numpy as np
        self.size = int(size)
        self.dtype = np.dtype(dtype)


def make_bucket_plan(leaves: list, bucket_bytes: int) -> list[list[int]]:
    """Pack leaf indices into ~``bucket_bytes`` buckets in REVERSE flatten
    order (torch DDP's Reducer packing, reference main_ddp.py:137's engine:
    late-backward/output-side grads fill the first-reduced bucket), the one
    packing shared by ``Bucketed``, the int8 ring strategies, and the
    in-backward overlap markers (``OverlapSync``) — so overlap=True and the
    post-backward path always agree on bucket membership.

    Indices within each bucket are returned ASCENDING (tree order): packing
    order decides membership only, concatenation layout stays the flatten
    order — which keeps the single-bucket case (trees under the cap)
    byte-identical to the historical whole-tree flattening.
    """
    buckets: list[list[int]] = [[]]
    size = 0
    for i in reversed(range(len(leaves))):
        nbytes = leaves[i].size * leaves[i].dtype.itemsize
        if size + nbytes > bucket_bytes and buckets[-1]:
            buckets.append([])
            size = 0
        buckets[-1].append(i)
        size += nbytes
    return [sorted(b) for b in buckets]


def _chain(leaf: jax.Array, token: jax.Array) -> jax.Array:
    """Tie ``leaf`` to ``token`` so its collective cannot be reordered/fused
    with the previous one (emulates the reference's sequential eager
    dispatch)."""
    leaf, _ = lax.optimization_barrier((leaf, token))
    return leaf


class NoSync:
    """Single-process baseline — no communication (reference main.py)."""

    name = "none"
    needs_mesh = False

    def __call__(self, grads: PyTree, axis: str | None = None) -> PyTree:
        return grads


class AllReduce:
    """Per-tensor sequential all-reduce-mean (reference main_all_reduce.py:45-48).

    ``psum / N`` is numerically the reference's sum-then-divide; sequencing
    is forced per tensor to preserve the 34-collectives-per-step structure.
    """

    name = "all_reduce"
    needs_mesh = True

    def __init__(self, sequential: bool = True):
        self.sequential = sequential

    def __call__(self, grads: PyTree, axis: str) -> PyTree:
        n = lax.axis_size(axis)
        leaves, treedef = jax.tree.flatten(grads)
        out = []
        token = jnp.zeros((), jnp.float32)
        for g in leaves:
            if self.sequential:
                g = _chain(g, token)
            g = lax.psum(g, axis) / n
            if self.sequential:
                token = g.ravel()[0].astype(jnp.float32)
            out.append(g)
        return jax.tree.unflatten(treedef, out)


class GatherScatter:
    """Per-tensor gather -> rank-0 mean -> scatter with ALL traffic routed
    through device 0 (reference main_gather.py:42-59).

    Wire-faithful to the reference's parameter-server baseline: for each
    tensor, every rank's gradient crosses to rank 0 (n-1 ``ppermute`` sends,
    all landing on device 0 — the gather, main_gather.py:49), rank 0 means
    them (main_gather.py:53-55), then rank 0 sends the mean back out to each
    rank (n-1 more ``ppermute`` sends, all departing device 0 — the scatter,
    main_gather.py:59).  Two crossings per tensor through rank 0, per-tensor
    sequential: device 0's links are the bandwidth hotspot, so this strategy
    is slow for exactly the reference's reason.  (For the symmetric
    ICI-friendly formulation that dissolves the hotspot, see
    ``gather_scatter_symmetric``.)

    vma note: each rank's result arrives via ``ppermute`` from rank 0 —
    bitwise identical everywhere by construction, but assembled from
    device-varying values the vma checker cannot prove invariant, hence
    ``vma_opaque`` (the trainer compiles this strategy's step with
    ``check_vma=False``, replaces the lost static proof with a one-time
    dynamic replication check after the first step, and tests pin the
    numerics against the exact mean).
    """

    name = "gather_scatter"
    needs_mesh = True
    vma_opaque = True  # replication holds by construction, not by proof

    def __init__(self, sequential: bool = True):
        self.sequential = sequential

    def __call__(self, grads: PyTree, axis: str) -> PyTree:
        n = lax.axis_size(axis)
        idx = lax.axis_index(axis)
        leaves, treedef = jax.tree.flatten(grads)
        out = []
        token = jnp.zeros((), jnp.float32)
        for g in leaves:
            if self.sequential:
                g = _chain(g, token)
            if n == 1:
                out.append(g)
                continue
            # gather (main_gather.py:49): rank r's grad crosses to rank 0.
            # The adds chain the hops, mirroring the synchronous dist.gather;
            # on ranks != 0 each recv is zeros and acc is unused garbage.
            acc = g
            for r in range(1, n):
                acc = acc + lax.ppermute(g, axis, [(r, 0)])
            # rank-0 mean (main_gather.py:53-55): stack-then-mean == sum/n
            mean = acc / n
            # scatter (main_gather.py:59): rank 0 sends the mean to each
            # rank; rank r receives exactly one nonzero payload.
            result = jnp.where(idx == 0, mean, jnp.zeros_like(mean))
            for r in range(1, n):
                result = result + lax.ppermute(mean, axis, [(0, r)])
            if self.sequential:
                token = result.ravel()[0].astype(jnp.float32)
            out.append(result)
        return jax.tree.unflatten(treedef, out)


class GatherScatterSymmetric:
    """The same gather -> rank-0 mean -> broadcast semantics expressed with
    symmetric collectives (``all_gather`` + masked ``psum``): numerically
    identical to ``gather_scatter`` but with no rank-0 hotspot — the
    ICI-friendly form XLA can schedule, kept as the contrast point showing
    what re-expressing the parameter-server pattern buys on a torus."""

    name = "gather_scatter_symmetric"
    needs_mesh = True

    def __init__(self, sequential: bool = True):
        self.sequential = sequential

    def __call__(self, grads: PyTree, axis: str) -> PyTree:
        idx = lax.axis_index(axis)
        leaves, treedef = jax.tree.flatten(grads)
        out = []
        token = jnp.zeros((), jnp.float32)
        for g in leaves:
            if self.sequential:
                g = _chain(g, token)
            # collective 1: gather all replicas' grads (main_gather.py:49)
            gathered = lax.all_gather(g, axis)
            # rank-0 mean (main_gather.py:53-55); other ranks contribute zeros
            mean0 = jnp.where(idx == 0, 1.0, 0.0).astype(g.dtype) * jnp.mean(
                gathered, axis=0)
            # collective 2: broadcast rank 0's mean (scatter, main_gather.py:59)
            g = lax.psum(mean0, axis)
            if self.sequential:
                token = g.ravel()[0].astype(jnp.float32)
            out.append(g)
        return jax.tree.unflatten(treedef, out)


class DDP:
    """Whole-pytree fused pmean — the idiomatic TPU path (reference
    main_ddp.py:137's DistributedDataParallel, minus the C++ machinery: XLA
    sees all 34 reductions at once and schedules/overlaps them itself)."""

    name = "ddp"
    needs_mesh = True
    supports_overlap = True
    bucket_bytes = BUCKET_CAP_MB * 1024 * 1024  # overlap marker grouping only

    def __call__(self, grads: PyTree, axis: str) -> PyTree:
        return jax.tree.map(lambda g: lax.pmean(g, axis), grads)

    def sync_bucket(self, leaves: list, axis: str) -> list:
        # per-leaf pmean: identical ops to __call__, so overlap=True is
        # bitwise-equal to the post-backward path regardless of bucketing
        return [lax.pmean(g, axis) for g in leaves]


class Bucketed:
    """Explicit DDP-style bucketing: pack leaves into ~bucket_mb buckets,
    one psum per bucket (torch DDP's Reducer with bucket_cap_mb=25,
    reference main_ddp.py:137's underlying engine)."""

    name = "bucketed"
    needs_mesh = True
    supports_overlap = True

    def __init__(self, bucket_mb: float = BUCKET_CAP_MB):
        self.bucket_bytes = int(bucket_mb * 1024 * 1024)

    def sync_bucket(self, leaves: list, axis: str) -> list:
        """One packed psum-mean over these leaves (a single bucket).  The
        psum is elementwise over devices, so the result is independent of
        how leaves are packed into buckets — post-backward and overlap
        bucketing agree bitwise whatever the bucket boundaries."""
        n = lax.axis_size(axis)
        flat = jnp.concatenate([g.ravel() for g in leaves])
        flat = lax.psum(flat, axis) / n
        out, offset = [], 0
        for g in leaves:
            out.append(flat[offset:offset + g.size].reshape(g.shape))
            offset += g.size
        return out

    def __call__(self, grads: PyTree, axis: str) -> PyTree:
        leaves, treedef = jax.tree.flatten(grads)
        out: list[jax.Array | None] = [None] * len(leaves)
        for bucket in make_bucket_plan(leaves, self.bucket_bytes):
            synced = self.sync_bucket([leaves[i] for i in bucket], axis)
            for i, s in zip(bucket, synced):
                out[i] = s
        return jax.tree.unflatten(treedef, out)


class QuantizedAllReduce:
    """Int8-quantized gradient all-reduce (the EQuARX/DynamiQ family of
    compressed collectives, e.g. arxiv.org/abs/2506.17615): per-tensor
    symmetric int8 quantization against a cross-replica-shared scale
    (pmax of |g|), integer psum, dequantize, mean.

    Scope note (honest accounting): with XLA's stock collectives the psum
    operand is int32, so the bytes on the wire match an fp32 all-reduce —
    this strategy demonstrates the *numerics* of quantized sync (shared
    scale makes the integer sum exact; only quantization loses precision,
    <1% relative error per tensor) and reserves the API slot.  For true
    wire compression see ``quantized_ring`` below, which moves int8 bytes
    on every hop.
    """

    name = "quantized"
    needs_mesh = True
    supports_overlap = True
    bucket_bytes = BUCKET_CAP_MB * 1024 * 1024  # overlap marker grouping only

    def __init__(self, bits: int = 8):
        self.levels = 2 ** (bits - 1) - 1  # 127 for int8

    def _sync_leaf(self, g: jax.Array, axis: str, n) -> jax.Array:
        g32 = g.astype(jnp.float32)
        absmax = lax.pmax(jnp.max(jnp.abs(g32)), axis)
        scale = jnp.maximum(absmax / self.levels, 1e-30)
        q = jnp.clip(jnp.round(g32 / scale), -self.levels,
                     self.levels).astype(jnp.int8)
        summed = lax.psum(q.astype(jnp.int32), axis)
        return (summed.astype(jnp.float32) * scale / n).astype(g.dtype)

    def __call__(self, grads: PyTree, axis: str) -> PyTree:
        n = lax.axis_size(axis)
        return jax.tree.map(lambda g: self._sync_leaf(g, axis, n), grads)

    def sync_bucket(self, leaves: list, axis: str) -> list:
        # per-leaf quantized all-reduce (the scale is per TENSOR, so the
        # bucket grouping cannot change numerics vs the post-backward path)
        n = lax.axis_size(axis)
        return [self._sync_leaf(g, axis, n) for g in leaves]


class QuantizedRing:
    """Int8 ring all-reduce with TRUE wire compression: a ring
    reduce-scatter followed by a ring all-gather built from ``ppermute``
    hops whose payloads are the int8 tensors themselves (plus one f32
    scale per ``block`` values, ~1.6% overhead).  Unlike ``quantized``
    (which feeds XLA's all_reduce int32, so full-width bytes move), every
    inter-chip transfer here is the quantized byte stream — the DynamiQ/
    EQuARX compressed-collective design point, expressed with JAX
    collectives instead of a custom RDMA kernel.

    Numerics: each reduce-scatter hop requantizes its partial sum, so
    quantization noise accumulates O(sqrt(n)) over the ring (the price of
    per-hop compression; block-wise scales keep the relative error ~1e-2
    at int8).  The all-gather forwards each reduced chunk's int8 payload
    verbatim — no further loss.

    vma note: every device dequantizes identical payloads, so the result
    is bitwise replicated by construction — but it is assembled from
    ``ppermute`` (varying) values, which the vma type system cannot prove
    invariant and there is no sanctioned downcast.  The trainer therefore
    runs this strategy with ``check_vma=False`` (see ``vma_opaque``).
    """

    name = "quantized_ring"
    needs_mesh = True
    vma_opaque = True  # replication holds by construction, not by proof
    supports_overlap = True

    def __init__(self, bits: int = 8, block: int = 256,
                 bucket_mb: float = BUCKET_CAP_MB):
        if bits not in (4, 8):
            raise ValueError(f"bits must be 4 or 8, got {bits}")
        self.bits = bits
        self.levels = 2 ** (bits - 1) - 1  # 127 at int8, 7 at int4
        self.block = block
        # One ring per ~bucket_mb bucket (make_bucket_plan, round 8): the
        # per-hop block scales are computed within each bucket's own flat
        # vector, so the ring's numerics depend on bucket LAYOUT — which is
        # why overlap=True and the post-backward path share one plan (and
        # why trees under the cap, every pre-round-8 test tree included,
        # pack to a single bucket bitwise-identical to the old whole-tree
        # flattening).
        self.bucket_bytes = int(bucket_mb * 1024 * 1024)

    def _plan(self, leaves: list) -> list[list[int]]:
        return make_bucket_plan(leaves, self.bucket_bytes)

    def _chunk(self, total: int, n: int) -> int:
        """Per-device ring chunk (block-aligned) for a ``total``-element
        flat vector over an ``n``-way ring."""
        return -(-total // (n * self.block)) * self.block

    def _quant(self, x: jax.Array):
        xb = x.reshape(-1, self.block)
        scale = jnp.maximum(
            jnp.max(jnp.abs(xb), axis=1, keepdims=True) / self.levels,
            1e-30)
        q = jnp.clip(jnp.round(xb / scale), -self.levels,
                     self.levels).astype(jnp.int8)
        return q, scale.astype(jnp.float32)

    def _dequant(self, q: jax.Array, scale: jax.Array) -> jax.Array:
        return (q.astype(jnp.float32) * scale).ravel()

    # -- int4 wire format (bits=4, round 16) ---------------------------
    # Quantized values live in [-7, 7]; two 4-bit two's-complement
    # nibbles ride per int8 lane on every ppermute, so the slow hop
    # moves HALF the int8 payload bytes.  block=256 keeps every chunk
    # even, so the lane pairing never needs padding.

    def _pack(self, q: jax.Array) -> jax.Array:
        """(..., even) int4-valued int8 -> flat int8 of half the size,
        low nibble first."""
        u = q.reshape(-1, 2).astype(jnp.uint8) & jnp.uint8(0xF)
        return (u[:, 0] | (u[:, 1] << 4)).astype(jnp.int8)

    def _unpack(self, packed: jax.Array, shape) -> jax.Array:
        """Inverse of ``_pack``: sign-extend both nibbles back to int8
        and restore ``shape``."""
        u = packed.astype(jnp.uint8)
        lo = ((u & jnp.uint8(0xF)).astype(jnp.int8) ^ 8) - 8
        hi = (((u >> 4) & jnp.uint8(0xF)).astype(jnp.int8) ^ 8) - 8
        return jnp.stack([lo, hi], axis=-1).astype(jnp.int8).reshape(shape)

    def _wire(self, q: jax.Array, axis: str, perm) -> jax.Array:
        """ppermute the quantized payload; at bits=4 the lanes are
        nibble-packed around the hop so the wire carries q.size/2
        bytes (the jaxpr pin in tests/test_lowbit.py measures this)."""
        if self.bits == 8:
            return lax.ppermute(q, axis, perm)
        return self._unpack(lax.ppermute(self._pack(q), axis, perm),
                            q.shape)

    def _ring_sum(self, flat: jax.Array, axis: str, n,
                  residual: jax.Array | None = None):
        """The int8 ring: reduce-scatter then all-gather, int8 + per-block
        f32 scales on every hop.  Returns ``(summed[:total], err_rows)``
        where ``summed`` is the (approximate) cross-device SUM of ``flat``
        and ``err_rows`` is the (n, chunk) array of quantization errors
        THIS device dropped (always computed; the plain strategy discards
        it and XLA dead-code-eliminates the bookkeeping).  With
        ``residual`` (error feedback), last step's dropped errors are
        added to this step's chunk contributions first."""
        total = flat.size
        me = lax.axis_index(axis)
        chunk = -(-total // (n * self.block)) * self.block
        parts = jnp.pad(flat, (0, n * chunk - total)).reshape(n, chunk)
        if residual is not None:
            parts = parts + residual.reshape(n, chunk)
        perm = [(i, (i + 1) % n) for i in range(n)]

        # -- ring reduce-scatter (int8 + scales per hop) -------------------
        # After t hops my accumulator holds the partial sum of chunk
        # (me - t) mod n over devices {me-t, ..., me}.
        acc = lax.dynamic_index_in_dim(parts, me, 0, keepdims=False)
        err_rows = jnp.zeros((n, chunk), jnp.float32)

        def rs_step(carry, t):
            acc, err_rows = carry
            q, s = self._quant(acc)
            # chunk (me - t) mod n leaves this device quantized; record the
            # dropped error (EF uses it; otherwise DCE'd)
            err_rows = lax.dynamic_update_index_in_dim(
                err_rows, acc - self._dequant(q, s), jnp.mod(me - t, n), 0)
            q = self._wire(q, axis, perm)
            s = lax.ppermute(s, axis, perm)
            idx = jnp.mod(me - t - 1, n)
            nxt = self._dequant(q, s) + lax.dynamic_index_in_dim(
                parts, idx, 0, keepdims=False)
            return (nxt, err_rows), None

        (acc, err_rows), _ = lax.scan(rs_step, (acc, err_rows),
                                      jnp.arange(n - 1))
        # acc == full sum of chunk (me + 1) mod n

        # -- ring all-gather (int8 payloads forwarded verbatim) ------------
        qf, sf = self._quant(acc)
        own = jnp.mod(me + 1, n)
        # the broadcast copy everyone (including us) uses is dequantized
        err_rows = lax.dynamic_update_index_in_dim(
            err_rows, acc - self._dequant(qf, sf), own, 0)
        q_all = lax.dynamic_update_index_in_dim(
            jnp.zeros((n,) + qf.shape, jnp.int8), qf, own, 0)
        s_all = lax.dynamic_update_index_in_dim(
            jnp.zeros((n,) + sf.shape, jnp.float32), sf, own, 0)

        def ag_step(carry, t):
            q_all, s_all, cur_q, cur_s = carry
            cur_q = self._wire(cur_q, axis, perm)
            cur_s = lax.ppermute(cur_s, axis, perm)
            # payload received at hop t originated at device me-(t+1),
            # i.e. holds reduced chunk (me - t) mod n
            src = jnp.mod(me - t, n)
            q_all = lax.dynamic_update_index_in_dim(q_all, cur_q, src, 0)
            s_all = lax.dynamic_update_index_in_dim(s_all, cur_s, src, 0)
            return (q_all, s_all, cur_q, cur_s), None

        (q_all, s_all, _, _), _ = lax.scan(
            ag_step, (q_all, s_all, qf, sf), jnp.arange(n - 1))
        summed = (q_all.astype(jnp.float32) * s_all).reshape(-1)[:total]
        return summed, err_rows

    def _split(self, mean: jax.Array, leaves: list) -> list:
        out, offset = [], 0
        for g in leaves:
            out.append(mean[offset:offset + g.size]
                       .reshape(g.shape).astype(g.dtype))
            offset += g.size
        return out

    def sync_bucket(self, leaves: list, axis: str) -> list:
        """One int8 ring over this bucket's flat (tree-order) vector."""
        n = lax.axis_size(axis)
        flat = jnp.concatenate([g.ravel().astype(jnp.float32)
                                for g in leaves])
        if n == 1:
            mean = flat
        else:
            summed, _ = self._ring_sum(flat, axis, n)
            mean = summed / n
        return self._split(mean, leaves)

    def __call__(self, grads: PyTree, axis: str) -> PyTree:
        leaves, treedef = jax.tree.flatten(grads)
        out: list[jax.Array | None] = [None] * len(leaves)
        for bucket in self._plan(leaves):
            synced = self.sync_bucket([leaves[i] for i in bucket], axis)
            for i, s in zip(bucket, synced):
                out[i] = s
        return jax.tree.unflatten(treedef, out)


class QuantizedRingEF(QuantizedRing):
    """``quantized_ring`` + error feedback (EF-SGD / EF21 family): every
    quantization error the ring DROPS is recorded locally and fed back
    into the next step's contribution, so compressed sync converges like
    exact sync instead of degrading O(sqrt(n)) with ring size.

    Exact bookkeeping, not an approximation: in the reduce-scatter, device
    d at hop t quantizes its partial sum of chunk (d-t) mod n — the
    residual ``acc - dequant(Q(acc))`` is precisely what the global sum
    loses at that hop, and d is the only device that knows it.  The final
    all-gather quantization of chunk (d+1) mod n drops one more residual.
    Each device therefore records exactly one residual per chunk row per
    step; adding the carried residuals to next step's (sum-space) chunk
    contributions restores them.  Invariant (pinned by tests):

        n * synced_mean + psum(residuals) == exact gradient sum   (to f32)

    i.e. nothing is ever lost — only delayed one step.

    State: one f32 vector per device — the per-bucket padded residuals
    concatenated in bucket-plan order (a single segment, the padded flat
    gradient size, for trees under the bucket cap) — carried through the
    train step's scan like BN state (leading device axis, sharded over the
    data axis).  Dropping the state on restart is safe (residuals
    re-accumulate within a step).  Under ``overlap=True`` the same layout
    threads through the scan carry with each bucket's segment consumed and
    refilled by that bucket's in-backward marker (``OverlapSync``).
    """

    name = "quantized_ring_ef"
    stateful = True  # __call__ takes and returns the residual carry

    def state_segments(self, leaves: list, n_axis: int) -> list[int]:
        """Per-bucket residual lengths (n_axis * block-aligned chunk), in
        bucket-plan order — the layout contract between ``init_state``,
        ``__call__``, and the overlap markers."""
        return [n_axis * self._chunk(sum(leaves[i].size for i in bucket),
                                     n_axis)
                for bucket in self._plan(leaves)]

    def init_state(self, params: PyTree, n_axis: int) -> jax.Array:
        """Per-device zero residual for a gradient pytree shaped like
        ``params`` over an ``n_axis``-way ring (local, unstacked view)."""
        leaves = jax.tree.leaves(params)
        return jnp.zeros((sum(self.state_segments(leaves, n_axis)),),
                         jnp.float32)

    def sync_bucket(self, leaves: list, axis: str,
                    residual: jax.Array) -> tuple[list, jax.Array]:
        """One error-feedback int8 ring over this bucket; ``residual`` is
        the bucket's state segment, returned updated."""
        n = lax.axis_size(axis)
        flat = jnp.concatenate([g.ravel().astype(jnp.float32)
                                for g in leaves])
        if n == 1:
            mean, new_res = flat, jnp.zeros_like(residual)
        else:
            summed, err_rows = self._ring_sum(flat, axis, n,
                                              residual=residual)
            mean, new_res = summed / n, err_rows.ravel()
        return self._split(mean, leaves), new_res

    def __call__(self, grads: PyTree, axis: str,
                 residual: jax.Array) -> tuple[PyTree, jax.Array]:
        n = lax.axis_size(axis)
        leaves, treedef = jax.tree.flatten(grads)
        out: list[jax.Array | None] = [None] * len(leaves)
        segs = self.state_segments(leaves, n)
        new_parts, offset = [], 0
        for bucket, seg in zip(self._plan(leaves), segs):
            synced, new_r = self.sync_bucket(
                [leaves[i] for i in bucket], axis,
                residual[offset:offset + seg])
            offset += seg
            new_parts.append(new_r)
            for i, s in zip(bucket, synced):
                out[i] = s
        return (jax.tree.unflatten(treedef, out),
                jnp.concatenate(new_parts))


class Hierarchical:
    """Two-level (within-slice ICI, cross-slice DCN) gradient mean for
    multi-slice data parallelism.

    The reference's real topology is N nodes over TCP (start_ddp.sh:1 — a
    flat Gloo ring).  At TPU-pod scale the data axis factors into two links
    with ~100x different bandwidth: ICI within a slice and DCN across
    slices.  A flat psum over the combined axis runs the slow ring over
    DCN with the FULL gradient payload; the right algorithm is the
    standard two-level reduction (the scaling-book multi-slice recipe):

      1. ``psum_scatter`` over ``'ici'`` — each chip ends with a 1/ici
         shard of its slice's summed gradient (bandwidth-optimal within
         the slice);
      2. ``psum`` over ``'dcn'`` — slices exchange only the 1/ici shard,
         so cross-slice traffic drops by the ici degree;
      3. all-gather over ``'ici'`` — the full mean returns on the fast
         link.

    Total DCN bytes per step: |grads|/ici vs |grads| for the flat psum.
    The result is the exact global mean, so numerics match ``ddp``
    (pinned by tests/test_strategies.py vs ddp on a 2x4 virtual mesh).

    The gather-back uses ``all_gather_invariant`` so the result is
    *provably* replicated (vma-invariant) over both axes — this strategy
    needs no ``check_vma=False`` escape hatch.  On a jax without it, the
    fallback embeds each shard at its offset and psums over ``'ici'``
    (same result, provable, 2x the ICI bytes of the gather).

    Runs over ``Mesh(('dcn', 'ici'))`` — the trainer builds it from
    ``TrainConfig.dcn_size`` (number of slices).  With a single flat axis
    (or axis size 1 on either level) it degrades gracefully to the exact
    flat mean.

    ``dcn_compress="int8"`` (round 9, ``TrainConfig.dcn_compress``)
    additionally quantizes ONLY the slow hop: step 2's shard exchange
    runs as an int8 ring over ``'dcn'`` (``QuantizedRing._ring_sum`` —
    int8 payloads + per-256-row f32 scales on every cross-slice
    transfer, the DynamiQ/EQuARX compress-the-scarce-link design point)
    while the ICI reduce-scatter/all-gather stay full-precision.  Every
    bit the wire drops lands in a per-device error-feedback residual
    threaded through the trainer's stateful sync-state channel (the
    ``quantized_ring_ef`` carry), so compressed sync converges like
    exact sync with one step of delay.  Compression makes the strategy
    stateful AND vma-opaque (the ring assembles its result from
    ppermute payloads — replicated by construction, not by proof);
    numerics become bucket-LAYOUT-dependent through the row scales, so
    post-backward and overlap share ONE ``make_bucket_plan`` packing
    exactly like the int8 rings.

    ``dcn_compress="int4"`` (round 16) is the same machinery one rung
    lower: the ring quantizes to [-7, 7] and nibble-packs two values
    per int8 lane around every ppermute, so the scarce hop carries
    ~0.51x the int8 bytes (0.5 + 1/64 scale overhead per element vs
    1 + 1/64).  Error feedback absorbs the coarser rounding the same
    way — the EF invariant and the ddp-curve pins hold bit-for-bit in
    structure, only the per-step quantization noise grows.
    """

    name = "hierarchical"
    needs_mesh = True
    axes = ("dcn", "ici")  # outer = cross-slice (slow), inner = within-slice
    supports_overlap = True

    def __init__(self, dcn_compress: str | None = None, dcn_size: int = 2,
                 bucket_mb: float = BUCKET_CAP_MB):
        self.bucket_bytes = int(bucket_mb * 1024 * 1024)
        self.set_dcn(dcn_compress, dcn_size)

    def set_dcn(self, compress: str | None, dcn_size: int) -> None:
        """Configure the slow-hop compression (the trainers propagate
        ``TrainConfig.dcn_compress``/``dcn_size`` here before building the
        step OR the sync state — the EF residual layout needs dcn_size)."""
        if compress not in (None, "int8", "int4"):
            raise ValueError(f"dcn_compress must be None, 'int8', or "
                             f"'int4', got {compress!r}")
        self.dcn_compress = compress
        self.dcn_size = dcn_size
        # quant/dequant/_ring_sum at the wire's bit width; the _chunk
        # layout is bits-independent, so the EF residual sizing (and
        # every sync-state contract built on it) is stable across rungs
        self._ring = QuantizedRing(bits=4 if compress == "int4" else 8)
        # compression adds the EF residual carry and gives up the static
        # replication proof (ppermute ring on the dcn hop)
        self.stateful = compress is not None
        self.vma_opaque = compress is not None

    @staticmethod
    def _factor(axis) -> tuple[str | None, str]:
        if isinstance(axis, str):
            return None, axis
        dcn, ici = axis
        return dcn, ici

    # -- EF residual layout (dcn_compress only) ---------------------------
    def _shard_len(self, total: int, n_ici: int) -> int:
        """Per-chip ICI shard length of a ``total``-element bucket
        (psum_scatter pads the flat vector to an n_ici multiple)."""
        return -(-total // n_ici)

    def _segments(self, leaves: list, n_dcn: int, n_ici: int) -> list[int]:
        return [n_dcn * self._ring._chunk(
                    self._shard_len(sum(leaves[i].size for i in b), n_ici),
                    n_dcn)
                for b in make_bucket_plan(leaves, self.bucket_bytes)]

    def state_segments(self, leaves: list, n_axis: int) -> list[int]:
        """Per-bucket residual lengths (n_dcn x the dcn-ring chunk of the
        ICI shard), bucket-plan order — the layout contract between
        ``init_state``, ``__call__``, and the overlap markers."""
        n_ici = n_axis // self.dcn_size
        return self._segments(leaves, self.dcn_size, n_ici)

    def init_state(self, params: PyTree, n_axis: int) -> jax.Array:
        if self.dcn_compress is None:
            return jnp.zeros((0,), jnp.float32)
        leaves = jax.tree.leaves(params)
        return jnp.zeros((sum(self.state_segments(leaves, n_axis)),),
                         jnp.float32)

    def _int8_dcn_reduce(self, dcn, n_dcn, residual, out: dict):
        """The compressed slow hop: a ``shard -> summed_shard`` callable
        for ``two_level_psum(dcn_reduce=...)`` that runs the shard
        exchange as a quantized ring over ``dcn`` at the configured bit
        width (int8, or nibble-packed int4) and records the dropped
        quantization error (the EF residual) in ``out``."""
        def reduce(shard):
            if n_dcn == 1:  # degraded topology: nothing crosses, no loss
                out["res"] = jnp.zeros_like(residual)
                return shard
            summed, err_rows = self._ring._ring_sum(
                shard, dcn, n_dcn, residual=residual)
            out["res"] = err_rows.ravel()
            return summed
        return reduce

    def sync_bucket(self, leaves: list, axis, residual: jax.Array | None
                    = None):
        # one two-level (reduce-scatter / shard-sized DCN exchange /
        # gather) reduction per bucket; the plain exchange is elementwise
        # over devices, so post-backward (whole-tree) and overlap
        # (per-bucket) sum the same addends per element either way.  The
        # int8 exchange quantizes against per-row scales of the bucket's
        # OWN shard, so compressed mode shares the bucket plan instead.
        dcn, ici = self._factor(axis)
        n_dcn = lax.axis_size(dcn) if dcn else 1
        n = lax.axis_size(ici) * n_dcn
        # the mean division happens on the f32 sum INSIDE two_level_psum
        # (before the cast back to leaf dtype): low-precision leaves must
        # not see the undivided sum, which can overflow their range
        if self.dcn_compress is None:
            return two_level_psum(leaves, dcn, ici, scale=1.0 / n)
        out: dict = {}
        synced = two_level_psum(
            leaves, dcn, ici, scale=1.0 / n,
            dcn_reduce=self._int8_dcn_reduce(dcn, n_dcn, residual, out))
        return synced, out["res"]

    # -- communication-sparse windows (round 18) ----------------------------
    # Local-SGD on the factored mesh splits the per-step sync in two:
    # ``local_sync`` runs EVERY step (the fast within-slice mean — exactly
    # the per-step path's ICI ops, zero DCN ops) and ``window_exchange``
    # runs only at window boundaries (the slow cross-slice hop over the
    # accumulated update delta, shard-sized like the per-step DCN payload).
    # DCN bytes per step therefore scale ~1/H while ICI bytes are
    # unchanged — the claim tests/test_localsgd.py measures per axis from
    # the schedule inspector.

    def local_sync(self, grads: PyTree, axis) -> PyTree:
        """Within-slice (ICI-only) gradient mean for a LOCAL step of a
        ``sync_every > 1`` window: the per-step reduce-scatter/all-gather
        over ``ici`` with NO cross-slice hop — each slice steps on its own
        slice-mean gradient.  Compression never applies here (it is the
        DCN hop's knob), so this path is stateless and vma-provable
        regardless of ``dcn_compress``."""
        _, ici = self._factor(axis)
        return two_level_psum(grads, None, ici,
                              scale=1.0 / lax.axis_size(ici))

    def window_exchange(self, delta: PyTree, axis,
                        sync_state: jax.Array | None = None):
        """Cross-slice mean of the window's accumulated update ``delta``
        (slice-uniform after H ``local_sync`` steps): each chip takes its
        own static ICI-indexed chunk of the flat delta (free — the value
        is already replicated within the slice, so slicing replaces the
        per-step reduce-scatter), exchanges ONLY that shard over ``dcn``
        (plain psum, or the int8/int4+EF ring under ``dcn_compress`` —
        same chunk length as the per-step exchange, so the EF residual
        layout and ``init_state`` are unchanged), gathers back over
        ``ici``, and divides by the slice count.  Stateful form returns
        ``(mean_delta, new_residual)``.

        Round 20: the window is the routed plan ``ici:slice → [dcn
        exchange] → ici:ag`` (the 'slice' rs algorithm encodes
        "already replicated within the slice") executed per bucket by
        ``parallel/routing.execute`` — same ops, same EF layout."""
        from . import routing
        dcn, ici = self._factor(axis)
        n_dcn = lax.axis_size(dcn) if dcn else 1
        n_ici = lax.axis_size(ici)
        leaves, treedef = jax.tree.flatten(delta)
        out: list[jax.Array | None] = [None] * len(leaves)
        segs = self._segments(leaves, n_dcn, n_ici)
        hops: list = [routing.Hop("rs", ici, algorithm="slice")]
        if dcn is not None:
            hops.append(routing.Hop("exchange", dcn))
        hops.append(routing.Hop("ag", ici))
        plan = routing.HopPlan(tuple(hops))
        new_parts, offset = [], 0
        for bucket, seg in zip(make_bucket_plan(leaves, self.bucket_bytes),
                               segs):
            sub = [leaves[i] for i in bucket]
            overrides = None
            captured: dict = {}
            if self.dcn_compress is not None:
                residual = sync_state[offset:offset + seg]
                offset += seg
                if dcn is not None:
                    overrides = {dcn: self._int8_dcn_reduce(
                        dcn, n_dcn, residual, captured)}
                else:  # degraded topology: nothing crosses, no loss
                    captured["res"] = jnp.zeros_like(residual)
            synced, _ = routing.execute(plan, sub, scale=1.0 / n_dcn,
                                        overrides=overrides)
            if self.dcn_compress is not None:
                new_parts.append(captured["res"])
            for i, s in zip(bucket, synced):
                out[i] = s
        tree = jax.tree.unflatten(treedef, out)
        if self.dcn_compress is None:
            return tree
        return tree, jnp.concatenate(new_parts)

    def _split(self, mean: jax.Array, leaves: list) -> list:
        out, offset = [], 0
        for g in leaves:
            out.append(mean[offset:offset + g.size]
                       .reshape(g.shape).astype(g.dtype))
            offset += g.size
        return out

    def __call__(self, grads: PyTree, axis,
                 sync_state: jax.Array | None = None):
        dcn, ici = self._factor(axis)
        if self.dcn_compress is None:
            n = lax.axis_size(ici) * (lax.axis_size(dcn) if dcn else 1)
            return two_level_psum(grads, dcn, ici, scale=1.0 / n)
        # compressed: one ring-exchanged two-level reduction per plan
        # bucket, residual segments consumed/refilled in plan order
        leaves, treedef = jax.tree.flatten(grads)
        out: list[jax.Array | None] = [None] * len(leaves)
        n_dcn = lax.axis_size(dcn) if dcn else 1
        segs = self._segments(leaves, n_dcn, lax.axis_size(ici))
        new_parts, offset = [], 0
        for bucket, seg in zip(make_bucket_plan(leaves, self.bucket_bytes),
                               segs):
            synced, new_r = self.sync_bucket(
                [leaves[i] for i in bucket], axis,
                sync_state[offset:offset + seg])
            offset += seg
            new_parts.append(new_r)
            for i, s in zip(bucket, synced):
                out[i] = s
        return (jax.tree.unflatten(treedef, out),
                jnp.concatenate(new_parts))


def two_level_psum(grads: PyTree, dcn: str | None, ici: str,
                   scale: float | None = None,
                   dcn_reduce: Callable | None = None) -> PyTree:
    """The two-level reduction underlying ``Hierarchical`` (steps 1-3 of
    its docstring): reduce-scatter over ``ici``, a SHARD-SIZED ``psum``
    over ``dcn`` (the only cross-slice traffic — |grads|/ici bytes, a
    claim scripts/bench_strategies.py now MEASURES per axis from the
    schedule inspector rather than asserts), ``all_gather_invariant``
    back over ``ici``.  ``scale`` (e.g. 1/n for a mean) applies to the
    f32 sum before the cast back to each leaf's dtype.  ``dcn_reduce``
    replaces the stock ``psum`` on the slow hop with a ``shard ->
    summed_shard`` callable — ``Hierarchical(dcn_compress='int8')``
    plugs its quantized ring exchange in here, leaving steps 1 and 3
    untouched.  Output is provably replicated over both axes (with the
    stock hop; a ppermute-based ``dcn_reduce`` forfeits the proof — see
    ``Hierarchical.vma_opaque``).  Shared with the LM trainer's
    factored-mesh gradient sync (lm.py dcn_size), whose jaxpr test pins
    the shard-sized DCN payload.

    Round 20: the hand-built loop is retired — the body is now the
    2-level ``HopPlan`` ``ici:rs → [dcn:psum] → ici:ag`` compiled by
    ``parallel/routing.execute``, which emits the identical op sequence
    (pad → psum_scatter → exchange → all_gather_invariant → slice →
    scale → split); every pre-existing bitwise pin on this function now
    pins the route compiler transitively."""
    from . import routing
    hops: list = [routing.Hop("rs", ici)]
    if dcn is not None:
        hops.append(routing.Hop("exchange", dcn))
    hops.append(routing.Hop("ag", ici))
    overrides = ({dcn: dcn_reduce}
                 if dcn is not None and dcn_reduce is not None else None)
    synced, _ = routing.execute(routing.HopPlan(tuple(hops)), grads,
                                scale=scale, overrides=overrides)
    return synced


# -- DiLoCo outer optimizer over window deltas (round 22) -------------------
#
# The round-18 window boundary applies the plain cross-slice MEAN of the
# accumulated deltas to the anchor.  The DiLoCo recipe (PAPERS.md) keeps
# an OUTER optimizer state on the anchor instead: the mean delta is the
# outer "gradient", and Nesterov/heavy-ball momentum over it lets a much
# wider window (H=8+) track the per-step trajectory — the "wider window
# at matched quality" claim tests/test_diloco.py measures with the
# round-18 convergence-band methodology.

class OuterOptimizer:
    """The window-boundary anchor update ``anchor <- anchor + lr * step``
    where ``step`` is Nesterov (``mu*m' + d``) or heavy-ball (``m'``)
    momentum over the exchanged mean delta (``m' = mu*m + d``).  Momentum
    state is f32, anchor-shaped; arithmetic runs in f32 and casts back to
    each leaf's dtype.

    ``trivial`` (mu == 0 and lr == 1) marks the configuration whose
    update IS the plain mean fold-in: the trainers branch at BUILD time
    and emit the round-18 ``jnp.add`` path with no momentum state at all,
    so zero-momentum outer-opt is bitwise (and jaxpr-census) identical to
    plain mean — the same build-time-branch discipline that keeps
    ``sync_every=1`` out of the windowed builders."""

    KINDS = ("nesterov", "momentum")

    def __init__(self, kind: str, momentum: float = 0.9,
                 lr: float = 1.0):
        if kind not in self.KINDS:
            raise ValueError(f"outer_opt must be one of {self.KINDS} "
                             f"(or None for the plain mean), got {kind!r}")
        self.kind = kind
        self.momentum = float(momentum)
        self.lr = float(lr)

    @property
    def trivial(self) -> bool:
        """True when the update degenerates to ``anchor + d_avg`` exactly
        — callers must then take the plain-mean build path (bitwise)."""
        return self.momentum == 0.0 and self.lr == 1.0

    # -- tree form (the LM trainer's anchor-shaped momentum) ---------------
    def init_state(self, anchor: PyTree) -> PyTree:
        """f32 zero momentum, one leaf per anchor leaf."""
        return jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), anchor)

    def apply(self, anchor: PyTree, d_avg: PyTree,
              m: PyTree) -> tuple[PyTree, PyTree]:
        """One outer step: ``(new_anchor, new_momentum)``.  Static Python
        branch on ``trivial`` so the degenerate config emits exactly the
        round-18 plain-mean ops."""
        if self.trivial:
            return jax.tree.map(jnp.add, anchor, d_avg), m
        mu = self.momentum
        m = jax.tree.map(
            lambda d, mi: mu * mi + d.astype(jnp.float32), d_avg, m)
        if self.kind == "nesterov":
            step = jax.tree.map(
                lambda d, mi: mu * mi + d.astype(jnp.float32), d_avg, m)
        else:
            step = m
        anchor = jax.tree.map(
            lambda a, s: (a.astype(jnp.float32)
                          + self.lr * s).astype(a.dtype), anchor, step)
        return anchor, m

    # -- flat form (the VGG trainer packs momentum into the sync-state
    #    carry, after the EF residual segments) ----------------------------
    @staticmethod
    def state_len(params: PyTree) -> int:
        return sum(int(p.size) for p in jax.tree.leaves(params))

    def init_flat(self, params: PyTree) -> jax.Array:
        return jnp.zeros((self.state_len(params),), jnp.float32)

    def apply_flat(self, anchor: PyTree, d_avg: PyTree,
                   flat_m: jax.Array) -> tuple[PyTree, jax.Array]:
        """``apply`` with the momentum held as ONE flat f32 vector (leaf
        order, ravelled) — the layout that rides train.py's per-device
        sync-state carry next to the EF residual segments."""
        if self.trivial:
            return jax.tree.map(jnp.add, anchor, d_avg), flat_m
        leaves, treedef = jax.tree.flatten(anchor)
        m_leaves, offset = [], 0
        for leaf in leaves:
            m_leaves.append(flat_m[offset:offset + leaf.size]
                            .reshape(leaf.shape))
            offset += leaf.size
        m_tree = jax.tree.unflatten(treedef, m_leaves)
        anchor, m_tree = self.apply(anchor, d_avg, m_tree)
        return anchor, jnp.concatenate(
            [m.ravel() for m in jax.tree.leaves(m_tree)])


# -- backward-overlapped gradient sync (round 8) ---------------------------
#
# The one trick torch DDP plays that the post-backward strategies above do
# not: its Reducer launches each ~25 MB bucket's all-reduce from a C++
# autograd hook the moment the bucket's gradients are produced, hiding the
# collective under the remaining backward compute.  The JAX analogue is a
# custom_vjp identity ("sync point") wrapping each bucket's params at the
# bucket's EARLIEST layer-group boundary in the model's forward pass: the
# transpose visits forward equations in reverse, so the marker's backward
# rule — which runs the bucket's collective on the accumulated cotangents —
# lands in the backward graph immediately after that layer group's backward
# matmuls, with every later bucket's collective already emitted.  XLA's
# latency-hiding scheduler can then run bucket N's collective concurrently
# with layer N-1's backward dot_generals (utils/debug.py op_schedule pins
# the interleaving; train.py overlap=True wires it up).

def sync_boundary(tree: PyTree, sync_fn: Callable[[PyTree], PyTree],
                  group_id: int | str | None = None) -> PyTree:
    """Identity on ``tree`` whose BACKWARD applies ``sync_fn`` to the
    accumulated cotangents at this position in the backward graph — the
    in-backward bucket collective of overlap mode.  ``group_id`` is
    documentation/debugging only (the layer group whose boundary this is).
    """

    @jax.custom_vjp
    def point(t):
        return t

    def fwd(t):
        return t, None

    def bwd(_, ct):
        return (sync_fn(ct),)

    point.defvjp(fwd, bwd)
    return point(tree)


def sync_boundary_stateful(
        tree: PyTree, residual: jax.Array,
        sync_fn: Callable[[PyTree, jax.Array], tuple[PyTree, jax.Array]],
        group_id: int | str | None = None) -> PyTree:
    """``sync_boundary`` for stateful (error-feedback) strategies: the
    residual rides the forward as an inert input and its COTANGENT channel
    carries the updated residual out of the backward — differentiate the
    loss w.r.t. ``(params, sync_state)`` and the sync-state "gradient" IS
    the next step's residual carry (train.py overlap=True threads it back
    into the scan carry).  ``sync_fn(cotangents, residual) -> (synced,
    new_residual)``."""

    @jax.custom_vjp
    def point(t, r):
        return t

    def fwd(t, r):
        return t, r

    def bwd(r, ct):
        synced, new_r = sync_fn(ct, r)
        return synced, new_r

    point.defvjp(fwd, bwd)
    return point(tree, residual)


def _leaf_group(path, group_index: dict) -> int:
    """Map a leaf's tree path to its model layer group via the top-level
    key (models expose ``sync_group_index``)."""
    entry = path[0]
    key = getattr(entry, "key", None)
    if key is None:  # tuple-style paths on older tree_util
        key = str(entry)
    try:
        return group_index[key]
    except KeyError:
        raise ValueError(
            f"param key {key!r} missing from the model's sync_group_index "
            f"map; overlap needs every top-level param entry assigned to a "
            f"forward layer group") from None


class OverlapSync:
    """Per-trace orchestrator for backward-overlapped gradient sync.

    Packs the param tree's leaves into reverse-topological ~bucket_bytes
    buckets (``make_bucket_plan`` — the SAME plan the bucketed/ring
    strategies use post-backward, so overlap=True compares bitwise against
    an equally-bucketed post-backward step), then inserts one sync-point
    marker per bucket at the bucket's earliest layer-group boundary.

    Usage (inside the loss function, fresh per trace):

        ov = OverlapSync(strategy, axis, params, model.sync_group_index(...),
                         sync_state=residual_or_None)
        logits = model.apply(params, ..., boundary=ov.boundary)

    The model calls ``params = boundary(group, params)`` at each layer-group
    boundary in forward order; the returned tree has the due buckets' leaves
    wrapped so their cotangents are synced in-backward.  For stateful
    strategies the residual's updated value comes back as the sync_state
    argument's gradient (see ``sync_boundary_stateful``).
    """

    def __init__(self, strategy, axis, params: PyTree,
                 group_index: dict, *, sync_state: jax.Array | None = None):
        require_overlap_capable(strategy)
        self.strategy, self.axis = strategy, axis
        flat, self.treedef = jax.tree_util.tree_flatten_with_path(params)
        self.leaves = [leaf for _, leaf in flat]
        groups = [_leaf_group(path, group_index) for path, _ in flat]
        self.plan = make_bucket_plan(self.leaves, strategy.bucket_bytes)
        self.stateful = getattr(strategy, "stateful", False)
        if self.stateful:
            if sync_state is None:
                raise ValueError(
                    f"stateful strategy {strategy.name!r} needs sync_state "
                    f"for overlap (the per-device EF residual)")
            # total device count over a possibly-factored axis (the
            # hierarchical strategy runs over the ('dcn', 'ici') tuple)
            n_axis = 1
            for a in ((axis,) if isinstance(axis, str) else tuple(axis)):
                n_axis *= lax.axis_size(a)
            segs = strategy.state_segments(self.leaves, n_axis)
            offs = [0]
            for s in segs:
                offs.append(offs[-1] + s)
            self._res = [sync_state[a:b] for a, b in zip(offs, offs[1:])]
        # bucket b fires at the boundary of its earliest forward group:
        # by then every later group's backward (hence every cotangent the
        # bucket needs) is complete
        self._due: dict[int, list[int]] = {}
        for b, bucket in enumerate(self.plan):
            trigger = min(groups[i] for i in bucket)
            self._due.setdefault(trigger, []).append(b)
        self._marked: set[int] = set()

    def boundary(self, group: int, params: PyTree) -> PyTree:
        """Mark the buckets due at this layer-group boundary; returns the
        params tree with those buckets' leaves replaced by sync-point
        outputs (identity forward, in-backward collective)."""
        due = self._due.get(group)
        if not due:
            return params
        leaves = [leaf for _, leaf in
                  jax.tree_util.tree_flatten_with_path(params)[0]]
        # later boundaries must see earlier markers' outputs: refresh from
        # the incoming tree, then overlay this boundary's markers
        self.leaves = leaves
        for b in due:
            assert b not in self._marked, (b, group)
            self._marked.add(b)
            bucket = self.plan[b]
            sub = tuple(self.leaves[i] for i in bucket)
            if self.stateful:
                def sync_fn(ct, r):
                    synced, new_r = self.strategy.sync_bucket(
                        list(ct), self.axis, r)
                    return tuple(synced), new_r
                marked = sync_boundary_stateful(sub, self._res[b], sync_fn,
                                                group_id=group)
            else:
                def sync_fn(ct):
                    return tuple(self.strategy.sync_bucket(list(ct),
                                                           self.axis))
                marked = sync_boundary(sub, sync_fn, group_id=group)
            for i, m in zip(bucket, marked):
                self.leaves[i] = m
        return jax.tree_util.tree_unflatten(self.treedef, self.leaves)


_REGISTRY: dict[str, Callable[[], Strategy]] = {
    "none": NoSync,
    "all_reduce": AllReduce,
    "gather_scatter": GatherScatter,
    "gather_scatter_symmetric": GatherScatterSymmetric,
    "ddp": DDP,
    "bucketed": Bucketed,
    "quantized": QuantizedAllReduce,
    "quantized_ring": QuantizedRing,
    "quantized_ring_ef": QuantizedRingEF,
    "hierarchical": Hierarchical,
}


def get(name: str) -> Strategy:
    """Look up a strategy by name (the pluggable axis the reference's five
    copy-pasted scripts should have had)."""
    try:
        return _REGISTRY[name]()
    except KeyError:
        if name == "auto":
            # "auto" is not a strategy: it resolves TO one.  The Trainer
            # does that (parallel/autotune.resolve_train_auto) before any
            # registry lookup; reaching here means a caller skipped it.
            raise ValueError(
                "strategy 'auto' must be resolved to a named strategy "
                "first (train.Trainer does this via "
                "parallel/autotune.resolve_train_auto); the registry "
                f"holds only concrete strategies: {sorted(_REGISTRY)}"
            ) from None
        raise ValueError(
            f"unknown strategy {name!r}; available: {sorted(_REGISTRY)}"
        ) from None


def available() -> list[str]:
    return sorted(_REGISTRY)


def overlap_capable() -> list[str]:
    """Strategies usable with ``TrainConfig(overlap=True)`` (they expose
    ``sync_bucket``, the per-bucket collective the in-backward markers
    call).  The sequential-by-design baselines (all_reduce, the
    gather_scatter pair) are deliberately excluded: their point is the
    serialized wire pattern overlap would dissolve (module docstring,
    'preserving naivety on purpose')."""
    return sorted(n for n, c in _REGISTRY.items()
                  if getattr(c, "supports_overlap", False))


# -- overlap capability checks (round 9): the ONE definition site ----------
#
# Both trainers used to hand-roll their overlap refusals (train.py's
# strategy check and lm.py's fsdp/dcn check), which let the two messages —
# and worse, the two CONDITIONS — drift.  They now both call here, next to
# the machinery (OverlapSync) whose capabilities the checks describe.

def require_overlap_capable(strategy) -> None:
    """Raise unless ``strategy`` can run as in-backward bucket collectives
    (``TrainConfig(overlap=True)``); shared by the VGG trainer's config
    validation and ``OverlapSync`` itself, so the refusal and the
    machinery can never disagree."""
    if not getattr(strategy, "supports_overlap", False):
        raise ValueError(
            f"strategy {strategy.name!r} does not support overlap=True; "
            f"overlap-capable strategies: {overlap_capable()} (the "
            f"sequential baselines keep their serialized wire pattern on "
            f"purpose)")


def require_lm_overlap_streamable(*, fsdp: bool, dcn: bool) -> None:
    """The LM trainer's overlap capability check
    (``LMTrainConfig(overlap=True)``): raise unless the config has a
    post-backward cluster the layer-group boundary hook can stream —
    ZeRO-3 weight gathers (``fsdp``) and/or the factored-mesh two-level
    DCN sync points (``dcn`` — dcn_size > 1 AND the sync actually runs
    in-backward: under grad_accum > 1 the one post-accumulation exchange
    sits outside the backward, so the caller passes dcn=False there;
    streamed per layer group since round 9).  With neither, the
    data-axis cotangent psums are already emitted at each param's use
    site by shard_map's transpose — there is nothing to stream."""
    if fsdp or dcn:
        return
    raise ValueError(
        "lm overlap=True streams the ZeRO-3 (fsdp) weight gathers and/or "
        "the factored-mesh (dcn_size > 1) two-level sync points through "
        "the layer boundaries; without either there is no post-backward "
        "cluster to dissolve (BASELINE.md rounds 8-9).  Enable fsdp, set "
        "dcn_size > 1, or drop overlap (the VGG "
        "trainer's overlap=True covers the explicit-strategy case)")


def require_lm_route(plan, *, dcn: bool, pp: bool,
                     dcn_compress: str | None,
                     sync_plan: str | None) -> None:
    """The LM trainer's routed-surface capability check
    (``LMTrainConfig(sync_route=...)``, round 21 — the round-20
    follow-up): ONE definition site shared by
    ``autotune.resolve_lm_route``, ``lm_cli``, and the bench pre-checks.
    ``plan`` is a parsed ``routing.HopPlan`` (duck-typed — strategies
    cannot import routing, routing imports us).

    The LM trainer executes exactly the routes its factored-mesh sync
    machinery (``_two_level_sync``) compiles: the flat ``data:psum`` on
    an unfactored mesh, and ``data:rs → dcn:psum → data:ag`` /
    ``data:rs → dcn:ring[int8|int4+ef] → data:ag`` on a factored one —
    anything else must refuse loudly rather than silently run a
    different program than the route names.  The pipeline's gradient
    path is its own (the long-standing dcn_compress refusal), and the
    route carries its own wire format, so combining with an explicit
    ``dcn_compress`` or with ``sync_plan='auto'`` (search vs pin) is
    ambiguous — set one, not both."""
    if sync_plan is not None:
        raise ValueError(
            "sync_route pins the gradient route by hand; "
            "sync_plan='auto' searches for one — ambiguous together, "
            "set one, not both")
    if dcn_compress is not None:
        raise ValueError(
            "sync_route encodes the dcn hop's wire format in the route "
            "itself (e.g. 'dcn:ring[int4+ef]'); an explicit "
            "dcn_compress alongside is ambiguous — drop it")
    if pp:
        raise ValueError(
            "sync_route does not compose with pipeline parallelism "
            "(pp): the pipeline's gradient reductions are per stage, "
            "not routed through _two_level_sync — drop the pipeline "
            "or the route")
    hops = list(plan.hops)
    if not dcn:
        if (len(hops) == 1 and hops[0].kind == "exchange"
                and hops[0].axis == "data"
                and hops[0].algorithm == "psum"):
            return
        raise ValueError(
            f"with dcn_size=1 the LM data sync is the flat 'data:psum' "
            f"(per-leaf cotangent psums); got {plan.describe()!r} — "
            f"factor the mesh (dcn_size >= 2) to route a two-level "
            f"plan")
    ok_shape = (len(hops) == 3
                and hops[0].kind == "rs" and hops[0].axis == "data"
                and hops[0].algorithm == "scatter"
                and hops[1].kind == "exchange" and hops[1].axis == "dcn"
                and hops[2].kind == "ag" and hops[2].axis == "data")
    if not ok_shape:
        raise ValueError(
            f"the LM factored-mesh sync executes routes shaped "
            f"'data:rs → dcn:psum → data:ag' or 'data:rs → "
            f"dcn:ring[int8|int4+ef] → data:ag' (what _two_level_sync "
            f"compiles); got {plan.describe()!r}")
    x = hops[1]
    if x.algorithm == "ring" and not x.ef:
        raise ValueError(
            f"the LM dcn ring threads the error-feedback residual "
            f"through the train step's sync-state channel; a "
            f"compressed dcn hop must be ring[int8|int4+ef], got "
            f"{x.describe()!r}")


def require_sync_window(*, sync_every: int, staleness: int = 0,
                        max_sync_every: int = 1, mesh: bool = True,
                        overlap: bool = False, pp: bool = False,
                        grad_accum: int = 1, dcn_size: int | None = None,
                        steps_per_loop: int | None = None,
                        trainer: str = "train",
                        outer_opt: str | None = None,
                        outer_momentum: float = 0.9,
                        outer_lr: float = 1.0,
                        sync_every_per_slice: tuple | None = None) -> None:
    """The communication-sparse window coherence check
    (``TrainConfig(sync_every=H)`` / ``LMTrainConfig(sync_every=H)``,
    round 18): ONE definition site — the round-9 ``require_*``
    consolidation — shared by both trainers' config validation, both
    CLIs, and bench's pre-bench knob validation, so the refusal
    conditions cannot drift from what the windowed step builders
    actually compile.

    Rejects the incoherent combos loudly: windows need a mesh (the
    meshless single-jit path has no collective to amortize and no
    per-device local state); pipeline stages own their own schedule
    (the pipeline step has no per-step data exchange a window could skip);
    grad_accum already IS a window over the exchange (composing the two
    double-counts the amortization); the VGG in-backward overlap
    machinery streams the very per-step collective a window removes;
    LM windows relax the DCN hop specifically, so they need a factored
    mesh (dcn_size >= 2) to have a slow axis to relax; and bounded
    staleness must leave the window room to hide under (0 <= S < H,
    S = 0 meaning apply-at-boundary).

    Round 22 (DiLoCo): ``outer_opt`` (None | 'nesterov' | 'momentum')
    is the window-boundary anchor optimizer — it updates at boundaries,
    so it needs a window (sync_every > 1) to have boundaries at all;
    ``outer_momentum`` must sit in [0, 1) and ``outer_lr`` be positive.
    ``sync_every_per_slice`` (LM only) gives each 'dcn' slice its own
    interval: a tuple of dcn_size entries, every entry a multiple of
    the base ``sync_every`` (slices exchange only at base boundaries,
    some skipping), with ``min == sync_every`` (the base IS the
    tightest slice's cadence — anything else would mean boundaries no
    compiled program runs).  Per-slice windows do not compose with
    bounded staleness (the skip mask and the deferred apply would both
    reinterpret the same boundary), and the VGG trainer's windows are
    gang-wide by construction (one flat replica axis — there is no
    per-slice program to skip)."""
    if sync_every < 1:
        raise ValueError(f"sync_every must be >= 1, got {sync_every}")
    if max_sync_every < 1:
        raise ValueError(
            f"max_sync_every must be >= 1, got {max_sync_every}")
    if staleness < 0:
        raise ValueError(f"staleness must be >= 0, got {staleness}")
    if sync_every == 1 and staleness > 0:
        raise ValueError(
            f"staleness={staleness} needs sync_every > 1: with per-step "
            f"sync there are no local steps to hide the exchange under")
    if staleness >= sync_every and staleness > 0:
        raise ValueError(
            f"staleness={staleness} >= sync_every={sync_every}: the "
            f"delayed window exchange must land before the next one "
            f"launches (0 <= S < H; S=0 applies at the boundary step)")
    if outer_opt is not None:
        if outer_opt not in OuterOptimizer.KINDS:
            raise ValueError(
                f"outer_opt must be None, 'nesterov', or 'momentum', "
                f"got {outer_opt!r}")
        if sync_every == 1:
            raise ValueError(
                f"outer_opt={outer_opt!r} needs sync_every > 1: the "
                f"outer step applies at window boundaries — with "
                f"per-step sync there is no window delta to apply it to")
        if not 0.0 <= outer_momentum < 1.0:
            raise ValueError(
                f"outer_momentum must sit in [0, 1), got "
                f"{outer_momentum}")
        if outer_lr <= 0.0:
            raise ValueError(f"outer_lr must be > 0, got {outer_lr}")
    if sync_every_per_slice is not None:
        per = tuple(sync_every_per_slice)
        if trainer != "lm":
            raise ValueError(
                "sync_every_per_slice is an LM-trainer (factored 'dcn' "
                "mesh) feature: the VGG trainer's windows are gang-wide "
                "over one flat replica axis — there is no per-slice "
                "boundary program to skip")
        if sync_every == 1:
            raise ValueError(
                "sync_every_per_slice needs the windowed mode "
                "(sync_every > 1): the base interval is the compiled "
                "boundary cadence the per-slice windows subdivide")
        if staleness > 0:
            raise ValueError(
                f"sync_every_per_slice does not compose with "
                f"staleness={staleness}: the skip mask and the deferred "
                f"apply would both reinterpret the same boundary; pick "
                f"one relaxation")
        if dcn_size is not None and len(per) != dcn_size:
            raise ValueError(
                f"sync_every_per_slice has {len(per)} entries but "
                f"dcn_size={dcn_size}: one interval per slice")
        if any(not isinstance(h, int) or h < 1 for h in per):
            raise ValueError(
                f"sync_every_per_slice entries must be ints >= 1, got "
                f"{per}")
        if any(h % sync_every for h in per):
            raise ValueError(
                f"every sync_every_per_slice entry must be a multiple "
                f"of the base sync_every={sync_every} (slices exchange "
                f"only at base boundaries), got {per}")
        if min(per) != sync_every:
            raise ValueError(
                f"min(sync_every_per_slice)={min(per)} must equal the "
                f"base sync_every={sync_every}: the base is the "
                f"tightest slice's cadence — a larger base would mean "
                f"boundaries no compiled program runs")
    if sync_every == 1:
        return
    if not mesh:
        raise ValueError(
            f"sync_every={sync_every} needs a device mesh: the meshless "
            f"single-jit path has no collective exchange to amortize "
            f"(and no per-device window state); use a mesh-backed "
            f"strategy or sync_every=1")
    if pp:
        raise ValueError(
            f"sync_every={sync_every} is incompatible with pipeline "
            f"parallelism (pp > 1): the pipeline schedule has no "
            f"per-step data exchange a window could skip")
    if grad_accum > 1:
        raise ValueError(
            f"sync_every={sync_every} with grad_accum={grad_accum}: "
            f"grad accumulation already amortizes the exchange over its "
            f"micro-steps — composing the two would double-count the "
            f"window; pick one")
    if trainer == "train" and overlap:
        raise ValueError(
            f"sync_every={sync_every} with overlap=True: the in-backward "
            f"markers stream the per-step collective a window removes; "
            f"run windows post-backward (overlap=False)")
    if trainer == "lm" and dcn_size is not None and dcn_size < 2:
        raise ValueError(
            f"sync_every={sync_every} needs dcn_size >= 2 on the LM "
            f"trainer: windows relax the slow DCN hop specifically — "
            f"with a single slice there is no scarce axis to relax")
    if (trainer == "train" and steps_per_loop is not None
            and steps_per_loop % sync_every):
        raise ValueError(
            f"steps_per_loop={steps_per_loop} is not a multiple of "
            f"sync_every={sync_every}: each compiled dispatch must end "
            f"on a window boundary so params leave the step replicated")
