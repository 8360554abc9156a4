"""Continuous batching: slot-based autoregressive serving.

The reference has no inference stack at all; ``generate.py`` adds static
batch decoding, and this module adds the serving-shaped missing piece:
**continuous batching** — a fixed pool of cache slots where sequences
enter (prefill into a free slot), decode in lockstep (ONE compiled ragged
step per token for every active slot), and retire independently (EOS or
length budget), their slot immediately refilled from the queue.  Unlike
static batching, a short request never waits for the batch's longest one.

TPU-first design constraints drive the shape:

- static shapes everywhere: the slot pool is a fixed (slots, Hkv, max_len,
  D) KV cache per layer; prompts pad to bucketed lengths (one compiled
  prefill per bucket) and the decode step is one compiled program
  regardless of which slots are live;
- per-sequence exactness comes from the ragged decode path
  (generate.decode_step_ragged): every sequence reads exactly its own
  ``pos+1`` cache prefix (the Pallas decode kernel's per-sequence
  scalar-prefetch bounds on TPU) and writes its K/V at its own offset;
- slot recycling needs no cache zeroing: a slot's stale K/V beyond the new
  occupant's write frontier is never read (reads are bounded by the
  occupant's own ``pos``), and each decode step overwrites its slot before
  the bound reaches it;
- the host side is a plain queue + bookkeeping: submission order is FIFO,
  retirement is per-sequence, and the device never waits on the host
  between steps beyond the sampled-token fetch that drives EOS detection;
- **multi-token scheduling** (``steps_per_sync``): the device decodes K
  tokens per dispatch as one ``lax.scan`` and the host processes the K x
  slots block at once — every sync costs a dispatch and a fetch (~0.2 ms
  and ~1.6 ms for a ready one-element value on the v5e machine, PR 21's
  chip smoke) plus the host's parse, which per token is of the order of a
  decode step itself.  The block is a
  DEVICE-SIDE EARLY-EXIT ``while_loop``: it ends as soon as every slot's
  request has sampled its eos or exhausted its budget (empty slots never
  extend it), so a 32-step block with 3 tokens of work runs 3 iterations
  — no host round-trip pays for the cut.  What remains at block
  granularity: a sequence retiring mid-block while OTHERS run on wastes
  its in-flight slot-steps, and its slot refills only at the next sync;
  ``stats`` accounts for every dispatched slot-step (emitted vs wasted);
- **per-request sampling**: temperature/top_k/top_p/eos_id are
  ``submit()`` parameters — the compiled decode step samples every slot
  with its own settings (gen.sample_per_seq), so a greedy request and a
  hot nucleus-sampled one share a dispatch;
- **chunked prefill** (``prefill_chunk``): admissions prefill a fixed
  chunk of prompt per ``step()`` into a scratch cache (attending causally
  to earlier chunks), interleaved with the pool's decode dispatches — a
  long prompt never stalls running slots for more than one chunk-sized
  dispatch;
- **in-block slot refill** (``inblock_refill``, round 4): the decode
  block dispatches K lockstep steps for the WHOLE pool whether or not a
  slot has work — an empty or mid-block-retired slot costs exactly the
  same device time computing garbage.  So instead of idling, such a slot
  consumes its next queued request's prompt one token per step
  (teacher-forced through the same ragged decode step, which writes the
  prompt token's K/V and discards the logits) and starts emitting the
  moment the prompt is exhausted — prefill and the retire→admit
  transition ride steps that run anyway, INSIDE the compiled
  ``while_loop``.  This closes the two block-granularity losses the
  round-3 accounting quantified (BASELINE.md: ~25% of slot-steps wasted
  to budget imbalance + admission idling): a retiring slot hands off to
  the next request in the same dispatch, and admissions stop idling
  through decode blocks.  Batched (bucketed/chunked) prefill still
  serves an idle pool and prompts wider than the in-block prompt buffer
  (the largest bucket);
- **preemption** (round 4, ``paged=True``): when live sequences outgrow
  an oversubscribed page pool, the youngest occupant is host-swapped —
  its pages gather to host memory in one packed fetch, the request
  waits on a resume queue, and the pages scatter back when the pool has
  room — instead of raising.  Host-swap rather than re-prefill because
  the generated prefix can exceed every compiled prompt bucket; the
  request resumes mid-generation with bitwise-identical KV;
- **in-batcher speculation** (round 5, ``speculate`` = n_spec): the
  decode block becomes a while_loop of speculation ROUNDS — every slot
  proposes n_spec tokens by prompt-lookup from its own stream and one
  (slots, n_spec+1)-token ragged verify forward checks them all
  (``_decode_spec_for``).  Decode at serving batch sizes is
  weight-read-bound, so emitting the accepted prefix per ONE weight
  pass is where the round-4 static-path speculation speedup actually
  pays; greedy slots stay exact-greedy, temperature>0 slots get exact
  warped-distribution sampling via point-mass rejection;
- **prefix caching** (round 5, ``prefix_cache=True``, paged): full
  512-token prompt pages are content-addressed by chain hash and
  SHARED across requests through the block tables with refcounts — a
  repeated system prompt admits by reusing the cached pages and
  prefilling only its suffix (one ``verify_step_ragged`` window
  attending the shared prefix).  Sharing is read-only by construction
  (decode writes always land in the slot's own fresh tail pages);
  unreferenced cached pages are reclaimed LRU under pool pressure
  before any occupant is preempted;
- **overlapped dispatch** (round 6, ``overlap=True``, default): the
  sequential loop — plan, dispatch, FETCH, parse, plan ... — leaves the
  device idle for a full host round-trip plus all host planning between
  blocks (how large that idle share is on the current machine is not
  measured).  The decode block's
  per-slot state machine (token, write position, prompt offset,
  remaining budget, done/active flags) is therefore threaded through
  the compiled block as an explicit device-side CARRY: when the host
  can prove the next block needs no intervention (every live slot
  either cannot retire within the next two blocks or hands off to an
  already-staged refill; no admissions are possible; pages cover the
  worst case — ``_try_chain``), block N+1 is dispatched DIRECTLY from
  block N's carry, BEFORE block N's packed results are fetched — the
  fetch RTT and the host-side parse then overlap block N+1's device
  compute instead of serializing with it.  Outputs are oracle-exact by
  construction: a chained block is the same compiled program the
  serial path would have dispatched (the carry holds exactly the state
  the host would have re-staged), host-visible emissions just arrive
  one ``step()`` later.  When the conditions fail (admission wanted,
  retirement without a staged successor, pool pressure, speculation,
  drained-tail compaction), the loop falls back to the serial
  plan→dispatch→fetch→parse order for that block.  Per-phase wall
  clock (plan / dispatch / fetch / parse) is accounted by a
  ``utils.tracing.PhaseTimer`` (``timing_stats()``), so ms/token
  decomposes instead of being one opaque number; each segment is also
  a ``serve.<phase>`` span on the profiler's clock, beside the device's
  operations, and ``stats["unchained_*"]`` count which condition failed.
  The cache and carry,
  plus the speculative block's staging dict, are DONATED
  (``compat.donate``), saving a transient HBM copy per dispatch.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .models import transformer as tfm
from . import generate as gen
from .utils import compat, monitor
from .utils.tracing import PhaseTimer, span


# submit() sentinel: "inherit the batcher default" — distinct from None,
# which explicitly DISABLES eos for that request
_INHERIT = object()


def prefix_page_hashes(prompt, page: int) -> list[bytes]:
    """Chain hash per FULL ``page``-token prompt page: page i's key
    commits to tokens [0, (i+1)*page), so equal keys imply the cached
    page's K/V was computed under the identical token context.
    Module-level because the fleet router (fleet/router.py) scores
    replicas by walking these same chains against each replica's page
    registry — the router and the batcher must hash identically or
    prefix-aware routing silently degrades to load balancing."""
    import hashlib
    prompt = np.asarray(prompt)
    out: list[bytes] = []
    h = b""
    for i in range(len(prompt) // page):
        h = hashlib.sha1(
            h + prompt[i * page:(i + 1) * page]
            .astype(np.int32).tobytes()).digest()
        out.append(h)
    return out


@dataclass
class _Request:
    rid: int
    prompt: np.ndarray            # (L,) int32
    max_new: int
    # per-request sampling (resolved against the batcher defaults at
    # submit): every slot can serve a different temperature/top_k/top_p/
    # eos in the same compiled decode step (gen.sample_per_seq)
    temperature: float = 1.0
    top_k: int = 0                # 0 = disabled
    top_p: float = 1.0            # >= 1 = disabled
    eos_id: int | None = None
    emitted: list = field(default_factory=list)
    done: bool = False
    # chain hashes of the prompt's full pages, computed ONCE at submit
    # when prefix caching is on (lookups run per scheduling decision)
    prefix_hashes: list | None = None
    # set once this request's full prompt pages were offered to the
    # registry — keeps the per-block publish hook O(1) for slots whose
    # prompt already published (batch admission, shared admission, or an
    # earlier block)
    pages_published: bool = False
    # latency bookkeeping (host clock; token times land at block syncs,
    # which is when the serving layer can actually hand tokens out)
    t_submit: float = 0.0
    # the moment the request took a slot (``_stamp_admit``): submit ->
    # admit is queue wait, admit -> first is prefill + first sampling
    t_admit: float | None = None
    t_first: float | None = None  # first emission (TTFT = t_first - t_submit)
    t_done: float | None = None


@dataclass
class _Admission:
    """A request mid-prefill (chunked): its reserved slot's scratch cache
    fills one prompt chunk per ``step()`` call, so live slots keep
    decoding between chunks instead of stalling for the whole prompt."""
    req: _Request
    cache: object                 # (1, hkv, bucket, d) scratch slabs
    bucket: int
    off: int = 0                  # tokens prefilled so far
    last_logits: object = None    # set once the final chunk ran; the
    #                               install can then wait for pool pages


@dataclass
class _Swapped:
    """A preempted request: its KV pages live on the HOST until the pool
    can take it back (serve paged=True).  Host-swap rather than requeue-
    and-re-prefill because the generated prefix can outgrow every
    compiled prompt bucket — restoring the pages bitwise keeps the
    request exactly where it was, mid-generation."""
    req: _Request
    kv: list                      # per cache leaf: (n_pages, hkv, page, *)
    n_pages: int
    pos: int                      # last written position
    poff: int                     # prompt progress (mid-prefill victims)
    last_tok: int


@dataclass
class _InFlight:
    """A dispatched-but-not-yet-fetched decode block (``overlap=True``):
    everything ``_collect`` needs to parse its packed results, plus the
    device-side carry and staging dicts a chained successor dispatch
    reuses (``_try_chain``)."""
    packed: object                # device (P,) int32; fetched at collect
    carry: dict                   # device per-slot machine state at block end
    cur: dict                     # device staging (reusable by a chained block)
    ref: dict                     # device refill staging (ditto)
    live: list                    # slots live at dispatch
    cols: dict                    # slot -> packed column
    w: int                        # compiled row count
    compact: bool
    npad: int
    plen: np.ndarray              # dispatch-time per-slot prompt lengths
    active0: np.ndarray           # rows already switched to their refill
    headroom: np.ndarray          # per-slot prompt-left + budget at dispatch
    upto: np.ndarray              # per-slot worst-case write frontier (paged)
    chainable: bool               # block flavor admits a chained successor
    refs_held: bool = False       # a chained successor reuses the staged refs


class ContinuousBatcher:
    """Fixed-slot continuous batching over one model.

    Usage::

        cb = ContinuousBatcher(params, cfg, slots=4, max_len=512,
                               eos_id=0, temperature=0.8, top_k=50)
        rid = cb.submit(prompt_tokens, max_new=128)   # queue (any number)
        while cb.pending():
            for rid, tok in cb.step():               # one token per active
                ...                                   # slot, as they land
        out = cb.result(rid)                          # (L + emitted,) int32

    ``run(prompts, max_new)`` drives submit/step to completion.
    """

    def __init__(self, params, cfg: tfm.TransformerConfig, *,
                 slots: int = 4, max_len: int = 1024,
                 temperature: float = 1.0, top_k: int | None = None,
                 top_p: float | None = None,
                 eos_id: int | None = None, dtype=None,
                 prompt_buckets: tuple[int, ...] = (32, 128, 512),
                 seed: int = 0, decode_kernel: bool | None = None,
                 steps_per_sync: int = 8,
                 prefill_chunk: int | None = None,
                 paged: bool = False, pool_pages: int | None = None,
                 inblock_refill: bool = True,
                 schedule: str = "fifo",
                 compact_tail: bool = True,
                 speculate: int = 0, spec_ngram: int = 2,
                 prefix_cache: bool = False,
                 overlap: bool = True,
                 kv_dtype=None,
                 mesh=None, tp_axis: str = "model"):
        tfm.require_servable(cfg, "ContinuousBatcher")
        self.params = params
        self.cfg = cfg
        self.slots = slots
        # INT8 KV cache (``kv_dtype="int8"``): the pool stores int8 K/V
        # with per-row float32 scales as extra rank-4 cache leaves
        # ("ks"/"vs") — writes quantize inside the SAME compiled blocks
        # (gen._forward_cached infers it from the pytree), the decode
        # kernels dequantize in their tiles, and the scales ride the
        # block tables / host-swap / prefix-sharing machinery because
        # they are just more pool leaves indexed by page id.  Halves the
        # HBM cache read per decode step vs bf16 AND roughly doubles the
        # sequences a byte-budgeted page pool admits (gen.kv_bytes_per_
        # token), which is the admission/preemption-pressure lever.
        self.kv_dtype = gen.canon_kv_dtype(kv_dtype)
        # whole 512-slot blocks keep the decode kernel's tiles MXU-friendly
        self.max_len = gen.pad_cache_len(max_len)
        # IN-BATCHER SPECULATION (``speculate`` = n_spec > 0): each
        # round, every slot proposes n_spec tokens by prompt-lookup from
        # its own stream (trailing ``spec_ngram`` match) and ONE
        # (slots, n_spec+1)-token ragged verify forward checks them all
        # — accepted prefixes advance multiple positions per weight
        # read, greedy slots get exact-greedy outputs and temperature>0
        # slots exact warped-distribution sampling (point-mass rejection:
        # accept proposal x with prob p(x), resample from p minus x).
        # The cache gains one extra 512-block of headroom: the verify
        # window writes up to n_spec positions past the accepted
        # frontier, and those garbage rows must never clamp onto live
        # ones.
        if speculate < 0:
            raise ValueError(f"speculate must be >= 0, got {speculate}")
        self.n_spec = speculate
        self.spec_ngram = spec_ngram
        if speculate and spec_ngram < 1:
            raise ValueError(f"spec_ngram must be >= 1, got {spec_ngram}")
        self.kv_len = (gen.pad_cache_len(self.max_len + speculate + 1)
                       if speculate else self.max_len)
        self._spec_fns: dict[int, object] = {}
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.eos_id = eos_id
        self.dtype = dtype
        self.buckets = tuple(sorted(b for b in prompt_buckets
                                    if b <= self.max_len))
        if not self.buckets:
            raise ValueError(f"no prompt bucket fits max_len {max_len}")
        self.use_kernel = gen.default_decode_kernel(decode_kernel)
        if steps_per_sync < 1:
            raise ValueError(f"steps_per_sync must be >= 1, got "
                             f"{steps_per_sync}")
        self.steps_per_sync = steps_per_sync
        # Chunked prefill: admissions prefill ``prefill_chunk`` prompt
        # tokens per step() call, interleaved with the pool's decode
        # dispatches — a long prompt never stalls running slots for more
        # than one chunk.  None = whole-bucket single-dispatch prefill.
        if prefill_chunk is not None:
            if prefill_chunk < 1:
                raise ValueError(f"prefill_chunk must be >= 1, got "
                                 f"{prefill_chunk}")
            bad = [b for b in self.buckets if b % prefill_chunk]
            if bad:
                raise ValueError(
                    f"prefill_chunk {prefill_chunk} must divide every "
                    f"prompt bucket (violates {bad})")
        self.prefill_chunk = prefill_chunk
        # Tensor-parallel serving: with ``mesh``, params stay in their
        # Megatron tfm.shard_specs sharding, the slot pool's kv heads
        # shard over ``tp_axis``, and prefill/decode run inside shard_map
        # (two psums per layer), exactly like generate_tp.
        self.mesh = mesh
        self.tp_axis = tp_axis
        if mesh is not None:
            ntp = mesh.shape[tp_axis]
            if cfg.n_heads % ntp or cfg.kv_heads % ntp:
                raise ValueError(
                    f"heads ({cfg.n_heads} q / {cfg.kv_heads} kv) must "
                    f"divide over the {ntp}-way '{tp_axis}' axis")
            if cfg.n_experts and cfg.n_experts % ntp:
                raise ValueError(f"{cfg.n_experts} experts do not shard "
                                 f"over {ntp} devices")
        # sharded jax arrays report their GLOBAL shape, so this is
        # cfg.kv_heads in the TP case too
        self.kv_heads = params["layer0"]["wk"].shape[1]
        # PAGED KV pool (vLLM-style, TPU-native): K/V live in a shared pool
        # of 512-token pages owned via per-slot block tables instead of
        # per-slot max_len buffers — cache memory scales with pages
        # actually allocated.  The page indirection rides the decode
        # kernel's scalar-prefetch index maps (measured free on TPU);
        # paged therefore requires the kernel decode path.
        self.paged = paged
        # one page is one decode-kernel tile: at 16 kv heads x 128 the K
        # and V tiles, double-buffered, take 4 MiB (int8), 8 MiB (bf16)
        # or 16 MiB (float32) of VMEM — the last is over the default
        # limit, and the kernel asks for more
        # (ops/attention._decode_compiler_params)
        self.page = 512
        self.pages_per_slot = self.kv_len // self.page
        if paged:
            if not self.use_kernel and decode_kernel is not None:
                raise ValueError("paged serving requires the decode-kernel "
                                 "path (the page table lives in its index "
                                 "maps); drop decode_kernel=False")
            self.use_kernel = True  # interpret mode covers off-TPU runs
            # page 0 is a RESERVED SCRATCH page, never allocated: empty
            # and freed slots' table rows point at it, so their lockstep
            # garbage writes (done slots keep computing until the block
            # exits) land there instead of corrupting recycled pages.
            self.pool_pages = (pool_pages if pool_pages is not None
                               else slots * self.pages_per_slot + 1)
            if self.pool_pages - 1 < self.pages_per_slot:
                raise ValueError(
                    f"pool_pages {self.pool_pages} cannot hold even one "
                    f"max_len sequence ({self.pages_per_slot} pages + the "
                    f"reserved scratch page)")
            self.cache = gen.init_paged_cache(cfg, self.pool_pages,
                                              self.page,
                                              dtype=dtype or jnp.float32,
                                              kv_heads=self.kv_heads,
                                              kv_dtype=self.kv_dtype)
            self.table = np.zeros((slots, self.pages_per_slot), np.int32)
            self.free_pages = deque(range(1, self.pool_pages))
            self.slot_pages: list[list[int]] = [[] for _ in range(slots)]
        else:
            self.cache = gen.init_cache(cfg, slots, self.kv_len,
                                        dtype=dtype or jnp.float32,
                                        kv_heads=self.kv_heads,
                                        kv_dtype=self.kv_dtype)
        # PREFIX CACHING (paged only): full 512-token pages of prompt K/V
        # are content-addressed by a per-page CHAIN hash (page i's key
        # commits to every token before it, so matching hash == matching
        # K/V context) and SHARED across requests via the block tables —
        # a repeated system prompt admits by pointing its table at the
        # cached pages (refcounted) and prefilling only the suffix.
        # Sharing is read-only by construction rather than copy-on-write:
        # decode writes land at positions >= the prompt length, which
        # always fall in the slot's own fresh tail pages (the partial
        # tail page is never registered), so no occupant ever writes a
        # shared page.  Retired requests' registered pages stay in the
        # registry at refcount 0 (that IS the cache) and are reclaimed
        # LRU under pool pressure before any occupant is preempted.
        self.prefix_cache = prefix_cache
        if prefix_cache:
            if not paged:
                raise ValueError("prefix_cache requires paged=True (the "
                                 "sharing rides the block tables)")
            if prefill_chunk is not None:
                raise ValueError(
                    "prefix_cache does not compose with prefill_chunk: "
                    "chunked admission re-prefills every prompt and "
                    "would silently never share pages — a shared-prefix "
                    "admission is already one suffix-sized dispatch, "
                    "which is the latency problem chunking solves")
            self.registry: dict[bytes, int] = {}   # chain hash -> page id
            self.page_hash: dict[int, bytes] = {}  # registered page -> hash
            self.page_refs: dict[int, int] = {}    # registered page -> refs
            self._suffix_fns: dict[int, object] = {}
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            self._cache_spec = jax.tree.map(lambda _: P(None, tp_axis),
                                            self.cache)
            self.cache = jax.device_put(
                self.cache,
                jax.tree.map(lambda sp: NamedSharding(mesh, sp),
                             self._cache_spec))
            self._param_specs = tfm.shard_specs(cfg, tp_axis=tp_axis)
        self.key = jax.random.key(seed)
        # host-side slot state
        self.pos = np.zeros(slots, np.int32)        # last written position
        self.occupant: list[_Request | None] = [None] * slots
        self.last_tok = np.zeros(slots, np.int32)   # next input token
        # per-slot sampling params, mirrored from each slot's occupant
        self.slot_temp = np.ones(slots, np.float32)
        self.slot_topk = np.zeros(slots, np.int32)
        self.slot_topp = np.ones(slots, np.float32)
        self.slot_eos = np.full(slots, -1, np.int32)  # -1 = no eos
        self.admitting: dict[int, _Admission] = {}  # slot -> in-progress
        self.queue: deque[_Request] = deque()
        self.requests: dict[int, _Request] = {}
        self._next_rid = 0
        self._prefill_fns: dict[int, object] = {}
        self._chunk_fns: dict[tuple[int, bool], object] = {}
        self._decode_fns: dict[int, object] = {}
        self._insert_fn = None
        self._insert_paged_fn = None
        # in-block refill (see module docstring): per-slot prompt progress
        # of the CURRENT occupant (poff >= len(prompt) = prefill complete),
        # plus the staged next-in-line request per slot
        self.inblock_refill = inblock_refill
        self.refill_width = self.buckets[-1]  # in-block prompt buffer
        # In-block ADMISSION (empty slot while the pool runs) teacher-
        # forces at one token per lockstep step, so it is only dispatch-
        # efficient for prompts on the order of a block (or a chunk, when
        # chunked prefill would otherwise batch them); longer prompts
        # keep the batched admission path.  The retire→refill HANDOFF is
        # exempt (full buffer width): it activates inside a block that is
        # running anyway, where the alternative is an idle slot.
        self.inblock_admit_limit = min(
            self.refill_width,
            max(steps_per_sync, prefill_chunk or steps_per_sync))
        # Queue discipline: "fifo" (arrival order), or "longest_first"
        # (LPT: admit the largest remaining budgets first, so slots
        # drain together and the end-of-stream tail — empty slots riding
        # lockstep while the last long request finishes — collapses).
        # LPT trades per-request fairness (short requests queue behind
        # long ones) for pool utilization; batch/offline serving wants
        # it, interactive serving keeps fifo.
        if schedule not in ("fifo", "longest_first"):
            raise ValueError(f"unknown schedule {schedule!r}: expected "
                             f"'fifo' or 'longest_first'")
        self.schedule = schedule
        self._queue_dirty = False
        # Drained-tail batch compaction (paged only): narrower compiled
        # blocks once no queued/staged work remains.  Determinism
        # caveats: (a) bf16 GREEDY streams can near-tie-flip at the
        # compaction boundary (a narrower dispatch is a different
        # accumulation shape; same ~0.3%/position rate as any
        # cross-shape bf16 comparison — BASELINE.md flip-rate table);
        # (b) SAMPLED (temperature > 0) streams change at the boundary
        # in ANY dtype — sample_per_seq draws per-row randomness over
        # the dispatch shape, so a request's draws shift when its row
        # moves.  compact_tail=False keeps every dispatch full-width
        # when seeded reproducibility matters; f32 greedy is exact
        # either way.
        self.compact_tail = compact_tail
        # Overlapped dispatch (module docstring): when the host can prove
        # the next block needs no intervention, it is dispatched from the
        # previous block's device-side carry BEFORE that block's results
        # are fetched — the fetch RTT and host parse hide under device
        # compute.  Emissions then arrive one step() later; oracle
        # exactness is unchanged (a chained block is the same program the
        # serial path would have dispatched).  The speculative block
        # keeps the serial order (its host parse is round-structured).
        self.overlap = overlap
        self._inflight: _InFlight | None = None
        self._break_chain = False
        # per-phase wall-clock attribution (host_plan / dispatch / fetch /
        # host_parse / prefill): timing_stats() summarizes
        self.timers = PhaseTimer()
        self.slot_poff = np.zeros(slots, np.int32)
        self.staged_refill: list[_Request | None] = [None] * slots
        self._staged_order: list[int] = []
        if paged:
            self.refill_pages: list[list[int]] = [[] for _ in range(slots)]
            self.r_table = np.zeros((slots, self.pages_per_slot), np.int32)
            # preemption: victims host-swap their pages and wait here;
            # admission sequence numbers pick the YOUNGEST victim
            self.swapped: deque[_Swapped] = deque()
            self.slot_admit_seq = np.zeros(slots, np.int64)
            self._admit_counter = 0
            self._gather_fn = None
            self._scatter_fn = None
        # accounting (BASELINE.md serving roofline): slot-steps dispatched
        # vs tokens actually delivered — the block-granularity waste.
        # inblock_prefill_steps are dispatched slot-steps consumed
        # teacher-forcing a prompt (useful work, counted separately from
        # emitted sampled tokens); utilization = (emitted + inblock
        # prefill) / slot_steps
        self.stats = {"decode_dispatches": 0, "slot_steps": 0,
                      "emitted_tokens": 0, "wasted_slot_steps": 0,
                      "prefill_dispatches": 0, "batch_admissions": 0,
                      "inblock_prefill_steps": 0, "inblock_refills": 0,
                      "evictions": 0, "swap_ins": 0,
                      "compact_dispatches": 0,
                      # overlap: blocks dispatched from the previous
                      # block's device carry, before its results were
                      # fetched (the fetch RTT hid under device compute)
                      "chained_dispatches": 0,
                      # ... and why a block was NOT: one counter per
                      # reason _try_chain declines; with
                      # chained_dispatches they add up to its calls
                      "unchained_off": 0, "unchained_admitting": 0,
                      "unchained_empty_slot": 0,
                      "unchained_retire_unstaged": 0,
                      "unchained_refill_pages": 0, "unchained_pool": 0,
                      # admission clock (sums, so a reader can take a
                      # window's delta): requests that took a slot and
                      # their submit -> admit wait; first emissions and
                      # their admit -> first-token time
                      "admitted": 0, "queue_wait_s": 0.0,
                      "first_tokens": 0, "admit_to_first_s": 0.0,
                      # speculation accounting (speculate > 0):
                      # slot_steps then counts dispatched VERIFY
                      # POSITIONS (rounds x slots x window) — the
                      # position-efficiency denominator; the speedup
                      # itself shows up as fewer rounds (weight reads)
                      # per emitted token = emitted / (spec_rounds x
                      # slots)
                      "spec_rounds": 0, "spec_proposed": 0,
                      "spec_accepted": 0,
                      # prefix caching: admissions that reused cached
                      # prompt pages, pages reused, and registry pages
                      # reclaimed under pool pressure
                      "prefix_hits": 0, "prefix_pages_shared": 0,
                      "prefix_reclaimed": 0,
                      # fleet handoffs (export_request / import_request):
                      # requests that left this batcher mid-flight as a
                      # portable KV unit, and ones admitted from one
                      "handoff_exports": 0, "handoff_imports": 0}

    # -- submission / results --------------------------------------------
    def submit(self, prompt, max_new: int = 128, *,
               temperature: float | None = None,
               top_k: int | None = None,
               top_p: float | None = None,
               eos_id=_INHERIT) -> int:
        """Queue a request.  Sampling parameters default to the batcher's;
        each request's settings apply to its slot only (the compiled decode
        step samples every slot with its own temperature/top_k/top_p).
        ``eos_id=None`` explicitly disables eos for this request even when
        the batcher has a default (omit the argument to inherit)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if len(prompt) == 0:
            raise ValueError("empty prompt")
        if len(prompt) > self.buckets[-1]:
            raise ValueError(
                f"prompt of {len(prompt)} tokens exceeds the largest "
                f"bucket {self.buckets[-1]}")
        if max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {max_new}")
        if len(prompt) + max_new > self.max_len:
            raise ValueError(
                f"prompt {len(prompt)} + max_new {max_new} exceeds "
                f"max_len {self.max_len}")
        rid = self._next_rid
        self._next_rid += 1
        top_k = self.top_k if top_k is None else top_k
        top_p = self.top_p if top_p is None else top_p
        req = _Request(
            rid, prompt, max_new,
            temperature=(self.temperature if temperature is None
                         else temperature),
            top_k=0 if top_k is None else top_k,
            top_p=1.0 if top_p is None else top_p,  # 0.0 stays: -> greedy
            eos_id=self.eos_id if eos_id is _INHERIT else eos_id)
        req.t_submit = time.perf_counter()
        if self.prefix_cache:
            req.prefix_hashes = self._prefix_hashes(req.prompt)
        self.requests[rid] = req
        self.queue.append(req)
        self._queue_dirty = True
        return rid

    def pending(self) -> bool:
        return (bool(self.queue) or bool(self.admitting)
                or (self.paged and bool(self.swapped))
                or self._inflight is not None
                or any(o is not None for o in self.occupant))

    def queue_depth(self) -> int:
        """Requests accepted but not yet generating (queued + mid-
        admission) — the backlog signal the fleet autoscaler watches."""
        return len(self.queue) + len(self.admitting)

    def timing_stats(self) -> dict:
        """Per-phase wall-clock summary (count / total / p50 / p95 per
        phase) over every ``step()`` so far: ``host_plan`` (admission +
        staging), ``dispatch`` (program enqueue), ``fetch`` (the blocking
        device->host transfer of a block's packed results), ``host_parse``
        (emission bookkeeping), ``prefill`` (admission dispatches).  With
        ``overlap`` on, ``fetch`` time is wall clock that ran CONCURRENTLY
        with the chained successor's device compute — compare against the
        serial (``overlap=False``) breakdown to see the hidden cost."""
        return self.timers.summary()

    def result(self, rid: int) -> np.ndarray:
        req = self.requests[rid]
        return np.concatenate([req.prompt,
                               np.asarray(req.emitted, np.int32)])

    def latency_stats(self) -> dict[str, float]:
        """Per-request latency percentiles over COMPLETED requests, in
        seconds (host clock; a token's timestamp is the block sync that
        delivered it — the moment the serving layer could hand it out,
        transfer included).  With no
        completed requests yet, returns ``{"completed": 0}`` ONLY — the
        percentile keys exist once ``completed`` is positive:

        - ``ttft_*``: time to first token (submit -> first emission);
          under in-block admission this includes queue wait;
        - ``queue_wait_*`` and ``admit_to_first_*``: its two parts, split
          at the moment the request took a slot (``t_admit``);
        - ``total_*``: submit -> retirement.

        No per-request decode rate is reported: token timestamps have
        BLOCK granularity (a whole burst lands at one sync), so
        tokens/(t_done - t_first) would exclude the first block's work
        from the denominator and overstate wildly for short requests —
        use aggregate throughput (emitted tokens / wall) instead.
        """
        done = [r for r in self.requests.values()
                if r.done and r.t_done is not None]
        if not done:
            return {"completed": 0}
        spans = {"ttft": [r.t_first - r.t_submit for r in done],
                 "queue_wait": [r.t_admit - r.t_submit for r in done],
                 "admit_to_first": [r.t_first - r.t_admit for r in done],
                 "total": [r.t_done - r.t_submit for r in done]}
        out: dict[str, float] = {"completed": len(done)}
        for name, values in spans.items():
            out[f"{name}_p50"] = float(np.percentile(values, 50))
            out[f"{name}_p95"] = float(np.percentile(values, 95))
        return out

    def utilization(self) -> float:
        """RAW DISPATCH slot-step utilization: (sampled emissions from
        decode dispatches + in-block teacher-forced prefill steps) /
        dispatched slot-steps.  Each batch-prefilled admission's first
        token came from its prefill dispatch, not a slot-step — the
        single source of truth for the BASELINE.md serving tables.

        Under speculation (``speculate > 0``) ``slot_steps`` counts
        dispatched VERIFY POSITIONS, so rejected proposals count as
        dispatched work and this reads low BY DESIGN (0.18-0.28 on the
        round-5 workloads) — use ``emitted_per_slot_step`` for the
        acceptance-adjusted number (VERDICT r5 weak #4).

        A batcher that never dispatched a decode block (fresh, or a
        fleet replica drained/exported before its first block) reports
        0.0 — never a ZeroDivisionError."""
        s = self.stats
        if s["slot_steps"] == 0:
            return 0.0
        return ((s["emitted_tokens"] - s["batch_admissions"]
                 + s["inblock_prefill_steps"]) / s["slot_steps"])

    def emitted_per_slot_step(self) -> float:
        """ACCEPTANCE-ADJUSTED utilization: sampled emissions actually
        delivered per dispatched slot-step.  Identical denominator to
        ``utilization`` but the numerator counts only emitted tokens
        (useful-positions accounting): under speculation this is
        emissions per verify position — the number that stays meaningful
        when rejected proposals inflate ``slot_steps`` — and without
        speculation it differs from ``utilization`` only by the teacher-
        forced in-block prefill steps.  Zero dispatched blocks (a
        drained replica) reads 0.0, as in ``utilization``."""
        s = self.stats
        if s["slot_steps"] == 0:
            return 0.0
        return ((s["emitted_tokens"] - s["batch_admissions"])
                / s["slot_steps"])

    # -- fleet handoff: export / import a request mid-flight ---------------
    def _flush_inflight(self) -> list[tuple[int, int]]:
        """Collect the overlapped in-flight block (if any) serially, so
        the host bookkeeping is caught up with the device before a
        request's state is exported.  Emissions land in each request's
        ``emitted`` list (and are returned) — nothing is lost."""
        out: list[tuple[int, int]] = []
        fl, self._inflight = self._inflight, None
        if fl is not None:
            out += self._collect(fl)
        return out

    def export_request(self, rid: int) -> dict | None:
        """Extract a not-yet-completed request as a portable state dict
        (the payload of ``fleet.handoff.KVHandoff``): prompt + resolved
        sampling parameters + tokens emitted so far, and — when the
        request holds pool pages — its KV pages as host arrays fetched
        through the host-swap gather path (one awaited dispatch; int8
        scale leaves ride along as extra leaves).  The request leaves
        this batcher entirely: its slot/pages/queue entry are released
        and its rid forgotten.

        ``kv`` is None for requests that never produced KV worth moving
        (still queued, staged, or mid-chunked-prefill — cheaper to
        re-prefill than to ship a partial scratch cache) and for dense
        (non-paged) occupants, whose cache is not a portable page unit.
        A ``kv=None`` export with emitted tokens can only continue by
        re-prefilling prompt+emitted — ``import_request`` rejects it and
        the fleet router owns that fallback.

        Returns None when the request completed inside the in-flight
        block this call had to flush first (its result is final — read
        it with ``result`` before the rid is reused)."""
        req = self.requests.get(rid)
        if req is None:
            raise KeyError(f"unknown request {rid}")
        if req.done:
            raise ValueError(f"request {rid} already completed")
        # a dispatched-but-unfetched block may still emit for this
        # request: collect it so the exported stream is complete
        self._flush_inflight()
        if req.done:
            return None
        state = {"prompt": np.asarray(req.prompt, np.int32),
                 "max_new": req.max_new, "temperature": req.temperature,
                 "top_k": req.top_k, "top_p": req.top_p,
                 "eos_id": req.eos_id, "emitted": list(req.emitted),
                 "kv": None, "n_pages": 0, "pos": 0, "poff": 0,
                 "last_tok": 0}
        if req in self.queue:
            self.queue.remove(req)
        elif any(req is r for r in self.staged_refill):
            slot = next(s for s, r in enumerate(self.staged_refill)
                        if r is req)
            self.staged_refill[slot] = None
            self._staged_order.remove(slot)
            if self.paged:
                self._release_refill_pages(slot)
        elif any(adm.req is req for adm in self.admitting.values()):
            # chunked prefill in progress: drop the scratch progress,
            # the importer re-prefills from the prompt
            slot = next(s for s, adm in self.admitting.items()
                        if adm.req is req)
            del self.admitting[slot]
        elif self.paged and any(sw.req is req for sw in self.swapped):
            # already host-swapped: the pages ARE the handoff payload
            sw = next(sw for sw in self.swapped if sw.req is req)
            self.swapped.remove(sw)
            state.update(kv=[np.asarray(x) for x in sw.kv],
                         n_pages=sw.n_pages, pos=sw.pos, poff=sw.poff,
                         last_tok=sw.last_tok)
        elif any(o is req for o in self.occupant):
            slot = next(s for s, o in enumerate(self.occupant)
                        if o is req)
            if self.paged and self.slot_pages[slot]:
                # the _evict gather, aimed at the handoff instead of the
                # local resume queue.  np.array(copy=True): the payload
                # outlives this batcher's donated cache chain, so it
                # must own its buffers (np.asarray can be a zero-copy
                # view of the device buffer on the CPU backend).
                pids = np.zeros(self.pages_per_slot, np.int32)
                n = len(self.slot_pages[slot])
                pids[:n] = self.slot_pages[slot]
                gather, _ = self._page_io_fns()
                n2 = min(self._pow2(n), self.pages_per_slot)
                kv = [np.array(x[:n], copy=True) for x in jax.device_get(
                    gather(self.cache, jnp.asarray(pids), n2))]
                state.update(kv=kv, n_pages=n, pos=int(self.pos[slot]),
                             poff=int(self.slot_poff[slot]),
                             last_tok=int(self.last_tok[slot]))
            self.occupant[slot] = None
            if self.paged:
                self._release_pages(slot)
        del self.requests[rid]
        self.stats["handoff_exports"] += 1
        return state

    def import_request(self, state: dict) -> int:
        """Admit a request exported by another batcher's
        ``export_request``.  Without KV it is a plain submission (fresh
        prefill); with KV pages it joins the host-swap resume queue and
        re-enters the pool through the scatter/refill path
        (``_resume_swapped``) — continuing mid-generation, token-exact,
        with the inherited ``emitted`` prefix intact.  Returns the LOCAL
        rid (rids are per-batcher; the fleet router maps global ids)."""
        prompt = np.asarray(state["prompt"], np.int32).reshape(-1)
        emitted = list(state.get("emitted") or [])
        kv = state.get("kv")
        if kv is None:
            if emitted:
                raise ValueError(
                    "cannot import a mid-stream request without KV: "
                    "re-prefilling prompt+emitted is the router's "
                    "fallback (fleet/router.py), not the batcher's")
            return self.submit(prompt, state["max_new"],
                               temperature=state["temperature"],
                               top_k=state["top_k"],
                               top_p=state["top_p"],
                               eos_id=state["eos_id"])
        if not self.paged:
            raise ValueError("KV handoff requires a paged batcher")
        n_pages = int(state["n_pages"])
        if n_pages > self.pages_per_slot:
            raise ValueError(
                f"handoff carries {n_pages} pages but this pool holds "
                f"{self.pages_per_slot} per slot")
        if len(prompt) + state["max_new"] > self.max_len:
            raise ValueError(
                f"prompt {len(prompt)} + max_new {state['max_new']} "
                f"exceeds max_len {self.max_len}")
        leaves = jax.tree.leaves(self.cache)
        if len(kv) != len(leaves) or any(
                tuple(x.shape[1:]) != tuple(leaf.shape[1:])
                or np.dtype(x.dtype) != np.dtype(leaf.dtype)
                for x, leaf in zip(kv, leaves)):
            raise ValueError(
                "handoff KV layout does not match this pool (leaf "
                "count / page shape / dtype) — replicas must share "
                "model config, page size, and kv_dtype")
        rid = self._next_rid
        self._next_rid += 1
        req = _Request(rid, prompt, int(state["max_new"]),
                       temperature=float(state["temperature"]),
                       top_k=int(state["top_k"]),
                       top_p=float(state["top_p"]),
                       eos_id=state["eos_id"])
        req.t_submit = time.perf_counter()
        req.emitted = emitted
        if self.prefix_cache:
            req.prefix_hashes = self._prefix_hashes(prompt)
            req.pages_published = True  # imported pages stay private
        self.requests[rid] = req
        self.swapped.append(_Swapped(
            req=req, kv=[np.asarray(x) for x in kv], n_pages=n_pages,
            pos=int(state["pos"]), poff=int(state["poff"]),
            last_tok=int(state["last_tok"])))
        self.stats["handoff_imports"] += 1
        return rid

    # -- compiled pieces --------------------------------------------------
    def _prefill(self, bucket: int):
        """(params, padded (1, bucket) prompt, true_len) ->
        ((vocab,) last valid logits, per-layer (1, hkv, bucket, d) slabs)."""
        fn = self._prefill_fns.get(bucket)
        if fn is None:
            cfg, dtype = self.cfg, self.dtype
            kv_dtype = self.kv_dtype
            tp = self.tp_axis if self.mesh is not None else None

            def prefill_body(params, prompt, true_len):
                # inside shard_map params are LOCAL shards: this is the
                # PER-DEVICE kv-head count (self.kv_heads is the global)
                cache = gen.init_cache(cfg, 1, bucket,
                                       dtype=dtype or jnp.float32,
                                       kv_heads=params["layer0"]
                                       ["wk"].shape[1],
                                       kv_dtype=kv_dtype)
                # single-row unembed at the last VALID prompt position —
                # no (bucket, vocab) logits buffer for padded rows
                logits, cache = gen._forward_cached(
                    params, cache, prompt, jnp.arange(bucket), 0,
                    cfg=cfg, dtype=dtype, k_len=bucket, tp_axis=tp,
                    unembed_at=true_len - 1)
                return logits[0, 0], cache

            if self.mesh is None:
                fn = jax.jit(prefill_body)
            else:
                from .utils.compat import shard_map
                from jax.sharding import PartitionSpec as P
                # spec trees carry no shapes: the pool's spec tree fits
                # the (1, hkv, bucket, d) prefill slabs too
                fn = jax.jit(shard_map(
                    prefill_body, mesh=self.mesh,
                    in_specs=(self._param_specs, P(), P()),
                    out_specs=(P(), self._cache_spec)))
            self._prefill_fns[bucket] = fn
        return fn

    def _decode_for(self, n_slots: int):
        """(params, cache, cur, ref, carry, key) -> (packed int32 vector,
        cache, carry) — ONE program runs up to ``steps_per_sync``
        lockstep steps for the whole pool per dispatch.  Sampling
        parameters are per-slot vectors (gen.sample_per_seq), so requests
        with different settings share the dispatch.

        ``carry`` is the per-slot machine state (input token, write
        position, prompt offset, remaining budget, done/active flags,
        last meaningful write): the serial path stages it from host
        mirrors each dispatch exactly as before; the OVERLAPPED path
        feeds one block's carry output straight into the next dispatch
        (``_try_chain``) so the previous block's results need not be
        fetched first.  Same compiled program either way — chaining adds
        zero compiles.  The cache and carry are donated
        (``compat.donate``).

        Each slot is a little state machine driven by ``cur`` (the
        current request: input token, write position, prompt buffer +
        offset for teacher-forced in-block prefill, sampling params,
        remaining emit budget, write cap, page-table row) and ``ref``
        (the staged NEXT request, same fields plus ``valid``):

        - while ``poff < plen`` the slot is PREFILLING: its input is
          ``prompt[poff]`` (the ragged decode step writes that token's
          K/V exactly like a prefill pass would), the sampled token is
          discarded — except at the last prompt position, whose sample
          is the request's first emission;
        - then it DECODES: each sampled token feeds the next step and
          decrements ``rem``;
        - on retirement (eos sampled, or ``rem`` exhausted) with a valid
          ``ref`` staged, the slot SWITCHES in place: position resets to
          0, the refill's prompt/params/budget take over, and prefill of
          the next request begins on the very next lockstep step — the
          retire→admit transition costs zero dispatches and zero wasted
          slot-steps.

        DEVICE-SIDE EARLY EXIT: the ``while_loop`` stops as soon as
        every slot is done (retired with no refill staged; empty slots
        pass ``rem=0`` and are done immediately).  Done slots keep
        computing in lockstep; their writes clamp at their allocated
        frontier (``cap``) so they cannot touch pages/rows they do not
        own.  Token rows beyond ``steps_executed`` are discarded; the
        emit mask distinguishes sampled emissions from prefill steps.

        ``n_slots`` is the compiled row count: the full pool width, or
        a NARROWER variant for drained-tail batch compaction (same
        program, fewer slot rows; one compile per width)."""
        if self._decode_fns.get(n_slots) is None:
            cfg, dtype = self.cfg, self.dtype
            use_kernel = self.use_kernel
            k_steps = self.steps_per_sync
            width = self.refill_width

            tp = self.tp_axis if self.mesh is not None else None

            paged = self.paged
            rows = np.arange(n_slots)

            def block_body(params, cache, cur, ref, carry, key):
                buf0 = jnp.zeros((k_steps, n_slots), jnp.int32)
                mask0 = jnp.zeros((k_steps, n_slots), jnp.bool_)
                # done folds the carried flag (a slot retired in an
                # earlier chained block) with budget exhaustion (empty
                # slots enter with rem=0); active carries so a refill
                # consumed by an earlier block cannot switch in twice
                done0 = carry["done"] | (carry["rem"] <= 0)
                c0 = dict(i=jnp.int32(0), cache=cache, tok=carry["tok"],
                          pos=carry["pos"], poff=carry["poff"],
                          active=carry["active"],
                          rem=carry["rem"], done=done0, key=key, buf=buf0,
                          mask=mask0,
                          sw=jnp.full((n_slots,), k_steps + 1, jnp.int32),
                          lw=carry["lw"],
                          pf=jnp.zeros((n_slots,), jnp.int32))

                def cond(c):
                    return (c["i"] < k_steps) & ~jnp.all(c["done"])

                def sel(a, b, active):
                    return jnp.where(active, a, b)

                def body(c):
                    i, active = c["i"], c["active"]
                    plen_eff = sel(ref["plen"], cur["plen"], active)
                    in_pf = c["poff"] < plen_eff
                    prow = jnp.where(active[:, None], ref["prompt"],
                                     cur["prompt"])
                    ptok = prow[rows, jnp.minimum(c["poff"], width - 1)]
                    itok = jnp.where(in_pf, ptok, c["tok"])
                    cap_eff = sel(ref["cap"], cur["cap"], active)
                    table_eff = (jnp.where(active[:, None], ref["table"],
                                           cur["table"])
                                 if paged else None)
                    logits, new_cache = gen.decode_step_ragged(
                        params, c["cache"], itok, c["pos"], cfg=cfg,
                        dtype=dtype, tp_axis=tp,
                        use_decode_kernel=use_kernel,
                        page_table=table_eff)
                    key, sub = jax.random.split(c["key"])
                    toks = gen.sample_per_seq(
                        sub, logits,
                        sel(ref["temp"], cur["temp"], active),
                        sel(ref["top_k"], cur["top_k"], active),
                        sel(ref["top_p"], cur["top_p"], active))
                    # the last prompt position's sample is the first
                    # emission; earlier prefill steps discard theirs
                    last_pf = in_pf & (c["poff"] + 1 >= plen_eff)
                    emit = ~c["done"] & (~in_pf | last_pf)
                    buf = jax.lax.dynamic_update_index_in_dim(
                        c["buf"], toks, i, 0)
                    mask = jax.lax.dynamic_update_index_in_dim(
                        c["mask"], emit, i, 0)
                    pf = c["pf"] + (~c["done"] & in_pf
                                    & ~last_pf).astype(jnp.int32)
                    rem = c["rem"] - emit.astype(jnp.int32)
                    eos_eff = sel(ref["eos"], cur["eos"], active)
                    fin = emit & (((toks == eos_eff) & (eos_eff >= 0))
                                  | (rem <= 0))
                    switch = fin & ~active & ref["valid"]
                    done = c["done"] | (fin & ~switch)
                    # last meaningful write position (done slots'
                    # lockstep writes are garbage clamped at cap)
                    lw = jnp.where(~c["done"], c["pos"], c["lw"])
                    poff = jnp.where(in_pf, c["poff"] + 1, c["poff"])
                    pos = jnp.minimum(c["pos"] + 1, cap_eff)
                    # in-place handoff: the refill takes over at pos 0
                    poff = jnp.where(switch, 0, poff)
                    pos = jnp.where(switch, 0, pos)
                    rem = jnp.where(switch, ref["budget"], rem)
                    return dict(
                        i=i + 1, cache=new_cache, tok=toks, pos=pos,
                        poff=poff, active=active | switch, rem=rem,
                        done=done, key=key, buf=buf, mask=mask,
                        sw=jnp.where(switch, i + 1, c["sw"]), lw=lw,
                        pf=pf)

                c = jax.lax.while_loop(cond, body, c0)
                # pack every host-bound output into ONE int32 vector:
                # each fetched buffer pays its own round trip (~1.6 ms
                # on the v5e machine, PR 21), so the block's results are
                # one transfer
                packed = jnp.concatenate([
                    c["buf"].reshape(-1),
                    c["mask"].astype(jnp.int32).reshape(-1),
                    c["sw"], c["lw"], c["poff"], c["pf"],
                    c["i"][None]])
                # the carry never crosses to the host: a chained dispatch
                # consumes it directly on device (_try_chain)
                carry_out = dict(tok=c["tok"], pos=c["pos"],
                                 poff=c["poff"], rem=c["rem"],
                                 done=c["done"], active=c["active"],
                                 lw=c["lw"])
                return packed, c["cache"], carry_out

            # compile lane (round 15): one program per slot width — a
            # fleet whose drained-tail compaction churns widths shows up
            # as cache growth here; telemetry off = no-op
            with monitor.compile_span(
                    "decode_build",
                    key=("decode", n_slots, k_steps, width),
                    cache_size=lambda: len(self._decode_fns),
                    n_slots=n_slots):
                if self.mesh is None:
                    fn = jax.jit(block_body,
                                 donate_argnums=compat.donate(1, 4))
                else:
                    from .utils.compat import shard_map
                    from jax.sharding import PartitionSpec as P
                    fn = jax.jit(shard_map(
                        block_body, mesh=self.mesh,
                        in_specs=(self._param_specs, self._cache_spec,
                                  P(), P(), P(), P()),
                        out_specs=(P(), self._cache_spec, P())),
                        donate_argnums=compat.donate(1, 4))
                self._decode_fns[n_slots] = fn
        return self._decode_fns[n_slots]

    def _decode_spec_for(self, n_slots: int, gather_cols: int = 0):
        """SPECULATIVE decode block: ``(params, cache, cur, ref, key) ->
        (packed int32 vector, cache)`` — a device-side ``while_loop`` of
        up to ``steps_per_sync`` speculation ROUNDS.  Each round, every
        slot:

        1. proposes ``n_spec`` tokens by PROMPT-LOOKUP from its own
           stream (continuation of the most recent earlier occurrence of
           the trailing ``spec_ngram``; repeat-last fallback), and
        2. joins ONE (slots, W = n_spec+1)-token ragged verify forward
           (gen.verify_step_ragged) — W tokens of MXU work per weight
           read instead of W bandwidth-bound lockstep steps; then
        3. accepts the longest correct prefix: greedy slots match the
           argmax, temperature>0 slots run point-mass rejection (accept
           proposal x with prob p(x) under the slot's own warped
           distribution, resample from p-minus-x on reject — emitted
           tokens are EXACTLY warped-target-distributed), and the
           frontier token comes free from the last accepted position's
           logits.

        The per-slot state machine mirrors ``_decode_for``'s, re-based
        on (``stream``, ``det``, ``wr``): ``stream`` holds the known
        tokens at their positions, ``det`` counts them, and ``wr`` is
        the cache frontier — positions in [wr, min(wr+W, det)-1] are
        known (teacher-forced prefill rides the SAME verify window at W
        tokens/round, including across the prompt→decode boundary),
        later window entries are proposals.  Writes clamp at ``cap``
        (done slots scribble on their frontier row, never on pages/rows
        they do not own); retirement hands off in place to the staged
        refill exactly as in the lockstep block.

        ``gather_cols`` (paged): the deepest allocated page frontier
        across this dispatch's rows, rounded up to a power of two by the
        caller — the verify forward's pool gather reads only that many
        table columns per layer per ROUND (a static ``k_len`` hint into
        ``gen.verify_step_ragged``) instead of the whole
        ``pages_per_slot`` logical range, so short sequences stop
        paying O(max_len) HBM traffic per round (ADVICE r5 #2).  Sound
        because every row's window positions stay below its allocated
        frontier (the host sizes allocations to the block's worst-case
        writes, verify tail included, before dispatch); one compiled
        block per (width, depth-bucket)."""
        key_ = (n_slots, gather_cols)
        if self._spec_fns.get(key_) is None:
            cfg, dtype = self.cfg, self.dtype
            r_max = self.steps_per_sync
            n_spec, ngram = self.n_spec, self.spec_ngram
            wk = n_spec + 1
            width = self.refill_width
            kv_len = self.kv_len
            vocab = cfg.vocab_size
            tp = self.tp_axis if self.mesh is not None else None
            paged = self.paged
            k_hint = (gather_cols * self.page
                      if (paged and gather_cols) else None)
            rows = np.arange(n_slots)

            def block_body(params, cache, cur, ref, key):
                ref_stream = jnp.zeros((n_slots, kv_len), jnp.int32)
                ref_stream = ref_stream.at[:, :width].set(ref["prompt"])
                c0 = dict(i=jnp.int32(0), cache=cache,
                          stream=cur["stream"], det=cur["det"],
                          wr=cur["wr"], rem=cur["rem"],
                          active=jnp.zeros((n_slots,), jnp.bool_),
                          done=cur["rem"] <= 0, key=key,
                          etok=jnp.zeros((r_max, n_slots, wk), jnp.int32),
                          ecnt=jnp.zeros((r_max, n_slots), jnp.int32),
                          sw=jnp.full((n_slots,), r_max + 1, jnp.int32),
                          pf=jnp.zeros((n_slots,), jnp.int32),
                          prop_n=jnp.int32(0), prop_acc=jnp.int32(0))

                def cond(c):
                    return (c["i"] < r_max) & ~jnp.all(c["done"])

                def sel(a, b, active):
                    return jnp.where(active, a, b)

                def body(c):
                    i, active, live = c["i"], c["active"], ~c["done"]
                    det, wr, stream = c["det"], c["wr"], c["stream"]
                    plen_eff = sel(ref["plen"], cur["plen"], active)
                    temp_eff = sel(ref["temp"], cur["temp"], active)
                    topk_eff = sel(ref["top_k"], cur["top_k"], active)
                    topp_eff = sel(ref["top_p"], cur["top_p"], active)
                    eos_eff = sel(ref["eos"], cur["eos"], active)
                    cap_eff = sel(ref["cap"], cur["cap"], active)
                    table_eff = (jnp.where(active[:, None], ref["table"],
                                           cur["table"])
                                 if paged else None)
                    key, ku, krj, kb = jax.random.split(c["key"], 4)

                    # 1. prompt-lookup proposals from each slot's stream
                    # (the same helper generate_lookup uses)
                    props = gen.lookup_proposals(stream, det - 1,
                                                 wk - 1, ngram)

                    # 2. the input window: known stream tokens (prefill /
                    # the frontier token), proposals beyond
                    idx = wr[:, None] + jnp.arange(wk)[None]
                    known = idx < det[:, None]
                    stream_at = jnp.take_along_axis(
                        stream, jnp.clip(idx, 0, kv_len - 1), 1)
                    prop_at = jnp.take_along_axis(
                        props, jnp.clip(idx - det[:, None], 0, wk - 2), 1)
                    inp = jnp.where(known, stream_at, prop_at)
                    wpos = jnp.minimum(idx, cap_eff[:, None])
                    logits, new_cache = gen.verify_step_ragged(
                        params, c["cache"], inp, idx, wpos, cfg=cfg,
                        dtype=dtype, tp_axis=tp, page_table=table_eff,
                        k_len=k_hint)

                    # 3. accept: greedy match or point-mass rejection
                    g = jnp.argmax(logits, -1).astype(jnp.int32)
                    masked = gen.filter_per_seq(
                        logits.reshape(n_slots * wk, vocab),
                        jnp.repeat(temp_eff, wk),
                        jnp.repeat(topk_eff, wk),
                        jnp.repeat(topp_eff, wk)).reshape(
                            n_slots, wk, vocab)
                    probs = jax.nn.softmax(masked, -1)
                    x_next = inp[:, 1:]                       # (n, W-1)
                    px = jnp.take_along_axis(
                        probs[:, :-1], x_next[..., None], 2)[..., 0]
                    u = jax.random.uniform(ku, (n_slots, wk - 1))
                    greedy_slot = (temp_eff <= 0.0)[:, None]
                    ok_prop = jnp.where(greedy_slot,
                                        x_next == g[:, :-1], u < px)
                    ok = known[:, 1:] | ok_prop
                    okc = jnp.cumprod(ok.astype(jnp.int32), axis=1)
                    m = jnp.sum(okc, axis=1)                  # [0, W-1]
                    # frontier token: argmax (greedy) / residual sample
                    # at the rejection point / bonus draw on full accept
                    viota = jax.lax.broadcasted_iota(
                        jnp.int32, (n_slots, wk - 1, vocab), 2)
                    repl_logits = jnp.where(
                        viota == x_next[..., None], gen.NEG_INF,
                        masked[:, :-1])
                    repl = jax.random.categorical(
                        krj, repl_logits.reshape(-1, vocab)).reshape(
                            n_slots, wk - 1).astype(jnp.int32)
                    bonus = jax.random.categorical(
                        kb, masked[:, -1]).astype(jnp.int32)
                    f_samp = jnp.where(
                        m == wk - 1, bonus,
                        jnp.take_along_axis(
                            repl, jnp.clip(m, 0, wk - 2)[:, None],
                            1)[:, 0])
                    f_greedy = jnp.take_along_axis(g, m[:, None], 1)[:, 0]
                    f = jnp.where(greedy_slot[:, 0], f_greedy, f_samp)

                    # 4. advance: write accepted proposals + frontier
                    # into the stream, count emissions, cap by eos/budget
                    wr_new = wr + m + 1
                    det_new = jnp.maximum(det, wr_new + 1)
                    jj = jnp.arange(1, wk + 1)[None]
                    inp_sh = jnp.concatenate([inp[:, 1:], f[:, None]], 1)
                    val = jnp.where(jj <= m[:, None], inp_sh, f[:, None])
                    posw = wr[:, None] + jj
                    write_ok = (live[:, None] & (jj <= (m + 1)[:, None])
                                & ~((jj == (m + 1)[:, None])
                                    & (posw < det[:, None]))
                                & (posw < kv_len))
                    cols = jnp.where(write_ok, posw, kv_len)
                    stream_new = stream.at[
                        rows[:, None], cols].set(
                            jnp.where(write_ok, val, 0), mode="drop")
                    e_new = jnp.where(live, det_new - det, 0)
                    eidx = jnp.clip(det[:, None] + jnp.arange(wk)[None],
                                    0, kv_len - 1)
                    echunk = jnp.take_along_axis(stream_new, eidx, 1)
                    tgrid = jnp.arange(wk)[None]
                    evalid = tgrid < e_new[:, None]
                    is_eos = (echunk == eos_eff[:, None]) \
                        & (eos_eff >= 0)[:, None] & evalid
                    has_eos = jnp.any(is_eos, axis=1)
                    first_eos = jnp.argmax(is_eos, axis=1)
                    n1 = jnp.where(has_eos,
                                   jnp.minimum(e_new, first_eos + 1),
                                   e_new)
                    n_allow = jnp.minimum(n1, c["rem"])
                    rem_new = c["rem"] - n_allow
                    fin = live & ((rem_new <= 0)
                                  | (has_eos & (first_eos < n_allow)))

                    etok = jax.lax.dynamic_update_index_in_dim(
                        c["etok"], echunk, i, 0)
                    ecnt = jax.lax.dynamic_update_index_in_dim(
                        c["ecnt"], n_allow, i, 0)
                    pf = c["pf"] + jnp.where(
                        live,
                        jnp.maximum(0, jnp.minimum(wr_new, plen_eff)
                                    - jnp.minimum(wr, plen_eff)), 0)
                    prop_used = live[:, None] & ~known[:, 1:]
                    jj2 = jnp.arange(1, wk)[None]
                    prop_n = c["prop_n"] + jnp.sum(prop_used)
                    prop_acc = c["prop_acc"] + jnp.sum(
                        prop_used & (jj2 <= m[:, None]))

                    # 5. retire / in-place handoff to the staged refill
                    switch = fin & ~active & ref["valid"]
                    done = c["done"] | (fin & ~switch)
                    stream_out = jnp.where(switch[:, None], ref_stream,
                                           stream_new)
                    det_out = jnp.where(switch, ref["plen"],
                                        jnp.where(live, det + n_allow,
                                                  det))
                    wr_out = jnp.where(switch, 0,
                                       jnp.where(live, wr_new, wr))
                    rem_out = jnp.where(switch, ref["budget"], rem_new)
                    return dict(
                        i=i + 1, cache=new_cache, stream=stream_out,
                        det=det_out, wr=wr_out, rem=rem_out,
                        active=active | switch, done=done, key=key,
                        etok=etok, ecnt=ecnt,
                        sw=jnp.where(switch, i + 1, c["sw"]), pf=pf,
                        prop_n=prop_n, prop_acc=prop_acc)

                c = jax.lax.while_loop(cond, body, c0)
                packed = jnp.concatenate([
                    c["etok"].reshape(-1), c["ecnt"].reshape(-1),
                    c["sw"], c["wr"], c["pf"],
                    c["prop_n"][None], c["prop_acc"][None],
                    c["i"][None]])
                return packed, c["cache"]

            # donate the cache AND the staging dict (argnum 2): its
            # (slots, kv_len) stream buffer is rebuilt host-side every
            # dispatch, so aliasing its storage into the loop's updates
            # saves an HBM copy per round (compat-gated, as ever)
            if self.mesh is None:
                fn = jax.jit(block_body, donate_argnums=compat.donate(1, 2))
            else:
                from .utils.compat import shard_map
                from jax.sharding import PartitionSpec as P
                fn = jax.jit(shard_map(
                    block_body, mesh=self.mesh,
                    in_specs=(self._param_specs, self._cache_spec,
                              P(), P(), P()),
                    out_specs=(P(), self._cache_spec)),
                    donate_argnums=compat.donate(1, 2))
            self._spec_fns[key_] = fn
        return self._spec_fns[key_]

    def _prefill_chunk_fn(self, bucket: int, first: bool):
        """One prompt chunk written at cache offset ``off``, attending
        causally to everything already prefilled (k_len=bucket; rows read
        slots <= their own position).  Returns ((vocab,) logits at
        ``unembed_idx``, cache); the final chunk's ``unembed_idx`` is the
        last true prompt position relative to the chunk, earlier chunks'
        logits are discarded.  The ``first`` variant creates the zeroed
        scratch cache INSIDE the jit (like _prefill) — no host-side
        allocation dispatches on the admission path."""
        fn = self._chunk_fns.get((bucket, first))
        if fn is None:
            cfg, dtype = self.cfg, self.dtype
            kv_dtype = self.kv_dtype
            c = self.prefill_chunk
            tp = self.tp_axis if self.mesh is not None else None

            def run_chunk(params, cache, chunk, off, unembed_idx):
                logits, cache = gen._forward_cached(
                    params, cache, chunk, off + jnp.arange(c), off,
                    cfg=cfg, dtype=dtype, k_len=bucket, tp_axis=tp,
                    unembed_at=unembed_idx)
                return logits[0, 0], cache

            if first:
                def chunk_body(params, chunk, unembed_idx):
                    # local (per-shard) kv-head count, as in prefill_body
                    cache = gen.init_cache(cfg, 1, bucket,
                                           dtype=dtype or jnp.float32,
                                           kv_heads=params["layer0"]
                                           ["wk"].shape[1],
                                           kv_dtype=kv_dtype)
                    return run_chunk(params, cache, chunk, jnp.int32(0),
                                     unembed_idx)
                donate = ()
            else:
                chunk_body = run_chunk
                donate = compat.donate(1)
            if self.mesh is None:
                fn = jax.jit(chunk_body, donate_argnums=donate)
            else:
                from .utils.compat import shard_map
                from jax.sharding import PartitionSpec as P
                in_specs = ((self._param_specs, P(), P()) if first else
                            (self._param_specs, self._cache_spec,
                             P(), P(), P()))
                fn = jax.jit(shard_map(
                    chunk_body, mesh=self.mesh,
                    in_specs=in_specs,
                    out_specs=(P(), self._cache_spec)),
                    donate_argnums=donate)
            self._chunk_fns[(bucket, first)] = fn
        return fn

    # -- paged-pool bookkeeping (self.paged) ------------------------------
    def _avail_pages(self) -> int:
        """Pages the pool can still supply: the free list plus registry
        pages no occupant references (reclaimable prefix cache)."""
        n = len(self.free_pages)
        if self.prefix_cache:
            n += sum(1 for pid in self.registry.values()
                     if self.page_refs.get(pid, 0) == 0)
        return n

    def _reclaim_registry(self, n: int) -> None:
        """Free ``n`` unreferenced registry pages, LEAST RECENTLY USED
        first (insertion order, with ``_admit_shared`` re-inserting on
        every hit) — cold cached prefixes yield to live work under pool
        pressure, before any occupant is preempted; hot ones survive."""
        for h in list(self.registry):
            if n <= 0:
                break
            pid = self.registry[h]
            if self.page_refs.get(pid, 0) == 0:
                del self.registry[h]
                del self.page_hash[pid]
                del self.page_refs[pid]
                self.free_pages.append(pid)
                self.stats["prefix_reclaimed"] += 1
                n -= 1

    def _take_free_page(self) -> int:
        if not self.free_pages and self.prefix_cache:
            self._reclaim_registry(1)
        if not self.free_pages:
            raise RuntimeError(
                f"KV page pool exhausted ({self.pool_pages} pages): "
                f"raise pool_pages or lower concurrency/max_new")
        return self.free_pages.popleft()

    def _alloc_pages(self, slot: int, upto_pos: int) -> None:
        """Ensure ``slot``'s block table covers positions [0, upto_pos]."""
        need = min(upto_pos // self.page + 1, self.pages_per_slot)
        pages = self.slot_pages[slot]
        while len(pages) < need:
            pid = self._take_free_page()
            self.table[slot, len(pages)] = pid
            pages.append(pid)

    def _release_pages(self, slot: int) -> None:
        """Return a retired slot's pages and repoint its table row at the
        scratch page 0 (resetting pos too): the slot keeps lockstep-
        writing in later dispatches until re-admitted, and those writes
        must never land in pages recycled to OTHER slots.  Registered
        (prefix-cache) pages stay in the registry at one fewer
        reference instead of returning to the free list."""
        for pid in self.slot_pages[slot]:
            if self.prefix_cache and pid in self.page_hash:
                self.page_refs[pid] -= 1
            else:
                self.free_pages.append(pid)
        self.slot_pages[slot] = []
        self.table[slot, :] = 0
        self.pos[slot] = 0

    # -- prefix cache (self.prefix_cache) ---------------------------------
    def _prefix_hashes(self, prompt: np.ndarray) -> list[bytes]:
        """Chain hash per FULL prompt page (module-level
        ``prefix_page_hashes`` — shared with the fleet router)."""
        return prefix_page_hashes(prompt, self.page)

    def _prefix_lookup(self, req: _Request) -> list[int]:
        """Longest cached chain of the request's full prompt pages
        (hashes memoized at submit), capped so at least one suffix token
        is always left to prefill (its logits seed the first emission;
        shared pages are never re-written)."""
        hashes = req.prefix_hashes
        if len(req.prompt) % self.page == 0:
            hashes = hashes[:-1]
        return self._registry_chain(hashes)

    def _registry_chain(self, hashes: list[bytes]) -> list[int]:
        """Pages of the longest chain prefix present in the registry."""
        shared: list[int] = []
        for h in hashes:
            pid = self.registry.get(h)
            if pid is None:
                break
            shared.append(pid)
        return shared

    def _register_prompt_pages(self, slot: int, req: _Request) -> None:
        """Publish a freshly prefilled prompt's full pages.  Only pages
        wholly covered by prompt tokens register — the partial tail page
        takes decode writes and must stay private."""
        for i, h in enumerate(req.prefix_hashes):
            pid = self.slot_pages[slot][i]
            if h in self.registry or pid in self.page_hash:
                continue  # this chain (or page) is already published
            self.registry[h] = pid
            self.page_hash[pid] = h
            self.page_refs[pid] = 1
        req.pages_published = True

    def _maybe_publish_prompt_pages(self, slot: int,
                                    req: _Request | None = None, *,
                                    prompt_done: bool | None = None
                                    ) -> None:
        """Publish hook for prompts prefilled INSIDE the decode block
        (teacher-forced in-block admissions and retire->refill handoffs
        — paths that never pass through ``_fill_free_slots``'s
        registration, ADVICE r5 #1).  Safe once the prompt is fully
        written: in-block writes are contiguous from position 0, garbage
        verify-tail writes land at positions >= the determined frontier
        (>= prompt length), and write clamps land on the LAST allocated
        row, which allocation always places beyond the full prompt pages
        — so a completed prompt's full pages hold exactly the K/V a
        batched prefill would have produced.  ``prompt_done=True``
        (retirement: an emission implies the prompt was consumed) skips
        the host-progress check, which lags the device mid-parse."""
        if not self.prefix_cache:
            return
        req = req if req is not None else self.occupant[slot]
        if req is None or req.pages_published or not req.prefix_hashes:
            return
        if prompt_done is None:
            prompt_done = self.slot_poff[slot] >= len(req.prompt)
        if (not prompt_done
                or len(self.slot_pages[slot]) < len(req.prefix_hashes)):
            return
        self._register_prompt_pages(slot, req)

    def _suffix_prefill(self, sbucket: int):
        """Compiled suffix prefill for shared-prefix admissions: a
        (1, sbucket) token window at positions base.. attends the shared
        pages through the slot's table (gen.verify_step_ragged) and
        writes its own K/V into the fresh tail pages; returns the
        (vocab,) logits at the TRUE last prompt position.  Pad tokens
        past the suffix all clamp onto position ``wcap`` = the prompt
        length L — decode's own first write position, overwritten before
        any read, and inside a page the occupant needs for decode anyway
        (no pages are ever allocated just for pad garbage)."""
        fn = self._suffix_fns.get(sbucket)
        if fn is None:
            cfg, dtype = self.cfg, self.dtype
            tp = self.tp_axis if self.mesh is not None else None

            def suffix_body(params, cache, chunk, base, uidx, wcap, trow):
                pos = base + jnp.arange(sbucket)[None]
                logits, cache = gen.verify_step_ragged(
                    params, cache, chunk, pos,
                    jnp.minimum(pos, wcap), cfg=cfg, dtype=dtype,
                    tp_axis=tp, page_table=trow)
                return logits[0, uidx], cache

            if self.mesh is None:
                fn = jax.jit(suffix_body, donate_argnums=compat.donate(1))
            else:
                from .utils.compat import shard_map
                from jax.sharding import PartitionSpec as P
                fn = jax.jit(shard_map(
                    suffix_body, mesh=self.mesh,
                    in_specs=(self._param_specs, self._cache_spec,
                              P(), P(), P(), P(), P()),
                    out_specs=(P(), self._cache_spec)),
                    donate_argnums=compat.donate(1))
            self._suffix_fns[sbucket] = fn
        return fn

    def _write_caps(self, pages: list[list[int]] | None = None
                    ) -> np.ndarray:
        """Per-slot last writable position: the allocated frontier under
        paging (in-block writes must never dereference unowned table
        entries), max_len-1 for the dense cache.  ``pages`` defaults to
        the occupants' page lists; pass ``self.refill_pages`` for the
        staged refills' caps."""
        if not self.paged:
            return np.full(self.slots, self.kv_len - 1, np.int32)
        return np.asarray(
            [max(len(p) * self.page - 1, 0)
             for p in (self.slot_pages if pages is None else pages)],
            np.int32)

    def _block_writes(self, pr: int, rem: int) -> int:
        """Worst-case cache writes ONE dispatch can make for a slot with
        ``pr`` prompt tokens left and ``rem`` emission budget: K lockstep
        single-token steps, or — under speculation — R rounds advancing
        up to W = n_spec+1 positions each (bounded by the slot's real
        progress pr + rem) plus the W-wide not-yet-accepted tail the
        verify window writes past the frontier."""
        k = self.steps_per_sync
        if self.n_spec:
            w_ = self.n_spec + 1
            return min(k * w_, pr + rem) + w_
        return min(k, pr + min(k, rem))

    def _pages_short(self, upto_pos: int, owned: int = 0) -> int:
        """How many pages the free list must supply to cover positions
        [0, upto_pos] given ``owned`` pages already held."""
        return min(upto_pos // self.page + 1, self.pages_per_slot) - owned

    def _alloc_refill_pages(self, slot: int) -> bool:
        """Reserve pages for a staged refill's worst-case in-block writes
        (it activates at step >= 1, so at most steps_per_sync - 1
        positions).  Returns False instead of raising when the pool
        cannot cover it — the request then simply stays queued."""
        k = self.steps_per_sync
        if self.n_spec:
            # spec block: a switched-in refill can advance W positions
            # per round from 0, plus the W-wide garbage tail
            w_ = self.n_spec + 1
            upto = min(k * w_ + w_ - 1, self.kv_len - 1)
        else:
            upto = min(max(k - 2, 0), self.kv_len - 1)
        need = self._pages_short(upto)
        if self._avail_pages() < need:
            return False
        pages = [self._take_free_page() for _ in range(need)]
        self.refill_pages[slot] = pages
        self.r_table[slot, :] = 0
        self.r_table[slot, :need] = pages
        return True

    def _release_refill_pages(self, slot: int) -> None:
        self.free_pages.extend(self.refill_pages[slot])
        self.refill_pages[slot] = []
        self.r_table[slot, :] = 0

    # -- preemption: host-swap under pool pressure -------------------------
    @staticmethod
    def _pow2(n: int) -> int:
        return 1 << max(n - 1, 0).bit_length()

    def _page_io_fns(self):
        """Compiled page gather/scatter for host-swap: the victim's pages
        come back as ONE dispatch whose per-leaf outputs land in a single
        tuple fetch, and restore writes them into freshly allocated
        pages.  Per-LEAF arrays rather than one ``jnp.stack``: the int8
        pool's scale leaves ((P, hkv, page, 1) f32) share neither shape
        nor dtype with the K/V leaves, and stacking would silently upcast
        the non-quantized pool's leaves anyway.  ``pids`` is padded to
        ``pages_per_slot``; rows past ``n`` are ignored."""
        if self._gather_fn is None:
            @partial(jax.jit, static_argnums=(2,))
            def gather(cache, pids, n):
                return [leaf[pids[:n]] for leaf in jax.tree.leaves(cache)]

            @partial(jax.jit, donate_argnums=compat.donate(0), static_argnums=(3,))
            def scatter(cache, kv, pids, n):
                leaves, td = jax.tree.flatten(cache)
                out = [leaf.at[pids[:n]].set(kv[i][:n].astype(leaf.dtype))
                       for i, leaf in enumerate(leaves)]
                return jax.tree.unflatten(td, out)

            self._gather_fn, self._scatter_fn = gather, scatter
        return self._gather_fn, self._scatter_fn

    def _evict(self, victim: int) -> None:
        """Preempt ``victim``: its KV pages move to host memory and the
        request joins the resume queue; the pages go back to the pool.
        The request continues mid-generation on swap-in — no re-prefill,
        so the generated prefix can exceed every prompt bucket."""
        occ = self.occupant[victim]
        pids = np.zeros(self.pages_per_slot, np.int32)
        n = len(self.slot_pages[victim])
        pids[:n] = self.slot_pages[victim]
        gather, _ = self._page_io_fns()
        # static gather width rounded to the next power of two (clamped
        # to the table width — pages_per_slot need not be a power of
        # two): bounds the distinct compiles at log2(pages_per_slot)
        # while fetching at most 2x the owned pages (pad rows hit the
        # scratch page)
        n2 = min(self._pow2(n), self.pages_per_slot)
        # ONE awaited fetch for all leaves (device_get starts every host
        # copy before blocking — the per-leaf list must not degrade to
        # one round-trip per leaf)
        kv = [x[:n] for x in jax.device_get(
            gather(self.cache, jnp.asarray(pids), n2))]
        self.swapped.append(_Swapped(
            req=occ, kv=kv, n_pages=n, pos=int(self.pos[victim]),
            poff=int(self.slot_poff[victim]),
            last_tok=int(self.last_tok[victim])))
        self.occupant[victim] = None
        self._release_pages(victim)
        self.stats["evictions"] += 1

    def _ensure_pages_or_evict(self, slot: int, upto: int) -> None:
        """Cover ``slot``'s write frontier, evicting the youngest
        occupant (possibly ``slot`` itself) while the pool is short.
        Progress is guaranteed: one sequence always fits the pool
        (``pool_pages - 1 >= pages_per_slot``, checked at init)."""
        while True:
            need = self._pages_short(upto, len(self.slot_pages[slot]))
            if need <= self._avail_pages():
                self._alloc_pages(slot, upto)
                return
            cands = [t for t in range(self.slots)
                     if self.occupant[t] is not None]
            victim = max(cands, key=lambda t: self.slot_admit_seq[t])
            self._evict(victim)
            if victim == slot:
                return  # the requester itself was youngest: it waits

    def _resume_swapped(self) -> None:
        """Swap preempted requests back into free slots, oldest first,
        when the pool can hold their pages plus the next block's writes
        (the headroom requirement prevents immediate re-eviction)."""
        k = self.steps_per_sync
        for slot in range(self.slots):
            if not self.swapped:
                break
            if self.occupant[slot] is not None or slot in self.admitting:
                continue
            sw = self.swapped[0]
            pr = max(len(sw.req.prompt) - sw.poff, 0)
            rem = sw.req.max_new - len(sw.req.emitted)
            writes = self._block_writes(pr, rem)
            base = sw.poff if pr else sw.pos + 1
            upto = min(base + writes - 1, self.kv_len - 1)
            need = max(self._pages_short(upto), sw.n_pages)
            if self._avail_pages() < need:
                break
            self.swapped.popleft()
            self._stamp_admit(sw.req)  # an imported request's first slot
            self._alloc_pages(slot, sw.n_pages * self.page - 1)
            pids = np.zeros(self.pages_per_slot, np.int32)
            pids[:sw.n_pages] = self.table[slot, :sw.n_pages]
            _, scatter = self._page_io_fns()
            # pad to the power-of-two compile width (clamped to the
            # table width, matching _evict); pad rows write zeros into
            # the reserved scratch page
            n2 = min(self._pow2(sw.n_pages), self.pages_per_slot)
            kv = sw.kv
            if n2 > sw.n_pages:
                kv = [np.concatenate(
                    [x, np.zeros((n2 - sw.n_pages,) + x.shape[1:],
                                 x.dtype)]) for x in kv]
            self.cache = scatter(self.cache,
                                 [jnp.asarray(x) for x in kv],
                                 jnp.asarray(pids), n2)
            self.occupant[slot] = sw.req
            self._set_slot_params(slot, sw.req)
            self.pos[slot] = sw.pos
            self.slot_poff[slot] = sw.poff
            self.last_tok[slot] = sw.last_tok
            self._alloc_pages(slot, upto)
            self.stats["swap_ins"] += 1

    def _insert_paged(self, slabs, slot: int) -> None:
        """Scatter a prefill's (1, hkv, bucket, d) slabs into this slot's
        OWNED pages (the paged twin of ``_insert``): allocation is by
        prompt length, so a padded bucket wider than the owned pages only
        writes the chunks the slot owns — the padded tail is never read
        (pos bound) and decode re-writes positions before reading them."""
        bucket = jax.tree.leaves(slabs)[0].shape[2]
        n = min(-(-bucket // self.page), len(self.slot_pages[slot]))
        if self._insert_paged_fn is None:
            page = self.page

            @partial(jax.jit, donate_argnums=compat.donate(0), static_argnums=(3,))
            def insert(cache, slabs, pids, n):
                def write(big, small):
                    for c in range(n):
                        chunk = jax.lax.dynamic_slice_in_dim(
                            small, c * page,
                            min(page, small.shape[2] - c * page), axis=2)
                        big = jax.lax.dynamic_update_slice(
                            big, chunk.astype(big.dtype),
                            (pids[c], 0, 0, 0))
                    return big
                return jax.tree.map(write, cache, slabs)

            self._insert_paged_fn = insert
        pids = jnp.asarray(self.table[slot, :n])
        self.cache = self._insert_paged_fn(self.cache, slabs, pids, n)

    def _insert(self, slabs, slot: int) -> None:
        """Write a prefill's (1, hkv, bucket, d) slabs into the pool slot
        (jitted with the pool donated — an in-place slab write, not a
        whole-pool copy per admission)."""
        if self._insert_fn is None:
            @partial(jax.jit, donate_argnums=compat.donate(0))
            def insert(cache, slabs, slot):
                return jax.tree.map(
                    lambda big, small: jax.lax.dynamic_update_slice(
                        big, small.astype(big.dtype), (slot, 0, 0, 0)),
                    cache, slabs)

            self._insert_fn = insert
        self.cache = self._insert_fn(self.cache, slabs,
                                     jnp.int32(slot))

    # -- scheduling -------------------------------------------------------
    def _sample_first(self, req: _Request, last_logits) -> int:
        """Sample a freshly-admitted request's first token with ITS
        sampling parameters."""
        # timeline only (nested in serve.prefill, whose phase counts it):
        # the eager sampling programs, then the blocking fetch of ``int()``
        with span("serve.first_token"):
            self.key, sub = jax.random.split(self.key)
            return int(gen.sample_per_seq(
                sub, last_logits[None],
                jnp.full((1,), req.temperature, jnp.float32),
                jnp.full((1,), req.top_k, jnp.int32),
                jnp.full((1,), req.top_p, jnp.float32))[0])

    def _stamp_admit(self, req: _Request) -> None:
        """``req`` takes a slot now: the end of its queue wait.  A request
        that held one before (swapped out and resumed) keeps its first
        stamp."""
        if req.t_admit is None:
            req.t_admit = time.perf_counter()
            self.stats["admitted"] += 1
            self.stats["queue_wait_s"] += req.t_admit - req.t_submit

    def _set_slot_params(self, slot: int, req: _Request) -> None:
        self.slot_temp[slot] = req.temperature
        self.slot_topk[slot] = req.top_k
        self.slot_topp[slot] = req.top_p
        self.slot_eos[slot] = -1 if req.eos_id is None else req.eos_id
        if self.paged:
            # admission order; preemption evicts the youngest occupant
            self._admit_counter += 1
            self.slot_admit_seq[slot] = self._admit_counter

    def _occupy(self, slot: int, req: _Request, first_tok: int,
                out: list) -> None:
        """Install a batch-prefilled request into its slot and emit
        token 0 (its K/V is already in the pool; prefill complete)."""
        self.occupant[slot] = req
        self.pos[slot] = len(req.prompt) - 1
        self.slot_poff[slot] = len(req.prompt)
        self._set_slot_params(slot, req)
        # each batch-prefilled admission emits exactly one token from
        # its prefill dispatch(es); accounting needs this count (NOT
        # prefill_dispatches — chunked admissions take several)
        self.stats["batch_admissions"] += 1
        self._emit(slot, first_tok, out)

    def _occupy_prefilling(self, slot: int, req: _Request) -> bool:
        """Install a queued request into an empty slot with NO prefill
        done yet: its prompt will be teacher-forced inside the decode
        block (in-block admission), one token per lockstep step.  Under
        paging, reserves pages for the first block's writes; returns
        False (request stays queued) when the pool cannot cover them."""
        if self.paged:
            upto = self._block_writes(len(req.prompt), req.max_new) - 1
            upto = min(upto, self.kv_len - 1)
            if self._avail_pages() < self._pages_short(upto):
                return False
            self._alloc_pages(slot, upto)
        self._stamp_admit(req)
        self.occupant[slot] = req
        self.pos[slot] = 0
        self.slot_poff[slot] = 0
        self.last_tok[slot] = 0
        self._set_slot_params(slot, req)
        return True

    def _install_refill(self, slot: int, req: _Request) -> None:
        """The device switched this slot to its staged refill mid-block:
        mirror that on the host — the refill becomes the occupant and
        (under paging) its reserved pages become the slot's pages (the
        retired occupant's pages were already released by ``_emit``)."""
        self._stamp_admit(req)
        self.occupant[slot] = req
        self._set_slot_params(slot, req)
        if self.paged:
            self.slot_pages[slot] = self.refill_pages[slot]
            self.refill_pages[slot] = []
            self.table[slot, :] = self.r_table[slot]
            self.r_table[slot, :] = 0

    def _fill_free_slots(self) -> list[tuple[int, int]]:
        """Unchunked admission: prefill queued requests into free slots in
        one whole-bucket dispatch each; returns (rid, first token) pairs.
        When the page pool cannot hold the prompt, the request WAITS in
        the queue (live work and swapped-out victims free pages as they
        finish) instead of raising."""
        if self._hold_for_resume():
            return []
        out = []
        for slot in range(self.slots):
            if self.occupant[slot] is not None or not self.queue:
                continue
            head = self.queue[0]
            L = len(head.prompt)
            shared = (self._prefix_lookup(head)
                      if self.prefix_cache else [])
            if self.paged:
                # shared admissions allocate through position L (the
                # suffix pad's clamp row, = decode's first write)
                upto = min(L, self.kv_len - 1) if shared else L - 1
                # fresh pages needed beyond the shared prefix; idle
                # shared pages must not double-count as reclaimable
                # (reclaiming them would destroy the very prefix we
                # are about to reuse)
                shared_idle = sum(1 for pid in shared
                                  if self.page_refs.get(pid, 0) == 0)
                if (self._avail_pages() - shared_idle
                        < self._pages_short(upto) - len(shared)):
                    break  # pool full: hold admissions until pages free
            req = self.queue.popleft()
            self._stamp_admit(req)
            if shared:
                last_logits = self._admit_shared(slot, req, shared)
            else:
                bucket = next(b for b in self.buckets if b >= L)
                padded = np.zeros((1, bucket), np.int32)
                padded[0, :L] = req.prompt
                last_logits, slabs = self._prefill(bucket)(
                    self.params, jnp.asarray(padded), L)
                self.stats["prefill_dispatches"] += 1
                if self.paged:
                    self._alloc_pages(slot, L - 1)
                    self._insert_paged(slabs, slot)
                    if self.prefix_cache:
                        self._register_prompt_pages(slot, req)
                else:
                    self._insert(slabs, slot)
            self._occupy(slot, req, self._sample_first(req, last_logits),
                         out)
        return out

    def _admit_shared(self, slot: int, req: _Request,
                      shared: list[int]):
        """Admit over cached prompt pages: the slot's table points at
        the shared pages (refcounted, LRU-touched), fresh tail pages are
        allocated, and only the un-cached suffix prefills — ONE dispatch
        whose window attends the shared prefix through the table
        (gen.verify_step_ragged) and writes its own K/V into the fresh
        pages.  Returns the last-prompt-position logits."""
        self.stats["prefix_hits"] += 1
        self.stats["prefix_pages_shared"] += len(shared)
        pages = self.slot_pages[slot]
        for i, pid in enumerate(shared):
            self.page_refs[pid] += 1
            h = self.page_hash[pid]
            self.registry.pop(h)        # LRU touch: re-insert newest
            self.registry[h] = pid
            self.table[slot, i] = pid
            pages.append(pid)
        L = len(req.prompt)
        base = len(shared) * self.page
        srem = L - base                  # >= 1 (_prefix_lookup cap)
        sbucket = next(b for b in self.buckets if b >= srem)
        # allocate through position L (decode's first write — needed
        # next dispatch regardless); pad writes clamp onto row L
        self._alloc_pages(slot, min(L, self.kv_len - 1))
        chunk = np.zeros((1, sbucket), np.int32)
        chunk[0, :srem] = req.prompt[base:]
        last_logits, self.cache = self._suffix_prefill(sbucket)(
            self.params, self.cache, jnp.asarray(chunk),
            jnp.int32(base), jnp.int32(srem - 1),
            jnp.int32(min(L, self.kv_len - 1)),
            jnp.asarray(self.table[slot:slot + 1]))
        self.stats["prefill_dispatches"] += 1
        # publish any freshly prefilled full pages BEYOND the shared
        # chain (a longer prompt extends the cached prefix; the register
        # skips pages/hashes already in the registry) — ADVICE r5 #1
        self._register_prompt_pages(slot, req)
        return last_logits

    def _advance_admissions(self) -> list[tuple[int, int]]:
        """Chunked admission: reserve free slots for queued requests, then
        push ONE prompt chunk per admitting slot (each a short dispatch —
        live slots decode between calls instead of waiting out a whole
        prompt).  Finishing admissions install into their slot and emit
        their first token."""
        c = self.prefill_chunk
        for slot in range(self.slots):
            if self._hold_for_resume():
                # don't reserve free slots for younger arrivals while a
                # preempted request waits: _resume_swapped skips slots in
                # self.admitting, so a reservation here would sit idle
                # behind its own held install (priority inversion)
                break
            if (self.occupant[slot] is None and slot not in self.admitting
                    and self.queue):
                req = self.queue.popleft()
                self._stamp_admit(req)
                bucket = next(b for b in self.buckets
                              if b >= len(req.prompt))
                # scratch cache is created inside the first chunk's jit
                self.admitting[slot] = _Admission(req, None, bucket)

        out = []
        for slot, adm in list(self.admitting.items()):
            req, L = adm.req, len(adm.req.prompt)
            if adm.last_logits is None:
                chunk = np.zeros((1, c), np.int32)
                take = min(c, L - adm.off)
                chunk[0, :take] = req.prompt[adm.off:adm.off + take]
                final = adm.off + c >= L
                unembed_idx = jnp.int32((L - 1 - adm.off) if final else 0)
                if adm.off == 0:
                    last_logits, adm.cache = self._prefill_chunk_fn(
                        adm.bucket, first=True)(
                        self.params, jnp.asarray(chunk), unembed_idx)
                else:
                    last_logits, adm.cache = self._prefill_chunk_fn(
                        adm.bucket, first=False)(
                        self.params, adm.cache, jnp.asarray(chunk),
                        jnp.int32(adm.off), unembed_idx)
                self.stats["prefill_dispatches"] += 1
                adm.off += c
                if final:
                    adm.last_logits = last_logits
            if adm.last_logits is not None:
                # prefill complete: install — or, when the page pool
                # cannot hold the prompt yet (or a preempted request is
                # waiting on freed pages), HOLD the finished slabs and
                # retry next step (pages free as work retires)
                if self.paged:
                    if (self._hold_for_resume()
                            or self._avail_pages()
                            < self._pages_short(L - 1)):
                        continue
                    self._alloc_pages(slot, L - 1)
                    self._insert_paged(adm.cache, slot)
                else:
                    self._insert(adm.cache, slot)
                del self.admitting[slot]
                self._occupy(slot, req,
                             self._sample_first(req, adm.last_logits),
                             out)
        return out

    def _emit(self, slot: int, tok: int, out: list) -> None:
        req = self.occupant[slot]
        if req.t_first is None:
            req.t_first = time.perf_counter()
            self.stats["first_tokens"] += 1
            self.stats["admit_to_first_s"] += req.t_first - req.t_admit
        req.emitted.append(tok)
        out.append((req.rid, tok))
        self.stats["emitted_tokens"] += 1
        if ((req.eos_id is not None and tok == req.eos_id)
                or len(req.emitted) >= req.max_new):
            req.done = True
            req.t_done = time.perf_counter()
            self.occupant[slot] = None  # slot free; stale K/V never read
            if self.paged:
                # a prompt that completed and retired inside ONE block
                # never hit the continuing-slot publish hook — publish
                # before releasing (an emission proves the prompt was
                # fully written; the registry IS the cache, refcount 0)
                self._maybe_publish_prompt_pages(slot, req,
                                                 prompt_done=True)
                # the block table row is rewritten at the next admission;
                # in-flight lockstep writes this dispatch stay within the
                # old frontier (write_cap), so reuse is race-free
                self._release_pages(slot)
        else:
            self.last_tok[slot] = tok

    def _hold_for_resume(self) -> bool:
        """True while a preempted request waits on the resume queue: all
        PAGE-CONSUMING admissions hold (as ``_stage_refills`` always has)
        so freed pages accumulate for the oldest victim's swap-in instead
        of being grabbed by younger arrivals — ``_resume_swapped`` runs
        first each step, so this is bounded wait, and progress is
        guaranteed because live occupants retire on finite budgets and
        one sequence always fits the emptied pool."""
        return self.paged and bool(self.swapped)

    def _stage_refills(self) -> None:
        """Pop queued requests behind occupants that can retire by BUDGET
        this block, so the device can hand their slot over in place.
        Every prompt fits the in-block buffer (``submit`` rejects prompts
        over the largest bucket == ``refill_width``).  Unused staged
        requests are returned to the queue front after the block.

        Occupants whose only retirement path this block is an armed eos
        (``pr + rem > k``) are NOT staged behind: whether the eos fires
        is unknowable here, and staging every block against the one
        block it eventually fires in is pure churn (pop + page reserve +
        requeue per block for the request's whole lifetime) to save at
        most one block's tail of slot-steps once — the slot refills via
        in-block admission at the next sync instead."""
        if self._hold_for_resume():
            # preempted requests are OLDEST and need a pages-restore
            # dispatch before decoding, which the in-block handoff
            # cannot do — let retiring slots go empty so the resume
            # pass takes them next step, instead of handing them to
            # younger queue arrivals (starvation)
            return
        k = self.steps_per_sync
        for slot in range(self.slots):
            if not self.queue:
                break
            if (self.prefix_cache
                    and self._prefix_lookup(self.queue[0])):
                # the queue head has a CACHED prefix: handing it off
                # in-block would teacher-force the whole prompt one
                # token per step and forfeit the shared pages — let the
                # batched path admit it over the cache instead
                break
            occ = self.occupant[slot]
            if (occ is None or slot in self.admitting
                    or self.staged_refill[slot] is not None):
                continue
            pr = max(len(occ.prompt) - int(self.slot_poff[slot]), 0)
            rem = occ.max_new - len(occ.emitted)
            if pr + rem > k:
                # cannot retire by budget this block (prompt alone spans
                # it, or budget unreachable): don't hold a request (or
                # pages) hostage behind it on the off-chance of an eos
                continue
            if self.paged and not self._alloc_refill_pages(slot):
                break
            self.staged_refill[slot] = self.queue.popleft()
            self._staged_order.append(slot)

    def _requeue_unused_refills(self) -> None:
        for slot in reversed(self._staged_order):
            req = self.staged_refill[slot]
            if req is not None:
                self.staged_refill[slot] = None
                if self.paged:
                    self._release_refill_pages(slot)
                self.queue.appendleft(req)
        self._staged_order.clear()

    def _req_fields(self, req: _Request):
        """(temp, top_k, top_p, eos, budget) staging vectors' entries."""
        return (req.temperature, req.top_k, req.top_p,
                -1 if req.eos_id is None else req.eos_id, req.max_new)

    def step(self) -> list[tuple[int, int]]:
        """Admit queued work, then run one decode block (up to
        ``steps_per_sync`` lockstep steps) for the whole pool in one
        device dispatch.

        With ``inblock_refill`` (default), admission into an empty slot
        while the pool is running costs nothing: the request's prompt is
        teacher-forced inside the block (one token per lockstep step that
        runs anyway), and slots whose occupant retires mid-block hand
        over to a staged next request in place.  Batched (bucketed /
        chunked) prefill serves an idle pool and prompts wider than the
        in-block prompt buffer.

        With ``overlap`` (default), a block's results are fetched on the
        NEXT ``step()`` call, and when the host can prove the next block
        needs no intervention it is dispatched from the in-flight
        block's device-side carry BEFORE the fetch — the fetch RTT and
        host parse then hide under device compute (module docstring).
        Emissions therefore arrive one call later than the dispatch that
        computed them; streams and stats totals are unchanged.

        Returns (rid, token) pairs emitted this call, in per-slot
        sampling order.
        """
        out: list[tuple[int, int]] = []
        fl, self._inflight = self._inflight, None
        if fl is not None:
            nfl = self._try_chain(fl)
            if nfl is not None:
                # block N+1 is already computing: N's fetch RTT + parse
                # run concurrently with it
                fl.refs_held = True
                self._break_chain = False
                out += self._collect(fl)
                if self._break_chain:
                    # N's parse revealed an occupancy change (a refill
                    # handoff or a retirement): N+1 was dispatched with
                    # exact device state and stays valid, but its
                    # metadata (headroom, page frontiers) is stale for
                    # deciding a FURTHER chain — go serial after it
                    nfl.chainable = False
                self._inflight = nfl
                return out
            out += self._collect(fl)
        nfl = self._plan_dispatch(out)
        if nfl is not None:
            if self.overlap:
                self._inflight = nfl  # collected (and maybe chained) next call
            else:
                out += self._collect(nfl)
        return out

    def _try_chain(self, fl: _InFlight) -> _InFlight | None:
        """Dispatch the successor of the in-flight block ``fl`` directly
        from its device-side carry — valid only when the host provably
        has no intervention to make between the two blocks:

        - no admission could happen (no chunked admissions or swapped
          requests waiting; no empty slot while the queue holds work);
        - every live slot either cannot retire within fl plus the
          chained block (``headroom > 2K``) or retires into an
          already-staged refill, whose device-side in-place handoff is
          exact without the host (the refill's reserved cap must cover
          its writes across both blocks: a parsed handoff BREAKS the
          chain — ``step`` — so a refill never runs more than one
          chained block past its switch, bounding them at ``2K - 1``);
        - under paging, the pool can cover one more block's worst-case
          writes for every continuing row without evicting anyone.

        A slot that retires on an ARMED EOS mid-chain simply idles for
        the rest of that chain (done is carried; the parsed retirement
        then breaks the chain) — exact, and accounted as waste.
        Returns the new in-flight record, or None to fall back to the
        serial plan→fetch→parse order."""
        if not (self.overlap and fl.chainable):
            self.stats["unchained_off"] += 1
            return None
        if self.admitting or (self.paged and self.swapped):
            self.stats["unchained_admitting"] += 1
            return None
        if self.queue and any(self.occupant[s] is None and s not in fl.live
                              for s in range(self.slots)):
            self.stats["unchained_empty_slot"] += 1
            return None  # an empty slot could admit queued work
        k = self.steps_per_sync
        staged = np.zeros(self.slots, bool)
        for s in fl.live:
            if fl.headroom[s] > 2 * k:
                continue
            if self.staged_refill[s] is None:
                # could retire with nothing staged: the host will want
                # to admit into (or compact away) the slot
                self.stats["unchained_retire_unstaged"] += 1
                return None
            staged[s] = True
            # the refill switches in during fl at the earliest at step 1
            # and the chain breaks once the switch is parsed, so it
            # lockstep-writes at most 2K - 1 positions from 0 before a
            # serial plan re-extends its pages
            if self.paged and 2 * k - 1 > \
                    len(self.refill_pages[s]) * self.page - 1:
                self.stats["unchained_refill_pages"] += 1
                return None
        upto = fl.upto.copy()
        if self.paged and not self._chain_pages(fl, staged, upto):
            self.stats["unchained_pool"] += 1
            return None
        with self.timers.phase("dispatch"):
            cur = fl.cur
            if self.paged:
                # tables/caps may have grown in _chain_pages
                cur = dict(fl.cur)
                cur["table"] = jnp.asarray(self.table.copy())
                cur["cap"] = jnp.asarray(self._write_caps())
            self.key, sub = jax.random.split(self.key)
            packed, self.cache, carry = self._decode_for(fl.w)(
                self.params, self.cache, cur, fl.ref, fl.carry, sub)
        self.stats["chained_dispatches"] += 1
        return _InFlight(
            packed=packed, carry=carry, cur=cur, ref=fl.ref,
            live=fl.live, cols=fl.cols, w=fl.w, compact=False, npad=0,
            plen=fl.plen, active0=fl.active0 | staged,
            headroom=np.maximum(fl.headroom - k, 0), upto=upto,
            chainable=True)

    def _chain_pages(self, fl: _InFlight, staged: np.ndarray,
                     upto: np.ndarray) -> bool:
        """Extend continuing rows' page tables to cover one more block's
        worst-case writes WITHOUT evicting (eviction is an intervention
        — the chain declines instead).  Rows handing off to a staged
        refill are skipped: their writes land in the refill's reserved
        pages (checked by the caller); the dead occupant's pages are
        released at parse."""
        plans = []
        need = 0
        for s in fl.live:
            if staged[s]:
                continue
            up = min(int(fl.upto[s]) + self.steps_per_sync,
                     self.kv_len - 1)
            short = self._pages_short(up, len(self.slot_pages[s]))
            if short > 0:
                plans.append((s, up))
                need += short
            upto[s] = up
        if need > self._avail_pages():
            return False
        for s, up in plans:
            self._alloc_pages(s, up)
        return True

    def _plan_dispatch(self, out: list) -> _InFlight | None:
        """Admit queued work from the CURRENT (fully parsed) host state,
        stage the pool, and dispatch one decode block — without fetching
        its results (``_collect`` does that; the serial path calls it
        immediately, the overlapped path on the next ``step()``).
        Admission first-tokens are appended to ``out``.  Speculative
        blocks (``n_spec > 0``) dispatch AND parse here — their
        round-structured parse is not pipelined.  Returns None when
        nothing is live after admission."""
        plan = self.timers.phase("host_plan").start()
        if (self.schedule == "longest_first" and self._queue_dirty
                and len(self.queue) > 1):
            # stable sort once per batch of submissions (dirty flag), not
            # per block; requeued unused refills re-enter at the front
            # they were popped from, preserving order
            self.queue = deque(sorted(self.queue,
                                      key=lambda r: -r.max_new))
        self._queue_dirty = False
        if self.paged and self.swapped:
            self._resume_swapped()  # preempted requests take priority
        live_any = any(o is not None for o in self.occupant)
        use_inblock = self.inblock_refill and live_any
        if use_inblock and not self._hold_for_resume():
            # in-block admission: empty slots take narrow queued requests
            # and prefill them inside the running block
            for slot in range(self.slots):
                if (self.occupant[slot] is not None
                        or slot in self.admitting or not self.queue):
                    continue
                if len(self.queue[0].prompt) > self.inblock_admit_limit:
                    break  # strict FIFO: long head admits batched below
                if (self.prefix_cache
                        and self._prefix_lookup(self.queue[0])):
                    break  # cached prefix: teacher-forcing from 0 would
                    #        forfeit the shared pages (as _stage_refills)
                req = self.queue.popleft()
                if not self._occupy_prefilling(slot, req):
                    self.queue.appendleft(req)  # page pool full: wait
                    break
        plan.stop()
        with self.timers.phase("prefill"):
            if self.prefill_chunk is None:
                if not use_inblock or (
                        self.queue and len(self.queue[0].prompt)
                        > self.inblock_admit_limit):
                    out += self._fill_free_slots()
            else:
                out += self._advance_admissions()
        # staging, pages and refills: too long to indent, so every way
        # out of it stops the span
        plan = self.timers.phase("host_plan").start()
        live = [s for s in range(self.slots) if self.occupant[s] is not None]
        if not live:
            plan.stop()
            return None
        k = self.steps_per_sync
        # per-slot staging: remaining budgets drive the device-side early
        # exit (empty slots: 0 — they never extend the block); mid-prefill
        # occupants carry their prompt + offset for teacher-forcing
        budget = np.zeros(self.slots, np.int32)
        plen = np.zeros(self.slots, np.int32)
        poff = np.zeros(self.slots, np.int32)
        prompt = np.zeros((self.slots, self.refill_width), np.int32)
        pos = self.pos.copy()
        for s in live:
            occ = self.occupant[s]
            budget[s] = occ.max_new - len(occ.emitted)
            if self.slot_poff[s] < len(occ.prompt):
                plen[s] = len(occ.prompt)
                poff[s] = self.slot_poff[s]
                prompt[s, :plen[s]] = occ.prompt
                pos[s] = poff[s]  # next write = next prompt position
            else:
                # established: advance to the new token's write position
                pos[s] = min(pos[s] + 1, self.max_len - 1)
        upto = np.zeros(self.slots, np.int32)
        if self.paged:
            # pre-allocate pages covering this dispatch's write frontier:
            # min(K, prompt-left + min(K, budget)) writes from pos — a
            # slot that retires early clamps at its frontier, so the
            # block never needs pages past its real writes.  Under pool
            # pressure the youngest occupant is preempted (host-swap)
            # rather than raising.
            for s in list(live):
                if self.occupant[s] is None:
                    continue  # evicted as an earlier slot's victim
                pr = int(plen[s]) - int(poff[s]) if plen[s] else 0
                writes = self._block_writes(pr, int(budget[s]))
                upto[s] = min(int(pos[s]) + writes - 1, self.kv_len - 1)
                self._ensure_pages_or_evict(s, int(upto[s]))
            for s in list(live):
                if self.occupant[s] is None:  # evicted: out of the block
                    live.remove(s)
                    budget[s] = 0
                    plen[s] = 0
            if not live:
                plan.stop()
                return None
        if use_inblock:
            self._stage_refills()
        # per-slot prompt-left + budget at dispatch: _try_chain's bound on
        # whether this block (or its chained successor) could retire it
        headroom = np.zeros(self.slots, np.int32)
        for s in live:
            pr = int(plen[s]) - int(poff[s]) if plen[s] else 0
            headroom[s] = pr + int(budget[s])
        table = (self.table if self.paged
                 else np.zeros((self.slots, 1), np.int32))
        caps = self._write_caps()
        if self.n_spec:
            # speculative staging: each live slot's STREAM (its known
            # tokens at their positions), determined count, and cache
            # frontier — the (stream, det, wr) machine _decode_spec_for
            # documents.  wr < det always: the frontier token is known.
            stream = np.zeros((self.slots, self.kv_len), np.int32)
            det = np.zeros(self.slots, np.int32)
            wr = np.zeros(self.slots, np.int32)
            for s in live:
                occ = self.occupant[s]
                lp = len(occ.prompt)
                stream[s, :lp] = occ.prompt
                ne = len(occ.emitted)
                if ne:
                    stream[s, lp:lp + ne] = np.asarray(occ.emitted,
                                                       np.int32)
                det[s] = lp + ne
                wr[s] = (self.slot_poff[s] if self.slot_poff[s] < lp
                         else self.pos[s] + 1)
        # Batch COMPACTION for the drained tail (paged): with no queued
        # or staged work left and few slots live, dispatch a NARROWER
        # compiled block over just the live slots' rows — the page
        # tables carry the cache indirection, so re-rowing is free.
        # This reclaims the empty-slot lockstep steps that neither
        # refill nor LPT can touch (BASELINE.md waste_when
        # 'queue_drained').  Dense caches are physically slot-indexed;
        # they keep the full width.  Decided BEFORE the refill staging
        # arrays are built: compact dispatches (the whole drained tail)
        # skip that full-width work.
        compact = (self.compact_tail and self.paged and not self.queue
                   and not self.admitting and not self.swapped
                   and all(r is None for r in self.staged_refill)
                   and len(live) <= self.slots // 2)
        if compact:
            w = 1 << max(len(live) - 1, 0).bit_length()
            sel = np.asarray(live + [live[0]] * (w - len(live)))
            npad = w - len(live)

            def cut_cur(a):
                a = np.asarray(a)[sel].copy()
                return a

            budget_c = cut_cur(budget)
            caps_c = cut_cur(caps)
            table_c = cut_cur(table)
            pos_c = cut_cur(pos)
            plen_c = cut_cur(plen)
            poff_c = cut_cur(poff)
            if npad:
                # pad rows are dead: zero budget makes them done at
                # step 0, zero plen keeps them out of prefill, and
                # their clamped writes land on the reserved scratch page
                budget_c[-npad:] = 0
                caps_c[-npad:] = 0
                table_c[-npad:] = 0
                pos_c[-npad:] = 0
                plen_c[-npad:] = 0
                poff_c[-npad:] = 0
            # the staging fields both block flavors share, then the
            # mode-specific state (ONE place defines the common set; the
            # full-width branch below builds the same shape uncut).  The
            # lockstep block's per-slot machine state lives in ``carry``
            # (tok/pos/poff/rem/done/active/lw): staged from host
            # mirrors here, fed back device-to-device by _try_chain.
            cur = dict(plen=plen_c, temp=cut_cur(self.slot_temp),
                       top_k=cut_cur(self.slot_topk),
                       top_p=cut_cur(self.slot_topp),
                       eos=cut_cur(self.slot_eos),
                       cap=caps_c, table=table_c)
            carry = None
            if self.n_spec:
                det_c, wr_c = cut_cur(det), cut_cur(wr)
                if npad:
                    det_c[-npad:] = 1  # pad rows: rem 0 -> done at round 0
                    wr_c[-npad:] = 0
                cur.update(stream=cut_cur(stream), det=det_c, wr=wr_c,
                           rem=budget_c)
            else:
                cur.update(prompt=cut_cur(prompt))
                carry = dict(tok=cut_cur(self.last_tok), pos=pos_c,
                             poff=poff_c, rem=budget_c,
                             done=np.zeros(w, bool),
                             active=np.zeros(w, bool), lw=pos_c.copy())
            ref = dict(valid=np.zeros(w, bool),
                       plen=np.zeros(w, np.int32),
                       prompt=np.zeros((w, self.refill_width), np.int32),
                       temp=np.ones(w, np.float32),
                       top_k=np.zeros(w, np.int32),
                       top_p=np.ones(w, np.float32),
                       eos=np.full(w, -1, np.int32),
                       budget=np.zeros(w, np.int32),
                       cap=np.zeros(w, np.int32),
                       table=np.zeros_like(table_c))
            cols = {s: j for j, s in enumerate(live)}
            self.stats["compact_dispatches"] += 1
        else:
            # full-width dispatch: build the refill staging arrays here,
            # their only consumer (compact dispatches skip the work —
            # the compact condition requires no staged refills)
            r_valid = np.zeros(self.slots, bool)
            r_plen = np.zeros(self.slots, np.int32)
            r_prompt = np.zeros((self.slots, self.refill_width), np.int32)
            r_temp = np.ones(self.slots, np.float32)
            r_topk = np.zeros(self.slots, np.int32)
            r_topp = np.ones(self.slots, np.float32)
            r_eos = np.full(self.slots, -1, np.int32)
            r_budget = np.zeros(self.slots, np.int32)
            for s, req in enumerate(self.staged_refill):
                if req is None:
                    continue
                r_valid[s] = True
                r_plen[s] = len(req.prompt)
                r_prompt[s, :r_plen[s]] = req.prompt
                (r_temp[s], r_topk[s], r_topp[s], r_eos[s],
                 r_budget[s]) = self._req_fields(req)
            if self.paged:
                r_cap = self._write_caps(self.refill_pages)
                r_table = self.r_table
            else:
                r_cap = np.full(self.slots, self.kv_len - 1, np.int32)
                r_table = np.zeros((self.slots, 1), np.int32)
            w = self.slots
            # live mirrors are COPIED into the staging arrays: with a
            # block in flight the host mutates them at parse, and a
            # host->device transfer may alias host memory on some
            # backends (the CPU backend's zero-copy transfers)
            cur = dict(plen=plen, temp=self.slot_temp.copy(),
                       top_k=self.slot_topk.copy(),
                       top_p=self.slot_topp.copy(),
                       eos=self.slot_eos.copy(), cap=caps,
                       table=table.copy())
            carry = None
            if self.n_spec:
                cur.update(stream=stream, det=det, wr=wr, rem=budget)
            else:
                cur.update(prompt=prompt)
                carry = dict(tok=self.last_tok.copy(), pos=pos,
                             poff=poff, rem=budget,
                             done=np.zeros(self.slots, bool),
                             active=np.zeros(self.slots, bool),
                             lw=pos.copy())
            ref = dict(valid=r_valid, plen=r_plen, prompt=r_prompt,
                       temp=r_temp, top_k=r_topk, top_p=r_topp,
                       eos=r_eos, budget=r_budget, cap=r_cap,
                       table=r_table.copy())
            cols = {s: s for s in live}
        cur = {k_: jnp.asarray(v) for k_, v in cur.items()}
        ref = {k_: jnp.asarray(v) for k_, v in ref.items()}
        self.key, sub = jax.random.split(self.key)
        plan.stop()
        if self.n_spec:
            gcols = 0
            if self.paged:
                # deepest allocated frontier across the dispatch's rows
                # (occupants + staged refills), power-of-two-bucketed so
                # a growing workload compiles O(log pages_per_slot)
                # block variants, not one per depth
                deep = max([len(self.slot_pages[s]) for s in live]
                           + [len(self.refill_pages[s])
                              for s in range(self.slots)
                              if self.staged_refill[s] is not None]
                           + [1])
                gcols = min(1 << (deep - 1).bit_length(),
                            self.pages_per_slot)
            with self.timers.phase("dispatch"):
                packed, self.cache = self._decode_spec_for(w, gcols)(
                    self.params, self.cache, cur, ref, sub)
            with self.timers.phase("fetch"):
                # owned copy — see _collect: the parse below dispatches
                # refill prefills (async, donated) while still reading
                flat = np.array(packed, copy=True)
            with self.timers.phase("host_parse"):
                self._parse_spec_block(flat, live, cols, w, out)
            return None
        with self.timers.phase("dispatch"):
            carry = {k_: jnp.asarray(v) for k_, v in carry.items()}
            packed, self.cache, carry = self._decode_for(w)(
                self.params, self.cache, cur, ref, carry, sub)
        return _InFlight(
            packed=packed, carry=carry, cur=cur, ref=ref, live=live,
            cols=cols, w=w, compact=compact,
            npad=(npad if compact else 0), plen=plen,
            active0=np.zeros(self.slots, bool), headroom=headroom,
            upto=upto, chainable=not compact)

    def _collect(self, fl: _InFlight) -> list[tuple[int, int]]:
        """Fetch an in-flight block's packed results (ONE device->host
        transfer — with a chained successor already dispatched, this
        transfer's RTT runs concurrently with the successor's device
        compute) and mirror them on the host: emissions, retire/refill
        handoffs, frontier sync, prefix publication, accounting.  With
        ``refs_held`` (a chained successor references the staged
        refills), unused refills stay staged instead of requeueing."""
        out: list[tuple[int, int]] = []
        k, w, live, cols = self.steps_per_sync, fl.w, fl.live, fl.cols
        plen, compact, npad = fl.plen, fl.compact, fl.npad
        with self.timers.phase("fetch"):
            # OWNED copy, not np.asarray: on the CPU backend the latter
            # can be a zero-copy VIEW of the device buffer, and the parse
            # below dispatches follow-up work (refill prefills; under
            # overlap the successor block is ALREADY executing from this
            # block's donated carry) that may reuse the buffer while the
            # view is still read.  packed is a small int32 vector; the
            # copy is noise next to the transfer itself.
            flat = np.array(fl.packed, copy=True)
        parse = self.timers.phase("host_parse").start()
        occ_before = [self.occupant[s] for s in live]
        kn = k * w
        toks = flat[:kn].reshape(k, w)  # rows >= steps_exec unused
        mask = flat[kn:2 * kn].reshape(k, w).astype(bool)
        sw = flat[2 * kn:2 * kn + w]
        lw = flat[2 * kn + w:2 * kn + 2 * w]
        poff_f = flat[2 * kn + 2 * w:2 * kn + 3 * w]
        pf = flat[2 * kn + 3 * w:2 * kn + 4 * w]
        if compact and npad:
            pf = pf[:len(live)]  # pad rows: plen zeroed, no prefill
        k_exec = int(flat[-1])
        self.stats["decode_dispatches"] += 1
        self.stats["slot_steps"] += k_exec * w
        self.stats["inblock_prefill_steps"] += int(np.sum(pf))
        emitted_before = self.stats["emitted_tokens"]
        for s in live:
            j = cols[s]
            cut = min(int(sw[j]), k_exec)
            for i in range(cut):
                if mask[i, j] and self.occupant[s] is not None:
                    self._emit(s, int(toks[i, j]), out)
            if self.occupant[s] is not None:
                # current request continues; carry prefill progress only
                # for slots staged mid-prefill (the device's poff is 0,
                # not len(prompt), for established slots) — or whose row
                # switched to a refill in an earlier chained block
                # (active0: the device's poff then tracks the refill)
                if plen[s] or fl.active0[s]:
                    self.slot_poff[s] = int(poff_f[j])
                self.pos[s] = int(lw[j])
                self._maybe_publish_prompt_pages(s)
            elif int(sw[j]) <= k_exec:
                # the device switched this slot to its staged refill
                req = self.staged_refill[s]
                self.staged_refill[s] = None
                self._staged_order.remove(s)
                self._install_refill(s, req)
                self.stats["inblock_refills"] += 1
                for i in range(int(sw[j]), k_exec):
                    if mask[i, j] and self.occupant[s] is not None:
                        self._emit(s, int(toks[i, j]), out)
                if self.occupant[s] is not None:
                    self.slot_poff[s] = int(poff_f[j])
                    self.pos[s] = int(lw[j])
                    self._maybe_publish_prompt_pages(s)
        if not fl.refs_held:
            self._requeue_unused_refills()
        # any occupancy change (retirement or refill handoff) makes a
        # chained successor's scheduling metadata stale: flag the chain
        # to break after the in-flight block (step())
        for idx, s in enumerate(live):
            if self.occupant[s] is not occ_before[idx]:
                self._break_chain = True
        self.stats["wasted_slot_steps"] += (
            k_exec * w
            - (self.stats["emitted_tokens"] - emitted_before)
            - int(np.sum(pf)))
        parse.stop()
        return out

    def _sync_spec_slot(self, s: int, wr: int) -> None:
        """Mirror a continuing slot's device frontier on the host after a
        speculative block: ``wr`` is the cache frontier, so the last
        written position is wr-1 and prompt progress is min(wr, plen)."""
        occ = self.occupant[s]
        self.slot_poff[s] = min(wr, len(occ.prompt))
        self.pos[s] = wr - 1
        if occ.emitted:
            self.last_tok[s] = occ.emitted[-1]
        self._maybe_publish_prompt_pages(s)

    def _parse_spec_block(self, packed, live, cols, w: int, out):
        """Unpack a speculative block's results and mirror them on the
        host: per-round emission chunks (device-truncated at eos/budget,
        re-checked by ``_emit``), the retire→refill handoff at round
        granularity, frontier sync, and the speculation accounting."""
        r_max, wk = self.steps_per_sync, self.n_spec + 1
        flat = np.asarray(packed)  # ONE device->host transfer per block
        n = r_max * w * wk
        etok = flat[:n].reshape(r_max, w, wk)
        ecnt = flat[n:n + r_max * w].reshape(r_max, w)
        off = n + r_max * w
        sw = flat[off:off + w]
        wrf = flat[off + w:off + 2 * w]
        pf = flat[off + 2 * w:off + 3 * w]
        prop_n, prop_acc = int(flat[-3]), int(flat[-2])
        n_exec = int(flat[-1])
        self.stats["decode_dispatches"] += 1
        self.stats["slot_steps"] += n_exec * w * wk
        self.stats["spec_rounds"] += n_exec
        self.stats["spec_proposed"] += prop_n
        self.stats["spec_accepted"] += prop_acc
        self.stats["inblock_prefill_steps"] += int(np.sum(pf))
        emitted_before = self.stats["emitted_tokens"]
        for s in live:
            j = cols[s]
            cut = min(int(sw[j]), n_exec)
            for r in range(cut):
                for t in range(int(ecnt[r, j])):
                    if self.occupant[s] is None:
                        break
                    self._emit(s, int(etok[r, j, t]), out)
            if self.occupant[s] is not None:
                self._sync_spec_slot(s, int(wrf[j]))
            elif int(sw[j]) <= n_exec:
                # the device switched this slot to its staged refill
                req = self.staged_refill[s]
                self.staged_refill[s] = None
                self._staged_order.remove(s)
                self._install_refill(s, req)
                self.stats["inblock_refills"] += 1
                for r in range(int(sw[j]), n_exec):
                    for t in range(int(ecnt[r, j])):
                        if self.occupant[s] is None:
                            break
                        self._emit(s, int(etok[r, j, t]), out)
                if self.occupant[s] is not None:
                    self._sync_spec_slot(s, int(wrf[j]))
        self._requeue_unused_refills()
        self.stats["wasted_slot_steps"] += (
            n_exec * w * wk
            - (self.stats["emitted_tokens"] - emitted_before)
            - int(np.sum(pf)))
        return out

    def run(self, prompts, max_new: int = 128) -> dict[int, np.ndarray]:
        """Submit every prompt, drive to completion, return rid -> tokens."""
        rids = [self.submit(p, max_new) for p in prompts]
        while self.pending():
            self.step()
        return {rid: self.result(rid) for rid in rids}
