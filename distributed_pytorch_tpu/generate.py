"""Autoregressive decoding with a KV cache for the transformer LM.

Inference counterpart of lm.py: one compiled ``lax.scan`` drives prefill and
sampling (no per-token dispatch), with per-layer K/V caches updated in place
via ``dynamic_update_slice`` — static shapes throughout, so the whole decode
is a single XLA program.

Supports greedy (temperature=0) and temperature/top-k sampling.  MoE layers
decode with a dense-evaluation trick (every expert runs on the B decode
tokens, the router's one-hot selects) — exact w.r.t. training semantics
minus capacity drops, and cheap at decode batch sizes.

Tensor-parallel decode (``generate_tp``): the same program runs inside
``shard_map`` over the Megatron 'model' axis with head/FFN-sharded weights
and a head-sharded KV cache; the two per-layer psums (after the attention
out-projection and the MLP down-projection) are the only communication, so
decode scales to models whose weights or KV cache exceed one chip.
"""

from __future__ import annotations

import warnings
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from .models import transformer as tfm
from .ops.attention import (NEG_INF, attention_reference,
                            decode_attention,
                            decode_attention_paged)

PyTree = Any

# int8 KV quantization floor: a zero row quantizes against this scale
# instead of dividing by zero (dequantized zeros stay exactly zero).
KV_SCALE_EPS = 1e-8


def canon_kv_dtype(kv_dtype):
    """Normalize a ``kv_dtype`` knob: None (store K/V in the compute
    ``dtype``, the historical behavior) or int8 (quantized cache with
    per-row scales — see ``quantize_kv``).  Accepts the string "int8"
    so CLI/bench surfaces need no jnp import."""
    if kv_dtype is None:
        return None
    try:
        ok = jnp.dtype(kv_dtype) == jnp.dtype(jnp.int8)
    except TypeError:
        ok = False
    if ok:
        return jnp.int8
    raise ValueError(f"unsupported kv_dtype {kv_dtype!r}: expected None "
                     f"or int8")


def quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric int8 row quantization of K/V: ``x`` (..., head_dim) ->
    (int8 values, float32 scales (..., 1)) with scale = absmax/127 per
    row.  One scale per (cache position, kv head) — the granularity
    incremental decode writes require: a whole-page scalar would force
    requantizing every already-written row of the page on each new
    token's write, and per-row is strictly more accurate anyway.  The
    scales array keeps a trailing length-1 lane dim so every cache leaf
    is rank-4 and rides the existing page-table/insert/swap machinery
    (and the Pallas (block, 1) scale-tile layout) unchanged."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                    keepdims=True) / 127.0
    scale = jnp.maximum(scale, KV_SCALE_EPS)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale),
                 -127.0, 127.0).astype(jnp.int8)
    return q, scale


def dequantize_kv(q: jax.Array, scale: jax.Array, dtype=None) -> jax.Array:
    """Inverse of ``quantize_kv``: int8 rows x their (..., 1) scales."""
    x = q.astype(jnp.float32) * scale
    return x.astype(dtype) if dtype is not None else x


def kv_bytes_per_token(cfg: tfm.TransformerConfig, dtype=jnp.float32,
                       kv_dtype=None, kv_heads: int | None = None) -> int:
    """HBM bytes one cached token position costs across all layers (K +
    V + scales) — the per-step decode cache-read estimate the bench JSON
    carries and the PagePool byte-budget accounting uses.  int8 halves
    the K/V bytes and adds one f32 scale per row (~2x net at head_dim
    128: 2x(128+4) vs 2x(128x2) bytes per head per layer)."""
    hk = kv_heads or cfg.kv_heads
    if canon_kv_dtype(kv_dtype) is not None:
        per_head = 2 * (cfg.head_dim + 4)  # int8 row + f32 scale, K and V
    else:
        per_head = 2 * cfg.head_dim * jnp.dtype(dtype or jnp.float32).itemsize
    return per_head * hk * cfg.n_layers


def _kv_leaves(shape, dtype, kv_dtype):
    if canon_kv_dtype(kv_dtype) is not None:
        sshape = shape[:-1] + (1,)
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "ks": jnp.zeros(sshape, jnp.float32),
                "vs": jnp.zeros(sshape, jnp.float32)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def init_cache(cfg: tfm.TransformerConfig, batch: int, max_len: int,
               dtype=jnp.float32, kv_heads: int | None = None,
               kv_dtype=None) -> PyTree:
    """Zeroed per-layer K/V buffers, (B, kv_heads, max_len, head_dim) —
    GQA models cache only the kv heads.  ``kv_heads`` overrides the config
    count (tensor-parallel decode caches only this shard's heads).  With
    ``kv_dtype=int8`` each layer stores int8 K/V plus per-row float32
    scales ("ks"/"vs", (..., max_len, 1)): writes quantize, the decode
    kernels dequantize inside their tiles (ops/attention.py)."""
    shape = (batch, kv_heads or cfg.kv_heads, max_len, cfg.head_dim)
    return {f"layer{i}": _kv_leaves(shape, dtype, kv_dtype)
            for i in range(cfg.n_layers)}


def init_paged_cache(cfg: tfm.TransformerConfig, n_pages: int,
                     page: int = 512, dtype=jnp.float32,
                     kv_heads: int | None = None,
                     kv_dtype=None) -> PyTree:
    """Zeroed per-layer PAGED K/V pools, (n_pages, kv_heads, page,
    head_dim): sequences own pages via a block table instead of a
    contiguous per-sequence buffer (serve.py paged mode), so cache memory
    scales with pages actually allocated, not slots x max_len.  With
    ``kv_dtype=int8`` the pools are int8 with per-row scale pools
    ("ks"/"vs", (n_pages, kv_heads, page, 1)) that ride the SAME block
    tables — shared (prefix-cache) pages share their scales by
    construction, and host-swap moves them with the page."""
    shape = (n_pages, kv_heads or cfg.kv_heads, page, cfg.head_dim)
    return {f"layer{i}": _kv_leaves(shape, dtype, kv_dtype)
            for i in range(cfg.n_layers)}


def pad_cache_len(n: int) -> int:
    """Round a cache length up to whole 512-slot blocks (the decode
    kernel's MXU-friendly tile granule; the zero-filled tail is never read
    thanks to the pos bound)."""
    return -(-n // 512) * 512


def force_fetch_last(tokens: jax.Array) -> int:
    """Force completion of a ``generate`` dispatch with a ONE-ELEMENT
    device fetch (row 0's final token) and return it.

    The bench-window convention: a timed window ends on work that waits
    for the device, and ``np.asarray(out)`` over the whole (B, S) buffer
    adds a size-dependent transfer to it.  Slicing one element still
    forces the whole dependency chain while making the transfer payload
    constant.  (On the v5e machine ``block_until_ready`` waits for the
    device too — it read 46.6 ms where the value fetch read 47.0 ms over
    the same 8.8 TFLOP of chained matmuls — and a one-element fetch of a
    ready value costs ~1.6 ms: chip_smoke.py's clock phase, PR 21.)"""
    return int(jax.device_get(tokens[0, -1]))


def default_decode_kernel(flag: bool | None) -> bool:
    """Resolve a decode_kernel tri-state: None = kernel on TPU, XLA path
    elsewhere (the kernel runs in interpret mode off-TPU but is slower
    than XLA there)."""
    return jax.default_backend() == "tpu" if flag is None else flag


def _warn_if_expert_choice(cfg: tfm.TransformerConfig) -> None:
    """Expert-choice routing has no autoregressive decode equivalent.

    EC selection ranks tokens per expert over the whole (B*S) batch, so it
    cannot be evaluated one token at a time; decode falls back to
    capacity-free token-choice top-k mixing, whose mixtures differ from the
    training-time routing (see ops/moe.py moe_apply acausality caveat).
    Warn rather than raise — the approximation is usable, but the loss is
    not comparable to training."""
    if cfg.n_experts and cfg.moe_router == "experts":
        warnings.warn(
            "decoding a model trained with expert-choice routing "
            "(moe_router='experts'): decode uses capacity-free token-choice "
            "top-k mixing, which differs from the training-time routing — "
            "decode losses are not comparable to train/eval losses",
            stacklevel=3)


def _moe_dense(lp: PyTree, h: jax.Array, cfg: tfm.TransformerConfig,
               tp_axis: str | None = None):
    """Capacity-free MoE for decode: run all experts, top-k one-hot combine
    (matches token-choice training routing — Switch gates for top_k=1,
    pair-normalized gates for top_k=2; for expert-choice-trained models
    this is an approximation and generate/generate_tp warn).  Under
    ``tp_axis`` the weights hold this shard's E/n experts; each shard
    evaluates its local experts' gate-weighted contributions and the
    caller's psum sums them across shards."""
    b, s, d = h.shape
    hf = h.reshape(b * s, d)
    probs = jax.nn.softmax(
        hf.astype(jnp.float32) @ lp["moe"]["router"].astype(jnp.float32), -1)
    k = cfg.moe_top_k
    top_probs, top_idx = jax.lax.top_k(probs, k)
    if k > 1:
        top_probs = top_probs / jnp.sum(top_probs, -1, keepdims=True)
    weights = jnp.einsum(
        "tk,tke->te", top_probs,
        jax.nn.one_hot(top_idx, cfg.n_experts, dtype=jnp.float32))
    if tp_axis is not None:
        e_local = lp["moe"]["w_gate"].shape[0]
        start = lax.axis_index(tp_axis) * e_local
        weights = lax.dynamic_slice_in_dim(weights, start, e_local, axis=1)
    g = jax.nn.silu(jnp.einsum("td,edf->tef", hf,
                               lp["moe"]["w_gate"].astype(hf.dtype)))
    u = jnp.einsum("td,edf->tef", hf, lp["moe"]["w_up"].astype(hf.dtype))
    y = jnp.einsum("tef,efd->ted", g * u,
                   lp["moe"]["w_down"].astype(hf.dtype))
    out = jnp.einsum("te,ted->td", weights.astype(hf.dtype), y)
    return out.reshape(b, s, d)


def _paged_put(leaf: jax.Array, pids: jax.Array, offs: jax.Array,
               u: jax.Array) -> jax.Array:
    """``leaf.at[pids, :, offs].set(u)`` for a (P, hkv, page, W) pool
    leaf, page ids and row offsets of one shape (...) and rows ``u`` of
    shape (..., hkv, W): row (pids[i], h, offs[i]) gets u[i, h].

    Written as a scatter along the two LEADING dimensions of the
    (P * hkv, page, W) view: the TPU compiler then keeps the pool
    row-major, the layout the paged decode kernel reads, and updates it in
    place.  A scatter over dimensions 0 and 2 of the leaf itself makes it
    keep the pool pages, rows, heads, and copy every leaf whole into the
    kernel's layout on every decode step (tests/test_chip_compile.py).
    A page id outside the pool is dropped all the same (pid * hkv + h is
    then outside the view), and duplicates (idle slots all write scratch
    page 0) land in any order."""
    n_pages, hkv, page, w = leaf.shape
    rows = pids[..., None] * hkv + jnp.arange(hkv)
    return (leaf.reshape(n_pages * hkv, page, w)
            .at[rows, offs[..., None]].set(u).reshape(leaf.shape))


def _forward_cached(params: PyTree, cache: PyTree, tokens: jax.Array,
                    pos: jax.Array, write_at, *,
                    cfg: tfm.TransformerConfig, dtype=None,
                    tp_axis: str | None = None,
                    unembed_last_only: bool = False,
                    unembed_at=None,
                    k_len: int | None = None,
                    use_decode_kernel: bool = False,
                    page_table: jax.Array | None = None):
    """Cache-backed forward over a (B, S) token block at positions ``pos``
    (S,), writing each layer's K/V into cache slots [write_at, write_at+S).
    Returns ((B, S, vocab) logits, cache).  The one implementation behind
    both prefill (S = prompt length, write_at = 0) and per-token decode
    (S = 1, write_at = pos).

    RAGGED batches (continuous batching): ``pos`` may be (B, S) — each
    sequence at its own depth — with ``write_at`` a (B,) vector of
    per-sequence cache offsets; S = 1 for plain lockstep decode.
    Attention bounds, rotary phases, and cache writes are then all
    per-sequence.  With S > 1 ragged (in-batcher speculative
    VERIFICATION, serve.py), ``write_at`` is instead a (B, S) matrix of
    per-TOKEN write positions (the caller clamps them at each
    sequence's allocated frontier — a clamped token overwrites the
    frontier row, which only happens for retired slots whose cache is
    dead), written as one scatter; the attention read is the bias path
    (per-row ``slot <= pos[b, j]`` bounds), with a paged pool first
    gathered into its per-sequence contiguous view.

    Causality comes from the cache-validity bias: query row j attends cache
    slots <= pos[j] (earlier positions plus itself), never the zero-filled
    future slots.  With ``tp_axis`` (inside shard_map) the params are
    Megatron head/FFN shards and the cache holds this shard's kv heads; one
    psum after the attention out-projection and one after the MLP
    reassemble the residual stream, exactly as in training
    (models/transformer.py block).  MoE layers use the capacity-free dense
    evaluation (_moe_dense) — exact mixture semantics, no drops.
    """
    x = params["embed"][tokens]  # (B, S, D)
    if dtype is not None:
        x = x.astype(dtype)
    # ``k_len`` (static) restricts attention to the first cache slots:
    # prefill passes the prompt length, segmented decode its segment's
    # bound, and the paged verify window the batcher's live-depth hint,
    # so none reads the not-yet-written (masked anyway) tail.
    k_len_hint = k_len
    k_len = k_len or next(iter(cache.values()))["k"].shape[2]
    s = tokens.shape[1]
    ragged = pos.ndim == 2  # (B, S) per-sequence positions
    multi_ragged = ragged and s > 1  # speculative verify window
    kernel_path = use_decode_kernel and s == 1
    if page_table is not None:
        # PAGED KV pool (serve.py paged mode): cache leaves are shared
        # (P, hkv, page, D) pools; ``page_table`` (B, n_pages) maps each
        # sequence's logical cache blocks to pool pages.  Single-token
        # decode rides the kernel (the page indirection lives in its
        # Pallas index maps — measured free on TPU); the multi-token
        # ragged verify window scatters writes through the table and
        # gathers the pool into a contiguous per-sequence view for the
        # bias-path attention read.
        if not ((kernel_path or multi_ragged) and ragged):
            raise ValueError("page_table requires ragged per-sequence "
                             "positions, and single-token decode must use "
                             "the kernel path (use_decode_kernel=True)")
        if multi_ragged and write_at.ndim != 2:
            raise ValueError("a paged multi-token ragged forward needs "
                             "(B, S) per-token write positions (the "
                             "scatter rides the page table)")
    # multi-token ragged writes: (B, S) write_at scatters each token at
    # its own (caller-clamped) position — the serve.py verify window;
    # (B,) write_at keeps the contiguous vmapped-DUS path the static
    # speculative decoders use (their windows always start at the
    # per-sequence frontier).
    scatter_writes = multi_ragged and write_at.ndim == 2
    gather_cols = page_table.shape[1] if page_table is not None else 0
    if page_table is not None and multi_ragged:
        # the gathered contiguous view spans the table's logical range,
        # BOUNDED by the caller's ``k_len`` hint when given: only the
        # first ceil(k_len / page) table columns are gathered — O(live
        # depth) HBM traffic instead of O(pages_per_slot * page) per
        # layer per speculation round (the serve batcher passes the
        # pool's deepest allocated frontier).  The per-row pos bias
        # masks everything beyond each sequence's own depth either way;
        # writes ride the FULL table, so the bound never clamps them.
        page = next(iter(cache.values()))["k"].shape[2]
        if k_len_hint:
            gather_cols = min(-(-k_len_hint // page), gather_cols)
        k_len = gather_cols * page
    if not kernel_path:
        # bias[j, slot]: query at global position pos[j] sees slots <= pos[j]
        slot = jax.lax.broadcasted_iota(jnp.int32, (s, k_len), 1)
        if ragged:  # (B, 1, S, k_len)
            bias = jnp.where(slot[None] <= pos[:, :, None], 0.0,
                             NEG_INF)[:, None]
        else:
            bias = jnp.where(slot <= pos[:, None], 0.0, NEG_INF)[None, None]

    # int8 KV cache: inferred from the cache pytree (scale leaves), so
    # every caller — prefill, lockstep decode, the spec verify window,
    # suffix prefill — quantizes/dequantizes without API changes.
    quant = "ks" in next(iter(cache.values()))
    for i in range(cfg.n_layers):
        lp = params[f"layer{i}"]
        c = cache[f"layer{i}"]
        h = tfm.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q = jnp.einsum("bsd,dhk->bhsk", h, lp["wq"].astype(h.dtype))
        k = jnp.einsum("bsd,dhk->bhsk", h, lp["wk"].astype(h.dtype))
        v = jnp.einsum("bsd,dhk->bhsk", h, lp["wv"].astype(h.dtype))
        q = tfm.rotary(q, pos, cfg.rope_theta)
        k = tfm.rotary(k, pos, cfg.rope_theta)
        # each branch below writes the same (leaf name, update) pairs
        # through one ``put``: K/V (quantized at WRITE time under int8,
        # their per-row scales riding the identical scatter/slice) in
        # the cache's (B|P, hkv, S|page, D[|1]) layout.
        if quant:
            kq, ksc = quantize_kv(k)
            vq, vsc = quantize_kv(v)
            pairs = (("k", kq), ("v", vq), ("ks", ksc), ("vs", vsc))
        else:
            pairs = (("k", k.astype(c["k"].dtype)),
                     ("v", v.astype(c["v"].dtype)))
        if scatter_writes:
            # speculative verify window: one scatter writes each token
            # at its own (caller-clamped) position — through the page
            # table under paging, straight into the (B, hkv, L, D)
            # buffers otherwise.  Colliding clamped rows (retired
            # slots) resolve arbitrarily; those rows are never read.
            if page_table is not None:
                page = c["k"].shape[2]
                pids = jnp.take_along_axis(page_table, write_at // page, 1)
                offs = write_at % page

                def put(leaf, u):
                    return _paged_put(leaf, pids, offs,
                                      u.transpose(0, 2, 1, 3))
            else:
                bidx = jnp.arange(tokens.shape[0])[:, None]

                def put(leaf, u):
                    return leaf.at[bidx, :, write_at].set(
                        u.transpose(0, 2, 1, 3))
        elif page_table is not None:
            # paged write: token at position p lands in pool page
            # table[b, p // page] at row p % page
            page = c["k"].shape[2]
            p_now = pos[:, 0]
            pids = jnp.take_along_axis(page_table,
                                       (p_now // page)[:, None], 1)[:, 0]
            offs = p_now % page

            def put(leaf, u):
                return _paged_put(leaf, pids, offs, u[:, :, 0])
        elif ragged:
            # per-sequence write offsets (vmapped update -> scatter)
            def put(leaf, u):
                return jax.vmap(
                    lambda c_, u_, w_: lax.dynamic_update_slice(
                        c_, u_, (0, w_, 0)))(leaf, u, write_at)
        else:
            def put(leaf, u):
                return lax.dynamic_update_slice(
                    leaf, u, (0, 0, write_at, 0))
        new_c = dict(c)
        for name, u in pairs:
            new_c[name] = put(c[name], u)
        cache[f"layer{i}"] = new_c
        ck, cv = new_c["k"], new_c["v"]
        if multi_ragged and page_table is not None:
            # contiguous per-sequence view of the owned pages (reads the
            # pool once; the verify is a fallback XLA path, not the hot
            # single-token kernel)
            bsz, hkv_l, page, hd = (tokens.shape[0], ck.shape[1],
                                    ck.shape[2], ck.shape[3])
            tbl = page_table[:, :gather_cols]  # live-depth-bounded gather

            def gat(leaf):
                w_ = leaf.shape[3]
                return (leaf[tbl].transpose(0, 2, 1, 3, 4)
                        .reshape(bsz, hkv_l, k_len, w_))

            ka, va = gat(ck), gat(cv)
            if quant:  # dequantize the gathered rows with their scales
                ka = dequantize_kv(ka, gat(new_c["ks"]))
                va = dequantize_kv(va, gat(new_c["vs"]))
            ka, va = ka.astype(q.dtype), va.astype(q.dtype)
            if q.shape[1] != hkv_l:
                rep = q.shape[1] // hkv_l
                ka = jnp.repeat(ka, rep, axis=1)
                va = jnp.repeat(va, rep, axis=1)
            o = attention_reference(q, ka, va, bias=bias)
        elif page_table is not None:
            o = decode_attention_paged(
                q, ck, cv, page_table, pos[:, 0],
                k_scale=new_c.get("ks"), v_scale=new_c.get("vs"))
        elif kernel_path:
            # Pallas decode kernel: exact pos+1 cache-read bound (dead
            # blocks neither fetched nor computed), GQA head groups folded
            # into MXU rows — no repeated cache reads, no k_len segmenting.
            # Ragged: pos[:, 0] gives each sequence its own bound.
            o = decode_attention(q, ck, cv,
                                 pos[:, 0] if ragged else pos[0],
                                 k_scale=new_c.get("ks"),
                                 v_scale=new_c.get("vs"))
        else:
            ka = ck[:, :, :k_len]
            va = cv[:, :, :k_len]
            if quant:
                ka = dequantize_kv(ka, new_c["ks"][:, :, :k_len])
                va = dequantize_kv(va, new_c["vs"][:, :, :k_len])
            ka, va = ka.astype(q.dtype), va.astype(q.dtype)
            if cfg.kv_heads != cfg.n_heads:
                # local head counts (identical ratio under TP sharding)
                rep = q.shape[1] // ka.shape[1]
                ka = jnp.repeat(ka, rep, axis=1)
                va = jnp.repeat(va, rep, axis=1)
            o = attention_reference(q, ka, va, bias=bias)
        o = jnp.einsum("bhsk,hkd->bsd", o, lp["wo"].astype(o.dtype))
        if tp_axis is not None:
            o = lax.psum(o, tp_axis)
        x = x + o
        h = tfm.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        if cfg.is_moe_layer(i):
            down = _moe_dense(lp, h, cfg, tp_axis=tp_axis)
        else:
            gate = jax.nn.silu(h @ lp["w_gate"].astype(h.dtype))
            up = h @ lp["w_up"].astype(h.dtype)
            down = (gate * up) @ lp["w_down"].astype(h.dtype)
        if tp_axis is not None:
            down = lax.psum(down, tp_axis)
        x = x + down

    x = tfm.rms_norm(x, params["final_norm"], cfg.norm_eps)
    if unembed_last_only:
        x = x[:, -1:]  # prefill needs one row, not (B, S, vocab) logits
    elif unembed_at is not None:
        # dynamic single-row unembed (bucketed prefill: the last VALID row
        # of a padded prompt) — slice before the d_model x vocab matmul
        x = lax.dynamic_slice_in_dim(x, unembed_at, 1, axis=1)
    logits = x.astype(jnp.float32) @ params["embed"].T.astype(jnp.float32)
    return logits, cache


def decode_step(params: PyTree, cache: PyTree, token: jax.Array,
                pos: jax.Array, *, cfg: tfm.TransformerConfig,
                dtype=None, tp_axis: str | None = None,
                k_len: int | None = None,
                use_decode_kernel: bool = False):
    """Process one token per sequence: (B,) ids at position ``pos`` ->
    ((B, vocab) logits, updated cache).  ``k_len`` (static) restricts the
    attend to the first cache slots — segmented decode passes its
    segment's bound so early tokens do not read the whole buffer.  With
    ``use_decode_kernel`` the Pallas decode kernel replaces both tricks:
    the read bound is the exact, dynamic ``pos+1`` — a caller-supplied
    ``k_len`` would be silently ignored on that path, so combining the
    two is rejected."""
    if use_decode_kernel and k_len is not None:
        raise ValueError(
            "k_len is ignored when use_decode_kernel=True (the kernel's "
            "read bound is the exact dynamic pos+1); pass one or the other")
    logits, cache = _forward_cached(
        params, cache, token[:, None], jnp.atleast_1d(pos), pos,
        cfg=cfg, dtype=dtype, tp_axis=tp_axis, k_len=k_len,
        use_decode_kernel=use_decode_kernel)
    return logits[:, 0], cache


def decode_step_ragged(params: PyTree, cache: PyTree, token: jax.Array,
                       pos: jax.Array, *, cfg: tfm.TransformerConfig,
                       dtype=None, tp_axis: str | None = None,
                       use_decode_kernel: bool = False,
                       page_table: jax.Array | None = None):
    """One token per sequence at PER-SEQUENCE positions: (B,) ids at (B,)
    positions -> ((B, vocab) logits, cache).  Every sequence reads exactly
    its own ``pos+1`` cache prefix and writes its K/V at its own offset —
    the step primitive of continuous batching (serve.py).  With ``tp_axis``
    (inside shard_map) the params are Megatron shards and the cache holds
    this shard's kv heads, exactly as in ``generate_tp``."""
    logits, cache = _forward_cached(
        params, cache, token[:, None], pos[:, None], pos,
        cfg=cfg, dtype=dtype, tp_axis=tp_axis,
        use_decode_kernel=use_decode_kernel, page_table=page_table)
    return logits[:, 0], cache


def verify_step_ragged(params: PyTree, cache: PyTree, tokens: jax.Array,
                       pos: jax.Array, write_pos: jax.Array, *,
                       cfg: tfm.TransformerConfig, dtype=None,
                       tp_axis: str | None = None,
                       page_table: jax.Array | None = None,
                       k_len: int | None = None):
    """MULTI-token ragged forward: (B, W) tokens at per-sequence
    positions ``pos`` (B, W) -> ((B, W, vocab) logits, cache) — the
    verification primitive of in-batcher speculative decoding
    (serve.py): each slot's whole proposal window streams through one
    weight read (the speculation win: W tokens of MXU work per HBM
    weight pass instead of W bandwidth-bound single-token steps).

    ``write_pos`` (B, W) gives each token's cache write position,
    already clamped at the sequence's allocated frontier by the caller
    (rejected tokens' K/V rows are garbage beyond the accepted prefix —
    never read, since reads are pos-bounded and later rounds overwrite
    them: the same free-rewind property ``generate_speculative``
    documents).  Attention runs the bias path with exact per-row
    ``slot <= pos`` bounds; a paged pool is gathered into its
    contiguous per-sequence view for the read, bounded to the first
    ``ceil(k_len / page)`` table columns when the caller passes a
    (static) ``k_len`` live-depth hint — every live row's positions must
    stay below it (the serve batcher derives it from the deepest
    allocated frontier, so this holds by construction)."""
    return _forward_cached(
        params, cache, tokens, pos, write_pos, cfg=cfg, dtype=dtype,
        tp_axis=tp_axis, page_table=page_table, k_len=k_len)


def lookup_proposals(stream: jax.Array, last_i: jax.Array, n_spec: int,
                     ngram: int) -> jax.Array:
    """PROMPT-LOOKUP proposals, shared by ``generate_lookup`` and the
    in-batcher speculative block (serve.py): for each row of ``stream``
    (B, T), find the most recent earlier occurrence of the trailing
    ``ngram`` ending at index ``last_i`` (B,) and copy the ``n_spec``
    tokens that followed it; rows with no match (or a prefix shorter
    than the ngram — the reads above index 0 would otherwise silently
    compare a clipped wrong window) fall back to repeating the last
    token.  Proposals are free to be wrong: verification rejects them
    at the cost of a round's speculation, never correctness."""
    b, total = stream.shape
    nwin = total - ngram + 1
    jgrid = jnp.arange(nwin)[None]
    win_ok = jnp.ones((b, nwin), bool)
    for o in range(ngram):
        tail = jnp.take_along_axis(
            stream, jnp.clip(last_i - (ngram - 1) + o,
                             0, total - 1)[:, None], axis=1)
        win_ok &= stream[:, o:nwin + o] == tail
    # exclude the trailing ngram matching itself; window tokens and at
    # least the first continuation token must be already written
    win_ok &= jgrid <= (last_i - ngram)[:, None]
    win_ok &= (ngram <= last_i)[:, None]
    jbest = jnp.max(jnp.where(win_ok, jgrid, -1), axis=1)
    base = jnp.where(jbest >= 0, jbest + ngram, 0)
    idx = jnp.clip(base[:, None] + jnp.arange(n_spec)[None], 0, total - 1)
    props = jnp.take_along_axis(stream, idx, axis=1)
    lastv = jnp.take_along_axis(
        stream, jnp.clip(last_i, 0, total - 1)[:, None], axis=1)
    return jnp.where((jbest >= 0)[:, None], props,
                     jnp.broadcast_to(lastv, (b, n_spec)))


def _filter_logits(logits, temperature: float, top_k: int | None,
                   top_p: float | None):
    """Temperature-scale + top-k/top-p mask (NEG_INF outside the keep
    set) over the last axis; requires ``temperature > 0``.  Filter
    semantics IDENTICAL to ``sample_per_seq`` (the serving path): both
    thresholds come from ONE descending sort of the temperature-scaled
    distribution — top-p is the smallest prefix with mass >= p computed
    on the FULL distribution (not the top-k-renormalized one), and the
    masks intersect.  ``softmax`` of the result is the WARPED target/
    draft distribution that sampled speculative decoding must preserve
    exactly (the rejection-sampling identity applies to whatever
    distribution both sides agree on — here the warped one)."""
    scaled = logits / temperature
    v = logits.shape[-1]
    # top_k outside (0, v) keeps all tokens (a 50-of-32 filter is a
    # no-op, and 0/None disable), matching sample_per_seq's clamping
    want_k = top_k is not None and 0 < top_k < v
    want_p = top_p is not None and top_p < 1.0
    if not want_k and not want_p:
        return scaled
    sorted_desc = jnp.sort(scaled, -1)[..., ::-1]
    masked = scaled
    if want_k:
        kth = sorted_desc[..., top_k - 1:top_k]
        masked = jnp.where(scaled < kth, NEG_INF, masked)
    if want_p:
        probs = jax.nn.softmax(sorted_desc, -1)
        exclusive_cum = jnp.cumsum(probs, -1) - probs
        nkeep = jnp.sum(exclusive_cum < top_p, -1)
        pidx = jnp.clip(nkeep - 1, 0, scaled.shape[-1] - 1)
        pth = jnp.take_along_axis(sorted_desc, pidx[..., None], axis=-1)
        masked = jnp.where(scaled < pth, NEG_INF, masked)
    return masked


def _sample(key, logits, temperature: float, top_k: int | None,
            top_p: float | None = None):
    """Static-parameter sampling: greedy at temperature 0, else a
    categorical draw from the ``_filter_logits``-warped distribution."""
    if temperature == 0.0:
        return jnp.argmax(logits, -1).astype(jnp.int32)
    return jax.random.categorical(
        key, _filter_logits(logits, temperature, top_k, top_p)
    ).astype(jnp.int32)


def filter_per_seq(logits, temperature, top_k, top_p):
    """PER-ROW ``_filter_logits``: temperature-scale + top-k/top-p mask
    with (B,)-vector parameters — the warp behind ``sample_per_seq``,
    exposed for callers that need each row's exact warped distribution
    (not just a draw from it).  ``temperature`` <= 0 rows are divided
    by 1e-6, i.e. sharpened toward argmax (the caller overrides them
    with an exact argmax anyway); ``top_k`` 0 and ``top_p`` >= 1
    disable their filters.  Threshold ties keep all tied tokens,
    matching ``_filter_logits``."""
    v = logits.shape[-1]
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    sorted_desc = jnp.sort(scaled, -1)[:, ::-1]
    # top-k: mask strictly below the k-th largest value (k=0: keep all)
    k = jnp.clip(top_k, 0, v)
    kidx = jnp.where(k > 0, k - 1, v - 1)
    kth = jnp.take_along_axis(sorted_desc, kidx[:, None], axis=1)
    masked = jnp.where((k[:, None] > 0) & (scaled < kth), NEG_INF, scaled)
    # top-p: smallest prefix of the sorted distribution with mass >= p
    probs = jax.nn.softmax(sorted_desc, -1)
    exclusive_cum = jnp.cumsum(probs, -1) - probs
    nkeep = jnp.sum(exclusive_cum < top_p[:, None], -1)  # >= 1 always
    pidx = jnp.clip(nkeep - 1, 0, v - 1)
    pth = jnp.take_along_axis(sorted_desc, pidx[:, None], axis=1)
    return jnp.where((top_p[:, None] < 1.0) & (scaled < pth),
                     NEG_INF, masked)


def sample_per_seq(key, logits, temperature, top_k, top_p):
    """Sampling with PER-ROW parameters (continuous batching: every slot
    serves a different request with its own settings, in one compiled
    step).  ``logits`` (B, V); ``temperature`` (B,) f32 — <= 0 means
    greedy; ``top_k`` (B,) int32 — 0 disables; ``top_p`` (B,) f32 — >= 1
    disables (nucleus sampling, computed on the temperature-scaled
    distribution).  One (B, V) sort serves both filters
    (``filter_per_seq``); V is the LM head width, so this is noise next
    to the decode matmuls."""
    greedy = jnp.argmax(logits, -1).astype(jnp.int32)
    masked = filter_per_seq(logits, temperature, top_k, top_p)
    sampled = jax.random.categorical(key, masked).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy, sampled)


def _generate_impl(
    params: PyTree,
    prompt: jax.Array,       # (B, S0) int32
    key: jax.Array,
    *,
    cfg: tfm.TransformerConfig,
    max_new: int,
    temperature: float = 1.0,
    top_k: int | None = None,
    top_p: float | None = None,
    dtype=None,
    eos_id: int | None = None,
    decode_segments: int = 8,
    tp_axis: str | None = None,
    decode_kernel: bool | None = None,
    kv_dtype=None,
) -> jax.Array:
    # the cached forward knows one kind of cache row (K and V per kv head),
    # a tied head and the capacity-routed block
    tfm.require_servable(cfg, "generate")
    b, s0 = prompt.shape
    # Pallas decode kernel by default on TPU: exact dynamic pos+1 cache-read
    # bounds make the static segment bounds below redundant (one compiled
    # scan body instead of decode_segments of them).
    use_kernel = default_decode_kernel(decode_kernel)
    # Under TP the params are head shards — cache this shard's kv heads
    # only.  The cache lives in the compute dtype: decode at long cache is
    # HBM-bandwidth-bound on cache reads, so a bf16 cache is ~2x faster
    # than f32 (measured; final logits stay f32 for sampling).
    max_len = s0 + max_new
    if use_kernel:
        max_len = pad_cache_len(max_len)
    cache = init_cache(cfg, b, max_len,
                       dtype=dtype or jnp.float32,
                       kv_heads=params["layer0"]["wk"].shape[1],
                       kv_dtype=kv_dtype)

    # Prefill: ONE batched causal forward over the whole prompt (matmul-bound
    # MXU work) through the cache-backed path — not a per-token scan of tiny
    # (B, 1, D) ops.
    logits, cache = _forward_cached(
        params, cache, prompt, jnp.arange(s0), 0, cfg=cfg, dtype=dtype,
        tp_axis=tp_axis, unembed_last_only=True, k_len=s0)
    last_logits = logits[:, 0]

    # Segmented sampling: decode cost is dominated by reading the KV cache
    # (measured: per-token time is linear in the attended length, and a
    # static k_len slice removes the cost).  Tokens in segment i attend
    # only the first s0 + (i+1)*max_new//n_seg slots — a static bound per
    # segment — so early tokens skip the not-yet-written tail.  Measured
    # ~1.7x at 8 segments for long generations (one compiled scan body per
    # segment is the price; diminishing returns beyond 8).
    n_seg = 1 if use_kernel else max(min(decode_segments, max_new), 1)
    done0 = jnp.zeros((b,), bool)
    carry = (cache, last_logits, key, done0)
    pieces, start = [], 0
    for i in range(n_seg):
        end = (max_new * (i + 1)) // n_seg
        step = partial(decode_step, cfg=cfg, dtype=dtype, tp_axis=tp_axis,
                       k_len=None if use_kernel else s0 + end,
                       use_decode_kernel=use_kernel)

        def sample_step(carry, t, step=step):
            cache, logits, key, done = carry
            key, sub = jax.random.split(key)
            tok = _sample(sub, logits, temperature, top_k,
                          top_p)
            if eos_id is not None:
                # Sequences past their EOS emit eos_id forever (SPMD
                # lockstep: compute still runs, the token is overridden).
                tok = jnp.where(done, eos_id, tok)
                done = done | (tok == eos_id)
            logits, cache = step(params, cache, tok, s0 + t)
            return (cache, logits, key, done), tok

        carry, toks = lax.scan(sample_step, carry, jnp.arange(start, end))
        pieces.append(toks)
        start = end
    tokens = jnp.concatenate(pieces, axis=0)
    return jnp.concatenate([prompt, tokens.T], axis=1)


@partial(jax.jit, static_argnames=("cfg", "max_new", "temperature", "top_k",
                                   "top_p", "dtype", "eos_id",
                                   "decode_segments", "decode_kernel",
                                   "kv_dtype"))
def generate(
    params: PyTree,
    prompt: jax.Array,       # (B, S0) int32
    key: jax.Array,
    *,
    cfg: tfm.TransformerConfig,
    max_new: int,
    temperature: float = 1.0,
    top_k: int | None = None,
    top_p: float | None = None,
    dtype=None,
    eos_id: int | None = None,
    decode_segments: int = 8,
    decode_kernel: bool | None = None,
    kv_dtype=None,
) -> jax.Array:
    """Sample ``max_new`` tokens after ``prompt``; returns (B, S0+max_new).

    One jitted program: a prefill scan feeds the prompt through the cache,
    then a sampling scan emits tokens (each step's sample feeds the next).
    ``dtype`` selects the compute AND KV-cache dtype (bf16 decode is ~2x
    faster — cache reads are the bandwidth bottleneck); sampling logits
    stay float32.  ``kv_dtype="int8"`` stores the cache quantized with
    per-row scales instead — HALF the cache-read bytes of bf16 again
    (decode at long cache is HBM-bound on exactly those reads), with
    writes quantizing and the decode kernel dequantizing in its tiles.
    With ``eos_id``, a sequence that samples it keeps emitting it
    (per-sequence stop with static shapes).
    """
    # generate is jitted, so this runs at trace time: once per compiled
    # config, not per call.
    _warn_if_expert_choice(cfg)
    return _generate_impl(params, prompt, key, cfg=cfg, max_new=max_new,
                          temperature=temperature, top_k=top_k, top_p=top_p,
                          dtype=dtype, eos_id=eos_id,
                          decode_segments=decode_segments,
                          decode_kernel=decode_kernel, kv_dtype=kv_dtype)


def _spec_prefill(params, prompt, cfg, dtype, max_len_pad):
    """Shared speculative prologue: prefill the model over the prompt,
    return ``(cache, (B, vocab) last-position logits)`` (each caller
    derives its own first token — argmax or a warped sample — and done
    mask from the logits)."""
    tfm.require_servable(cfg, "speculative decoding")
    b, s0 = prompt.shape
    cache = init_cache(cfg, b, max_len_pad, dtype=dtype or jnp.float32,
                       kv_heads=params["layer0"]["wk"].shape[1])
    logits, cache = _forward_cached(
        params, cache, prompt, jnp.arange(s0), 0, cfg=cfg, dtype=dtype,
        unembed_last_only=True, k_len=s0)
    return cache, logits[:, 0]


def _spec_epilogue(prompt, out, state, eos_id):
    """Shared speculative epilogue: eos-repeat padding (generate()'s
    fixed-shape convention), prompt concat, and the stats dict."""
    if eos_id is not None:
        seen = jnp.cumsum((out == eos_id).astype(jnp.int32), axis=1) > 0
        out = jnp.where(seen, eos_id, out)
    tokens = jnp.concatenate([prompt, out], axis=1)
    stats = {"rounds": state["rounds"], "drafted": state["drafted"],
             "accepted": state["accepted"]}
    return tokens, stats


def _spec_reject_tokens(key, drafts, q, p):
    """Draft-distribution REJECTION SAMPLING (Leviathan/Chen et al.),
    vectorized over every speculated position at once: ``drafts``
    (B, k) tokens drawn from the draft distributions ``q`` (B, k, V);
    ``p`` (B, k+1, V) the target's (warped) distributions at the same
    positions plus the one after.  Returns ``(match, g)`` in the shape
    ``_spec_accept_emit`` consumes:

    - ``match[b, j]`` — position j's draft is accepted, with probability
      ``min(1, p_j(x_j) / q_j(x_j))`` (x_j was drawn from q_j, so
      q_j(x_j) > 0);
    - ``g[b, j]`` — the token emitted after accepting a length-j prefix:
      for j < k a sample from the RESIDUAL ``norm(max(p_j - q_j, 0))``
      (the distribution that makes accept-or-resample marginally EXACTLY
      p_j — the standard guarantee), for j = k a plain sample from
      ``p_k`` (every draft accepted: the bonus token).

    All k residual draws happen up front (cheap next to the verify
    forward); only the one at the actual rejection point is emitted.  A
    pointwise-zero residual (p_j <= q_j everywhere except x_j) can only
    arise where acceptance is certain, so its replacement is never
    emitted — it falls back to p_j to stay NaN-free."""
    b, k, v = q.shape
    ku, kr, kb = jax.random.split(key, 3)
    px = jnp.take_along_axis(p[:, :k], drafts[..., None], 2)[..., 0]
    qx = jnp.take_along_axis(q, drafts[..., None], 2)[..., 0]
    u = jax.random.uniform(ku, (b, k))
    match = u * qx < px                             # u < p(x)/q(x)
    resid = jnp.maximum(p[:, :k] - q, 0.0)
    rs = jnp.sum(resid, -1, keepdims=True)
    resid = jnp.where(rs > 0, resid / rs, p[:, :k])
    repl = jax.random.categorical(kr, jnp.log(resid + 1e-38), axis=-1)
    bonus = jax.random.categorical(kb, jnp.log(p[:, -1] + 1e-38), axis=-1)
    return match, jnp.concatenate(
        [repl, bonus[:, None]], axis=1).astype(jnp.int32)


def _spec_accept_emit(drafts, g, done, n, buf, buf_off, n_spec, max_new,
                      eos_id, match=None):
    """One speculative round's accept + emit + scatter, shared by the
    draft-model and prompt-lookup paths.  ``drafts`` (B, n_spec)
    proposals, ``g`` (B, n_spec+1) the per-prefix-length continuation
    tokens (greedy: the target argmaxes; sampled: rejection-sampling
    replacements); returns (updated ``buf`` — emissions scattered at row
    offsets ``buf_off + n``, n_emit, accepted count m, last emitted
    token, new done mask).

    GREEDY default (``match=None``): draft j is accepted iff it equals
    the target's argmax after the previous accepted prefix.  A sampled
    path passes its own accept mask (``_spec_reject_tokens``).  Either
    way the emitted round is drafts[:m] plus g[m] — m+1 tokens, capped
    by eos and max_new."""
    b = drafts.shape[0]
    k_tok = n_spec + 1
    if match is None:
        match = drafts == g[:, :n_spec]             # (B, n_spec)
    m = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
    j = jnp.arange(k_tok)[None]                     # (B, k_tok) grid
    gm = jnp.take_along_axis(g, m[:, None], axis=1)
    emit = jnp.where(j < m[:, None],
                     jnp.concatenate([drafts, drafts[:, -1:]], 1),
                     jnp.broadcast_to(gm, (b, k_tok)))
    n_emit = jnp.where(done, 0, m + 1)
    if eos_id is not None:
        # stop at the first emitted eos (inclusive)
        is_eos = emit == eos_id
        first_eos = jnp.argmax(is_eos, axis=1)
        has_eos = jnp.any(is_eos & (j < n_emit[:, None]), axis=1)
        n_emit = jnp.where(has_eos,
                           jnp.minimum(n_emit, first_eos + 1), n_emit)
    n_emit = jnp.minimum(n_emit, max_new - n)

    cols = buf_off + n[:, None] + j                 # (B, k_tok)
    valid = j < n_emit[:, None]
    rows = jnp.broadcast_to(jnp.arange(b)[:, None], (b, k_tok))
    buf = buf.at[rows, jnp.where(valid, cols, buf.shape[1])].set(
        jnp.where(valid, emit, 0), mode="drop")

    new_done = done | (n + n_emit >= max_new)
    if eos_id is not None:
        new_done = new_done | jnp.any((emit == eos_id) & valid, axis=1)
    last_new = jnp.take_along_axis(
        emit, jnp.maximum(n_emit - 1, 0)[:, None], axis=1)[:, 0]
    return buf, n_emit, m, last_new, new_done


@partial(jax.jit, static_argnames=("cfg", "draft_cfg", "max_new",
                                   "n_spec", "dtype", "eos_id",
                                   "decode_kernel", "temperature",
                                   "top_k", "top_p"))
def generate_speculative(
    params: PyTree,
    draft_params: PyTree,
    prompt: jax.Array,       # (B, S0) int32
    key: jax.Array | None = None,
    *,
    cfg: tfm.TransformerConfig,
    draft_cfg: tfm.TransformerConfig,
    max_new: int,
    n_spec: int = 4,
    temperature: float = 0.0,
    top_k: int | None = None,
    top_p: float | None = None,
    dtype=None,
    eos_id: int | None = None,
    decode_kernel: bool | None = None,
):
    """SPECULATIVE decoding: a small draft model proposes ``n_spec``
    tokens per round, the target model verifies them all in ONE batched
    forward, and the longest accepted prefix plus one continuation
    token are emitted — at up to ``n_spec + 1`` tokens per target pass.

    ``temperature == 0`` (default): GREEDY speculation — a draft is
    accepted iff it equals the target's argmax, and the output is
    identical to the target's plain greedy decode (the standard
    guarantee; ``key`` is ignored).

    ``temperature > 0``: SAMPLED speculation via draft-distribution
    rejection sampling (``_spec_reject_tokens``): the draft SAMPLES its
    proposals from its warped distribution q, the target accepts each
    with probability min(1, p/q), and a rejection resamples from the
    residual norm(max(p - q, 0)) — the emitted tokens are distributed
    EXACTLY as the target's own warped (temperature/top-k/top-p)
    distribution, per the standard speculative-sampling identity.
    Requires ``key``.  Both models are warped with the same
    temperature/top_k/top_p (the sharper the draft, the higher the
    acceptance — warping symmetrically is the usual choice).

    TPU-first shape: the verification pass is a (B, n_spec+1)-token
    batched forward — exactly the matmul-heavy work the MXU wants,
    replacing n_spec+1 bandwidth-bound single-token steps; the draft
    runs the cheap single-token scan.  Cache REWIND after a rejected
    proposal is free by construction: this framework's caches are
    position-bounded (reads never pass the caller's ``pos``, stale rows
    are overwritten before the bound reaches them — the same property
    slot recycling in serve.py relies on), so rejecting speculated
    tokens is just not advancing ``pos`` over their rows.

    Returns ``(tokens (B, S0 + max_new), stats)`` with
    ``stats = {"rounds": r, "drafted": d, "accepted": a}`` —
    ``a / d`` is the acceptance rate and ``(max_new * B) / (r)`` the
    mean tokens per target pass.  No reference analog (the reference
    has no inference stack).
    """
    b, s0 = prompt.shape
    k_tok = n_spec + 1
    sampled = temperature > 0.0
    if sampled and key is None:
        raise ValueError("sampled speculative decoding (temperature > 0) "
                         "needs a PRNG key")
    use_kernel = default_decode_kernel(decode_kernel)
    max_len = pad_cache_len(s0 + max_new + k_tok)

    # prefill BOTH models over the prompt; t0 = target's first token
    cache, logits0 = _spec_prefill(params, prompt, cfg, dtype, max_len)
    dcache, _ = _spec_prefill(draft_params, prompt, draft_cfg, dtype,
                              max_len)
    if sampled:
        key, sub = jax.random.split(key)
        t0 = _sample(sub, logits0, temperature, top_k, top_p)
    else:
        key = jax.random.key(0)  # unused; a concrete carry leaf
        t0 = jnp.argmax(logits0, -1).astype(jnp.int32)

    out0 = jnp.zeros((b, max_new), jnp.int32)
    out0 = out0.at[:, 0].set(t0)
    done0 = ((t0 == eos_id) if eos_id is not None
             else jnp.zeros((b,), bool))

    def cond(c):
        return jnp.any((c["n"] < max_new) & ~c["done"])

    def body(c):
        pos, last = c["pos"], c["last"]
        rkey, dkey, vkey = jax.random.split(c["key"], 3)

        # 1. draft proposes n_spec tokens (single-token steps): greedy
        # argmaxes, or samples from its warped distribution (whose
        # probs the rejection step needs).  One EXTRA step runs so the
        # last proposal's own KV row lands in the draft cache too —
        # when every draft is accepted, the next round's reads pass
        # that row (the scan writes each step's INPUT, so n steps alone
        # would leave d_n's row unwritten and poison every later
        # round's draft context).
        def draft_step(carry, dk):
            dc, tok, p = carry
            lg, dc = decode_step_ragged(draft_params, dc, tok, p + 1,
                                        cfg=draft_cfg, dtype=dtype,
                                        use_decode_kernel=use_kernel)
            if sampled:
                warped = _filter_logits(lg, temperature, top_k, top_p)
                nxt = jax.random.categorical(dk, warped).astype(jnp.int32)
                qp = jax.nn.softmax(warped, -1)
            else:
                nxt = jnp.argmax(lg, -1).astype(jnp.int32)
                qp = jnp.zeros((b, 0), jnp.float32)  # unused
            return (dc, nxt, p + 1), (nxt, qp)

        (dcache, _, _), (drafts, qprobs) = lax.scan(
            draft_step, (c["dcache"], last, pos),
            jax.random.split(dkey, n_spec + 1))
        drafts = drafts[:n_spec].T  # (B, n_spec); the extra is discarded

        # 2. target verifies all proposals in ONE (B, k_tok) forward
        tokens_in = jnp.concatenate([last[:, None], drafts], axis=1)
        vpos = pos[:, None] + 1 + jnp.arange(k_tok)[None]  # (B, k_tok)
        vlogits, cache2 = _forward_cached(
            params, c["cache"], tokens_in, vpos,
            pos + 1, cfg=cfg, dtype=dtype, k_len=max_len)
        if sampled:
            pprobs = jax.nn.softmax(
                _filter_logits(vlogits, temperature, top_k, top_p), -1)
            match, g = _spec_reject_tokens(
                vkey, drafts, qprobs[:n_spec].transpose(1, 0, 2), pprobs)
        else:
            match = None
            g = jnp.argmax(vlogits, -1).astype(jnp.int32)  # (B, k_tok)

        # 3+4. accept the longest accepted prefix and scatter the
        # emissions (shared with prompt-lookup speculation)
        out, n_emit, m, last_new, new_done = _spec_accept_emit(
            drafts, g, c["done"], c["n"], c["out"], 0, n_spec, max_new,
            eos_id, match=match)
        return dict(
            cache=cache2, dcache=dcache, key=rkey,
            pos=jnp.where(c["done"], pos, pos + n_emit),
            last=jnp.where(c["done"] | (n_emit == 0), last, last_new),
            out=out, n=c["n"] + n_emit, done=new_done,
            rounds=c["rounds"] + 1,
            drafted=c["drafted"] + jnp.sum(
                jnp.where(c["done"], 0, n_spec)),
            accepted=c["accepted"] + jnp.sum(jnp.where(c["done"], 0, m)))

    state = lax.while_loop(cond, body, dict(
        cache=cache, dcache=dcache, key=key,
        pos=jnp.full((b,), s0 - 1, jnp.int32),
        last=t0, out=out0, n=jnp.ones((b,), jnp.int32), done=done0,
        rounds=jnp.int32(0), drafted=jnp.int32(0), accepted=jnp.int32(0)))
    return _spec_epilogue(prompt, state["out"], state, eos_id)


@partial(jax.jit, static_argnames=("cfg", "max_new", "n_spec", "ngram",
                                   "dtype", "eos_id", "temperature",
                                   "top_k", "top_p"))
def generate_lookup(
    params: PyTree,
    prompt: jax.Array,       # (B, S0) int32
    key: jax.Array | None = None,
    *,
    cfg: tfm.TransformerConfig,
    max_new: int,
    n_spec: int = 8,
    ngram: int = 2,
    temperature: float = 0.0,
    top_k: int | None = None,
    top_p: float | None = None,
    dtype=None,
    eos_id: int | None = None,
):
    """PROMPT-LOOKUP speculative decoding: draft-model-free speculation
    where each round's proposals come from matching the trailing
    ``ngram`` tokens against the prompt + generated-so-far stream and
    copying the continuation of the most recent match.  The target
    verifies all ``n_spec`` proposals in one batched forward (exactly
    as ``generate_speculative``), so bad lookups only waste a round's
    speculation, never correctness.

    ``temperature == 0`` (default): greedy — output identical to the
    target's plain greedy decode.  ``temperature > 0`` (requires
    ``key``): the lookup proposal is a POINT-MASS draft distribution,
    so rejection sampling degenerates cleanly — proposal x is accepted
    with probability p(x) (its own warped target probability), and a
    rejection resamples from p with x removed and renormalized
    (``_spec_reject_tokens`` with one-hot q) — emitted tokens are
    distributed exactly as the target's warped distribution.

    Wins on copy-heavy continuations (summarization, code, retrieval,
    repetitive corpora) where the next tokens literally appear earlier
    in the context; costs nothing when they don't (the proposal lookup
    is a handful of vector compares — no draft model, no draft cache).
    Returns ``(tokens, stats)`` as ``generate_speculative``.
    """
    b, s0 = prompt.shape
    k_tok = n_spec + 1
    sampled = temperature > 0.0
    if sampled and key is None:
        raise ValueError("sampled lookup decoding (temperature > 0) "
                         "needs a PRNG key")
    total = s0 + max_new
    max_len = pad_cache_len(total + k_tok)
    cache, logits0 = _spec_prefill(params, prompt, cfg, dtype, max_len)
    if sampled:
        key, sub = jax.random.split(key)
        t0 = _sample(sub, logits0, temperature, top_k, top_p)
    else:
        key = jax.random.key(0)  # unused; a concrete carry leaf
        t0 = jnp.argmax(logits0, -1).astype(jnp.int32)

    stream0 = jnp.zeros((b, total), jnp.int32)
    stream0 = stream0.at[:, :s0].set(prompt).at[:, s0].set(t0)
    done0 = ((t0 == eos_id) if eos_id is not None
             else jnp.zeros((b,), bool))

    def cond(c):
        return jnp.any((c["n"] < max_new) & ~c["done"])

    def body(c):
        pos = c["pos"]
        rkey, vkey = jax.random.split(c["key"])
        last = jnp.take_along_axis(c["stream"],
                                   (s0 + c["n"] - 1)[:, None], axis=1)[:, 0]
        drafts = lookup_proposals(c["stream"], s0 + c["n"] - 1, n_spec,
                                  ngram)
        tokens_in = jnp.concatenate([last[:, None], drafts], axis=1)
        vpos = pos[:, None] + 1 + jnp.arange(k_tok)[None]
        vlogits, cache2 = _forward_cached(
            params, c["cache"], tokens_in, vpos, pos + 1,
            cfg=cfg, dtype=dtype, k_len=max_len)
        if sampled:
            pprobs = jax.nn.softmax(
                _filter_logits(vlogits, temperature, top_k, top_p), -1)
            q = jax.nn.one_hot(drafts, cfg.vocab_size, dtype=jnp.float32)
            match, g = _spec_reject_tokens(vkey, drafts, q, pprobs)
        else:
            match = None
            g = jnp.argmax(vlogits, -1).astype(jnp.int32)
        stream, n_emit, m, _, new_done = _spec_accept_emit(
            drafts, g, c["done"], c["n"], c["stream"], s0, n_spec,
            max_new, eos_id, match=match)
        return dict(
            cache=cache2, stream=stream, key=rkey,
            pos=jnp.where(c["done"], pos, pos + n_emit),
            n=c["n"] + n_emit, done=new_done,
            rounds=c["rounds"] + 1,
            drafted=c["drafted"] + jnp.sum(
                jnp.where(c["done"], 0, n_spec)),
            accepted=c["accepted"] + jnp.sum(jnp.where(c["done"], 0, m)))

    state = lax.while_loop(cond, body, dict(
        cache=cache, stream=stream0, key=key,
        pos=jnp.full((b,), s0 - 1, jnp.int32),
        n=jnp.ones((b,), jnp.int32), done=done0,
        rounds=jnp.int32(0), drafted=jnp.int32(0), accepted=jnp.int32(0)))
    return _spec_epilogue(prompt, state["stream"][:, s0:], state, eos_id)


_TP_JIT_CACHE: dict = {}


def generate_tp(
    params: PyTree,          # tfm.shard_specs-sharded on ``mesh``
    prompt: jax.Array,       # (B, S0) int32 (replicated)
    key: jax.Array,
    *,
    cfg: tfm.TransformerConfig,
    mesh,
    axis: str = "model",
    max_new: int,
    temperature: float = 1.0,
    top_k: int | None = None,
    top_p: float | None = None,
    dtype=None,
    eos_id: int | None = None,
    decode_segments: int = 8,
    decode_kernel: bool | None = None,
    kv_dtype=None,
    specs: PyTree | None = None,
) -> jax.Array:
    """Tensor-parallel decode: ``generate`` inside shard_map over ``axis``.

    ``params`` stay in their training-time Megatron sharding (no host
    gather); each device runs the decode program on its head/FFN shard with
    a head-sharded KV cache, communicating only the two per-layer psums.
    Sampling keys are replicated, so every shard draws identical tokens.

    ``specs`` overrides the parameter PartitionSpecs (default: the Megatron
    ``tfm.shard_specs``).  Pass the training-time specs for ZeRO-3/FSDP
    params (lm.param_specs): dims sharded over axes other than ``axis`` are
    all-gathered inside the program right before use, instead of jit
    silently replicating the shards at dispatch.

    The compiled program is cached per (cfg, mesh, decode shape, specs) —
    repeated sampling calls do not retrace.
    """
    from .utils.compat import shard_map
    from jax.sharding import PartitionSpec as P

    _warn_if_expert_choice(cfg)
    ntp = mesh.shape[axis]
    if cfg.n_heads % ntp or cfg.kv_heads % ntp:
        raise ValueError(
            f"heads ({cfg.n_heads} q / {cfg.kv_heads} kv) must divide over "
            f"the {ntp}-way '{axis}' axis")
    if cfg.n_experts and cfg.n_experts % ntp:
        raise ValueError(f"{cfg.n_experts} experts do not shard over "
                         f"{ntp} devices")
    if specs is None:
        specs = tfm.shard_specs(cfg, tp_axis=axis)
    spec_leaves, spec_def = jax.tree.flatten(specs)
    cache_key = (cfg, mesh, axis, max_new, temperature, top_k, top_p,
                 jnp.dtype(dtype).name if dtype is not None else None,
                 eos_id, decode_segments, decode_kernel,
                 jnp.dtype(kv_dtype).name if kv_dtype is not None else None,
                 tuple(spec_leaves), spec_def)
    fn = _TP_JIT_CACHE.get(cache_key)
    if fn is None:
        def run(params, prompt, key):
            def gather(p, spec):
                # reassemble dims sharded over non-tp axes (ZeRO-3 'data'
                # shards) — the transposeless analogue of lm._fsdp_gather
                for dim, ax in enumerate(spec):
                    if ax is not None and ax != axis:
                        p = lax.all_gather(p, ax, axis=dim, tiled=True)
                return p

            params = jax.tree.map(gather, params, specs)
            out = _generate_impl(params, prompt, key, cfg=cfg,
                                 max_new=max_new, temperature=temperature,
                                 top_k=top_k, top_p=top_p, dtype=dtype,
                                 eos_id=eos_id,
                                 decode_segments=decode_segments,
                                 decode_kernel=decode_kernel,
                                 kv_dtype=kv_dtype, tp_axis=axis)
            # Certify replication for the P() out_spec: gathered ZeRO-3
            # leaves are still *marked* varying over their gather axes, so
            # the sampled tokens inherit that mark — a pmax over identical
            # values is a no-op that restores provable invariance.
            inv = tuple(a for a in mesh.axis_names if a != axis)
            return lax.pmax(out, inv) if inv else out

        fn = jax.jit(shard_map(
            run, mesh=mesh, in_specs=(specs, P(), P()), out_specs=P()))
        _TP_JIT_CACHE[cache_key] = fn
    return fn(params, prompt, key)
