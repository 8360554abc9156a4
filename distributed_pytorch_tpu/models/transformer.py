"""Decoder-only transformer LM, TPU-native and parallelism-aware.

The reference framework's only model is a CNN (reference model.py); this is
the model family the TPU build adds for its long-context/distributed
capabilities.  Same design idiom as models/vgg.py — pure functions over an
explicit parameter pytree — with a modern decoder stack: RMSNorm -> causal
self-attention with rotary embeddings -> residual, RMSNorm -> SwiGLU MLP ->
residual, tied embedding head.

Parallelism is expressed through two optional named-axis hooks, so the same
code runs single-device, tensor-parallel, sequence-parallel, or both:

- ``tp_axis``: the params passed in are each device's HEAD/FFN shard (heads
  split over the axis for wq/wk/wv, rows for wo; columns for w_gate/w_up,
  rows for w_down).  The only communication is one ``psum`` after the
  attention out-projection and one after the MLP down-projection — the
  standard Megatron factoring, here compiled by XLA over ICI.
- ``seq_axis``: activations hold this device's sequence chunk — laid out
  per ``seq_layout`` ('contiguous', or the balanced 'zigzag' ring layout of
  parallel/context.py) — and attention runs as a ring over the axis.
  ``pos0`` (contiguous offset) or ``pos`` (explicit positions, required for
  zigzag) carries the chunk's absolute positions for rotary embeddings.

Head dim defaults to 128 — one MXU lane tile — and d_ff to 4*d_model.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import attention as attn_ops
from ..ops import moe as moe_ops
from ..parallel import context as ctx

Array = jax.Array
PyTree = Any


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32_000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 4
    n_kv_heads: int | None = None  # grouped-query attention (None = MHA)
    head_dim: int = 128   # MXU lane tile
    d_ff: int | None = None  # default 4*d_model
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-5
    # Mixture-of-Experts: 0 = dense; otherwise every ``moe_every``-th layer
    # (counting from layer moe_every-1) uses a Switch-routed MoE MLP whose
    # experts shard over the tensor axis, or over a dedicated 'expert'
    # axis with tp-sharded FFNs when the trainer runs EP x TP (ops/moe.py,
    # shard_specs ep_axis).
    n_experts: int = 0
    moe_every: int = 2
    moe_top_k: int = 1   # 1 = Switch routing, 2 = classic top-2
    moe_router: str = "tokens"   # 'tokens' (top-k) | 'experts' (expert choice)
    router_z_coef: float = 0.0   # z-loss weight relative to the aux weight
    capacity_factor: float = 2.0
    # Round 21: wire precision of the expert-parallel dispatch/combine
    # all_to_alls ('f32' exact; 'int8'/'int4' rowwise-quantized payloads
    # with per-token f32 scale rows on the same exchange — the routed
    # expert:a2a@bits format), and the capacity-chunk count whose
    # combine/FFN interleaving hides the exchange (1 = the historical
    # unchunked program, bitwise).  Both apply only where the MoE layer
    # actually crosses a mesh axis (the EP / tensor-axis call sites).
    moe_dispatch_bits: str = "f32"
    moe_a2a_chunks: int = 1
    # -- what further architectures need; the defaults are the block above --
    # False: the output projection is a table of its own, params["lm_head"]
    tie_embeddings: bool = True
    # Per-layer attention kinds (ATTN_KINDS), one name a layer, e.g.
    # ("global_nope", "window", "window", "window").  () = every layer
    # "global" with its leaves flat in the layer's dict (the dense tree);
    # otherwise a layer keeps wq/wk/wv/wo under "attn_<kind>", so the tree
    # itself says which kind a layer is.  ``attn_window``: the width of the
    # "window" kind (a query sees that many keys, its own included).
    attn_kinds: tuple[str, ...] = ()
    attn_window: int = 0
    # The dropless routed layer (ops/moe.moe_dropless_apply): EVERY layer is
    # routed, top ``moe_top_k`` of the router's ``n_experts`` outputs, softmax
    # over the picks, no capacity.  This chip holds ``moe_experts_held``
    # experts (None = all) from ``moe_first_expert`` on and computes their
    # part of the result.  ``moe_act``: the gate branch ('silu' SwiGLU,
    # 'relu' ReGLU); ``moe_router_input``: 'mlp_norm' (the experts' own
    # input) or 'attn_norm' (the attention's normed input: a router placed
    # before attention).  ``d_ff`` is one expert's width.
    moe_dropless: bool = False
    moe_experts_held: int | None = None
    moe_first_expert: int = 0
    moe_act: str = "silu"
    moe_router_input: str = "mlp_norm"

    def __post_init__(self):
        kv = self.n_kv_heads if self.n_kv_heads is not None else self.n_heads
        if self.n_heads % kv:
            raise ValueError(f"n_heads {self.n_heads} not divisible by "
                             f"n_kv_heads {kv}")
        if self.attn_kinds:
            unknown = set(self.attn_kinds) - set(ATTN_KINDS)
            if unknown or len(self.attn_kinds) != self.n_layers:
                raise ValueError(
                    f"attn_kinds must name one of {sorted(ATTN_KINDS)} for "
                    f"each of the {self.n_layers} layers, got "
                    f"{self.attn_kinds!r}")
            if "window" in self.attn_kinds and self.attn_window < 1:
                raise ValueError("a 'window' layer needs attn_window >= 1")
        if self.moe_dropless and not 1 <= self.moe_top_k <= self.n_experts:
            raise ValueError(
                f"moe_dropless routes top {self.moe_top_k} of "
                f"n_experts={self.n_experts}: need 1 <= top_k <= n_experts")
        if self.moe_act not in ("silu", "relu"):
            raise ValueError(f"moe_act must be 'silu' or 'relu', got "
                             f"{self.moe_act!r}")
        if self.moe_router_input not in ("mlp_norm", "attn_norm"):
            raise ValueError(f"moe_router_input must be 'mlp_norm' or "
                             f"'attn_norm', got {self.moe_router_input!r}")
        if self.moe_dispatch_bits not in ("f32", "int8", "int4"):
            raise ValueError(
                f"moe_dispatch_bits must be f32, int8, or int4, got "
                f"{self.moe_dispatch_bits!r}")
        if self.moe_a2a_chunks < 1:
            raise ValueError(
                f"moe_a2a_chunks must be >= 1, got {self.moe_a2a_chunks}")

    @property
    def ff(self) -> int:
        return self.d_ff if self.d_ff is not None else 4 * self.d_model

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads if self.n_kv_heads is not None else self.n_heads

    def is_moe_layer(self, i: int) -> bool:
        if self.moe_dropless:
            return True
        return self.n_experts > 0 and i % self.moe_every == self.moe_every - 1

    def attn_kind(self, i: int) -> str:
        return self.attn_kinds[i] if self.attn_kinds else "global"

    def training_only(self) -> list[str]:
        """The mechanisms of this configuration that only the training
        path implements (decode and serving refuse them by these names)."""
        found = []
        if "window" in self.attn_kinds:
            found.append("windowed attention layers (attn_kinds 'window': "
                         "no windowed cache rows or decode kernel)")
        if "global_nope" in self.attn_kinds:
            found.append("attention layers without rotary (attn_kinds "
                         "'global_nope')")
        if not self.tie_embeddings:
            found.append("an untied output head (tie_embeddings=False)")
        if self.moe_dropless:
            found.append("the dropless routed layer (moe_dropless=True: no "
                         "grouped product in decode)")
        return found


# attention kind -> (rotary on q and k, windowed)
ATTN_KINDS = {"global": (True, False), "global_nope": (False, False),
              "window": (True, True)}


def require_servable(cfg: TransformerConfig, where: str) -> None:
    """``generate`` and ``ContinuousBatcher`` compute the dense block and
    the capacity-routed one; a configuration with anything else is refused
    by the name of the missing mechanism rather than computed as something
    it is not."""
    missing = cfg.training_only()
    if missing:
        raise NotImplementedError(
            f"{where} does not implement " + "; ".join(missing)
            + ": this configuration runs on the training path only")


# Named size presets, in the spirit of the reference's cfg dict
# (reference model.py:3-8 defines VGG11..19 the same way).
PRESETS = {
    "LM-tiny": TransformerConfig(vocab_size=1024, d_model=256, n_layers=2,
                                 n_heads=2),
    "LM-small": TransformerConfig(d_model=768, n_layers=12, n_heads=6),
    "LM-base": TransformerConfig(d_model=1024, n_layers=24, n_heads=8),
}


def init(key: Array, cfg: TransformerConfig) -> PyTree:
    """Build the parameter pytree (same-seed construction on every replica,
    the reference's init-parity mechanism — SURVEY.md 2.3)."""
    d, h, dh, f = cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.ff
    kv = cfg.kv_heads

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                / math.sqrt(fan_in))

    keys = iter(jax.random.split(
        key, 2 + 7 * cfg.n_layers + (not cfg.tie_embeddings)))
    params: dict = {
        "embed": jax.random.normal(next(keys), (cfg.vocab_size, d),
                                   jnp.float32) * 0.02,
        "final_norm": jnp.ones((d,), jnp.float32),
    }
    for i in range(cfg.n_layers):
        attn = {
            "wq": dense(next(keys), (d, h, dh), d),
            "wk": dense(next(keys), (d, kv, dh), d),
            "wv": dense(next(keys), (d, kv, dh), d),
            "wo": dense(next(keys), (h, dh, d), h * dh),
        }
        layer = {"attn_norm": jnp.ones((d,), jnp.float32),
                 **_under_kind(cfg, i, attn),
                 "mlp_norm": jnp.ones((d,), jnp.float32)}
        if cfg.is_moe_layer(i):
            layer["moe"] = moe_ops.moe_init(
                next(keys), d, f, cfg.n_experts,
                held=cfg.moe_experts_held if cfg.moe_dropless else None)
        else:
            layer.update(
                w_gate=dense(next(keys), (d, f), d),
                w_up=dense(next(keys), (d, f), d),
                w_down=dense(next(keys), (f, d), f),
            )
        params[f"layer{i}"] = layer
    if not cfg.tie_embeddings:
        params["lm_head"] = jax.random.normal(
            next(keys), (cfg.vocab_size, d), jnp.float32) * 0.02
    return params


def _under_kind(cfg: TransformerConfig, i: int, attn: dict) -> dict:
    """A layer's attention leaves as the tree keeps them: flat for the
    dense model, under ``attn_<kind>`` where layers differ in kind."""
    return {"attn_" + cfg.attn_kind(i): attn} if cfg.attn_kinds else attn


def shard_specs(cfg: TransformerConfig, *, tp_axis: str = "model",
                ep_axis: str | None = None) -> PyTree:
    """PartitionSpec pytree matching ``init``'s structure: the Megatron
    sharding (heads/FFN columns over ``tp_axis``), norms/embed replicated.

    Without ``ep_axis``, MoE experts shard over the tensor axis (the
    round-2 layout).  With ``ep_axis``, experts shard over their OWN mesh
    axis and each expert's FFN width additionally shards over ``tp_axis``
    — EP x TP composition (VERDICT round-2 #6): the all_to_all rides the
    expert axis while the Megatron psum reassembles the FFN inside every
    expert."""
    from jax.sharding import PartitionSpec as P

    specs: dict = {"embed": P(), "final_norm": P()}
    if not cfg.tie_embeddings:
        specs["lm_head"] = P()
    for i in range(cfg.n_layers):
        layer = {
            "attn_norm": P(),
            **_under_kind(cfg, i, {
                "wq": P(None, tp_axis, None),
                "wk": P(None, tp_axis, None),
                "wv": P(None, tp_axis, None),
                "wo": P(tp_axis, None, None)}),
            "mlp_norm": P(),
        }
        if cfg.is_moe_layer(i):
            # the router is replicated everywhere
            if ep_axis is not None:
                layer["moe"] = {
                    "router": P(),
                    "w_gate": P(ep_axis, None, tp_axis),
                    "w_up": P(ep_axis, None, tp_axis),
                    "w_down": P(ep_axis, tp_axis, None),
                }
            else:
                layer["moe"] = {
                    "router": P(),
                    "w_gate": P(tp_axis, None, None),
                    "w_up": P(tp_axis, None, None),
                    "w_down": P(tp_axis, None, None),
                }
        else:
            layer.update(w_gate=P(None, tp_axis), w_up=P(None, tp_axis),
                         w_down=P(tp_axis, None))
        specs[f"layer{i}"] = layer
    return specs


def sync_group_index(cfg: TransformerConfig) -> dict[str, int]:
    """Top-level param key -> forward layer-group index, the boundary
    schedule ``apply(boundary=...)`` walks: the tied embedding first
    (group 0 — it is consumed at BOTH ends of the stack, so its cotangent
    completes only at the very end of the backward pass and any gradient
    bucket holding it must fire at the earliest boundary), then the layers
    in forward order, then final_norm.  Used by the overlap gradient-sync
    machinery (parallel/strategies.OverlapSync via train-side wiring) and
    by lm.py's streaming ZeRO-3 gather placement."""
    idx = {"embed": 0, "final_norm": cfg.n_layers + 1}
    if not cfg.tie_embeddings:   # the head's own table is read last
        idx["lm_head"] = cfg.n_layers + 1
    for i in range(cfg.n_layers):
        idx[f"layer{i}"] = i + 1
    return idx


def rms_norm(x: Array, scale: Array, eps: float) -> Array:
    x32 = x.astype(jnp.float32)
    rms = lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return (x32 * rms * scale.astype(jnp.float32)).astype(x.dtype)


def rotary(x: Array, pos: Array, theta: float) -> Array:
    """Rotary position embedding over (B, H, S, D); ``pos`` is (S,) absolute
    positions (a sequence-parallel shard passes its global offsets), or
    (B, S) per-sequence positions (ragged decode — every sequence sits at
    its own depth)."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)  # (D/2,)
    angles = pos[..., None].astype(jnp.float32) * freqs  # (S|B,S, D/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if pos.ndim == 2:  # (B, S, D/2) -> broadcast over heads
        cos, sin = cos[:, None], sin[:, None]
    x1, x2 = x[..., ::2], x[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x1 * sin + x2 * cos
    out = jnp.stack([y1, y2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def block(
    lp: PyTree,
    x: Array,
    *,
    cfg: TransformerConfig,
    is_moe: bool,
    pos: Array,
    attn_impl: str = "flash",
    seq_axis: str | None = None,
    seq_layout: str = "contiguous",
    tp_axis: str | None = None,
    ep_axis: str | None = None,
    matmul_dtype: str | None = None,
    save_attn: bool = False,
    kind: str = "global",
    with_stats: bool = False,
) -> tuple[Array, Array] | tuple[Array, Array, dict | None]:
    """One transformer block: (layer params, (B, S, D)) -> (x, moe aux).

    The single implementation of the layer body, shared by ``apply`` and
    the pipeline-parallel stage runner (parallel/pipeline.py); decode has
    its own cache-backed twin (generate.py _forward_cached).

    ``ep_axis``: dedicated expert-parallel axis (EP x TP).  The batch is
    sharded over it like a data axis (each EP rank owns distinct tokens,
    so attention is not duplicated), MoE params hold this rank's E/ep
    experts with each expert's FFN width tp-sharded, and the all_to_all
    rides the expert axis.  Without it, experts shard over ``tp_axis``
    (the round-2 layout).

    ``matmul_dtype="int8"`` (round 16) routes the DENSE projections —
    q/k/v/o and the (non-MoE) MLP matmuls — through the int8 forward /
    straight-through backward ``ops.quantized.quantized_matmul`` (3D
    einsum weights reshaped to 2D around the call); ``None`` traces the
    historical einsums bit-for-bit.

    ``save_attn`` (round 17, ``apply(remat="selective")``): request the
    flash kernel's ``(o, lse)`` form so its residuals carry the
    ``attn_out``/``attn_lse`` checkpoint names (ops/attention.py) that a
    ``save_only_these_names`` policy pins — attention stays saved while
    the MLP recomputes.  ``False`` traces the historical kernel call.

    ``kind``: the layer's attention kind (``ATTN_KINDS``: rotary or not,
    windowed or not; ``cfg.attn_kind(i)``).  ``with_stats``: also return
    the dropless routed layer's counters (``ops/moe.py``; None for any
    other layer) as a third result.
    """
    b, s, d = x.shape
    q8 = matmul_dtype == "int8"
    rope, windowed = ATTN_KINDS[kind]
    window = cfg.attn_window if windowed else None
    if window is not None and seq_axis is not None:
        raise NotImplementedError(
            "windowed attention layers under sequence parallelism: ring "
            "attention has no window")
    ap = lp["attn_" + kind] if cfg.attn_kinds else lp   # attention leaves

    def proj2d(h2: Array, w2: Array) -> Array:
        from ..ops import quantized as qz
        return qz.quantized_matmul(h2, w2)

    # -- attention ---------------------------------------------------------
    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    if q8:
        hf = h.reshape(b * s, d)

        def head_proj(w):
            heads, dh = w.shape[1], w.shape[2]
            out = proj2d(hf, w.reshape(d, heads * dh).astype(h.dtype))
            return out.reshape(b, s, heads, dh).transpose(0, 2, 1, 3)

        q, k, v = (head_proj(ap["wq"]), head_proj(ap["wk"]),
                   head_proj(ap["wv"]))
    else:
        q = jnp.einsum("bsd,dhk->bhsk", h, ap["wq"].astype(h.dtype))
        k = jnp.einsum("bsd,dhk->bhsk", h, ap["wk"].astype(h.dtype))
        v = jnp.einsum("bsd,dhk->bhsk", h, ap["wv"].astype(h.dtype))
    if rope:
        q = rotary(q, pos, cfg.rope_theta)
        k = rotary(k, pos, cfg.rope_theta)
    if cfg.kv_heads != cfg.n_heads:
        # GQA: q heads share repeated K/V heads (params and decode cache stay
        # kv_heads-sized; the repeat is a view XLA folds into the attention)
        rep = q.shape[1] // k.shape[1]  # local head counts (same under TP)
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    if seq_axis is not None:
        o = ctx.ring_attention(
            q, k, v, seq_axis, causal=True, layout=seq_layout,
            impl="flash" if attn_impl == "flash" else "reference")
    elif attn_impl == "flash":
        if save_attn:
            o, _ = attn_ops.flash_attention(q, k, v, causal=True,
                                            with_lse=True, window=window)
        else:
            o = attn_ops.flash_attention(q, k, v, causal=True, window=window)
    else:
        o = attn_ops.attention_reference(q, k, v, causal=True, window=window)
    if q8:
        of = o.transpose(0, 2, 1, 3).reshape(b * s, -1)
        o = proj2d(of, ap["wo"].reshape(-1, d).astype(o.dtype)
                   ).reshape(b, s, d)
    else:
        o = jnp.einsum("bhsk,hkd->bsd", o, ap["wo"].astype(o.dtype))
    if tp_axis is not None:
        o = lax.psum(o, tp_axis)  # Megatron row-parallel reduction 1
    x = x + o
    # -- MLP ---------------------------------------------------------------
    h_attn = h    # what a router placed before attention reads
    h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    stats = None
    if is_moe and cfg.moe_dropless:
        if ep_axis is not None or (tp_axis is not None
                                   and lax.axis_size(tp_axis) > 1):
            raise NotImplementedError(
                "the dropless routed layer over an expert or tensor axis: "
                "it has no exchange yet (ops/moe.moe_dropless_apply); run "
                "it with ep=1 and tp=1")
        router_in = (h_attn.reshape(b * s, d)
                     if cfg.moe_router_input == "attn_norm" else None)
        down, stats = moe_ops.moe_dropless_apply(
            lp["moe"], h.reshape(b * s, d), top_k=cfg.moe_top_k,
            first_expert=cfg.moe_first_expert, router_input=router_in,
            act=cfg.moe_act)
        down = down.reshape(b, s, d)
    elif is_moe:
        hf = h.reshape(b * s, d)
        if ep_axis is not None:
            # EP x TP (dedicated expert axis): every tp rank routes the
            # SAME local tokens (routing is replicated across 'model',
            # like the Megatron MLP's input), dispatches through ITS
            # f-shard of each expert, and the all_to_all rides the expert
            # axis.  Each rank's output is an f-partial sum; the final
            # Megatron psum below completes the contraction.  Tokens must
            # NOT be sliced over tp here — a sliced token would only ever
            # meet 1/tp of its expert's FFN columns.
            down, aux = moe_ops.moe_apply(
                lp["moe"], hf, n_experts=cfg.n_experts,
                capacity_factor=cfg.capacity_factor, axis=ep_axis,
                top_k=cfg.moe_top_k, router_mode=cfg.moe_router,
                z_coef=cfg.router_z_coef,
                dispatch_bits=cfg.moe_dispatch_bits,
                a2a_chunks=cfg.moe_a2a_chunks)
            # aux is identical on every tp rank (replicated routing)
        elif tp_axis is not None:
            # Experts on the tensor axis itself (round-2 layout): tokens
            # are replicated across 'model'; each rank routes its 1/n
            # slice, experts exchange via all_to_all (ops/moe.py), and
            # the final psum (shared with the Megatron reduction below)
            # reassembles the full token set.
            n = lax.axis_size(tp_axis)
            if (b * s) % n:
                raise ValueError(
                    f"tokens per device {b * s} not divisible by the "
                    f"{n}-way '{tp_axis}' axis for MoE routing")
            t_loc = b * s // n
            idx = lax.axis_index(tp_axis)
            h_loc = lax.dynamic_slice_in_dim(hf, idx * t_loc, t_loc)
            out_loc, aux = moe_ops.moe_apply(
                lp["moe"], h_loc, n_experts=cfg.n_experts,
                capacity_factor=cfg.capacity_factor, axis=tp_axis,
                top_k=cfg.moe_top_k, router_mode=cfg.moe_router,
                z_coef=cfg.router_z_coef,
                dispatch_bits=cfg.moe_dispatch_bits,
                a2a_chunks=cfg.moe_a2a_chunks)
            down = jnp.zeros_like(hf)
            down = lax.dynamic_update_slice_in_dim(
                down, out_loc, idx * t_loc, 0)
            aux = lax.pmean(aux, tp_axis)
        else:
            down, aux = moe_ops.moe_apply(
                lp["moe"], hf, n_experts=cfg.n_experts,
                capacity_factor=cfg.capacity_factor, axis=None,
                top_k=cfg.moe_top_k, router_mode=cfg.moe_router,
                z_coef=cfg.router_z_coef)
        down = down.reshape(b, s, d)
    elif q8:
        hf = h.reshape(b * s, d)
        gate = jax.nn.silu(proj2d(hf, lp["w_gate"].astype(h.dtype)))
        up = proj2d(hf, lp["w_up"].astype(h.dtype))
        down = proj2d(gate * up, lp["w_down"].astype(h.dtype)
                      ).reshape(b, s, d)
    else:
        gate = jax.nn.silu(h @ lp["w_gate"].astype(h.dtype))
        up = h @ lp["w_up"].astype(h.dtype)
        down = (gate * up) @ lp["w_down"].astype(h.dtype)
    if tp_axis is not None:
        down = lax.psum(down, tp_axis)  # Megatron reduction 2
    if with_stats:
        return x + down, aux, stats
    return x + down, aux


def apply(
    params: PyTree,
    tokens: Array,
    *,
    cfg: TransformerConfig,
    dtype: jnp.dtype | None = None,
    attn_impl: str = "flash",      # 'flash' (Pallas) | 'reference' (XLA)
    seq_axis: str | None = None,   # ring-attention sequence parallelism
    seq_layout: str = "contiguous",  # ring chunk layout (see parallel/context)
    tp_axis: str | None = None,    # Megatron tensor parallelism
    ep_axis: str | None = None,    # dedicated expert axis (EP x TP)
    pos0: Array | int = 0,         # absolute position of tokens[:, 0]
    pos: Array | None = None,      # explicit absolute positions (S,)
    return_aux: bool = False,
    boundary=None,                 # layer-group hook (sync_group_index)
    matmul_dtype: str | None = None,  # "int8": quantized dense projections
    remat: str | None = None,      # None/"none" | "full" | "selective"
    head_fn=None,                  # (h, table) -> loss head replacement
    return_stats: bool = False,    # with return_aux: the routed counters too
) -> Array | tuple[Array, Array]:
    """Forward pass: (B, S) int32 tokens -> (B, S, vocab) float32 logits.

    Under ``seq_axis``, ``tokens`` is this device's sequence chunk laid out
    per ``seq_layout`` ('contiguous': one chunk whose global offset is
    ``pos0``; 'zigzag': the balanced ring layout — pass the chunk's global
    positions via ``pos``); logits come back chunk-sharded the same way.
    Under ``tp_axis``, the weights are the local head/FFN shards and two
    psums restore the full residual stream (MoE layers additionally
    expert-shard over the axis and exchange tokens with all_to_all).

    With ``return_aux`` the result is the tuple ``(logits, aux)`` where aux
    is this device's summed MoE load-balance loss (0.0 for dense models);
    callers average it across their mesh axes.

    ``boundary``: a hook ``params = boundary(group, params)`` called at
    every layer-group boundary of :func:`sync_group_index` in forward
    order — value-identity, used to place per-group gradient-sync markers
    or streaming ZeRO-3 gathers exactly where each group's params are
    first consumed (lm.py overlap=True).  ``None`` traces the historical
    graph.

    ``remat`` (round 17): activation rematerialization of the per-layer
    body.  ``"full"`` wraps each block in ``jax.checkpoint`` with the
    default policy (only the layer-boundary carry is saved; everything
    recomputes in the backward); ``"selective"`` additionally saves the
    flash kernel's ``(o, lse)`` via the ``attn_out``/``attn_lse``
    checkpoint names so only the projections and MLP recompute.  The
    ``boundary`` hook stays OUTSIDE the checkpointed region — its sync /
    ZeRO-3-gather collectives are traced once, never re-emitted by the
    remat backward.  ``None``/``"none"`` traces the historical graph
    bit-for-bit.

    ``head_fn``: when given, called as ``head_fn(h, table)`` on the
    final-norm hidden states in place of the logits matmul and its result
    returned where logits would be — the seam lm.py routes the unified
    head loss through (ops/losses.py head_loss).  ``table`` is the output
    table: the tied embedding, the BOUNDARY-transformed one (under
    streaming ZeRO-3 the gathered copy, not the caller's shard), or
    ``params["lm_head"]`` where the model has a head of its own
    (``tie_embeddings=False``).

    ``return_stats`` (with ``return_aux``): the result is ``(logits, aux,
    stats)``, ``stats`` the dropless routed layers' counters merged over
    the layers (``ops/moe.merge_stats``), None for a model without them.
    """
    if remat not in (None, "none", "full", "selective"):
        raise ValueError(
            f"unknown remat {remat!r}: expected 'none', 'full' or "
            "'selective'")
    if boundary is not None:
        params = boundary(0, params)  # the tied embedding's group
    x = params["embed"][tokens]  # (B, S, D)
    if dtype is not None:
        x = x.astype(dtype)
    if pos is None:
        pos = pos0 + jnp.arange(x.shape[1])
    aux_total = jnp.zeros((), jnp.float32)
    layer_stats = []

    use_remat = remat in ("full", "selective")
    remat_policy = (jax.checkpoint_policies.save_only_these_names(
        "attn_out", "attn_lse") if remat == "selective" else None)

    for i in range(cfg.n_layers):
        if boundary is not None:
            params = boundary(i + 1, params)

        def run(lp, x_in, pos_in, _i=i):
            return block(
                lp, x_in, cfg=cfg, is_moe=cfg.is_moe_layer(_i),
                pos=pos_in, attn_impl=attn_impl, seq_axis=seq_axis,
                seq_layout=seq_layout, tp_axis=tp_axis, ep_axis=ep_axis,
                matmul_dtype=matmul_dtype,
                save_attn=remat == "selective", kind=cfg.attn_kind(_i),
                with_stats=cfg.moe_dropless)

        if use_remat:
            # prevent_cse=False: inside jit/shard_map the CSE concern
            # jax.checkpoint guards against does not arise (same setting
            # as the pipeline stage remat, parallel/pipeline.py)
            run = jax.checkpoint(run, policy=remat_policy,
                                 prevent_cse=False)
        if cfg.moe_dropless:
            x, aux, stats = run(params[f"layer{i}"], x, pos)
            layer_stats.append(stats)
        else:
            x, aux = run(params[f"layer{i}"], x, pos)
        aux_total = aux_total + aux

    if boundary is not None:
        params = boundary(cfg.n_layers + 1, params)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    table = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    if head_fn is not None:
        out = head_fn(x, table)
    else:
        out = x.astype(jnp.float32) @ table.T.astype(jnp.float32)
    if return_aux and return_stats:
        return out, aux_total, (moe_ops.merge_stats(layer_stats)
                                if layer_stats else None)
    if return_aux:
        return out, aux_total
    return out


def param_count(params: PyTree) -> int:
    return sum(p.size for p in jax.tree.leaves(params))
