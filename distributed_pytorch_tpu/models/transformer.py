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
import numpy as np
from jax import lax

from ..ops import attention as attn_ops
from ..ops import gated_delta as gd_ops
from ..ops import moe as moe_ops
from ..parallel import context as ctx

Array = jax.Array
PyTree = Any


@dataclass(frozen=True)
class RopeSpec:
    """Rotary settings of one attention kind (``TransformerConfig.
    rope_by_kind``).  The leading ``rotary_share`` of each head's dimensions
    rotates, the rest pass through.  ``yarn_factor`` > 1 scales the
    frequencies as YaRN does: those that turn fewer than ``yarn_beta_slow``
    times over ``yarn_original_len`` positions are divided by the factor,
    those that turn more than ``yarn_beta_fast`` times are kept, with a
    linear ramp between; ``attention_factor`` multiplies cos and sin."""
    theta: float = 10_000.0
    rotary_share: float = 1.0
    yarn_factor: float = 1.0
    yarn_original_len: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    attention_factor: float = 1.0

    def __post_init__(self):
        if not 0.0 < self.rotary_share <= 1.0:
            raise ValueError(f"rotary_share {self.rotary_share} not in (0, 1]")
        if self.yarn_factor != 1.0 and self.yarn_original_len < 1:
            raise ValueError("YaRN scaling needs yarn_original_len")

    @property
    def plain(self) -> bool:
        """Every pair rotates at ``theta ** (-2j / d)``, unscaled."""
        return (self.rotary_share == 1.0 and self.yarn_factor == 1.0
                and self.attention_factor == 1.0)

    def inv_freq(self, head_dim: int) -> np.ndarray:
        """The rotated pairs' frequencies (float64; as many as rotate)."""
        rot = int(head_dim * self.rotary_share) // 2 * 2
        inv = self.theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
        if self.yarn_factor == 1.0:
            return inv

        def turns_at(beta):     # the pair that turns beta times over the
            return (rot * math.log(self.yarn_original_len    # original length
                                   / (beta * 2 * math.pi))
                    / (2 * math.log(self.theta)))

        low = max(math.floor(turns_at(self.yarn_beta_fast)), 0)
        high = min(math.ceil(turns_at(self.yarn_beta_slow)), rot - 1)
        ramp = np.clip((np.arange(rot // 2) - low)
                       / (high - low if high > low else 0.001), 0.0, 1.0)
        return inv * (1.0 - ramp) + inv / self.yarn_factor * ramp


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
    # How the dropless router weighs its picks: 'softmax' over the picks'
    # logits, or 'sigmoid': scores sigmoid(logits), the top ``moe_top_k`` of
    # them, normalised over the picks and times ``moe_score_scale``.
    moe_scoring: str = "softmax"
    moe_score_scale: float = 1.0
    # A shared expert beside the routed ones (a SwiGLU of this width that
    # every token passes through, leaves under the layer's "shared"; 0 =
    # none), and leading dense layers in a dropless model: the first
    # ``n_dense_layers`` have a dense SwiGLU MLP of width ``d_ff_dense``
    # (None = ``d_ff``) in place of the routed layer.
    moe_shared_ff: int = 0
    n_dense_layers: int = 0
    d_ff_dense: int | None = None
    # What differs by attention kind, as (kind, value) pairs; a kind that is
    # not named takes ``n_heads`` / plain rotary at ``rope_theta``.
    heads_by_kind: tuple[tuple[str, int], ...] = ()
    rope_by_kind: tuple[tuple[str, RopeSpec], ...] = ()
    # A gate on the attention output: head a's output times sigmoid(h wg)[a],
    # ``wg`` (d_model, heads) beside wq/wk/wv/wo, read from the normed input.
    attn_gate: bool = False
    # ... or, with ``attn_gate_form="element"``, one gate a dimension: ``wq``
    # (d_model, heads, 2 head_dim) projects each head's query and its gate
    # side by side, and o * sigmoid(gate) is taken element-wise (no ``wg``).
    attn_gate_form: str = "head"
    # RMSNorm over each head's dimensions of q and k before rotary (leaves
    # ``q_norm`` / ``k_norm`` (head_dim,) beside wq/wk).
    qk_norm: bool = False
    # Norm scales stored as an offset from this: a norm applies
    # ``norm_offset + w`` (1.0: zero-centred scales, stored as 0 at init).
    norm_offset: float = 0.0
    # A sigmoid gate on the shared expert, one scalar a token:
    # sigmoid(h w_sg) * shared(h), ``w_sg`` (d_model, 1) under "shared".
    moe_shared_gate: bool = False
    # Linear-attention layers (attn_kinds "linear", ops/gated_delta.py): the
    # gated delta rule over ``linear_v_heads`` value heads of
    # ``linear_v_dim``, each key head of ``linear_k_dim`` serving
    # linear_v_heads / linear_k_heads consecutive value heads, after a
    # causal depthwise convolution of ``linear_conv`` taps over q, k and v;
    # leaves under "attn_linear" (no wq/wk/wv/wo).
    linear_k_heads: int = 0
    linear_v_heads: int = 0
    linear_k_dim: int = 128
    linear_v_dim: int = 128
    linear_conv: int = 4

    def __post_init__(self):
        kv = self.n_kv_heads if self.n_kv_heads is not None else self.n_heads
        for h in self.head_counts():
            if h % kv:
                raise ValueError(f"n_heads {h} not divisible by "
                                 f"n_kv_heads {kv}")
        for name, pairs in (("heads_by_kind", self.heads_by_kind),
                            ("rope_by_kind", self.rope_by_kind)):
            unknown = {k for k, _ in pairs} - set(self.attn_kinds or
                                                  ("global",))
            if unknown:
                raise ValueError(f"{name} names {sorted(unknown)}, which no "
                                 f"layer of attn_kinds is")
        if self.moe_scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"moe_scoring must be 'softmax' or 'sigmoid', "
                             f"got {self.moe_scoring!r}")
        if not self.moe_dropless and (
                self.moe_scoring != "softmax" or self.moe_score_scale != 1.0
                or self.moe_shared_ff or self.n_dense_layers
                or self.d_ff_dense is not None):
            raise ValueError(
                "moe_scoring, moe_score_scale, moe_shared_ff, n_dense_layers "
                "and d_ff_dense belong to the dropless routed model "
                "(moe_dropless=True)")
        if self.moe_scoring == "softmax" and self.moe_score_scale != 1.0:
            raise ValueError("moe_score_scale scales the sigmoid router's "
                             "normalised scores; a softmax over the picks "
                             "has none")
        if not 0 <= self.n_dense_layers <= self.n_layers:
            raise ValueError(f"n_dense_layers {self.n_dense_layers} of "
                             f"{self.n_layers} layers")
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
        if self.attn_gate_form not in ("head", "element"):
            raise ValueError(f"attn_gate_form must be 'head' or 'element', "
                             f"got {self.attn_gate_form!r}")
        if self.attn_gate_form != "head" and not self.attn_gate:
            raise ValueError("attn_gate_form shapes the gate of attn_gate=True")
        if self.moe_shared_gate and not self.moe_shared_ff:
            raise ValueError("moe_shared_gate gates the shared expert "
                             "(moe_shared_ff > 0)")
        if self.has_linear and not (
                self.linear_k_heads >= 1
                and self.linear_v_heads % self.linear_k_heads == 0):
            raise ValueError(
                f"linear layers need linear_k_heads >= 1 dividing "
                f"linear_v_heads, got {self.linear_k_heads} / "
                f"{self.linear_v_heads}")

    @property
    def ff(self) -> int:
        return self.d_ff if self.d_ff is not None else 4 * self.d_model

    @property
    def dense_ff(self) -> int:
        """Width of a dense layer's MLP (the leading dense layers of a
        dropless model may have one of their own)."""
        return self.d_ff_dense if self.d_ff_dense is not None else self.ff

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads if self.n_kv_heads is not None else self.n_heads

    def is_moe_layer(self, i: int) -> bool:
        if self.moe_dropless:
            return i >= self.n_dense_layers
        return self.n_experts > 0 and i % self.moe_every == self.moe_every - 1

    @property
    def has_linear(self) -> bool:
        """Some layer is a linear-attention layer (attn_kinds "linear")."""
        return "linear" in self.attn_kinds

    def attn_kind(self, i: int) -> str:
        return self.attn_kinds[i] if self.attn_kinds else "global"

    def heads(self, kind: str) -> int:
        """Query heads of a layer of this attention kind."""
        return dict(self.heads_by_kind).get(kind, self.n_heads)

    def head_counts(self) -> set[int]:
        """Every query-head count some layer of the model has."""
        return {self.heads(k) for k in self.attn_kinds or ("global",)}

    def rope(self, kind: str) -> RopeSpec | None:
        """The kind's rotary settings where they are not the plain
        ``rotary(x, pos, rope_theta)``; None where they are."""
        spec = dict(self.rope_by_kind).get(kind)
        return None if spec is None or (
            spec.plain and spec.theta == self.rope_theta) else spec

    def stat_names(self) -> tuple[str, ...]:
        """The counters a step of this model returns beside its loss
        (``apply(return_stats=True)``), in the order ``lm`` stacks them."""
        if not self.moe_dropless:
            return ()
        return (moe_ops.STATS
                + (("score_sum_mean",) if self.moe_scoring == "sigmoid"
                   else ())
                + (("gate_mean",) if self.attn_gate else ())
                + (("shared_gate_mean",) if self.moe_shared_gate else ())
                + (("decay_mean", "beta_mean", "state_norm_max")
                   if self.has_linear else ()))

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
        if self.moe_scoring != "softmax":
            found.append(f"a router that scores by {self.moe_scoring} "
                         "(moe_scoring)")
        if self.moe_shared_ff:
            found.append("a shared expert beside the routed ones "
                         "(moe_shared_ff)")
        if self.n_dense_layers:
            found.append("leading dense layers before the routed ones "
                         "(n_dense_layers)")
        if self.heads_by_kind:
            found.append("a head count per attention kind (heads_by_kind: "
                         "the cache has one)")
        if any(self.rope(k) is not None for k, _ in self.rope_by_kind):
            found.append("rotary settings per attention kind (rope_by_kind: "
                         "partial or YaRN-scaled rotary)")
        if self.attn_gate:
            found.append("a gate on the attention output (attn_gate)")
        if self.attn_gate_form != "head":
            found.append("an element-wise gate on the attention output from "
                         "the second half of each head's query projection "
                         "(attn_gate_form 'element')")
        if self.qk_norm:
            found.append("QK-norm (qk_norm)")
        if self.norm_offset:
            found.append("norm scales stored as an offset (norm_offset)")
        if self.moe_shared_gate:
            found.append("a sigmoid gate on the shared expert "
                         "(moe_shared_gate)")
        if self.has_linear:
            found.append("linear-attention layers (attn_kinds 'linear': the "
                         "gated delta rule's recurrent state and the causal "
                         "convolution's; no per-slot state in decode)")
        return found


# attention kind -> (rotary on q and k, windowed); "linear" is the gated
# delta rule (ops/gated_delta.py), no softmax attention at all
ATTN_KINDS = {"global": (True, False), "global_nope": (False, False),
              "window": (True, True), "linear": (False, False)}


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
    d, dh, f = cfg.d_model, cfg.head_dim, cfg.ff
    kv = cfg.kv_heads

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                / math.sqrt(fan_in))

    def swiglu(width):
        return {"w_gate": dense(next(keys), (d, width), d),
                "w_up": dense(next(keys), (d, width), d),
                "w_down": dense(next(keys), (width, d), width)}

    def norm(width):    # a norm's scale: 1 as it is applied
        return jnp.full((width,), 1.0 - cfg.norm_offset, jnp.float32)

    keys = iter(jax.random.split(
        key, 2 + (7 + cfg.attn_gate + 3 * bool(cfg.moe_shared_ff)
                  + cfg.moe_shared_gate) * cfg.n_layers
        + 5 * cfg.attn_kinds.count("linear") + (not cfg.tie_embeddings)))
    params: dict = {
        "embed": jax.random.normal(next(keys), (cfg.vocab_size, d),
                                   jnp.float32) * 0.02,
        "final_norm": norm(d),
    }
    for i in range(cfg.n_layers):
        h = cfg.heads(cfg.attn_kind(i))
        if cfg.attn_kind(i) == "linear":
            attn = _linear_init(keys, cfg, dense)
        else:
            q_width = 2 * dh if cfg.attn_gate_form == "element" else dh
            attn = {
                "wq": dense(next(keys), (d, h, q_width), d),
                "wk": dense(next(keys), (d, kv, dh), d),
                "wv": dense(next(keys), (d, kv, dh), d),
                "wo": dense(next(keys), (h, dh, d), h * dh),
            }
            if cfg.attn_gate and cfg.attn_gate_form == "head":
                attn["wg"] = dense(next(keys), (d, h), d)
            if cfg.qk_norm:
                attn["q_norm"], attn["k_norm"] = norm(dh), norm(dh)
        layer = {"attn_norm": norm(d),
                 **_under_kind(cfg, i, attn),
                 "mlp_norm": norm(d)}
        if cfg.is_moe_layer(i):
            layer["moe"] = moe_ops.moe_init(
                next(keys), d, f, cfg.n_experts,
                held=cfg.moe_experts_held if cfg.moe_dropless else None)
            if cfg.moe_shared_ff:
                layer["shared"] = swiglu(cfg.moe_shared_ff)
                if cfg.moe_shared_gate:
                    layer["shared"]["w_sg"] = dense(next(keys), (d, 1), d)
        else:
            layer.update(swiglu(cfg.dense_ff))
        params[f"layer{i}"] = layer
    if not cfg.tie_embeddings:
        params["lm_head"] = jax.random.normal(
            next(keys), (cfg.vocab_size, d), jnp.float32) * 0.02
    return params


def _linear_widths(cfg: TransformerConfig) -> tuple[int, int]:
    """Channels of a linear layer's q (and of its k), and of its v (and of
    its output gate z)."""
    return (cfg.linear_k_heads * cfg.linear_k_dim,
            cfg.linear_v_heads * cfg.linear_v_dim)


def _linear_init(keys, cfg: TransformerConfig, dense) -> dict:
    """A linear-attention layer's leaves: ``w_qkvz`` (d, [q | k | v | z]),
    ``w_ba`` (d, [beta logits | decay inputs], one a value head), the
    convolution's taps over [q | k | v], ``A_log`` and ``dt_bias`` (one a
    value head: log U(1, 16) and 1), the gated norm's plain scale and
    ``w_out``."""
    d, (nk, nv), hv = cfg.d_model, _linear_widths(cfg), cfg.linear_v_heads
    return {
        "w_qkvz": dense(next(keys), (d, 2 * nk + 2 * nv), d),
        "w_ba": dense(next(keys), (d, 2 * hv), d),
        "conv": dense(next(keys), (cfg.linear_conv, 2 * nk + nv),
                      cfg.linear_conv),
        "A_log": jnp.log(jax.random.uniform(next(keys), (hv,), jnp.float32,
                                            1.0, 16.0)),
        "dt_bias": jnp.ones((hv,), jnp.float32),
        "norm": jnp.ones((cfg.linear_v_dim,), jnp.float32),
        "w_out": dense(next(keys), (nv, d), nv),
    }


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
    swiglu = {"w_gate": P(None, tp_axis), "w_up": P(None, tp_axis),
              "w_down": P(tp_axis, None)}
    for i in range(cfg.n_layers):
        if cfg.attn_kind(i) == "linear":     # replicated: no tensor split
            attn = dict.fromkeys(("w_qkvz", "w_ba", "conv", "A_log",
                                  "dt_bias", "norm", "w_out"), P())
        else:
            attn = {
                "wq": P(None, tp_axis, None),
                "wk": P(None, tp_axis, None),
                "wv": P(None, tp_axis, None),
                "wo": P(tp_axis, None, None),
                **({"wg": P(None, tp_axis)}
                   if cfg.attn_gate and cfg.attn_gate_form == "head"
                   else {}),
                **({"q_norm": P(), "k_norm": P()} if cfg.qk_norm else {})}
        layer = {
            "attn_norm": P(),
            **_under_kind(cfg, i, attn),
            "mlp_norm": P(),
        }
        if cfg.is_moe_layer(i):
            if cfg.moe_shared_ff:
                layer["shared"] = dict(swiglu)
                if cfg.moe_shared_gate:
                    layer["shared"]["w_sg"] = P()
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
            layer.update(swiglu)
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


def rotary(x: Array, pos: Array, theta: float,
           spec: RopeSpec | None = None) -> Array:
    """Rotary position embedding over (B, H, S, D); ``pos`` is (S,) absolute
    positions (a sequence-parallel shard passes its global offsets), or
    (B, S) per-sequence positions (ragged decode — every sequence sits at
    its own depth).  ``spec``: an attention kind's own settings in place of
    the plain ones at ``theta`` (``RopeSpec``: the leading share of the head
    rotates at its frequencies, the rest passes through)."""
    d = x.shape[-1]
    if spec is None:
        freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)  # (D/2,)
    else:
        freqs = jnp.asarray(spec.inv_freq(d), jnp.float32)
        x, rest = x[..., :2 * len(freqs)], x[..., 2 * len(freqs):]
    angles = pos[..., None].astype(jnp.float32) * freqs  # (S|B,S, D/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if spec is not None and spec.attention_factor != 1.0:
        cos, sin = cos * spec.attention_factor, sin * spec.attention_factor
    if pos.ndim == 2:  # (B, S, D/2) -> broadcast over heads
        cos, sin = cos[:, None], sin[:, None]
    x1, x2 = x[..., ::2], x[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x1 * sin + x2 * cos
    out = jnp.stack([y1, y2], axis=-1).reshape(x.shape)
    if spec is not None and rest.shape[-1]:
        return jnp.concatenate([out.astype(x.dtype), rest], axis=-1)
    return out.astype(x.dtype)


def _softmax_attention(ap: PyTree, h: Array, *, cfg: TransformerConfig,
                       pos: Array, kind: str, attn_impl: str,
                       seq_axis: str | None, seq_layout: str,
                       tp_axis: str | None, proj2d, save_attn: bool,
                       norm) -> tuple[Array, dict | None]:
    """A softmax-attention layer's mixer on the normed input ``h`` (B, S, D)
    up to and including the output projection (``block`` adds the tensor
    reduction and the residual); ``proj2d``: the int8 projection, or None.
    Returns the result and the gate's counter (None without a gate)."""
    b, s, d = h.shape
    dh = cfg.head_dim
    rope, windowed = ATTN_KINDS[kind]
    window = cfg.attn_window if windowed else None
    if proj2d is not None:
        hf = h.reshape(b * s, d)

        def head_proj(w):
            heads, width = w.shape[1], w.shape[2]
            out = proj2d(hf, w.reshape(d, heads * width).astype(h.dtype))
            return out.reshape(b, s, heads, width).transpose(0, 2, 1, 3)

        q, k, v = (head_proj(ap["wq"]), head_proj(ap["wk"]),
                   head_proj(ap["wv"]))
    else:
        q = jnp.einsum("bsd,dhk->bhsk", h, ap["wq"].astype(h.dtype))
        k = jnp.einsum("bsd,dhk->bhsk", h, ap["wk"].astype(h.dtype))
        v = jnp.einsum("bsd,dhk->bhsk", h, ap["wv"].astype(h.dtype))
    element_gate = cfg.attn_gate and cfg.attn_gate_form == "element"
    if element_gate:     # each head's query and its gate, side by side
        q, q_gate = q[..., :dh], q[..., dh:]
    if cfg.qk_norm:
        q, k = norm(q, ap["q_norm"]), norm(k, ap["k_norm"])
    if rope:
        q = rotary(q, pos, cfg.rope_theta, cfg.rope(kind))
        k = rotary(k, pos, cfg.rope_theta, cfg.rope(kind))
    if cfg.kv_heads != cfg.heads(kind):
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
    stats = None
    if element_gate:
        gate = jax.nn.sigmoid(q_gate.astype(jnp.float32))
        o = o * gate.astype(o.dtype)
    elif cfg.attn_gate:
        # one gate a head and token, read from the layer's normed input
        gate = jax.nn.sigmoid(jnp.einsum("bsd,dh->bhs", h,
                                         ap["wg"].astype(h.dtype)))
        o = o * gate[..., None].astype(o.dtype)
    if cfg.attn_gate:
        gate_mean = jnp.mean(gate.astype(jnp.float32))
        if tp_axis is not None:     # each tensor rank gates its own heads
            gate_mean = lax.pmean(gate_mean, tp_axis)
        stats = {"gate_mean": gate_mean}
    if proj2d is not None:
        of = o.transpose(0, 2, 1, 3).reshape(b * s, -1)
        return proj2d(of, ap["wo"].reshape(-1, d).astype(o.dtype)
                      ).reshape(b, s, d), stats
    return jnp.einsum("bhsk,hkd->bsd", o, ap["wo"].astype(o.dtype)), stats


def _l2norm(x: Array, eps: float = 1e-6) -> Array:
    return x * lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


def _gated_delta_mixer(ap: PyTree, h: Array, cfg: TransformerConfig
                       ) -> tuple[Array, dict]:
    """A linear-attention layer's mixer on the normed input ``h`` (B, S, D):
    ``[q, k, v, z] = h w_qkvz``, ``[b, a] = h w_ba``; q, k, v through the
    causal convolution and silu (float32); q and k l2-normed per key head
    (q then over sqrt(key dim)), each key head serving its consecutive value
    heads; ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``
    in float32; the gated delta rule (ops/gated_delta.py, its kernels); per
    value head ``rms(o) * norm * silu(z)``; then ``w_out``.  Returns the
    result and the layer's counters."""
    b, s, _ = h.shape
    (nk, nv), hv = _linear_widths(cfg), cfg.linear_v_heads
    dk, dv = cfg.linear_k_dim, cfg.linear_v_dim
    qkvz = h @ ap["w_qkvz"].astype(h.dtype)
    ba = (h @ ap["w_ba"].astype(h.dtype)).astype(jnp.float32)
    # the convolution in float32 (its taps' gradient sums over every token)
    c = jax.nn.silu(gd_ops.causal_conv(qkvz[..., :2 * nk + nv],
                                       ap["conv"].astype(jnp.float32))
                    ).astype(h.dtype)

    def heads(x, width):    # (B, S, n * width) -> (B, S, n, width)
        return x.reshape(b, s, -1, width)

    def l2(x, scale=1.0):   # in float32, handed on in the step's type
        return (_l2norm(x.astype(jnp.float32)) * scale).astype(h.dtype)

    q = l2(heads(c[..., :nk], dk), 1 / math.sqrt(dk))
    k = l2(heads(c[..., nk:2 * nk], dk))
    v = heads(c[..., 2 * nk:], dv)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(ap["A_log"]) * jax.nn.softplus(ba[..., hv:] + ap["dt_bias"])
    o, _, state_norm_max = gd_ops.gated_delta(q, k, v, g, beta)
    z = heads(qkvz[..., 2 * nk + nv:], dv).astype(jnp.float32)
    o = rms_norm(o, ap["norm"], cfg.norm_eps) * jax.nn.silu(z)
    out = o.reshape(b, s, nv).astype(h.dtype) @ ap["w_out"].astype(h.dtype)
    # counters ride the loss's aux output: no tangent (pmax has no rule)
    return out, lax.stop_gradient({"decay_mean": jnp.mean(jnp.exp(g)),
                                   "beta_mean": jnp.mean(beta),
                                   "state_norm_max": state_norm_max})


def _swiglu(p: PyTree, h: Array) -> Array:
    """``down(silu(gate(h)) * up(h))`` over the leaves w_gate, w_up, w_down
    of ``p``."""
    gate = jax.nn.silu(h @ p["w_gate"].astype(h.dtype))
    up = h @ p["w_up"].astype(h.dtype)
    return (gate * up) @ p["w_down"].astype(h.dtype)


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
    windowed or not; ``cfg.attn_kind(i)``; its head count and rotary
    settings are ``cfg.heads(kind)`` and ``cfg.rope(kind)``); "linear" is
    the gated delta rule's mixer in place of softmax attention.
    ``with_stats``: also return the layer's counters as a third result: the
    dropless routed layer's (``ops/moe.py``), with ``cfg.attn_gate``
    ``gate_mean``, with ``cfg.moe_shared_gate`` ``shared_gate_mean``, and a
    linear layer's ``decay_mean``, ``beta_mean`` and ``state_norm_max``;
    None for a layer with none of them.
    """
    b, s, d = x.shape
    q8 = matmul_dtype == "int8"
    if ATTN_KINDS[kind][1] and seq_axis is not None:
        raise NotImplementedError(
            "windowed attention layers under sequence parallelism: ring "
            "attention has no window")
    ap = lp["attn_" + kind] if cfg.attn_kinds else lp   # attention leaves

    def proj2d(h2: Array, w2: Array) -> Array:
        from ..ops import quantized as qz
        return qz.quantized_matmul(h2, w2)

    def norm(y: Array, w: Array) -> Array:
        return rms_norm(y, w + cfg.norm_offset if cfg.norm_offset else w,
                        cfg.norm_eps)

    # -- attention ---------------------------------------------------------
    h = norm(x, lp["attn_norm"])
    if kind == "linear":
        if seq_axis is not None or q8 or (tp_axis is not None
                                          and lax.axis_size(tp_axis) > 1):
            raise NotImplementedError(
                "linear-attention layers under sequence or tensor "
                "parallelism or int8 matmuls: the gated delta rule's state "
                "has no exchange, its projections no quantized path")
        # checkpointed: its backward recomputes the mixer's forward rather
        # than keep its float32 intermediates beside the routed layer's
        o, stats = jax.checkpoint(_gated_delta_mixer, static_argnums=(2,),
                                  prevent_cse=False)(ap, h, cfg)
    else:
        o, stats = _softmax_attention(
            ap, h, cfg=cfg, pos=pos, kind=kind, attn_impl=attn_impl,
            seq_axis=seq_axis, seq_layout=seq_layout, tp_axis=tp_axis,
            proj2d=proj2d if q8 else None, save_attn=save_attn, norm=norm)
    if tp_axis is not None:
        o = lax.psum(o, tp_axis)  # Megatron row-parallel reduction 1
    x = x + o
    # -- MLP ---------------------------------------------------------------
    h_attn = h    # what a router placed before attention reads
    h = norm(x, lp["mlp_norm"])
    aux = jnp.zeros((), jnp.float32)
    if is_moe and cfg.moe_dropless:
        if ep_axis is not None or (tp_axis is not None
                                   and lax.axis_size(tp_axis) > 1):
            raise NotImplementedError(
                "the dropless routed layer over an expert or tensor axis: "
                "it has no exchange yet (ops/moe.moe_dropless_apply); run "
                "it with ep=1 and tp=1")
        router_in = (h_attn.reshape(b * s, d)
                     if cfg.moe_router_input == "attn_norm" else None)
        down, routed = moe_ops.moe_dropless_apply(
            lp["moe"], h.reshape(b * s, d), top_k=cfg.moe_top_k,
            first_expert=cfg.moe_first_expert, router_input=router_in,
            act=cfg.moe_act, scoring=cfg.moe_scoring,
            score_scale=cfg.moe_score_scale)
        stats = {**routed, **(stats or {})}
        down = down.reshape(b, s, d)
        if cfg.moe_shared_ff:
            # every token passes through the shared expert: a plain SwiGLU
            # beside the routed call, outside whatever exchange wraps that
            shared = _swiglu(lp["shared"], h)
            if cfg.moe_shared_gate:     # one sigmoid gate a token
                sg = jax.nn.sigmoid((h @ lp["shared"]["w_sg"].astype(
                    h.dtype)).astype(jnp.float32))
                shared = shared * sg.astype(shared.dtype)
                stats["shared_gate_mean"] = lax.stop_gradient(jnp.mean(sg))
            down = down + shared
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
        down = _swiglu(lp, h)
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
    stats)``, ``stats`` the layers' counters merged over the layers
    (``ops/moe.merge_stats``; ``cfg.stat_names()``), None for a model
    without them.
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
            if stats is not None:   # a plain leading dense layer has none
                layer_stats.append(stats)
        else:
            x, aux = run(params[f"layer{i}"], x, pos)
        aux_total = aux_total + aux

    if boundary is not None:
        params = boundary(cfg.n_layers + 1, params)
    x = rms_norm(x, params["final_norm"] + cfg.norm_offset
                 if cfg.norm_offset else params["final_norm"], cfg.norm_eps)
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
