"""The plain equations of a decoder with a leading dense layer, routed layers
beside a shared expert, and attention of two kinds and two head counts
(Laguna-XS.2's published block, as ``benchmarks/configs/laguna-xs.2.json``
reads it and lists under ``assumed`` what the public config does not spell
out):

    n1 = n(x);  q, k, v = Wq n1, Wk n1, Wv n1        heads: as many as Wq holds
          global: rotary on the first ``rotary_dims_global`` dimensions of each head, the rest passing
                  through; frequencies scaled as YaRN does, cos and sin times ``yarn_attention_factor``;
                  causal over every earlier key
          window: rotary over the whole head at base ``rope_theta_window``, unscaled; causal and
                  key > query - sliding_window
    g = sigmoid(Wg n1)                               one gate a head and token
    h = x + Wo . concat_a(g_a . attn_a)
    n2 = n(h)
    dense layer:   out = h + Wdown (silu(Wgate n2) * Wup n2)
    sparse layer:  s = sigmoid(n2 R)                 R: d x E, all E published experts; float32, outside ``mm``
                   (s_1..s_k), (e_1..e_k) = top-k(s);  w_i = moe_routed_scaling_factor . s_i / sum_j s_j
                   out = h + shared(n2) + sum_i w_i . expert_{e_i}(n2)      over the picks whose expert is held here
    logits = n(out_L) Whead                          Whead a table of its own

with RMSNorm ``n``, each K/V head serving its group of query heads, every expert
and the shared one ``Wdown (silu(Wgate x) * Wup x)``.  This chip's share holds
the experts ``moe_first_expert .. moe_first_expert + num_experts - 1`` of the
router's ``moe_router_width``: picks of experts held elsewhere add nothing
here and their scores still take their part of the sum.  The shared expert is
whole on every chip.

The shared driver hands ``layer`` no index and only the configuration's
top-level numbers, so a layer tells what it is from its own leaves:
``attn_window`` or ``attn_global``, the head count from ``wq``, sparse where
it has ``moe``.  Attention goes in blocks of query rows, each recomputed in
the backward, over K/V at their own head count; the held experts go one
after another in a scan: a layer's backward then fits beside 11 GB of
float32 state on one chip.  Imports nothing of the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

import families
from reference import mm

_dense = families.load("dense_gqa").reference
_windowed = families.load("moe_window_gqa").reference
rms_norm, rope = _dense.rms_norm, _dense.rope
layer_keys, embed, final = _dense.layer_keys, _dense.embed, _dense.final
embed_backward, head_params = _windowed.embed_backward, _windowed.head_params
project = _windowed.project

Q_BLOCK = 256       # query rows per attention block


def attention(q, k, v, window=None):
    """Causal softmax attention, q (S, H, D) over k/v (S, KV, D): K/V head g
    serves the query heads g H/KV .. (g + 1) H/KV - 1, as repeating K/V to
    the query heads' count would; with ``window`` a query sees the
    ``window`` newest keys, its own included.  Blocks of ``Q_BLOCK`` query
    rows, one after another in a scan and each recomputed in the backward:
    a block of a windowed layer over the ``Q_BLOCK + window - 1`` keys its
    rows can see (the keys padded in front, so every block's slice is as
    long), a block of a global layer over every key, masked.  What the
    backward keeps is q, k and v once, and one block's scores at a time (at
    64 query heads and float32 a whole row's would be gigabytes beside the
    float32 state); one block body keeps the compile short."""
    s, h, d = q.shape
    kv = k.shape[1]
    rows = min(Q_BLOCK, s)
    if s % rows:
        raise ValueError(f"{s} rows are no whole blocks of {rows}")
    front = 0 if window is None else window - 1     # padding before key 0
    span = s if window is None else rows + front
    k, v = (jnp.pad(a, ((front, 0), (0, 0), (0, 0))) for a in (k, v))

    @jax.checkpoint
    def block(qb, lo):
        k0 = 0 if window is None else lo            # in padded rows
        kb = jax.lax.dynamic_slice_in_dim(k, k0, span)
        vb = jax.lax.dynamic_slice_in_dim(v, k0, span)
        sc = jnp.einsum("qgrd,kgd->grqk", qb, kb) / math.sqrt(d)
        qi = lo + jnp.arange(rows)[:, None]
        kj = k0 - front + jnp.arange(span)[None, :]
        seen = (qi >= kj) & (kj >= 0)
        if window is not None:
            seen &= kj > qi - window
        p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", p, vb)

    _, out = jax.lax.scan(
        lambda _, x: (None, block(*x)), None,
        (q.reshape(s // rows, rows, kv, h // kv, d),
         jnp.arange(0, s, rows)))
    return out.reshape(s, h, d)


def yarn_inv_freq(cfg) -> np.ndarray:
    """Frequencies of the global layers' rotated pairs: ``base^(-2j / r)``
    over the ``r`` rotated dimensions, then YaRN's blend: pairs that turn
    more than ``yarn_beta_fast`` times over the original length keep their
    frequency, those that turn fewer than ``yarn_beta_slow`` times have it
    divided by ``yarn_factor``, with a linear ramp between."""
    r, base = int(cfg["rotary_dims_global"]), cfg["rope_theta_global"]
    inv = base ** (-np.arange(0, r, 2, dtype=np.float64) / r)

    def pair_turning(times):
        return (r * math.log(cfg["yarn_original_positions"]
                             / (times * 2 * math.pi)) / (2 * math.log(base)))

    low = max(math.floor(pair_turning(cfg["yarn_beta_fast"])), 0)
    high = min(math.ceil(pair_turning(cfg["yarn_beta_slow"])), r - 1)
    ramp = np.clip((np.arange(r // 2) - low) / max(high - low, 0.001), 0, 1)
    return inv * (1 - ramp) + inv / cfg["yarn_factor"] * ramp


def rope_global(x, pos, cfg):
    """x (S, H, D): the first ``rotary_dims_global`` dimensions of each head
    rotated in interleaved pairs at YaRN's frequencies, cos and sin times
    the attention factor; the other dimensions as they are."""
    freqs = jnp.asarray(yarn_inv_freq(cfg), jnp.float32)
    r = 2 * freqs.shape[0]
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos = (jnp.cos(ang) * cfg["yarn_attention_factor"])[:, None]
    sin = (jnp.sin(ang) * cfg["yarn_attention_factor"])[:, None]
    x1, x2 = x[..., 0:r:2], x[..., 1:r:2]
    turned = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                       -1).reshape(x.shape[:-1] + (r,))
    return jnp.concatenate([turned, x[..., r:]], -1)


def swiglu(p, x, quant):
    return mm(jax.nn.silu(mm(x, p["w_gate"], quant)) * mm(x, p["w_up"], quant),
              p["w_down"], quant)


def layer(lp, x, pos, cfg, quant):
    d = x.shape[-1]
    windowed = "attn_window" in lp
    ap = lp["attn_window"] if windowed else lp["attn_global"]
    h, kv, dh = ap["wq"].shape[1], ap["wk"].shape[1], ap["wq"].shape[2]
    n1 = rms_norm(x, lp["attn_norm"], cfg["rms_norm_eps"])
    q = mm(n1, ap["wq"].reshape(d, h * dh), quant).reshape(-1, h, dh)
    k = mm(n1, ap["wk"].reshape(d, kv * dh), quant).reshape(-1, kv, dh)
    v = mm(n1, ap["wv"].reshape(d, kv * dh), quant).reshape(-1, kv, dh)
    if windowed:
        q, k = (rope(q, pos, cfg["rope_theta_window"]),
                rope(k, pos, cfg["rope_theta_window"]))
    else:
        q, k = rope_global(q, pos, cfg), rope_global(k, pos, cfg)
    o = attention(q, k, v, cfg["sliding_window"] if windowed else None)
    o = o * jax.nn.sigmoid(mm(n1, ap["wg"], quant))[:, :, None]
    x = x + mm(o.reshape(-1, h * dh), ap["wo"].reshape(h * dh, d), quant)

    n2 = rms_norm(x, lp["mlp_norm"], cfg["rms_norm_eps"])
    if "moe" not in lp:
        return x + swiglu(lp, n2, quant)
    moe = lp["moe"]
    top, idx = jax.lax.top_k(jax.nn.sigmoid(n2 @ moe["router"]),
                             cfg["num_experts_per_tok"])
    weights = (cfg["moe_routed_scaling_factor"] * top
               / jnp.sum(top, -1, keepdims=True))

    @jax.checkpoint
    def expert(stacks, w):
        return w * swiglu(stacks, n2, quant)

    def add_expert(out, held):
        e, stacks = held
        w = jnp.sum(jnp.where(idx == cfg["moe_first_expert"] + e, weights,
                              0.0), -1, keepdims=True)
        return out + expert(stacks, w), None

    # one held expert after another, each masked to the tokens that picked
    # it (a scan, so that the backward holds one expert's rows at a time)
    stacks = {k: moe[k] for k in ("w_gate", "w_up", "w_down")}
    x, _ = jax.lax.scan(add_expert, x + swiglu(lp["shared"], n2, quant),
                        (jnp.arange(cfg["num_experts"]), stacks))
    return x
