"""The plain equations of a decoder whose every layer is routed and whose
layers attend in one of two ways (SmallThinker's published block, as
``benchmarks/configs/smallthinker-21b-a3b.json`` reads it and lists under
``assumed`` what the public config does not spell out):

    n1 = n(x);  r = n1 R                           R: d x E, all E published experts; float32, outside ``mm``
    (l_1..l_k), (e_1..e_k) = top-k(r);  g = softmax(l_1..l_k)
    h = x + Wo . attn_kind(Wq n1, Wk n1, Wv n1)
          global_nope: causal, no positional encoding
          window:      rotary(base rope_theta), causal and key > query - sliding_window_size
    n2 = n(h);  out = h + sum_i g_i . Wdown_{e_i} (relu(Wgate_{e_i} n2) * Wup_{e_i} n2)      over the picks whose expert is held here
    logits = n(out_L) Whead                        Whead a table of its own

with RMSNorm ``n``, K/V heads repeated to the query heads' count, and the
router reading the attention's normed input (a router placed before
attention).  This chip's share holds the experts ``moe_first_expert ..
moe_first_expert + moe_num_primary_experts - 1`` of the router's
``moe_router_width``: picks of experts held elsewhere add nothing here, and
their weight still takes its part of the softmax.  Experts are a plain loop
over the experts held, each masked to the tokens that picked it.

A layer's kind is read from its own leaves (``attn_window`` or
``attn_global_nope``): the shared driver hands ``layer`` no index, and of the
configuration only its top-level numbers.  Attention goes in blocks of query
rows, each recomputed in the backward, so an 8,192-token row fits beside the
float32 parameters, gradients and moments on one 16 GB chip.  Imports
nothing of the program; the shared ``reference`` drives it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import families
from reference import mm, scatter_rows

_dense = families.load("dense_gqa").reference
rms_norm, rope = _dense.rms_norm, _dense.rope
layer_keys, embed, final = _dense.layer_keys, _dense.embed, _dense.final

Q_BLOCK = 512       # query rows per attention block


def attention(q, k, v, window=None):
    """Causal softmax attention, q/k/v (S, H, D); with ``window`` a query
    sees the ``window`` newest keys, its own included.  Blocks of query
    rows, each over the keys it can see and recomputed in the backward."""
    s, _, d = q.shape

    @jax.checkpoint
    def block(qb, kb, vb, q0, k0):
        sc = jnp.einsum("qhd,khd->hqk", qb, kb) / math.sqrt(d)
        qi = q0 + jnp.arange(qb.shape[0])[:, None]
        kj = k0 + jnp.arange(kb.shape[0])[None, :]
        seen = qi >= kj
        if window is not None:
            seen &= kj > qi - window
        p = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, vb)

    outs = []
    for lo in range(0, s, Q_BLOCK):
        hi = min(lo + Q_BLOCK, s)
        k0 = 0 if window is None else max(0, lo - window + 1)
        outs.append(block(q[lo:hi], k[k0:hi], v[k0:hi], lo, k0))
    return jnp.concatenate(outs, 0)


def layer(lp, x, pos, cfg, quant):
    d = x.shape[-1]
    h, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    windowed = "attn_window" in lp
    ap = lp["attn_window"] if windowed else lp["attn_global_nope"]
    n1 = rms_norm(x, lp["attn_norm"], cfg["rms_norm_eps"])
    q = mm(n1, ap["wq"].reshape(d, h * dh), quant).reshape(-1, h, dh)
    k = mm(n1, ap["wk"].reshape(d, kv * dh), quant).reshape(-1, kv, dh)
    v = mm(n1, ap["wv"].reshape(d, kv * dh), quant).reshape(-1, kv, dh)
    if windowed:
        q, k = rope(q, pos, cfg["rope_theta"]), rope(k, pos, cfg["rope_theta"])
    k, v = jnp.repeat(k, h // kv, 1), jnp.repeat(v, h // kv, 1)
    o = attention(q, k, v, cfg["sliding_window_size"] if windowed else None)
    x = x + mm(o.reshape(-1, h * dh), ap["wo"].reshape(h * dh, d), quant)

    moe = lp["moe"]
    top, idx = jax.lax.top_k(n1 @ moe["router"],
                             cfg["moe_num_active_primary_experts"])
    gates = jax.nn.softmax(top, axis=-1)
    n2 = rms_norm(x, lp["mlp_norm"], cfg["rms_norm_eps"])

    @jax.checkpoint
    def expert(w_gate, w_up, w_down, g):
        up = jax.nn.relu(mm(n2, w_gate, quant)) * mm(n2, w_up, quant)
        return g * mm(up, w_down, quant)

    for e in range(cfg["moe_num_primary_experts"]):
        g = jnp.sum(jnp.where(idx == cfg["moe_first_expert"] + e, gates, 0.0),
                    -1, keepdims=True)
        x = x + expert(moe["w_gate"][e], moe["w_up"][e], moe["w_down"][e], g)
    return x


def embed_backward(grads, params, tokens, dx):
    # untied: the lookup's table gets the lookup's cotangent alone
    grads["embed"] = scatter_rows(jnp.zeros_like(params["embed"]), tokens, dx)


def head_params(params):
    return {"final_norm": params["final_norm"], "lm_head": params["lm_head"]}


def project(hp, hs, cfg, quant):
    return mm(hs, hp["lm_head"].T, quant)
