"""The plain equations of the dense tied-head decoder (ERNIE-4.5's published
block; every dense GQA configuration shares them):

    h   = x + Wo . softmax(causal(rope(Wq n1(x)) rope(Wk n1(x))^T / sqrt(dh))) Wv n1(x)
    out = h + Wdown (silu(Wgate n2(h)) * Wup n2(h))
    logits = n3(out_L) E^T          (tied table E, no biases anywhere)

with RMSNorm ``n(x) = x / sqrt(mean(x^2) + eps) * scale``, rotary over
interleaved pairs at base ``rope_theta``, and K/V heads repeated to the query
heads' count.  Attention goes in blocks of query rows, so a 4,096-token row
fits beside the float32 parameters, gradients and moments on one 16 GB chip.
Imports nothing of the program; the shared ``reference`` drives it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference import mm, scatter_rows

Q_BLOCK = 1024      # query rows per attention block


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """x (S, H, D) at positions pos (S,), interleaved pairs."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * freqs          # (S, D/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     -1).reshape(x.shape)


def attention(q, k, v):
    """Causal softmax attention, q (S, H, D), k/v (S, H, D), in blocks of
    query rows so the (H, S, S) scores never exist whole."""
    s, _, d = q.shape
    outs = []
    for lo in range(0, s, Q_BLOCK):
        hi = min(lo + Q_BLOCK, s)
        sc = jnp.einsum("qhd,khd->hqk", q[lo:hi], k[:hi]) / math.sqrt(d)
        mask = (jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :])
        sc = jnp.where(mask[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v[:hi]))
    return jnp.concatenate(outs, 0)


def attention_block(lp, x, pos, cfg, quant):
    """x + the attention sublayer (what a family with another MLP reuses)."""
    d = x.shape[-1]
    h, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    n = rms_norm(x, lp["attn_norm"], cfg["rms_norm_eps"])
    q = mm(n, lp["wq"].reshape(d, h * dh), quant).reshape(-1, h, dh)
    k = mm(n, lp["wk"].reshape(d, kv * dh), quant).reshape(-1, kv, dh)
    v = mm(n, lp["wv"].reshape(d, kv * dh), quant).reshape(-1, kv, dh)
    q, k = rope(q, pos, cfg["rope_theta"]), rope(k, pos, cfg["rope_theta"])
    k, v = jnp.repeat(k, h // kv, 1), jnp.repeat(v, h // kv, 1)
    o = attention(q, k, v).reshape(-1, h * dh)
    return x + mm(o, lp["wo"].reshape(h * dh, d), quant)


def layer(lp, x, pos, cfg, quant):
    x = attention_block(lp, x, pos, cfg, quant)
    n = rms_norm(x, lp["mlp_norm"], cfg["rms_norm_eps"])
    gate = jax.nn.silu(mm(n, lp["w_gate"], quant))
    return x + mm(gate * mm(n, lp["w_up"], quant), lp["w_down"], quant)


def layer_keys(cfg):
    return [f"layer{i}" for i in range(cfg["num_hidden_layers"])]


def embed(params, tokens):
    return params["embed"][tokens]


def embed_backward(grads, params, tokens, dx):
    # tied: the table's gradient already holds the head's part
    grads["embed"] = scatter_rows(grads["embed"], tokens, dx)


def head_params(params):
    return {"final_norm": params["final_norm"], "embed": params["embed"]}


def final(hp, x, cfg):
    return rms_norm(x, hp["final_norm"], cfg["rms_norm_eps"])


def project(hp, hs, cfg, quant):
    return mm(hs, hp["embed"].T, quant)
