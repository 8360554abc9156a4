"""The plain equations of a decoder whose layers are Gated DeltaNet linear
attention in three of every four and gated softmax attention in the fourth,
each followed by routed experts beside a gated shared expert
(Qwen3-Next-80B-A3B's published block, as
``benchmarks/configs/qwen3-next-80b-a3b.json`` reads it and lists under
``assumed`` what the public config does not spell out):

    n(x) = x / sqrt(mean(x^2) + eps) (1 + w)            the decoder's norm: zero-centred scale w
    linear:  [q, k, v, z] = W_qkvz n1;  [b, a] = W_ba n1
             c = silu(causalconv(q, k, v))             depthwise, ``linear_conv_kernel_dim`` taps, zeros before 0
             q, k = l2(q), l2(k) per key head; q = q / sqrt(key dim); each key head serves its
                    consecutive value heads
             beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)       one a value head and token
             S_t = e^{g_t} S_{t-1} + k_t (beta_t (v_t - e^{g_t} S_{t-1}^T k_t))^T,  S_0 = 0
             o_t = S_t^T q_t
             h = x + W_out (o / sqrt(mean(o^2) + eps) w_n silu(z))  per value head, w_n a plain scale
    full:    [q | gate] = W_q n1 (each head's query and its gate side by side); k, v = W_k n1, W_v n1
             q, k = n_q(q), n_k(k) over each head's dimensions (zero-centred)
             rotary on the first head_dim x partial_rotary_factor dimensions, base rope_theta
             h = x + W_o (softmax(q k^T / sqrt(head_dim), causal) v * sigmoid(gate))    element-wise
    n2 = n(h);  p = softmax(n2 R) over all E published experts (float32, outside ``mm``)
    (p_1..p_k), (e_1..e_k) = top-k(p);  w_i = p_i / sum_j p_j
    out = h + sigmoid(n2 w_sg) shared(n2) + sum_i w_i expert_{e_i}(n2)   over the picks held here
    logits = n(out_L) W_head

with every expert and the shared one ``Wdown (silu(Wgate x) * Wup x)``.  This
chip's share holds the experts ``moe_first_expert .. moe_first_expert +
num_experts - 1`` of the router's ``moe_router_width``; the shared expert
and its gate are whole on every chip.

**The delta rule goes token by token**, as the recurrence above, not in the
chunked form the program runs: an independent check of the chunk algebra.
So that a layer's backward fits, the positions go in segments of
``SEGMENT``, each under ``jax.checkpoint``: the backward keeps one state a
segment (128 of 32 x 128 x 128 float32 at 8,192 positions: 268 MB) and one
segment's states at a time.  A layer tells its kind from its own leaves
(``attn_linear`` or ``attn_global``).  Attention, the held experts and the
head are ``moe_shared_window_gqa``'s.  Imports nothing of the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import families
from reference import mm

_shared = families.load("moe_shared_window_gqa").reference
_dense = families.load("dense_gqa").reference
layer_keys, embed, rope = _dense.layer_keys, _dense.embed, _dense.rope
rms_norm = _dense.rms_norm      # the plain-scale norm (the gated norm's)
embed_backward, head_params = _shared.embed_backward, _shared.head_params
project, attention, swiglu = _shared.project, _shared.attention, _shared.swiglu

SEGMENT = 64        # positions of the delta rule between two kept states


def norm(x, w, eps):
    """The decoder's norm: its stored scale ``w`` applied as ``1 + w``."""
    return rms_norm(x, 1.0 + w, eps)


def final(hp, x, cfg):
    return norm(x, hp["final_norm"], cfg["rms_norm_eps"])


def l2(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def causal_conv(x, taps):
    """x (S, C), taps (width, C): position t reads t - width + 1 .. t."""
    width, s = taps.shape[0], x.shape[0]
    padded = jnp.pad(x, ((width - 1, 0), (0, 0)))
    return sum(taps[j] * padded[j:j + s] for j in range(width))


def delta_rule(q, k, v, g, beta):
    """q, k (S, H, Dk), v (S, H, Dv), g and beta (S, H) -> o (S, H, Dv):
    the recurrence one position after another, in checkpointed segments."""
    s, h, dk = k.shape
    dv = v.shape[-1]
    pad = -s % SEGMENT
    xs = [jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
          for a in (q, k, v, g, beta)]      # g = beta = 0: nothing changes
    xs = [a.reshape((-1, SEGMENT) + a.shape[1:]) for a in xs]

    def token(state, x):
        qt, kt, vt, gt, bt = x
        state = state * jnp.exp(gt)[:, None, None]
        u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", state, kt))
        state = state + kt[:, :, None] * u[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, qt)

    @jax.checkpoint
    def segment(state, x):
        return jax.lax.scan(token, state, x)

    _, o = jax.lax.scan(segment, jnp.zeros((h, dk, dv), jnp.float32),
                        tuple(xs))
    return o.reshape(-1, h, dv)[:s]


def linear_mixer(ap, n1, cfg, quant):
    s = n1.shape[0]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    nk, nv = hk * dk, hv * dv
    qkvz = mm(n1, ap["w_qkvz"], quant)
    ba = mm(n1, ap["w_ba"], quant)
    c = jax.nn.silu(causal_conv(qkvz[:, :2 * nk + nv], ap["conv"]))
    q = l2(c[:, :nk].reshape(s, hk, dk)) / math.sqrt(dk)
    k = l2(c[:, nk:2 * nk].reshape(s, hk, dk))
    q, k = jnp.repeat(q, hv // hk, 1), jnp.repeat(k, hv // hk, 1)
    v = c[:, 2 * nk:].reshape(s, hv, dv)
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(ap["A_log"]) * jax.nn.softplus(ba[:, hv:] + ap["dt_bias"])
    o = delta_rule(q, k, v, g, beta)
    z = qkvz[:, 2 * nk + nv:].reshape(s, hv, dv)
    o = rms_norm(o, ap["norm"], cfg["rms_norm_eps"]) * jax.nn.silu(z)
    return mm(o.reshape(s, nv), ap["w_out"], quant)


def rope_partial(x, pos, cfg):
    """The first head_dim x partial_rotary_factor dimensions of each head
    rotated (interleaved pairs, frequencies over the rotated dimensions),
    the rest as they are."""
    r = int(x.shape[-1] * cfg["partial_rotary_factor"])
    return jnp.concatenate([rope(x[..., :r], pos, cfg["rope_theta"]),
                            x[..., r:]], -1)


def gated_attention(ap, n1, pos, cfg, quant):
    d, eps = n1.shape[-1], cfg["rms_norm_eps"]
    h, kv, dh = ap["wq"].shape[1], ap["wk"].shape[1], ap["wk"].shape[2]
    qg = mm(n1, ap["wq"].reshape(d, h * 2 * dh), quant).reshape(-1, h, 2 * dh)
    q, gate = qg[..., :dh], qg[..., dh:]
    k = mm(n1, ap["wk"].reshape(d, kv * dh), quant).reshape(-1, kv, dh)
    v = mm(n1, ap["wv"].reshape(d, kv * dh), quant).reshape(-1, kv, dh)
    q, k = norm(q, ap["q_norm"], eps), norm(k, ap["k_norm"], eps)
    q, k = rope_partial(q, pos, cfg), rope_partial(k, pos, cfg)
    o = attention(q, k, v) * jax.nn.sigmoid(gate)
    return mm(o.reshape(-1, h * dh), ap["wo"].reshape(h * dh, d), quant)


def sparse(lp, n2, cfg, quant):
    """The gated shared expert and the held experts' part of the routed
    layer, for rows ``n2``."""
    moe = lp["moe"]
    top, idx = jax.lax.top_k(jax.nn.softmax(n2 @ moe["router"], -1),
                             cfg["num_experts_per_tok"])
    weights = top / jnp.sum(top, -1, keepdims=True)

    @jax.checkpoint
    def expert(stacks, w):
        return w * swiglu(stacks, n2, quant)

    def add_expert(out, held):
        e, stacks = held
        w = jnp.sum(jnp.where(idx == cfg["moe_first_expert"] + e, weights,
                              0.0), -1, keepdims=True)
        return out + expert(stacks, w), None

    shared = (jax.nn.sigmoid(n2 @ lp["shared"]["w_sg"])
              * swiglu(lp["shared"], n2, quant))
    stacks = {k: moe[k] for k in ("w_gate", "w_up", "w_down")}
    out, _ = jax.lax.scan(add_expert, shared,
                          (jnp.arange(cfg["num_experts"]), stacks))
    return out


def layer(lp, x, pos, cfg, quant):
    eps = cfg["rms_norm_eps"]
    n1 = norm(x, lp["attn_norm"], eps)
    if "attn_linear" in lp:
        x = x + linear_mixer(lp["attn_linear"], n1, cfg, quant)
    else:
        x = x + gated_attention(lp["attn_global"], n1, pos, cfg, quant)
    return x + sparse(lp, norm(x, lp["mlp_norm"], eps), cfg, quant)
