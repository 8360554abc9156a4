"""The program's top-2 routed block, as a family that lives under the tests:
the dense family's attention, and on every ``moe_every``-th layer a routed
SwiGLU in place of the dense one,

    p = softmax(n2(h) R);  (g1, g2), (e1, e2) = top2(p);  g = g / (g1 + g2)
    out = h + g1 expert_e1(n2(h)) + g2 expert_e2(n2(h))

with the router in float32 and no capacity limit (what serving computes).
"""

import jax
import jax.numpy as jnp

import families
from reference import mm

dense = families.load("dense_gqa").reference
layer_keys, embed, embed_backward = (dense.layer_keys, dense.embed,
                                     dense.embed_backward)
head_params, final, project = dense.head_params, dense.final, dense.project


def layer(lp, x, pos, cfg, quant):
    if "moe" not in lp:
        return dense.layer(lp, x, pos, cfg, quant)
    x = dense.attention_block(lp, x, pos, cfg, quant)
    n, m = dense.rms_norm(x, lp["mlp_norm"], cfg["rms_norm_eps"]), lp["moe"]
    gates, idx = jax.lax.top_k(jax.nn.softmax(n @ m["router"], -1), 2)
    gates = gates / gates.sum(-1, keepdims=True)
    for e in range(cfg["n_experts"]):
        g = jnp.sum(jnp.where(idx == e, gates, 0.0), -1, keepdims=True)
        up = jax.nn.silu(mm(n, m["w_gate"][e], quant)) * mm(n, m["w_up"][e],
                                                             quant)
        x = x + g * mm(up, m["w_down"][e], quant)
    return x
