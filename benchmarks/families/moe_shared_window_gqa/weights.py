"""The parameter tree the program's model takes for a decoder with a leading
dense layer, routed layers beside a shared expert, and attention of two
kinds and two head counts: a layer keeps its attention leaves (``wq``, ``wk``,
``wv``, ``wo`` and the gate's ``wg``) under ``attn_global`` or ``attn_window``,
with as many query heads as its kind has; a dense layer its MLP flat, a
sparse one its router over all ``moe_router_width`` published experts and
the stacks of the ``num_experts`` held here under ``moe``, and the shared
expert under ``shared``.  The values are the benchmark's (``weights._make``).

Only the configuration's top-level numbers reach here, so the published lists
arrive as numbers (``program.model_config`` holds each to its list): layer i
is global where i is a multiple of ``global_attention_every``, and dense
where i < ``leading_dense_layers``."""


def leaf_shapes(cfg: dict) -> dict:
    """name -> (shape, fan_in; None for a norm scale, "embed" for a table)."""
    d, kv, dh = cfg["hidden_size"], cfg["num_key_value_heads"], cfg["head_dim"]
    held, width = cfg["num_experts"], cfg["moe_router_width"]

    def swiglu(f):
        return {"w_gate": ((d, f), d), "w_up": ((d, f), d),
                "w_down": ((f, d), f)}

    rows = (cfg["vocab_size"], d)
    # lookup rows of unit scale, the head's 0.02, as ``moe_window_gqa`` has
    # them and for its reason: random routers stay near even
    out = {"embed": (rows, 1), "lm_head": (rows, "embed"),
           "final_norm": ((d,), None)}
    for i in range(cfg["num_hidden_layers"]):
        kind = ("global" if i % cfg["global_attention_every"] == 0
                else "window")
        h = cfg["heads_" + kind]
        layer = {
            "attn_norm": ((d,), None), "mlp_norm": ((d,), None),
            "attn_" + kind: {
                "wq": ((d, h, dh), d), "wk": ((d, kv, dh), d),
                "wv": ((d, kv, dh), d), "wo": ((h, dh, d), h * dh),
                "wg": ((d, h), d)}}
        if i < cfg["leading_dense_layers"]:
            layer.update(swiglu(cfg["intermediate_size"]))
        else:
            f = cfg["moe_intermediate_size"]
            layer["moe"] = {"router": ((d, width), d),
                            "w_gate": ((held, d, f), d),
                            "w_up": ((held, d, f), d),
                            "w_down": ((held, f, d), f)}
            layer["shared"] = swiglu(cfg["shared_expert_intermediate_size"])
        out[f"layer{i}"] = layer
    return out


# the dense layer and one whole period after it, 4 of 16 experts held, two
# head counts (the published list shrunk with them), half of each global
# head rotated, a window shorter than the rehearsal's 128-token rows so that
# the band is live; YaRN's numbers stay the published ones (its ramp then
# runs over pairs 1 to 4 of the 8 rotated)
tiny = {"hidden_size": 64, "num_hidden_layers": 5, "num_attention_heads": 4,
        "heads_global": 4, "heads_window": 6,
        "num_attention_heads_per_layer": [4, 6, 6, 6, 4],
        "num_key_value_heads": 2, "head_dim": 32, "rotary_dims_global": 16,
        "intermediate_size": 128, "moe_intermediate_size": 32,
        "shared_expert_intermediate_size": 32, "num_experts": 4,
        "moe_router_width": 16, "num_experts_per_tok": 4,
        "sliding_window": 48, "vocab_size": 512}
