"""The parameter tree the program's model takes for a decoder with layers of
two attention kinds, every layer routed and a head of its own: a layer keeps
its attention leaves under ``attn_global_nope`` or ``attn_window`` (the tree
itself says which kind a layer is), its router over all
``moe_router_width`` published experts and the stacks of the
``moe_num_primary_experts`` experts held here under ``moe``.  The values are
the benchmark's (``weights._make``).

Only the configuration's top-level numbers reach here (``reference.
cfg_items``), so the layer pattern arrives as ``global_attention_every``:
layer i is the NoPE-global kind where i is a multiple of it, else windowed
(the published ``sliding_window_layout`` / ``rope_layout``, which
``program.model_config`` holds this number to)."""


def leaf_shapes(cfg: dict) -> dict:
    """name -> (shape, fan_in; None for a norm scale, "embed" for a table)."""
    d, f = cfg["hidden_size"], cfg["moe_ffn_hidden_size"]
    h, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    held, width = cfg["moe_num_primary_experts"], cfg["moe_router_width"]
    rows = (cfg["vocab_size"], d)
    # The lookup table's rows are of unit scale (fan-in 1), the head's the
    # usual 0.02.  With rows of 0.02 the first layers' outputs, which are
    # alike for every token, drown the token's own row, the routers of the
    # later layers then send most tokens to the same few experts (largest
    # expert 5 to 14 times the mean), and how many picks fall on the held
    # experts, and with it the step's time, follows the seed by 3%.
    out = {"embed": (rows, 1), "lm_head": (rows, "embed"),
           "final_norm": ((d,), None)}
    for i in range(cfg["num_hidden_layers"]):
        kind = ("attn_global_nope" if i % cfg["global_attention_every"] == 0
                else "attn_window")
        out[f"layer{i}"] = {
            "attn_norm": ((d,), None), "mlp_norm": ((d,), None),
            kind: {"wq": ((d, h, dh), d), "wk": ((d, kv, dh), d),
                   "wv": ((d, kv, dh), d), "wo": ((h, dh, d), h * dh)},
            "moe": {"router": ((d, width), d),
                    "w_gate": ((held, d, f), d), "w_up": ((held, d, f), d),
                    "w_down": ((held, f, d), f)},
        }
    return out


# one whole period, 4 of 16 experts held, a window shorter than the
# rehearsal's 128-token rows so that the band is live
tiny = {"hidden_size": 64, "num_hidden_layers": 4, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "moe_ffn_hidden_size": 32,
        "moe_num_primary_experts": 4, "moe_router_width": 16,
        "sliding_window_size": 48, "vocab_size": 512}
