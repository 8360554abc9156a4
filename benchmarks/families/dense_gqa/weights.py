"""The parameter tree the program's dense model takes (``embed``,
``final_norm``, ``layer<i>`` with ``wq`` (d, h, dh) ...): that layout is the
program's interface.  The values are the benchmark's (``weights._make``)."""


def leaf_shapes(cfg: dict) -> dict:
    """name -> (shape, fan_in or None for a norm scale)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    out = {"embed": ((cfg["vocab_size"], d), "embed"),
           "final_norm": ((d,), None)}
    for i in range(cfg["num_hidden_layers"]):
        out[f"layer{i}"] = {
            "attn_norm": ((d,), None), "mlp_norm": ((d,), None),
            "wq": ((d, h, dh), d), "wk": ((d, kv, dh), d),
            "wv": ((d, kv, dh), d), "wo": ((h, dh, d), h * dh),
            "w_gate": ((d, f), d), "w_up": ((d, f), d),
            "w_down": ((f, d), f),
        }
    return out


tiny = {"hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
        "vocab_size": 512}
