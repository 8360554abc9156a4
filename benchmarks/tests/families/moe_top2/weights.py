"""The tree the program's model takes with ``n_experts`` set: a ``moe`` leaf
group in place of the dense MLP on every ``moe_every``-th layer."""

import families

_dense = families.load("dense_gqa").weights


def leaf_shapes(cfg: dict) -> dict:
    out = _dense.leaf_shapes(cfg)
    d, f, e = cfg["hidden_size"], cfg["intermediate_size"], cfg["n_experts"]
    every = cfg["moe_every"]
    for i in range(cfg["num_hidden_layers"]):
        if i % every == every - 1:
            lp = out[f"layer{i}"]
            for k in ("w_gate", "w_up", "w_down"):
                del lp[k]
            lp["moe"] = {"router": ((d, e), d), "w_gate": ((e, d, f), d),
                         "w_up": ((e, d, f), d), "w_down": ((e, f, d), f)}
    return out


tiny = {}   # the configuration beside this file is tiny as it stands
