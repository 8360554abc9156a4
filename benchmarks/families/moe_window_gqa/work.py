"""Operations and bytes that one chip's share of the routed, two-kind decoder
needs, from shapes alone (``cfg``: the configuration file's dict, in which
``moe_num_primary_experts`` counts the experts held here,
``moe_router_width`` all that the router scores, and ``vocab_size`` the slice
of the vocabulary held here).

Routing is counted as even: a token's ``moe_num_active_primary_experts``
picks fall on the experts held here in the proportion held / width, so a
step's share of expert work does not follow the seed's router.  Every
roofline and share of the peak divides one of these by a measured time, so
they count what the mathematics requires, never what an implementation does
(recomputation, a row buffer sized for the worst case, K/V repeated to the
query heads' count).
"""

from __future__ import annotations


def _attn_params(cfg: dict) -> int:
    d, h, kv, dh = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    return 2 * d * h * dh + 2 * d * kv * dh


def _expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_ffn_hidden_size"]


def _layers_by_kind(cfg: dict) -> tuple[int, int]:
    """(NoPE-global layers, windowed layers)."""
    n, every = cfg["num_hidden_layers"], cfg["global_attention_every"]
    n_global = -(-n // every)
    return n_global, n - n_global


def mean_keys(seq: int, window: int | None = None) -> float:
    """Keys a query sees, averaged over the ``seq`` positions of a row:
    causal, and with ``window`` at most that many (its own included)."""
    if window is None or window >= seq:
        return (seq + 1) / 2.0
    return (window * (window + 1) / 2.0 + (seq - window) * window) / seq


def _pairs(cfg: dict, seq: int) -> float:
    """Query-key pairs of one row summed over the layers, per query head."""
    n_global, n_window = _layers_by_kind(cfg)
    return seq * (n_global * mean_keys(seq)
                  + n_window * mean_keys(seq, cfg["sliding_window_size"]))


def held_share(cfg: dict) -> float:
    return cfg["moe_num_primary_experts"] / cfg["moe_router_width"]


def routed_rows(cfg: dict, tokens: float) -> dict:
    """Of ``tokens`` tokens through one layer: the picks the router makes,
    under even routing those that fall on the experts held here, and the
    rows of the program's buffer (every pick and a 512-row tile of padding
    for each held expert and one more: ``ops/moe.ROW_TILE``), by which the
    trace's reader knows the routed layer's operations."""
    picks = tokens * cfg["moe_num_active_primary_experts"]
    return {"picks": picks, "here": picks * held_share(cfg),
            "buffer": picks + (cfg["moe_num_primary_experts"] + 1) * 512}


def param_count(cfg: dict, active: bool = False) -> float:
    """Parameters held here (the lookup table and the head's own counted
    each), or with ``active`` those one token passes through here."""
    d = cfg["hidden_size"]
    experts = (routed_rows(cfg, 1)["here"] if active
               else cfg["moe_num_primary_experts"])
    layer = (2 * d + _attn_params(cfg) + d * cfg["moe_router_width"]
             + experts * _expert_params(cfg))
    tables = (1 if active else 2) * cfg["vocab_size"] * d
    return tables + d + cfg["num_hidden_layers"] * layer


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward FLOPs a training token requires of this share: 6
    per parameter it passes through (projections, the router, its picks'
    share of the held experts, the sliced head; the lookup is free) plus
    the attention products over the pairs it sees, 2 matmuls x 2 FLOPs x 3
    (fwd + bwd), global and windowed layers apart."""
    attn = 12.0 * _pairs(cfg, seq) / seq * (cfg["num_attention_heads"]
                                            * cfg["head_dim"])
    return 6.0 * param_count(cfg, active=True) + attn


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """K and V of one position over all layers (a windowed layer keeps
    them for its window only; no cache exists for this family yet)."""
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * cfg["head_dim"] * itemsize)


def decode_flops(cfg: dict, tokens: float, context_tokens: float) -> float:
    attn = (4.0 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * cfg["head_dim"])
    return 2.0 * param_count(cfg, active=True) * tokens + attn * context_tokens


def prompt_flops(cfg: dict, length: int) -> float:
    return (2.0 * param_count(cfg, active=True) * length
            + 4.0 * _pairs(cfg, length) * cfg["num_attention_heads"]
            * cfg["head_dim"])


def flash_attn_work(cfg: dict, rows: int, seq: int, itemsize: int = 2) -> dict:
    """Attention forward + backward over ``rows`` sequences of ``seq``, one
    NoPE-global layer and the windowed ones of each period: 6 matmuls x 2
    FLOPs over the pairs seen per head, and the bytes that must cross HBM
    once (as the dense family counts them; K/V at the kv heads' count)."""
    h, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    per_head = rows * seq * dh * itemsize
    return {"flops": 12.0 * _pairs(cfg, seq) * dh * h * rows,
            "bytes": 6.0 * per_head * (h + kv) * cfg["num_hidden_layers"]}


def grouped_ffn_work(cfg: dict, tokens: float, itemsize: int = 2) -> dict:
    """The held experts' gate, up and down products over a step of
    ``tokens`` tokens, all layers, forward + backward: 3 products x 3
    passes x 2 FLOPs x rows x d x f, and per pass each product's rows in
    and out and the held stacks once."""
    d, f = cfg["hidden_size"], cfg["moe_ffn_hidden_size"]
    rows = routed_rows(cfg, tokens)["here"]
    stacks = cfg["moe_num_primary_experts"] * _expert_params(cfg)
    return {"flops": cfg["num_hidden_layers"] * 18.0 * rows * d * f,
            "bytes": (cfg["num_hidden_layers"] * 3.0 * itemsize
                      * (stacks + 3.0 * rows * (d + f)))}


kernels = {"flash_attn": flash_attn_work, "grouped_ffn": grouped_ffn_work}
