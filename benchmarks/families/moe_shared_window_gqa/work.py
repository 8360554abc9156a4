"""Operations and bytes that one chip's share of the shared-expert, two-kind
decoder needs, from shapes alone (``cfg``: the configuration file's dict, in
which ``num_experts`` counts the routed experts held here,
``moe_router_width`` all that the router scores, ``vocab_size`` the slice of
the vocabulary held here, ``heads_global`` / ``heads_window`` the query heads
of a layer of each kind, and layer i is global where i is a multiple of
``global_attention_every`` and dense where i < ``leading_dense_layers``).

Routing is counted as even: a token's ``num_experts_per_tok`` picks fall on
the experts held here in the proportion held / width.  Every roofline and
share of the peak divides one of these by a measured time, so they count
what the mathematics requires, never what an implementation does
(recomputation, a row buffer sized for the worst case and padded to whole
tiles an expert, K/V repeated to the query heads' count, whole blocks where
a window's band crosses them).
"""

from __future__ import annotations


def _kinds(cfg: dict) -> list[str]:
    return ["global" if i % cfg["global_attention_every"] == 0 else "window"
            for i in range(cfg["num_hidden_layers"])]


def _attn_params(cfg: dict, kind: str) -> int:
    """wq, wo, wk, wv and the gate's wg of a layer of ``kind``."""
    d, kv, dh = cfg["hidden_size"], cfg["num_key_value_heads"], cfg["head_dim"]
    h = cfg["heads_" + kind]
    return 2 * d * h * dh + 2 * d * kv * dh + d * h


def _swiglu_params(cfg: dict, width: int) -> int:
    return 3 * cfg["hidden_size"] * width


def mean_keys(seq: int, window: int | None = None) -> float:
    """Keys a query sees, averaged over the ``seq`` positions of a row:
    causal, and with ``window`` at most that many (its own included)."""
    if window is None or window >= seq:
        return (seq + 1) / 2.0
    return (window * (window + 1) / 2.0 + (seq - window) * window) / seq


def _pair_dims(cfg: dict, seq: int, kinds: tuple[str, ...]) -> float:
    """Query-key pairs of one row times the dimensions they are taken over
    (heads x head size), summed over the layers of ``kinds``."""
    keys = {"global": mean_keys(seq),
            "window": mean_keys(seq, cfg["sliding_window"])}
    return sum(seq * keys[k] * cfg["heads_" + k] * cfg["head_dim"]
               for k in _kinds(cfg) if k in kinds)


def held_share(cfg: dict) -> float:
    return cfg["num_experts"] / cfg["moe_router_width"]


def _sparse_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["leading_dense_layers"]


def routed_rows(cfg: dict, tokens: float) -> dict:
    """Of ``tokens`` tokens through one sparse layer: the picks the router
    makes, under even routing those that fall on the experts held here, and
    the rows of the program's buffer (every pick and a 512-row tile of
    padding for each held expert, in whole tiles: ``ops/moe._layout``), by
    which the trace's reader knows the routed layer's operations."""
    picks = tokens * cfg["num_experts_per_tok"]
    return {"picks": picks, "here": picks * held_share(cfg),
            "buffer": -(-(picks + cfg["num_experts"] * 512) // 512) * 512}


def param_count(cfg: dict, active: bool = False) -> float:
    """Parameters held here (the lookup table and the head's own counted
    each), or with ``active`` those one token passes through here: attention,
    the dense layer, the router and the shared expert whole, and its picks'
    share of the held experts."""
    d = cfg["hidden_size"]
    experts = (routed_rows(cfg, 1)["here"] if active else cfg["num_experts"])
    sparse = (d * cfg["moe_router_width"]
              + _swiglu_params(cfg, cfg["shared_expert_intermediate_size"])
              + experts * _swiglu_params(cfg, cfg["moe_intermediate_size"]))
    layers = sum(2 * d + _attn_params(cfg, kind)
                 + (_swiglu_params(cfg, cfg["intermediate_size"])
                    if i < cfg["leading_dense_layers"] else sparse)
                 for i, kind in enumerate(_kinds(cfg)))
    tables = (1 if active else 2) * cfg["vocab_size"] * d
    return tables + d + layers


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward FLOPs a training token requires of this share: 6
    per parameter it passes through (the lookup is free) plus the attention
    products over the pairs it sees, 2 matmuls x 2 FLOPs x 3 (fwd + bwd),
    each layer at its own kind's keys and head count."""
    return (6.0 * param_count(cfg, active=True)
            + 12.0 * _pair_dims(cfg, seq, ("global", "window")) / seq)


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """K and V of one position over all layers (a windowed layer keeps them
    for its window only; no cache exists for this family yet)."""
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * cfg["head_dim"] * itemsize)


def decode_flops(cfg: dict, tokens: float, context_tokens: float) -> float:
    attn = 4.0 * cfg["head_dim"] * sum(cfg["heads_" + k] for k in _kinds(cfg))
    return 2.0 * param_count(cfg, active=True) * tokens + attn * context_tokens


def prompt_flops(cfg: dict, length: int) -> float:
    return (2.0 * param_count(cfg, active=True) * length
            + 4.0 * _pair_dims(cfg, length, ("global", "window")))


def _flash_work(cfg: dict, rows: int, seq: int, kinds: tuple[str, ...],
                itemsize: int) -> dict:
    """Attention forward + backward over ``rows`` sequences of ``seq`` in
    the layers of ``kinds``: 6 matmuls x 2 FLOPs over the pairs a query
    sees, at the layer's head count, and the bytes that must cross HBM once
    (q, o, dq, do at the query heads' count, K/V and their gradients at the
    kv heads': 6 passes, as the dense family counts them)."""
    heads = sum(cfg["heads_" + k] + cfg["num_key_value_heads"]
                for k in _kinds(cfg) if k in kinds)
    return {"flops": 12.0 * _pair_dims(cfg, seq, kinds) * rows,
            "bytes": 6.0 * rows * seq * cfg["head_dim"] * itemsize * heads}


def flash_attn_work(cfg: dict, rows: int, seq: int, itemsize: int = 2) -> dict:
    """Every layer's attention, global and windowed."""
    return _flash_work(cfg, rows, seq, ("global", "window"), itemsize)


def flash_attn_window_work(cfg: dict, rows: int, seq: int,
                           itemsize: int = 2) -> dict:
    """The windowed layers' attention alone: a query's mean of
    ``mean_keys(seq, sliding_window)`` keys, not the blocks a kernel visits."""
    return _flash_work(cfg, rows, seq, ("window",), itemsize)


def window_kernel_operand(cfg: dict, rows: int, seq: int) -> str | None:
    """The shape by which the trace tells the windowed layers' kernels from
    the global ones': rows x that kind's heads, the row, the head.  None
    where both kinds have one head count: the trace's names do not tell
    their kernels apart then."""
    if cfg["heads_window"] == cfg["heads_global"]:
        return None
    return f"[{rows * cfg['heads_window']},{seq},{cfg['head_dim']}]"


def grouped_ffn_work(cfg: dict, tokens: float, itemsize: int = 2) -> dict:
    """The held experts' gate, up and down products over a step of
    ``tokens`` tokens, all sparse layers, forward + backward: 3 products x 3
    passes x 2 FLOPs x rows x d x f, and per pass each product's rows in
    and out and the held stacks once."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = routed_rows(cfg, tokens)["here"]
    stacks = cfg["num_experts"] * _swiglu_params(cfg, f)
    return {"flops": _sparse_layers(cfg) * 18.0 * rows * d * f,
            "bytes": (_sparse_layers(cfg) * 3.0 * itemsize
                      * (stacks + 3.0 * rows * (d + f)))}


kernels = {"flash_attn": flash_attn_work,
           "flash_attn_window": flash_attn_window_work,
           "grouped_ffn": grouped_ffn_work}
