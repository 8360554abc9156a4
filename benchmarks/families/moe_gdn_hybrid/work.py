"""Operations and bytes that one chip's share of the linear/full-attention
hybrid needs, from shapes alone (``cfg``: the configuration file's dict, in
which ``num_experts`` counts the routed experts held here,
``moe_router_width`` all that the router scores, ``vocab_size`` the slice of
the vocabulary held here, and layer i is a full-attention layer where
``i % full_attention_interval`` is ``full_attention_interval - 1``, a
linear-attention layer otherwise).

Routing is counted as even: a token's ``num_experts_per_tok`` picks fall on
the experts held here in the proportion held / width.  The delta rule is
counted at chunks of ``CHUNK`` positions from the mathematics of the chunked
form: what a position needs of its chunk's triangle, and the three state
products, never what an implementation does (key heads repeated to the value
heads' count, whole squares where a triangle is needed, recomputation).
"""

from __future__ import annotations

CHUNK = 64


def _full(cfg: dict, i: int) -> bool:
    every = cfg["full_attention_interval"]
    return i % every == every - 1


def _counts(cfg: dict) -> tuple[int, int]:
    """(linear layers, full-attention layers)."""
    full = sum(_full(cfg, i) for i in range(cfg["num_hidden_layers"]))
    return cfg["num_hidden_layers"] - full, full


def _linear_params(cfg: dict) -> int:
    d, hv = cfg["hidden_size"], cfg["linear_num_value_heads"]
    nk = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    nv = hv * cfg["linear_value_head_dim"]
    return (d * (2 * nk + 2 * nv) + d * 2 * hv
            + cfg["linear_conv_kernel_dim"] * (2 * nk + nv) + 2 * hv
            + cfg["linear_value_head_dim"] + nv * d)


def _full_params(cfg: dict) -> int:
    d, h, kv, dh = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    return d * h * 2 * dh + 2 * d * kv * dh + h * dh * d + 2 * dh


def _swiglu_params(cfg: dict, width: int) -> int:
    return 3 * cfg["hidden_size"] * width


def held_share(cfg: dict) -> float:
    return cfg["num_experts"] / cfg["moe_router_width"]


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
    each), or with ``active`` those one token passes through here: the
    mixers, the router, the shared expert and its gate whole, and its picks'
    share of the held experts."""
    d = cfg["hidden_size"]
    experts = routed_rows(cfg, 1)["here"] if active else cfg["num_experts"]
    sparse = (d * cfg["moe_router_width"]
              + _swiglu_params(cfg, cfg["shared_expert_intermediate_size"])
              + d + experts * _swiglu_params(cfg, cfg["moe_intermediate_size"]))
    linear, full = _counts(cfg)
    layers = (linear * _linear_params(cfg) + full * _full_params(cfg)
              + cfg["num_hidden_layers"] * (2 * d + sparse))
    tables = (1 if active else 2) * cfg["vocab_size"] * d
    return tables + d + layers


def mean_keys(seq: int) -> float:
    return (seq + 1) / 2.0


def delta_rule_flops(cfg: dict) -> float:
    """Forward FLOPs of the delta rule for one position of one linear layer,
    at chunks of ``CHUNK``, per value head: Q K^T over the chunk's keys up
    to the position, K K^T below it, the two WY products (the triangular
    system on beta V and on beta K e^G), the masked intra-chunk product, and
    the three (key x value) state products; 2 FLOPs a multiply-add."""
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    incl, strict = (CHUNK + 1) / 2.0, (CHUNK - 1) / 2.0
    macs = (incl * dk + strict * dk + incl * (dv + dk) + incl * dv
            + 3 * dk * dv)
    return 2.0 * macs * cfg["linear_num_value_heads"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward FLOPs a training token requires of this share: 6
    per parameter it passes through (the lookup is free), the full layers'
    attention over the pairs a query sees (2 products x 2 FLOPs x 3), and 3
    times the delta rule's forward in each linear layer."""
    linear, full = _counts(cfg)
    pairs = (mean_keys(seq) * cfg["num_attention_heads"] * cfg["head_dim"]
             * full)
    return (6.0 * param_count(cfg, active=True) + 12.0 * pairs
            + 3.0 * linear * delta_rule_flops(cfg))


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """K and V of one position in the full-attention layers (a linear layer
    keeps a state of fixed size; no cache exists for this family yet)."""
    return (2 * _counts(cfg)[1] * cfg["num_key_value_heads"]
            * cfg["head_dim"] * itemsize)


def decode_flops(cfg: dict, tokens: float, context_tokens: float) -> float:
    linear, full = _counts(cfg)
    attn = 4.0 * cfg["head_dim"] * cfg["num_attention_heads"] * full
    state = (6.0 * linear * cfg["linear_num_value_heads"]
             * cfg["linear_key_head_dim"] * cfg["linear_value_head_dim"])
    return ((2.0 * param_count(cfg, active=True) + state) * tokens
            + attn * context_tokens)


def prompt_flops(cfg: dict, length: int) -> float:
    linear, full = _counts(cfg)
    return ((2.0 * param_count(cfg, active=True)
             + linear * delta_rule_flops(cfg)) * length
            + 4.0 * length * mean_keys(length) * cfg["num_attention_heads"]
            * cfg["head_dim"] * full)


def flash_attn_work(cfg: dict, rows: int, seq: int, itemsize: int = 2) -> dict:
    """The full layers' attention, forward + backward: 6 matmuls x 2 FLOPs
    over the pairs a query sees, and q, o, dq, do at the query heads' count,
    K/V and their gradients at the KV heads' (6 passes, as the dense family
    counts them)."""
    full = _counts(cfg)[1]
    h, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    return {"flops": 12.0 * rows * seq * mean_keys(seq) * h * dh * full,
            "bytes": 6.0 * rows * seq * dh * itemsize * (h + kv) * full}


def grouped_ffn_work(cfg: dict, tokens: float, itemsize: int = 2) -> dict:
    """The held experts' gate, up and down products over a step of
    ``tokens`` tokens, all sparse layers, forward + backward: 3 products x 3
    passes x 2 FLOPs x rows x d x f, and per pass each product's rows in
    and out and the held stacks once."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = routed_rows(cfg, tokens)["here"]
    layers = cfg["num_hidden_layers"]
    stacks = cfg["num_experts"] * _swiglu_params(cfg, f)
    return {"flops": layers * 18.0 * rows * d * f,
            "bytes": layers * 3.0 * itemsize * (stacks
                                                 + 3.0 * rows * (d + f))}


def gated_delta_work(cfg: dict, rows: int, seq: int, itemsize: int = 2
                     ) -> dict:
    """The delta rule of every linear layer, forward + backward (3 times the
    forward's FLOPs), and the bytes that must cross HBM: per position q and
    k at the key heads' count and v, o at the value heads' (``itemsize``),
    g and beta (float32) a value head, and the float32 state once a chunk;
    three passes."""
    linear = _counts(cfg)[0]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    per_position = (itemsize * (2 * hk * dk + 2 * hv * dv) + 4 * 2 * hv
                    + 4 * hv * dk * dv / CHUNK)
    tokens = rows * seq
    return {"flops": 3.0 * linear * delta_rule_flops(cfg) * tokens,
            "bytes": 3.0 * linear * per_position * tokens}


def gated_delta_operands(cfg: dict, rows: int, seq: int) -> dict:
    """Result shapes by which the trace tells the linear layers' operations
    apart (``ops/gated_delta.py`` runs as XLA operations, so no kernel name
    marks them): {"delta_rule": [...], "conv": [...]}, each a list of
    dimension runs at the end of a result shape that the delta rule's (or
    the convolution's) operations have and no other operation of the step
    has.  The delta rule's: a chunk's square (``64,64]``), a chunk's rows of
    a head (``64,128]``) or of the solve's right side (``64,256]``), a state
    (``128,128]``), the decay's cumulative sums (``32,64]``: value heads,
    a chunk), and its operands and result in the row's layout, chunked
    (``64,32,128]``) or not (``8192,32,128]``, the key heads' before the
    repeat ``8192,16,128]``, g and beta ``8192,32]``, and the row's
    positions in eights as the chip lays a float32 row out,
    ``,8,32,128]``); the convolution's, the row beside its channels (and
    the row padded in front).  Read off the chip's trace of
    ``qwen3next_train_8k`` (PR 36)."""
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    width = cfg["linear_conv_kernel_dim"]
    channels = 2 * hk * dk + hv * dv
    return {
        "delta_rule": sorted({
            f",{CHUNK},{CHUNK}]", f",{CHUNK},{dk}]", f",{CHUNK},{dv}]",
            f",{CHUNK},{dk + dv}]", f",{dk},{dv}]", f",{hv},{CHUNK}]",
            f",{CHUNK},{hv},{dv}]", f"{seq},{hv},{dv}]", f"{seq},{hk},{dk}]",
            f",8,{hv},{dv}]", f",{seq},{hv}]"}),
        "conv": [f"[{rows},{seq},{channels}]",
                 f"[{rows},{seq + width - 1},{channels}]"],
    }


kernels = {"flash_attn": flash_attn_work, "grouped_ffn": grouped_ffn_work,
           "gated_delta": gated_delta_work}
