"""Operations and bytes that the dense tied-head decoder's algorithm needs,
from shapes alone.

Every roofline and every share of the peak divides one of these by a
measured time, so they count what the mathematics requires and never what an
implementation happens to do (recomputation, padding, a copied page pool).
``cfg`` is the configuration file's dict (Hugging Face key names).
"""

from __future__ import annotations


def param_count(cfg: dict) -> int:
    """Parameters of the dense tied-head decoder (embedding counted once)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    layer = (2 * d                    # two RMSNorm scales
             + d * h * dh + 2 * d * kv * dh + h * dh * d   # q, k, v, o
             + 3 * d * f)             # gate, up, down
    return cfg["vocab_size"] * d + d + cfg["num_hidden_layers"] * layer


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward FLOPs a training token requires: 6 per parameter
    (the tied table is one matmul, the head; the lookup is free) plus the
    causal attention products, 2 matmuls x 2 FLOPs x 3 (fwd + bwd) x seq/2
    visible positions per head and layer.  Recomputation is not counted.
    (Copied from ``bench.lm_train_flops_per_token``.)"""
    attn = (6.0 * seq * cfg["num_hidden_layers"]
            * cfg["num_attention_heads"] * cfg["head_dim"])
    return 6.0 * param_count(cfg) + attn


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """K and V of one position over all layers."""
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * cfg["head_dim"] * itemsize)


def decode_flops(cfg: dict, tokens: float, context_tokens: float) -> float:
    """FLOPs to process ``tokens`` new tokens one at a time whose contexts
    (positions attended, the token itself included) add up to
    ``context_tokens``: 2 per parameter plus QK^T and PV over the context."""
    attn = (4.0 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * cfg["head_dim"])
    return 2.0 * param_count(cfg) * tokens + attn * context_tokens


def prompt_flops(cfg: dict, length: int) -> float:
    """Causal forward over a prompt of ``length`` tokens: ``decode_flops``
    over contexts 1..length."""
    return decode_flops(cfg, length, length * (length + 1) / 2.0)


def flash_attn_work(cfg: dict, rows: int, seq: int,
                    itemsize: int = 2) -> dict:
    """Causal attention forward + backward over ``rows`` sequences of
    ``seq``, all layers: FLOPs (fwd 2 matmuls, bwd 4 -- the backward needs
    P again, but that is recomputation and not counted -- so 6 x 2 x
    seq^2/2 x head_dim per head) and the bytes that must cross HBM at least
    once (fwd reads q, k, v and writes o; bwd reads q, k, v, o, do and
    writes dq, dk, dv; K/V at the kv heads' count)."""
    h, kv, dh, layers = (cfg["num_attention_heads"],
                         cfg["num_key_value_heads"], cfg["head_dim"],
                         cfg["num_hidden_layers"])
    flops = 6.0 * 2.0 * (seq * seq / 2.0) * dh * h * rows * layers
    per_head = rows * seq * dh * itemsize
    return {"flops": flops,
            "bytes": 6.0 * per_head * (h + kv) * layers}


def decode_attn_work(cfg: dict, context_tokens: float,
                     itemsize: int = 2) -> dict:
    """Paged decode attention of all layers over steps whose live slots hold
    ``context_tokens`` positions in total: the K/V bytes it must read
    (memory-bound: its QK^T and PV are 8 FLOPs a byte), and those FLOPs."""
    return {"flops": (4.0 * cfg["num_hidden_layers"]
                      * cfg["num_attention_heads"] * cfg["head_dim"]
                      * float(context_tokens)),
            "bytes": kv_bytes_per_token(cfg, itemsize) * float(context_tokens)}


kernels = {"flash_attn": flash_attn_work, "decode_attn": decode_attn_work}
