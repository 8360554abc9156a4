"""Operations and bytes that the model's algorithm needs, from shapes alone.

These are the yardstick's: every roofline and every share of the peak in the
benchmark divides one of these by a measured time, so they count what the
mathematics requires and never what an implementation happens to do
(recomputation, padding, a copied page pool).  ``cfg`` is the configuration
file's dict (Hugging Face key names).  The peaks are one table, keyed by
``device_kind``; a device that is not in it is an error.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """{"bf16_flops_per_s", "hbm_bytes_per_s", "hbm_bytes"} of one chip."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise ValueError(f"no peaks for device kind {device_kind!r}; "
                         f"known: {sorted(table)}")
    return table[device_kind]


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


def decode_attn_bytes(cfg: dict, live_context_tokens: float,
                      itemsize: int = 2) -> float:
    """K/V bytes the decode attention of all layers must read for steps
    whose live slots hold ``live_context_tokens`` positions in total."""
    return kv_bytes_per_token(cfg, itemsize) * float(live_context_tokens)


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> dict:
    """The least time the chip could take, and which bound sets it."""
    t_f = flops / peak["bf16_flops_per_s"]
    t_b = nbytes / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_f, t_b),
            "bound": "compute" if t_f >= t_b else "memory"}
