"""Counts of the routed block: a token passes through two experts of the
``n_experts`` a routed layer holds; attention and its kernels are the dense
family's."""

import families

_dense = families.load("dense_gqa").work
kv_bytes_per_token, kernels = _dense.kv_bytes_per_token, _dense.kernels


def _routed_layers(cfg):
    every = cfg["moe_every"]
    return sum(i % every == every - 1 for i in range(cfg["num_hidden_layers"]))


def param_count(cfg, active=False):
    """All parameters, or with ``active`` those a token passes through."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    experts = 2 if active else cfg["n_experts"]
    return (_dense.param_count(cfg)
            + _routed_layers(cfg) * ((experts - 1) * 3 * d * f
                                     + d * cfg["n_experts"]))


def _attn(cfg):
    return (cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * cfg["head_dim"])


def train_flops_per_token(cfg, seq):
    return 6.0 * param_count(cfg, active=True) + 6.0 * seq * _attn(cfg)


def decode_flops(cfg, tokens, context_tokens):
    return (2.0 * param_count(cfg, active=True) * tokens
            + 4.0 * _attn(cfg) * context_tokens)


def prompt_flops(cfg, length):
    return decode_flops(cfg, length, length * (length + 1) / 2.0)
