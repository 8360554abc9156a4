"""The routed block against the program: the dense family's configuration
with the program's own expert settings."""

import dataclasses

import families

_dense = families.load("dense_gqa").program
trainer_keywords: dict = {}
server_keywords: dict = {}
kernel_compiles = _dense.kernel_compiles


def model_config(cfg):
    return dataclasses.replace(
        _dense.model_config(cfg), n_experts=cfg["n_experts"],
        moe_every=cfg["moe_every"], moe_top_k=2)
