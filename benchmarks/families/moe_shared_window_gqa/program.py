"""The shared-expert two-kind family against the program under test: the
only file of the family that imports it."""

from __future__ import annotations

KINDS = {"full_attention": "global", "sliding_attention": "window"}


def model_config(cfg: dict):
    """The program's ``TransformerConfig`` for a configuration file.  The
    published per-layer lists are read as far as the layers kept, and held
    to the numbers that state them again for the reference."""
    from distributed_pytorch_tpu.models import transformer as tfm

    n = cfg["num_hidden_layers"]
    kinds = tuple(KINDS[t] for t in cfg["layer_types"][:n])
    every, dense = cfg["global_attention_every"], cfg["leading_dense_layers"]
    rope = cfg["rope_parameters"]
    full, sliding = rope["full_attention"], rope["sliding_attention"]
    said = {
        "layer_types": (kinds, tuple(
            "global" if i % every == 0 else "window" for i in range(n))),
        "mlp_layer_types": (cfg["mlp_layer_types"][:n],
                            ["dense"] * dense + ["sparse"] * (n - dense)),
        "num_attention_heads_per_layer": (
            cfg["num_attention_heads_per_layer"][:n],
            [cfg["heads_" + k] for k in kinds]),
        "rope_parameters": (
            [full["rope_theta"], full["factor"],
             full["original_max_position_embeddings"], full["beta_fast"],
             full["beta_slow"], full["attention_factor"],
             full["partial_rotary_factor"] * cfg["head_dim"],
             sliding["rope_theta"], sliding["partial_rotary_factor"]],
            [cfg["rope_theta_global"], cfg["yarn_factor"],
             cfg["yarn_original_positions"], cfg["yarn_beta_fast"],
             cfg["yarn_beta_slow"], cfg["yarn_attention_factor"],
             cfg["rotary_dims_global"], cfg["rope_theta_window"], 1]),
    }
    for key, (published, stated) in said.items():
        if published != stated:
            raise ValueError(f"{key} {published} is not what the "
                             f"configuration's numbers state: {stated}")
    for key, want in (("tie_word_embeddings", False), ("gating", True),
                      ("attention_bias", False),
                      ("moe_apply_router_weight_on_input", False)):
        if cfg.get(key, want) != want:
            raise ValueError(f"the program's block has no {key}={cfg[key]!r}")
    return tfm.TransformerConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"], n_layers=n,
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["moe_intermediate_size"], norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=False, attn_kinds=kinds,
        attn_window=cfg["sliding_window"],
        heads_by_kind=(("global", cfg["heads_global"]),
                       ("window", cfg["heads_window"])),
        rope_by_kind=(
            ("global", tfm.RopeSpec(
                theta=cfg["rope_theta_global"],
                rotary_share=cfg["rotary_dims_global"] / cfg["head_dim"],
                yarn_factor=cfg["yarn_factor"],
                yarn_original_len=cfg["yarn_original_positions"],
                yarn_beta_fast=cfg["yarn_beta_fast"],
                yarn_beta_slow=cfg["yarn_beta_slow"],
                attention_factor=cfg["yarn_attention_factor"])),
            ("window", tfm.RopeSpec(theta=cfg["rope_theta_window"]))),
        attn_gate=True, n_experts=cfg["moe_router_width"],
        moe_top_k=cfg["num_experts_per_tok"], moe_dropless=True,
        moe_experts_held=cfg["num_experts"],
        moe_first_expert=cfg["moe_first_expert"], moe_scoring="sigmoid",
        moe_score_scale=cfg["moe_routed_scaling_factor"],
        moe_shared_ff=cfg["shared_expert_intermediate_size"],
        n_dense_layers=dense, d_ff_dense=cfg["intermediate_size"])


# the reference is plain cross-entropy: no auxiliary loss in this job
trainer_keywords: dict = {"aux_coef": 0.0}
server_keywords: dict = {}


def kernel_compiles(cell: dict) -> dict:
    """Flash attention forward and backward at a ``train`` mix's rows, once
    at the global layers' head count without a window and once at the
    windowed layers' with the configuration's: {name: (fn, shapes)}."""
    import jax
    import jax.numpy as jnp

    from distributed_pytorch_tpu.ops import attention as attn

    cfg, mix = cell["config_file"], cell["mix"]
    if mix["kind"] != "train":
        raise ValueError("this family has no serving path yet")

    def fwd_bwd(window):
        def f(q, k, v):
            return attn.flash_attention(
                q, k, v, causal=True, window=window,
                interpret=False).astype(jnp.float32).sum()
        return jax.grad(f, argnums=(0, 1, 2))

    def qkv(heads):
        return [((int(mix["rows_per_chip"]), heads, int(mix["seq_len"]),
                  cfg["head_dim"]), jnp.bfloat16)] * 3

    return {"flash_fwd_bwd": (fwd_bwd(None), qkv(cfg["heads_global"])),
            "flash_window_fwd_bwd": (fwd_bwd(cfg["sliding_window"]),
                                     qkv(cfg["heads_window"]))}
