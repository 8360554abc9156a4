"""The linear/full-attention hybrid family against the program under test:
the only file of the family that imports it."""

from __future__ import annotations


def model_config(cfg: dict):
    """The program's ``TransformerConfig`` for a configuration file: layer i
    full attention where ``i % full_attention_interval`` is the period's
    last, linear attention otherwise; every layer sparse."""
    from distributed_pytorch_tpu.models import transformer as tfm

    n, every = cfg["num_hidden_layers"], cfg["full_attention_interval"]
    for key, want in (("tie_word_embeddings", False), ("norm_topk_prob", True),
                      ("rope_scaling", None), ("decoder_sparse_step", 1),
                      ("mlp_only_layers", []), ("hidden_act", "silu"),
                      ("use_sliding_window", False)):
        if cfg.get(key, want) != want:
            raise ValueError(f"the program's block has no {key}={cfg[key]!r}")
    return tfm.TransformerConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"], n_layers=n,
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["moe_intermediate_size"], rope_theta=cfg["rope_theta"],
        norm_eps=cfg["rms_norm_eps"], tie_embeddings=False,
        attn_kinds=tuple("global" if i % every == every - 1 else "linear"
                         for i in range(n)),
        rope_by_kind=(("global", tfm.RopeSpec(
            theta=cfg["rope_theta"],
            rotary_share=cfg["partial_rotary_factor"])),),
        attn_gate=True, attn_gate_form="element", qk_norm=True,
        norm_offset=1.0, n_experts=cfg["moe_router_width"],
        moe_top_k=cfg["num_experts_per_tok"], moe_dropless=True,
        moe_experts_held=cfg["num_experts"],
        moe_first_expert=cfg["moe_first_expert"],
        moe_shared_ff=cfg["shared_expert_intermediate_size"],
        moe_shared_gate=True,
        linear_k_heads=cfg["linear_num_key_heads"],
        linear_v_heads=cfg["linear_num_value_heads"],
        linear_k_dim=cfg["linear_key_head_dim"],
        linear_v_dim=cfg["linear_value_head_dim"],
        linear_conv=cfg["linear_conv_kernel_dim"])


# the reference is plain cross-entropy: no auxiliary loss in this job
trainer_keywords: dict = {"aux_coef": 0.0}
server_keywords: dict = {}


def kernel_compiles(cell: dict) -> dict:
    """Flash attention forward and backward at a ``train`` mix's rows and
    the full layers' query heads (K/V repeated to them, as the program
    does): {name: (fn, shapes)}.  The delta rule is XLA operations, no
    kernel."""
    import jax
    import jax.numpy as jnp

    from distributed_pytorch_tpu.ops import attention as attn

    cfg, mix = cell["config_file"], cell["mix"]
    if mix["kind"] != "train":
        raise ValueError("this family has no serving path yet")
    x = ((int(mix["rows_per_chip"]), cfg["num_attention_heads"],
          int(mix["seq_len"]), cfg["head_dim"]), jnp.bfloat16)

    def f(q, k, v):
        return attn.flash_attention(q, k, v, causal=True,
                                    interpret=False).astype(jnp.float32).sum()

    return {"flash_fwd_bwd": (jax.grad(f, argnums=(0, 1, 2)), [x, x, x])}
