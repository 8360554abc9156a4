"""The routed two-kind family against the program under test: the only file
of the family that imports it."""

from __future__ import annotations


def model_config(cfg: dict):
    """The program's ``TransformerConfig`` for a configuration file."""
    from distributed_pytorch_tpu.models import transformer as tfm

    n = cfg["num_hidden_layers"]
    window, nope = cfg["sliding_window_layout"][:n], cfg["rope_layout"][:n]
    every = cfg["global_attention_every"]
    if window != nope or window != [int(i % every != 0) for i in range(n)]:
        raise ValueError(
            "the program knows NoPE-global and rotary-window layers; the "
            f"layouts {window} / {nope} are not that pattern with a global "
            f"layer every {every}")
    for key, want in (("tie_word_embeddings", False),
                      ("moe_primary_router_apply_softmax", True),
                      ("norm_topk_prob", True), ("rope_scaling", None)):
        if cfg.get(key, want) != want:
            raise ValueError(f"the program's block has no {key}={cfg[key]!r}")
    return tfm.TransformerConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=n, n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["moe_ffn_hidden_size"], rope_theta=cfg["rope_theta"],
        norm_eps=cfg["rms_norm_eps"], tie_embeddings=False,
        attn_kinds=tuple("window" if w else "global_nope" for w in window),
        attn_window=cfg["sliding_window_size"],
        n_experts=cfg["moe_router_width"],
        moe_top_k=cfg["moe_num_active_primary_experts"], moe_dropless=True,
        moe_experts_held=cfg["moe_num_primary_experts"],
        moe_first_expert=cfg["moe_first_expert"], moe_act="relu",
        moe_router_input="attn_norm")


# the reference is plain cross-entropy: no auxiliary loss in this job
trainer_keywords: dict = {"aux_coef": 0.0}
server_keywords: dict = {}


def kernel_compiles(cell: dict) -> dict:
    """Flash attention forward and backward at a ``train`` mix's rows, once
    without a window (the NoPE-global layers) and once with the
    configuration's (the windowed ones): {name: (fn, shapes)}."""
    import jax
    import jax.numpy as jnp

    from distributed_pytorch_tpu.ops import attention as attn

    cfg, mix = cell["config_file"], cell["mix"]
    if mix["kind"] != "train":
        raise ValueError("this family has no serving path yet")
    x = ((int(mix["rows_per_chip"]), cfg["num_attention_heads"],
          int(mix["seq_len"]), cfg["head_dim"]), jnp.bfloat16)

    def fwd_bwd(window):
        def f(q, k, v):
            return attn.flash_attention(
                q, k, v, causal=True, window=window,
                interpret=False).astype(jnp.float32).sum()
        return jax.grad(f, argnums=(0, 1, 2))

    return {"flash_fwd_bwd": (fwd_bwd(None), [x, x, x]),
            "flash_window_fwd_bwd": (fwd_bwd(cfg["sliding_window_size"]),
                                     [x, x, x])}
