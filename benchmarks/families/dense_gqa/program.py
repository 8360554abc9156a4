"""The dense family against the program under test: the only file of the
family that imports it."""

from __future__ import annotations


def model_config(cfg: dict):
    """The program's ``TransformerConfig`` for a configuration file."""
    from distributed_pytorch_tpu.models import transformer as tfm

    for key, want in (("tie_word_embeddings", True), ("use_bias", False),
                      ("hidden_act", "silu")):
        if cfg.get(key, want) != want:
            raise ValueError(f"the program's block has no {key}="
                             f"{cfg[key]!r}")
    return tfm.TransformerConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], rope_theta=cfg["rope_theta"],
        norm_eps=cfg["rms_norm_eps"])


# the dense block needs nothing beyond what the mix and the deployment state
trainer_keywords: dict = {}
server_keywords: dict = {}


def kernel_compiles(cell: dict) -> dict:
    """Flash attention forward and backward at a ``train`` mix's rows, paged
    decode attention at a ``serve`` mix's pool: {name: (fn, shapes)}."""
    import jax
    import jax.numpy as jnp

    from distributed_pytorch_tpu.ops import attention as attn

    cfg, mix = cell["config_file"], cell["mix"]
    h, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    if mix["kind"] == "train":
        x = ((int(mix["rows_per_chip"]), h, int(mix["seq_len"]), dh),
             jnp.bfloat16)

        def fwd_bwd(q, k, v):
            def f(q, k, v):
                return attn.flash_attention(
                    q, k, v, causal=True,
                    interpret=False).astype(jnp.float32).sum()
            return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

        return {"flash_fwd_bwd": (fwd_bwd, [x, x, x])}
    dep = cell["deployment"]
    slots, pages = int(dep["slots"]), int(dep["pool_pages"])
    per_slot = -(-int(dep["max_len"]) // 512)
    pool = ((pages, kv, 512, dh), jnp.bfloat16)

    def decode(q, k, v, table, pos):
        return attn.decode_attention_paged(q, k, v, table, pos,
                                           interpret=False)

    return {"decode_attention_paged": (
        decode, [((slots, h, 1, dh), jnp.bfloat16), pool, pool,
                 ((slots, per_slot), jnp.int32), ((slots,), jnp.int32)])}
