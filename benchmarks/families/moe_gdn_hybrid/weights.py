"""The parameter tree the program's model takes for a decoder whose layers are
linear attention (the gated delta rule) in three of every four and gated
softmax attention in the fourth, each followed by routed experts beside a
gated shared expert.  A linear layer keeps its mixer under ``attn_linear``
(``w_qkvz``, ``w_ba``, the convolution's taps ``conv``, ``A_log``, ``dt_bias``,
the gated norm's ``norm`` and ``w_out``), a full layer under ``attn_global``
(``wq`` holding each head's query and its gate side by side, ``wk``, ``wv``,
``wo``, ``q_norm``, ``k_norm``); every layer its router over all
``moe_router_width`` published experts and the stacks of the ``num_experts``
held here under ``moe``, and the shared expert with its gate ``w_sg`` under
``shared``.  The values are the benchmark's (``weights._make``).

Layer i is a full-attention layer where ``i % full_attention_interval`` is
``full_attention_interval - 1``.

Norm scales applied as ``1 + w`` (the decoder's norms, QK-norm) are made
with the tables' fan, ``0.02 N(0, 1)``: near one as they are applied and not
all equal, as ``_make`` makes a plain scale.  ``A_log`` and ``dt_bias`` are
made as plain scales, near one: ``A = e^{A_log}`` near 2.7 and ``dt_bias``
near upstream's 1, so a token's decay ``e^g`` is small (PERF.md section 4)."""

ZERO_CENTRED = "embed"      # 0.02 N(0, 1): a scale applied as 1 + w


def full_attention(cfg: dict, i: int) -> bool:
    every = cfg["full_attention_interval"]
    return i % every == every - 1


def leaf_shapes(cfg: dict) -> dict:
    """name -> (shape, fan_in; None for a plain scale, "embed" for a table
    or a zero-centred scale)."""
    d, held = cfg["hidden_size"], cfg["num_experts"]
    h, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    hv, width = cfg["linear_num_value_heads"], cfg["linear_conv_kernel_dim"]
    nk = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    nv = hv * cfg["linear_value_head_dim"]
    f, fs = cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"]
    rows = (cfg["vocab_size"], d)
    # lookup rows of unit scale, the head's 0.02, as ``moe_window_gqa`` has
    # them and for its reason: random routers stay near even
    out = {"embed": (rows, 1), "lm_head": (rows, "embed"),
           "final_norm": ((d,), ZERO_CENTRED)}
    for i in range(cfg["num_hidden_layers"]):
        if full_attention(cfg, i):
            mixer = {"attn_global": {
                "wq": ((d, h, 2 * dh), d), "wk": ((d, kv, dh), d),
                "wv": ((d, kv, dh), d), "wo": ((h, dh, d), h * dh),
                "q_norm": ((dh,), ZERO_CENTRED),
                "k_norm": ((dh,), ZERO_CENTRED)}}
        else:
            mixer = {"attn_linear": {
                "w_qkvz": ((d, 2 * nk + 2 * nv), d), "w_ba": ((d, 2 * hv), d),
                "conv": ((width, 2 * nk + nv), width),
                "A_log": ((hv,), None), "dt_bias": ((hv,), None),
                "norm": ((cfg["linear_value_head_dim"],), None),
                "w_out": ((nv, d), nv)}}
        out[f"layer{i}"] = {
            "attn_norm": ((d,), ZERO_CENTRED),
            "mlp_norm": ((d,), ZERO_CENTRED), **mixer,
            "moe": {"router": ((d, cfg["moe_router_width"]), d),
                    "w_gate": ((held, d, f), d), "w_up": ((held, d, f), d),
                    "w_down": ((held, f, d), f)},
            "shared": {"w_gate": ((d, fs), d), "w_up": ((d, fs), d),
                       "w_down": ((fs, d), fs), "w_sg": ((d, 1), d)}}
    return out


# one whole period (linear, linear, linear, full), 4 of 16 experts held, the
# published ratios of heads (16 query / 2 KV; 16 key / 32 value heads
# scaled to 2 / 4), a quarter of each full head rotated; the rehearsal's
# 128-token rows are two chunks of the delta rule
tiny = {"hidden_size": 64, "num_hidden_layers": 4, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 32,
        "linear_num_key_heads": 2, "linear_num_value_heads": 4,
        "linear_key_head_dim": 16, "linear_value_head_dim": 16,
        "intermediate_size": 128, "moe_intermediate_size": 32,
        "shared_expert_intermediate_size": 32, "num_experts": 4,
        "moe_router_width": 16, "num_experts_per_tok": 4, "vocab_size": 512}
