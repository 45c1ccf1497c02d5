"""mellum2-12b-a2.5b [moe]: 28L d=2304 32H (GQA kv=4, head 128) vocab=98304.
Three 1024-token window layers to one full layer ("LLLG"); full layers use
YaRN RoPE (factor 16 over 8192 original positions), window layers plain
RoPE, both at theta 5e5.  Every MLP is sparse: softmax router, 64 experts
of width 896, top-8 renormalised, no shared expert; intermediate_size 7168
is stated but no layer uses it.
[hf:JetBrains/Mellum2-12B-A2.5B-Instruct config.json]"""
import dataclasses

from ._smoke import shrink
from .base import AttentionConfig, ModelConfig, MoEConfig, YarnConfig

CONFIG = ModelConfig(
    name="mellum2-12b-a2.5b",
    family="moe",
    num_layers=28,
    d_model=2304,
    d_ff=7168,
    vocab_size=98_304,
    attention=AttentionConfig(
        num_heads=32,
        num_kv_heads=4,
        head_dim=128,
        rope_theta=500_000.0,
        global_yarn=YarnConfig(
            factor=16.0,
            original_max_position_embeddings=8192,
            beta_fast=32.0,
            beta_slow=1.0,
            attention_factor=1.2772588722239782,
        ),
        sliding_window=1024,
        layer_pattern="LLLG",
    ),
    moe=MoEConfig(num_experts=64, top_k=8, expert_ffn_dim=896),
    norm_eps=1e-6,
    tie_embeddings=False,
)


def smoke_config() -> ModelConfig:
    """One "LLLG" period at test widths: grouped-query heads (4 over 2),
    window 16, and top-8 of 16 experts so a token still routes to eight."""
    cfg = shrink(CONFIG)
    return dataclasses.replace(
        cfg,
        attention=dataclasses.replace(cfg.attention, num_kv_heads=2),
        moe=dataclasses.replace(cfg.moe, num_experts=16, top_k=8),
    )
