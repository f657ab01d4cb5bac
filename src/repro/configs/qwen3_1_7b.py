"""qwen3-1.7b — qk_norm, GQA, tied embeddings [hf:Qwen/Qwen3-1.7B config.json].

28L d_model=2048 16H (GQA kv=8, head_dim 128) d_ff=6144 vocab=151936,
rope_theta 1e6, tie_word_embeddings true (1.7B parameters).
"""
from repro.configs.base import ModelConfig
from repro.core.attention import AttentionSpec

ARCH_ID = "qwen3-1.7b"

CONFIG = ModelConfig(
    name=ARCH_ID,
    family="dense",
    num_layers=28,
    d_model=2048,
    num_heads=16,
    kv_heads=8,
    d_ff=6144,
    vocab=151936,
    head_dim=128,
    qk_norm=True,
    rope_theta=1e6,
    tie_embeddings=True,
    attention=AttentionSpec(kind="mra2", block_size=128, blocks_per_row=4,
                            decode_blocks=16),
    remat="full",
    scan_layers=True,
)


def smoke():
    return CONFIG.replace(
        num_layers=2, d_model=64, num_heads=4, kv_heads=2, head_dim=16,
        d_ff=128, vocab=512,
        attention=AttentionSpec(kind="mra2", block_size=16, blocks_per_row=2,
                                decode_blocks=2),
        remat="none",
        scan_layers=False,
    )
