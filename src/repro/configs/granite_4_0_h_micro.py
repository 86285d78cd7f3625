"""granite-4.0-h-micro [hybrid] — 40L d_model=2048, a 10-layer period of
five Mamba-2 layers, one attention layer and four Mamba-2 layers
(attention at 5, 15, 25, 35).  Mamba-2: 64 heads x 64 (d_inner 4096),
d_state 128, 1 group, conv width 4 with bias, chunk 256.  Attention: GQA
32/8 heads of 64, no positional encoding, softmax scale 0.015625.  Every
layer has a SwiGLU FFN of 8192.  Embeddings x12, each branch x0.22 before
its residual add, logits /8; tied embeddings over 100,352 rows; RMSNorm
eps 1e-5.  3,191,396,096 parameters.
[hf:ibm-granite/granite-4.0-h-micro config.json, model_type granitemoehybrid]
"""
import dataclasses

from repro.models.config import ATTN_GLOBAL, MAMBA2, ModelConfig

PERIOD = (MAMBA2,) * 5 + (ATTN_GLOBAL,) + (MAMBA2,) * 4

CONFIG = ModelConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    n_layers=40,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    head_dim=64,
    d_ff=8192,
    vocab_size=100_352,
    block_pattern=PERIOD,
    mlp_type="glu",
    act="silu",
    norm="rmsnorm",
    norm_eps=1e-5,
    tie_embeddings=True,
    conv1d_width=4,
    mamba_n_heads=64,
    mamba_head_dim=64,
    mamba_d_state=128,
    mamba_n_groups=1,
    mamba_chunk=256,
    rope=False,
    attn_scale=0.015625,
    embed_scale=12.0,
    residual_scale=0.22,
    logits_scaling=8.0,
    # a period at 8k tokens holds every layer's internals under the
    # period-level remat policies: checkpoint each layer instead
    remat="block",
)

SMOKE = dataclasses.replace(
    CONFIG, name="granite-h-smoke", n_layers=10, d_model=64, n_heads=4,
    n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=512, mamba_n_heads=8,
    mamba_head_dim=16, mamba_d_state=16, mamba_chunk=16, attn_scale=0.0625)
