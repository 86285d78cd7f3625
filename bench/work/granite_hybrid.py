"""Work counts of a Granite 4.0-H training step from its logical shapes.

Operations count two per multiply-add, forward and backward (three times
the forward), with nothing recomputed: the matmul weights (6·N a token:
the Mamba-2 projections, attention, the SwiGLU FFNs and the tied head over
the vocabulary held), the chunked SSD at the configuration's chunk length,
and attention over the causal query-key pairs within documents.  Bytes
count the SSD's inputs and output once each (x, B, C in bfloat16, dt and y
in float32), three times for forward and backward.
"""
from __future__ import annotations

BF16, F32 = 2, 4


def _types(cfg):
    return cfg["layer_types"][:cfg["num_hidden_layers"]]


def _n_mamba(cfg) -> int:
    return sum(t == "mamba" for t in _types(cfg))


def _n_attn(cfg) -> int:
    return sum(t == "attention" for t in _types(cfg))


def matmul_params(cfg) -> int:
    """Weights a token multiplies: the projections of every layer, the
    FFNs and the tied head."""
    d, f, v = cfg["hidden_size"], cfg["shared_intermediate_size"], cfg["vocab_size"]
    hd = d // cfg["num_attention_heads"]
    di = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    bc = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    mamba = d * (2 * di + 2 * bc + cfg["mamba_n_heads"]) + di * d
    attn = d * hd * (cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"]) + d * d
    ffn = 3 * d * f
    return _n_mamba(cfg) * (mamba + ffn) + _n_attn(cfg) * (attn + ffn) + v * d


def ssd_flops_per_token(cfg) -> int:
    """One layer's chunked SSD forward, per token: C·Bᵀ in the chunk, the
    decay-weighted product with x, the chunk states and the carried
    state's read-out."""
    q, h, p = cfg["mamba_chunk_size"], cfg["mamba_n_heads"], cfg["mamba_d_head"]
    n, g = cfg["mamba_d_state"], cfg["mamba_n_groups"]
    return 2 * (q * n * g + q * h * p + 2 * h * p * n)


def ssd_bytes_per_token(cfg) -> int:
    di = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    bc = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return di * BF16 + 2 * bc * BF16 + cfg["mamba_n_heads"] * F32 + di * F32


def attn_flops_per_pair(cfg) -> int:
    """Q·Kᵀ and P·V of one query-key pair, forward."""
    return 2 * 2 * cfg["hidden_size"]


def step_flops(cfg, tokens: int, attn_pairs: float) -> float:
    """Model operations of one training step over `tokens` tokens with
    `attn_pairs` causal query-key pairs within documents."""
    return (6 * matmul_params(cfg) * tokens
            + 3 * _n_mamba(cfg) * ssd_flops_per_token(cfg) * tokens
            + 3 * _n_attn(cfg) * attn_flops_per_pair(cfg) * attn_pairs)


def ssd_step_flops(cfg, tokens: int) -> int:
    return 3 * _n_mamba(cfg) * ssd_flops_per_token(cfg) * tokens


def ssd_step_bytes(cfg, tokens: int) -> int:
    return 3 * _n_mamba(cfg) * ssd_bytes_per_token(cfg) * tokens


def least_time_s(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """The larger of operations over the bf16 peak and bytes over HBM
    bandwidth, and which of the two bounds it."""
    tc, tb = flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tb else (tb, "bytes")
