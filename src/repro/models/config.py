"""Model configuration schema for the assigned architecture zoo.

One `ModelConfig` describes any of the assigned architectures (plus the
reduced smoke variants).  Heterogeneous layer stacks (gemma3's 5:1
local:global, recurrentgemma's 2:1 RG-LRU:local-attn, granite-4.0-h's
9:1 Mamba-2:attention) are expressed as a
`block_pattern` cycled over the depth; the transformer assembly scans over
whole pattern periods and unrolls the remainder (MaxText-style stacked-param
scan, see transformer.py).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp

# block types
ATTN_GLOBAL = "global"        # full (causal or bidir) attention + MLP
ATTN_LOCAL = "local"          # sliding-window attention + MLP
RWKV6 = "rwkv6"               # RWKV-6 time-mix + channel-mix
RGLRU = "rglru"               # RecurrentGemma recurrent block + MLP
MAMBA2 = "mamba2"             # Mamba-2 (SSD) mixer + MLP (granite-4.0-h)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None          # default d_model // n_heads
    block_pattern: tuple[str, ...] = (ATTN_GLOBAL,)
    window: int = 1024                      # local-attention window
    rope_theta: float = 10_000.0
    qkv_bias: bool = False                  # qwen2
    mlp_type: str = "glu"                   # "glu" | "mlp"
    act: str = "silu"                       # "silu" | "gelu"
    norm: str = "rmsnorm"                   # "rmsnorm" | "layernorm"
    tie_embeddings: bool = False
    causal: bool = True                     # False => encoder (hubert)
    # --- MoE ---
    n_experts: int = 0
    experts_per_token: int = 0
    moe_capacity_factor: float = 1.25
    # --- recurrent (rwkv6 / rglru) ---
    rnn_state_dim: Optional[int] = None     # rglru recurrent width
    rwkv_head_dim: int = 64
    conv1d_width: int = 4                   # rglru / mamba2 temporal conv
    # --- Mamba-2 (SSD) mixer: d_inner = mamba_n_heads * mamba_head_dim ---
    mamba_n_heads: int = 0
    mamba_head_dim: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_chunk: int = 256                  # SSD chunk length
    # --- granite-style scalings (defaults: the usual transformer) ---
    rope: bool = True                       # False: no positional encoding
    attn_scale: Optional[float] = None      # softmax scale; None: 1/sqrt(hd)
    embed_scale: Optional[float] = None     # None: sqrt(d_model)
    residual_scale: float = 1.0             # each branch x this before its add
    logits_scaling: float = 1.0             # logits divided by this
    norm_eps: float = 1e-6
    # --- frontend stubs (vlm/audio): embeddings arrive precomputed ---
    frontend: str = "none"                  # none | vision_stub | audio_stub
    frontend_dim: int = 0                   # incoming embedding width
    frontend_len: int = 0                   # number of frontend positions
    # --- numerics / training ---
    dtype: str = "bfloat16"
    # "dots" (checkpoint_dots) measured strictly better than "full" on the
    # roofline: full remat re-executes the psum-bearing ops in the backward
    # pass (gemma3 train: collective 3.76 -> 1.80 s, compute -21%, §Perf-6).
    # "block" checkpoints each layer on its own (only layer inputs are kept):
    # the one period of a long-sequence hybrid holds every layer's internals
    # under the period-level policies.
    remat: str = "dots"                     # none | dots | full | block
    # QAT (FIXAR technique as a first-class feature)
    qat: bool = False
    qat_delay: int = 0
    qat_bits: int = 16

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def n_periods(self) -> int:
        return self.n_layers // len(self.block_pattern)

    @property
    def n_tail(self) -> int:
        return self.n_layers % len(self.block_pattern)

    def layer_types(self) -> list[str]:
        p = self.block_pattern
        return [p[i % len(p)] for i in range(self.n_layers)]

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_head_dim

    def _mamba_params(self) -> int:
        """One Mamba-2 mixer: in_proj to [z | xBC | dt], the depthwise conv
        with its bias, dt_bias, A_log and D per head, the gated norm, and
        out_proj."""
        d, di, h = self.d_model, self.mamba_d_inner, self.mamba_n_heads
        conv_dim = di + 2 * self.mamba_n_groups * self.mamba_d_state
        return (d * (di + conv_dim + h) + conv_dim * (self.conv1d_width + 1)
                + 3 * h + di + di * d)

    @property
    def _norm_params(self) -> int:
        return self.d_model * (2 if self.norm == "layernorm" else 1)

    def _layer_params(self, t: str, active: bool) -> int:
        """One layer of type t with its two pre-norms."""
        return self._mixer_params(t) + self._mlp_params(
            self.d_model, self.d_ff, active=active) + 2 * self._norm_params

    def _mixer_params(self, t: str) -> int:
        d, hd = self.d_model, self.hd
        n_q, n_kv = self.n_heads, self.n_kv_heads
        if t in (ATTN_GLOBAL, ATTN_LOCAL):
            return d * hd * (n_q + 2 * n_kv) + n_q * hd * d
        if t == RWKV6:
            # time-mix: r,k,v,g,o projections + decay lora; channel-mix
            return 5 * d * d
        if t == RGLRU:
            rnn = self.rnn_state_dim or d
            return 2 * d * rnn + rnn * d
        if t == MAMBA2:
            return self._mamba_params()
        raise ValueError(t)

    def _head_params(self) -> int:
        """The embedding and the output head (one matrix when tied; a
        vocabulary slice counts its rows), and the final norm."""
        d = self.d_model
        total = 2 * d * self.vocab_size if not self.tie_embeddings \
            else d * self.vocab_size
        return total + self._norm_params

    def params_per_token(self) -> int:
        """Active parameter count per token (for 6·N·D MODEL_FLOPS)."""
        return (sum(self._layer_params(t, active=True) for t in self.layer_types())
                + self._head_params())

    def _mlp_params(self, d, f, active=False):
        per_expert = (3 if self.mlp_type == "glu" else 2) * d * f
        if not self.is_moe:
            return per_expert
        k = self.experts_per_token if active else self.n_experts
        return per_expert * k + d * self.n_experts  # + router

    def total_params(self) -> int:
        return (sum(self._layer_params(t, active=False) for t in self.layer_types())
                + self._head_params())


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One cell of the (arch × shape) grid."""

    name: str              # train_4k | prefill_32k | decode_32k | long_500k
    kind: str              # "train" | "prefill" | "decode"
    seq_len: int
    global_batch: int


TRAIN_4K = ShapeConfig("train_4k", "train", 4_096, 256)
PREFILL_32K = ShapeConfig("prefill_32k", "prefill", 32_768, 32)
DECODE_32K = ShapeConfig("decode_32k", "decode", 32_768, 128)
LONG_500K = ShapeConfig("long_500k", "decode", 524_288, 1)
ALL_SHAPES = (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)
