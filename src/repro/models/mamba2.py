"""Mamba-2 mixer with the chunked SSD scan (arXiv:2405.21060), as
granite-4.0-h uses it.

Mixer: x -> in_proj -> [z | xBC | dt]
           xBC -> causal depthwise conv (width 4, bias) -> silu -> [x | B | C]
           dt  -> softplus(dt + dt_bias);  A = -exp(A_log)
           h_t = exp(dt_t·A)·h_{t-1} + dt_t·B_t x_tᵀ,   y_t = C_t h_t + D·x_t
           y -> rmsnorm(y · silu(z)) · w   (over all d_inner channels)
           -> out_proj

The SSD (state-space duality) form computes the recurrence in chunks of
`cfg.mamba_chunk` tokens: within a chunk as a masked (decay-weighted)
attention-like product, across chunks by carrying each chunk's end state.
The backward pass is autodiff through the same chunked computation.

Packed documents: with `segment_ids` (B, S), the state is zeroed at the
first token of each document and the conv sees no token of an earlier
document, as `seq_idx` does in the Mamba-2 kernels.  Segment ids must not
decrease along a row.

Precision: matmul inputs and the conv in the compute dtype, matmuls with
float32 accumulation; decays, chunk states, softplus, exp and the gated
norm in float32, as Mamba-2's kernels.
"""
from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.parallelism import Logical, ShardingRules, constrain
from repro.models.config import ModelConfig
from repro.models.layers import LayerQAT, _uniform_init

Array = jax.Array
Params = dict[str, Any]

F32 = jnp.float32


def _dims(cfg: ModelConfig) -> tuple[int, int, int]:
    """(d_inner, width of B or C, conv channels)."""
    di = cfg.mamba_d_inner
    bc = cfg.mamba_n_groups * cfg.mamba_d_state
    return di, bc, di + 2 * bc


def mamba2_init(key, cfg: ModelConfig) -> Params:
    """Uniform ±1/sqrt(fan_in) weights; A_log = log U(1, 16); dt_bias the
    inverse softplus of a log-uniform dt in [1e-3, 1e-1]; D = 1."""
    d, h = cfg.d_model, cfg.mamba_n_heads
    di, _, conv_dim = _dims(cfg)
    w = cfg.conv1d_width
    ks = jax.random.split(key, 6)
    dt = jnp.exp(jax.random.uniform(ks[3], (h,), F32, math.log(1e-3), math.log(1e-1)))
    return {
        "in_proj": _uniform_init(ks[0], (d, di + conv_dim + h), d),
        "conv_w": _uniform_init(ks[1], (w, conv_dim), w),
        "conv_b": _uniform_init(ks[2], (conv_dim,), w),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jax.random.uniform(ks[4], (h,), F32, 1.0, 16.0)),
        "D": jnp.ones((h,), F32),
        "norm": jnp.ones((di,), F32),
        "out_proj": _uniform_init(ks[5], (di, d), di),
    }


def mamba2_specs(cfg: ModelConfig) -> Params:
    return {
        "in_proj": Logical("embed", None),
        "conv_w": Logical(None, None),
        "conv_b": Logical(None),
        "dt_bias": Logical(None),
        "A_log": Logical(None),
        "D": Logical(None),
        "norm": Logical(None),
        "out_proj": Logical(None, "embed"),
    }


def causal_conv(x: Array, w: Array, b: Array, seg: Optional[Array]) -> Array:
    """Width-K causal depthwise conv. x: (B, S, C); w: (K, C); b: (C,).
    A tap that reaches into an earlier document (or before the row) reads
    zero."""
    k = w.shape[0]
    s = x.shape[1]
    y = x * w[k - 1].astype(x.dtype)
    for lag in range(1, k):
        xs = jnp.pad(x, ((0, 0), (lag, 0), (0, 0)))[:, :s]
        if seg is not None:
            same = seg == jnp.pad(seg, ((0, 0), (lag, 0)), constant_values=-1)[:, :s]
            xs = jnp.where(same[..., None], xs, 0)
        y = y + xs * w[k - 1 - lag].astype(x.dtype)
    return y + b.astype(x.dtype)


def _exp_masked(mask: Array, log_decay: Array) -> Array:
    """exp(log_decay) where mask holds, else 0 (masked before the exp, so
    neither value nor gradient overflows)."""
    return jnp.exp(jnp.where(mask, log_decay, -jnp.inf))


def ssd(x: Array, dt: Array, A: Array, B: Array, C: Array,
        seg: Optional[Array], chunk: int) -> Array:
    """Chunked SSD.  x: (b, l, h, p) compute dtype; dt: (b, l, h) float32;
    A: (h,) float32; B, C: (b, l, g, n) compute dtype; seg: (b, l) or None.
    Returns y: (b, l, h, p) float32 (without the D skip)."""
    b, l, h, p = x.shape
    g, n = B.shape[2:]
    cdt = x.dtype
    pad = -l % chunk
    if seg is None:
        seg = jnp.zeros((b, l), jnp.int32)
    if pad:   # trailing pad tokens are their own document: they touch no real token
        padt = lambda a, v=0: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2),
                                      constant_values=v)
        x, dt, B, C = padt(x), padt(dt), padt(B), padt(C)
        seg = padt(seg, jnp.iinfo(jnp.int32).max)
    lp = l + pad
    c, q, r = lp // chunk, chunk, h // g
    x = x.reshape(b, c, q, g, r, p)
    dt = dt.reshape(b, c, q, g, r)
    B = B.reshape(b, c, q, g, n)
    C = C.reshape(b, c, q, g, n)
    seg = seg.reshape(b, c, q)

    a = dt * A.reshape(g, r)                                  # log decay per token
    cum = jnp.cumsum(a, axis=2)                               # (b,c,q,g,r)
    xdt = x.astype(F32) * dt[..., None]                      # (b,c,q,g,r,p)

    # within a chunk: y_i = sum_{j<=i, same doc} (C_i.B_j) exp(cum_i - cum_j) dt_j x_j
    i_ge_j = jnp.tril(jnp.ones((q, q), jnp.bool_))
    same = jnp.logical_and(seg[..., :, None] == seg[..., None, :], i_ge_j)  # (b,c,i,j)
    cum_t = jnp.moveaxis(cum, 2, -1)                          # (b,c,g,r,q)
    decay = _exp_masked(same[:, :, None, None],
                        cum_t[..., :, None] - cum_t[..., None, :])  # (b,c,g,r,i,j)
    cb = jnp.einsum("bcign,bcjgn->bcgij", C, B, preferred_element_type=F32)
    m = (cb[:, :, :, None] * decay).astype(cdt)
    y = jnp.einsum("bcgrij,bcjgrp->bcigrp", m, xdt.astype(cdt),
                   preferred_element_type=F32)

    # each chunk's end state from its own tokens
    seg_end = seg[:, :, -1]                                   # (b,c)
    last = cum[:, :, -1]                                      # (b,c,g,r)
    to_end = _exp_masked((seg == seg_end[..., None])[..., None, None],
                         last[:, :, None] - cum)              # (b,c,q,g,r)
    states = jnp.einsum("bcjgn,bcjgrp->bcgrpn", B,
                        (xdt * to_end[..., None]).astype(cdt),
                        preferred_element_type=F32)

    # across chunks: h_z = exp(last_z)·h_{z-1} + states_z, unless a document
    # starts inside chunk z (then the carried state is gone by its end)
    keep = jnp.concatenate([jnp.zeros((b, 1), jnp.bool_),
                            seg_end[:, 1:] == seg_end[:, :-1]], axis=1)
    carry = jnp.where(keep[..., None, None], jnp.exp(last), 0.0)[..., None, None]

    def combine(e1, e2):
        a1, s1 = e1
        a2, s2 = e2
        return a1 * a2, a2 * s1 + s2

    _, ends = jax.lax.associative_scan(combine, (carry, states), axis=1)
    prev = jnp.concatenate([jnp.zeros_like(ends[:, :1]), ends[:, :-1]], axis=1)

    # the carried state read at token i of chunk z, decayed to i, while token
    # i is still in the document that ended chunk z - 1
    seg_prev = jnp.concatenate([jnp.full((b, 1), -1, seg.dtype), seg_end[:, :-1]], 1)
    from_start = _exp_masked((seg == seg_prev[..., None])[..., None, None], cum)
    y_off = jnp.einsum("bcign,bcgrpn->bcigrp", C, prev.astype(cdt),
                       preferred_element_type=F32)
    y = y + y_off * from_start[..., None]
    return y.reshape(b, lp, h, p)[:, :l]


def gated_rmsnorm(y: Array, z: Array, w: Array, eps: float) -> Array:
    """rmsnorm(y · silu(z)) · w over the last axis, in float32."""
    hs = y.astype(F32) * jax.nn.silu(z.astype(F32))
    hs = hs * jax.lax.rsqrt(jnp.mean(jnp.square(hs), -1, keepdims=True) + eps)
    return hs * w


def mamba2_forward(x: Array, p: Params, cfg: ModelConfig,
                   rules: Optional[ShardingRules], qat: LayerQAT,
                   segment_ids: Optional[Array] = None) -> Array:
    """Full-sequence mixer (training / stateless prefill). x: (B, S, d).
    The SSD lies in the `ssd` scope and everything else in `mamba_proj`,
    so the two read apart in a device trace."""
    dt_ = cfg.compute_dtype
    h, hp = cfg.mamba_n_heads, cfg.mamba_head_dim
    g, n = cfg.mamba_n_groups, cfg.mamba_d_state
    di, bc, conv_dim = _dims(cfg)
    bsz, s, _ = x.shape
    with jax.named_scope("mamba_proj"):
        x = qat.site("mamba_in", x)
        zxbcdt = x @ p["in_proj"].astype(dt_)
        z, xbc, dt = jnp.split(zxbcdt, [di, di + conv_dim], axis=-1)
        xbc = jax.nn.silu(causal_conv(xbc, p["conv_w"], p["conv_b"], segment_ids))
        xs, B, C = jnp.split(xbc, [di, di + bc], axis=-1)
        dt = jax.nn.softplus(dt.astype(F32) + p["dt_bias"])
        A = -jnp.exp(p["A_log"])
        xs = xs.reshape(bsz, s, h, hp)
    with jax.named_scope("ssd"):
        y = ssd(xs, dt, A, B.reshape(bsz, s, g, n), C.reshape(bsz, s, g, n),
                segment_ids, cfg.mamba_chunk)
        y = y + p["D"][:, None] * xs.astype(F32)
    with jax.named_scope("mamba_proj"):
        y = gated_rmsnorm(y.reshape(bsz, s, di), z, p["norm"], cfg.norm_eps)
        y = qat.site("mamba_out_in", y.astype(dt_))
        out = y @ p["out_proj"].astype(dt_)
    return constrain(out, rules, "batch", "seq", "embed")
