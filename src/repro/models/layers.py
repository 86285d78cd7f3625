"""Shared neural layers for the architecture zoo.

Everything is functional: params are plain dicts of arrays, each `*_init`
has a matching `*_specs` returning the same tree with `Logical` leaves
(logical sharding axes, resolved by core.parallelism rules), and every
activation-entering-a-matmul passes through a `LayerQAT` site so FIXAR's
Algorithm 1 applies to any architecture (DESIGN.md §4).
"""
from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core import fixedpoint as fxp
from repro.core.parallelism import Logical, ShardingRules, constrain
from repro.core.ranges import RangeStat, finalized, update_minmax
from repro.models.config import ModelConfig

Array = jax.Array
Params = dict[str, Any]

# ---------------------------------------------------------------------------
# QAT sites for stacked-layer scans
# ---------------------------------------------------------------------------

# site names per block type (used to build the stacked (L,) range trees)
ATTN_SITES = ("attn_in", "attn_o_in", "mlp_in", "mlp_down_in")
MOE_SITES = ("attn_in", "attn_o_in", "router_in", "expert_in", "expert_down_in")
RWKV_SITES = ("tmix_in", "cmix_in")
RGLRU_SITES = ("rnn_in", "mlp_in", "mlp_down_in")
MAMBA2_SITES = ("mamba_in", "mamba_out_in", "mlp_in", "mlp_down_in")
HEAD_SITES = ("head_in",)


class LayerQAT:
    """Per-layer QAT context: scalar RangeStats (sliced from the stacked
    (L,) tree by the layer scan), the traced phase flag, and the collected
    updates.  None-stats => QAT disabled (plain passthrough)."""

    def __init__(self, stats: Optional[dict[str, RangeStat]],
                 quant_phase: Optional[Array], n_bits: int = 16):
        self.stats = dict(stats) if stats is not None else None
        self.quant_phase = quant_phase
        self.n_bits = n_bits

    def site(self, name: str, x: Array) -> Array:
        if self.stats is None:
            return x
        stat = self.stats[name]
        xf = x.astype(jnp.float32)
        cand = update_minmax(stat, jax.lax.stop_gradient(xf))
        new_stat = jax.tree.map(
            lambda old, new: jnp.where(self.quant_phase, old, new), stat, cand)
        self.stats[name] = new_stat
        a_min, a_max = finalized(new_stat)
        x_q = fxp.fake_quant_affine(xf, a_min, a_max, self.n_bits)
        x_full = fxp.fake_quant(xf, fxp.FXP32)
        return jnp.where(self.quant_phase, x_q, x_full).astype(x.dtype)

    def collect(self) -> Optional[dict[str, RangeStat]]:
        return self.stats

    # -- extension points for shard_map regions (see moe.py) ----------------
    def params_for(self, name: str):
        """(a_min, a_max, quant_phase) for quantizing inside a shard_map
        body, where `site()` cannot thread the stat update itself."""
        if self.stats is None:
            return None
        a_min, a_max = finalized(self.stats[name])
        return a_min, a_max, self.quant_phase

    def fold_external(self, name: str, local_min: Array, local_max: Array):
        """Fold externally-computed (already cross-shard-reduced) min/max
        into a site's running stats (same freeze-after-delay rule)."""
        if self.stats is None:
            return
        stat = self.stats[name]
        cand = RangeStat(
            a_min=jnp.minimum(stat.a_min, local_min).astype(jnp.float32),
            a_max=jnp.maximum(stat.a_max, local_max).astype(jnp.float32),
            count=stat.count + 1)
        self.stats[name] = jax.tree.map(
            lambda old, new: jnp.where(self.quant_phase, old, new), stat, cand)


def init_site_ranges(sites: tuple[str, ...], n: int) -> dict[str, RangeStat]:
    """Stacked (n,) range tree for n layers of one pattern slot."""
    mk = lambda v: jnp.full((n,), v, jnp.float32)
    return {s: RangeStat(a_min=mk(jnp.inf), a_max=mk(-jnp.inf),
                         count=jnp.zeros((n,), jnp.int32)) for s in sites}


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def norm_init(cfg: ModelConfig) -> Params:
    p = {"scale": jnp.ones((cfg.d_model,), jnp.float32)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((cfg.d_model,), jnp.float32)
    return p


def norm_specs(cfg: ModelConfig) -> Params:
    p = {"scale": Logical("embed")}
    if cfg.norm == "layernorm":
        p["bias"] = Logical("embed")
    return p


def apply_norm(x: Array, p: Params, cfg: ModelConfig,
               eps: Optional[float] = None) -> Array:
    eps = cfg.norm_eps if eps is None else eps
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.var(xf, -1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    else:
        ms = jnp.mean(jnp.square(xf), -1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * p["scale"]
    return y.astype(x.dtype)


def group_norm_heads(x: Array, scale: Array, bias: Array, n_heads: int,
                     eps: float = 64e-5) -> Array:
    """Per-head group norm (RWKV wkv output norm). x: (..., H*hd)."""
    shape = x.shape
    xf = x.astype(jnp.float32).reshape(*shape[:-1], n_heads, -1)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.var(xf, -1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y.reshape(shape) * scale + bias).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope(x: Array, positions: Array, theta: float) -> Array:
    """Rotary embedding. x: (B, S, H, hd), positions: (B, S) or (S,)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (B,S,half)|(S,half)
    if ang.ndim == 2:  # (S, half) -> broadcast over batch
        ang = ang[None]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], -1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Dense helper
# ---------------------------------------------------------------------------


def _uniform_init(key, shape, fan_in):
    bound = fan_in ** -0.5
    return jax.random.uniform(key, shape, jnp.float32, -bound, bound)


# ---------------------------------------------------------------------------
# GQA attention (global / sliding-window, causal / bidirectional)
# ---------------------------------------------------------------------------


def attn_init(key, cfg: ModelConfig) -> Params:
    d, hq, hk, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    p = {
        "wq": _uniform_init(ks[0], (d, hq, hd), d),
        "wk": _uniform_init(ks[1], (d, hk, hd), d),
        "wv": _uniform_init(ks[2], (d, hk, hd), d),
        "wo": _uniform_init(ks[3], (hq, hd, d), hq * hd),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq, hd), jnp.float32)
        p["bk"] = jnp.zeros((hk, hd), jnp.float32)
        p["bv"] = jnp.zeros((hk, hd), jnp.float32)
    return p


def attn_specs(cfg: ModelConfig) -> Params:
    p = {
        "wq": Logical("embed", "q_heads", "head_dim"),
        "wk": Logical("embed", "kv_heads", "head_dim"),
        "wv": Logical("embed", "kv_heads", "head_dim"),
        "wo": Logical("q_heads", "head_dim", "embed"),
    }
    if cfg.qkv_bias:
        p["bq"] = Logical("q_heads", "head_dim")
        p["bk"] = Logical("kv_heads", "head_dim")
        p["bv"] = Logical("kv_heads", "head_dim")
    return p


def _qkv(x, p, cfg: ModelConfig, qat: LayerQAT):
    x = qat.site("attn_in", x)
    dt = cfg.compute_dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    return q, k, v


def _mask(q_pos: Array, k_pos: Array, cfg: ModelConfig, local: bool,
          q_seg: Optional[Array] = None, k_seg: Optional[Array] = None) -> Array:
    """(…, Sq, Sk) boolean mask. q_pos/k_pos: (..., S).  With segment
    (document) ids (B, Sq) / (B, Sk), a query sees only its own document."""
    m = jnp.ones(q_pos.shape[:-1] + (q_pos.shape[-1], k_pos.shape[-1]),
                 jnp.bool_)
    qp = q_pos[..., :, None]
    kp = k_pos[..., None, :]
    if cfg.causal:
        m = jnp.logical_and(m, kp <= qp)
    if local:
        m = jnp.logical_and(m, kp > qp - cfg.window)
    if q_seg is not None:
        m = jnp.logical_and(m, q_seg[:, :, None] == k_seg[:, None, :])
    return m


def _scale(cfg: ModelConfig, hd: int) -> float:
    return 1.0 / math.sqrt(hd) if cfg.attn_scale is None else cfg.attn_scale


def _sdpa(q, k, v, mask, cfg: ModelConfig, rules) -> Array:
    """Grouped scaled-dot-product attention.
    q: (B,Sq,Hq,hd), k/v: (B,Sk,Hk,hd), mask: (B,Sq,Sk) or (Sq,Sk)."""
    b, sq, hq, hd = q.shape
    hk = k.shape[2]
    g = hq // hk
    qg = q.reshape(b, sq, hk, g, hd)
    scores = jnp.einsum("bskgh,btkh->bkgst", qg, k).astype(jnp.float32)
    scores = scores * _scale(cfg, hd)
    if mask.ndim == 2:
        mask = mask[None]
    scores = jnp.where(mask[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v)
    return out.reshape(b, sq, hq, hd)


def _banded_local_sdpa(q, k, v, cfg: ModelConfig) -> Array:
    """Sliding-window attention over (prev, self) key chunks — O(S·2w)
    scores instead of O(S²) (§Perf-3).  Exactly equivalent to the full-score
    band mask for window w = chunk width; verified in
    tests/kernels/test_attention.py.  q: (B,S,Hq,hd), k/v: (B,S,Hk,hd)."""
    w = cfg.window
    b, s, hq, hd = q.shape
    hk = k.shape[2]
    g = hq // hk
    nc = s // w
    qc = q.reshape(b, nc, w, hk, g, hd)
    kc = k.reshape(b, nc, w, hk, hd)
    vc = v.reshape(b, nc, w, hk, hd)
    kk = jnp.concatenate(
        [jnp.concatenate([jnp.zeros_like(kc[:, :1]), kc[:, :-1]], 1), kc], 2)
    vv = jnp.concatenate(
        [jnp.concatenate([jnp.zeros_like(vc[:, :1]), vc[:, :-1]], 1), vc], 2)

    scores = jnp.einsum("znakgh,znmkh->znkgam", qc, kk).astype(jnp.float32)
    scores = scores * _scale(cfg, hd)
    a_idx = jnp.arange(w)[:, None]
    m_idx = jnp.arange(2 * w)[None, :]
    band = jnp.logical_and(m_idx <= w + a_idx, m_idx > a_idx)
    first_ok = m_idx >= w            # chunk 0 has no previous chunk
    chunk_i = jnp.arange(nc)[:, None, None]
    mask = jnp.logical_and(band[None], jnp.logical_or(chunk_i > 0,
                                                      first_ok[None]))
    scores = jnp.where(mask[None, :, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, -1).astype(q.dtype)
    out = jnp.einsum("znkgam,znmkh->znakgh", probs, vv)
    return out.reshape(b, s, hq, hd)


def attn_forward(x: Array, p: Params, cfg: ModelConfig, *, local: bool,
                 positions: Array, rules: Optional[ShardingRules],
                 qat: LayerQAT, chunk: int = 0, unroll: bool = False,
                 cache: Optional[dict[str, Array]] = None,
                 segment_ids: Optional[Array] = None
                 ) -> tuple[Array, Optional[dict[str, Array]]]:
    """Full-sequence attention (train / prefill). x: (B, S, d).

    `chunk` bounds the score-matrix working set by scanning query chunks
    (under `cfg.remat == "block"` each is rematerialized in the backward
    pass, so that no chunk's scores outlive it); `unroll=True` replaces
    the scan with a python loop (identical math, no while-loop — used by
    the roofline harness, where cost_analysis must see every chunk).

    `segment_ids` (B, S) (packed documents): a query attends only to keys
    of its own document.

    `cache` (prefill): a decode-shaped KV cache ({"k","v"}: (B, T, Hk, hd));
    the prompt's roped K / raw V are written into the exact slots
    `attn_decode` would have used (ring layout p % T for local layers,
    absolute positions for global), so decode can continue at pos = S.
    Returns (y, written_cache) — cache is None when none was passed."""
    q, k, v = _qkv(x, p, cfg, qat)
    if cfg.rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    q = constrain(q, rules, "batch", "seq", "q_heads", "head_dim")
    k = constrain(k, rules, "batch", "seq", "kv_heads", "head_dim")

    if cache is not None and positions.ndim == 1:
        s_all = x.shape[1]
        t = cache["k"].shape[1]
        keep = min(s_all, t)  # ring keeps only the last window of the prompt
        slots = positions[-keep:]
        if local and t <= cfg.window:
            slots = slots % t
        elif s_all > t:
            # absolute-slot cache: positions >= t would be silently dropped
            # by the out-of-bounds scatter and decode would read zeros
            raise ValueError(
                f"prompt length {s_all} exceeds the KV cache length {t}; "
                "init_cache with max_seq >= prompt + max_new")
        cache = {"k": cache["k"].at[:, slots].set(
                     k[:, s_all - keep:].astype(cache["k"].dtype)),
                 "v": cache["v"].at[:, slots].set(
                     v[:, s_all - keep:].astype(cache["v"].dtype))}

    s = x.shape[1]
    seg = segment_ids
    if local and s >= 2 * cfg.window and s % cfg.window == 0 \
            and positions.ndim == 1 and seg is None:
        out = _banded_local_sdpa(q, k, v, cfg)
    elif chunk and s > chunk:
        n_chunks = s // chunk
        assert s % chunk == 0

        def body(carry, qc_pc):
            qc, pc, sc = qc_pc
            m = _mask(pc, positions, cfg, local, sc, seg)
            oc = _sdpa(qc, k, v, m, cfg, rules)
            return carry, oc

        if cfg.remat == "block":
            body = jax.checkpoint(body)

        split = lambda a: a.reshape(x.shape[0], n_chunks, chunk,
                                    *a.shape[2:]).swapaxes(0, 1)
        qs = split(q)
        ps = positions.reshape(n_chunks, chunk) if positions.ndim == 1 else \
            split(positions)
        ss = split(seg) if seg is not None else None
        if unroll:
            outs = jnp.stack([body(None, (qs[i], ps[i],
                                          None if ss is None else ss[i]))[1]
                              for i in range(n_chunks)])
        else:
            _, outs = jax.lax.scan(body, None, (qs, ps, ss))
        out = outs.swapaxes(0, 1).reshape(x.shape[0], s, cfg.n_heads, cfg.hd)
    else:
        m = _mask(positions, positions, cfg, local, seg, seg)
        out = _sdpa(q, k, v, m, cfg, rules)

    out = qat.site("attn_o_in", out.reshape(x.shape[0], s, -1))
    out = out.reshape(x.shape[0], s, cfg.n_heads, cfg.hd)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(cfg.compute_dtype))
    return constrain(y, rules, "batch", "seq", "embed"), cache


def attn_decode(x: Array, p: Params, cfg: ModelConfig, *, local: bool,
                cache: dict[str, Array], pos: Array,
                rules: Optional[ShardingRules], qat: LayerQAT
                ) -> tuple[Array, dict[str, Array]]:
    """One-token decode against a KV cache.

    x: (B, 1, d); cache: {"k","v"}: (B, T, Hk, hd); pos: () current index,
    or (B,) per-row indices — the continuous-batching case (serve/lm),
    where every cache lane decodes at its own position.

    Local layers use a RING cache of length `window` (§Perf-3): slot j
    holds position p_j = pos − ((pos − j) mod w), which is always inside
    the window, so the whole buffer is attended with an "is-filled" mask —
    O(w) storage and O(w) reads instead of O(S) for sliding-window layers
    (the long_500k storage win for gemma3/recurrentgemma).
    """
    q, k_new, v_new = _qkv(x, p, cfg, qat)
    pos = jnp.asarray(pos, jnp.int32)
    per_row = pos.ndim == 1
    positions = pos[:, None] if per_row else jnp.full((x.shape[0], 1), pos, jnp.int32)
    if cfg.rope:
        q = rope(q, positions, cfg.rope_theta)
        k_new = rope(k_new, positions, cfg.rope_theta)

    t = cache["k"].shape[1]
    ring = local and t <= cfg.window
    slot = (pos % t).astype(jnp.int32) if ring else pos
    if per_row:
        # per-row scatter: lane b writes its own slot (vectorized .at[]
        # instead of dynamic_update_slice, which needs one shared index)
        rows = jnp.arange(x.shape[0])
        k_cache = cache["k"].at[rows, slot].set(k_new[:, 0].astype(cache["k"].dtype))
        v_cache = cache["v"].at[rows, slot].set(v_new[:, 0].astype(cache["v"].dtype))
    else:
        k_cache = jax.lax.dynamic_update_slice(
            cache["k"], k_new.astype(cache["k"].dtype), (0, slot, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            cache["v"], v_new.astype(cache["v"].dtype), (0, slot, 0, 0))
    k_cache = constrain(k_cache, rules, "batch", "kv_seq", "kv_heads", "head_dim")
    v_cache = constrain(v_cache, rules, "batch", "kv_seq", "kv_heads", "head_dim")

    j = jnp.arange(t, dtype=jnp.int32)
    kpos = pos[:, None] if per_row else pos    # (B, 1) against j's (T,)
    if ring:
        slot_pos = kpos - (kpos - j) % t   # position stored in slot j
        valid = slot_pos >= 0              # slot filled yet?
    else:
        valid = j <= kpos
        if local:
            valid = jnp.logical_and(valid, j > kpos - cfg.window)
    # (B, Sq=1, Sk) when per-row, (1, Sq=1, Sk) broadcast otherwise
    mask = valid[:, None, :] if per_row else valid[None, None, :]

    out = _sdpa(q, k_cache, v_cache, mask, cfg, rules)
    out = qat.site("attn_o_in", out.reshape(x.shape[0], 1, -1))
    out = out.reshape(x.shape[0], 1, cfg.n_heads, cfg.hd)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(cfg.compute_dtype))
    return y, {"k": k_cache, "v": v_cache}


# ---------------------------------------------------------------------------
# MLP (dense; MoE lives in moe.py)
# ---------------------------------------------------------------------------


def mlp_init(key, cfg: ModelConfig) -> Params:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp_type == "glu":
        return {"wg": _uniform_init(ks[0], (d, f), d),
                "wu": _uniform_init(ks[1], (d, f), d),
                "wd": _uniform_init(ks[2], (f, d), f)}
    return {"wu": _uniform_init(ks[0], (d, f), d),
            "wd": _uniform_init(ks[1], (f, d), f),
            "bu": jnp.zeros((f,), jnp.float32),
            "bd": jnp.zeros((d,), jnp.float32)}


def mlp_specs(cfg: ModelConfig) -> Params:
    if cfg.mlp_type == "glu":
        return {"wg": Logical("embed", "mlp"), "wu": Logical("embed", "mlp"),
                "wd": Logical("mlp", "embed")}
    return {"wu": Logical("embed", "mlp"), "wd": Logical("mlp", "embed"),
            "bu": Logical("mlp"), "bd": Logical("embed")}


def _act(x, kind: str):
    return jax.nn.silu(x) if kind == "silu" else jax.nn.gelu(x)


def mlp_forward(x: Array, p: Params, cfg: ModelConfig,
                rules: Optional[ShardingRules], qat: LayerQAT,
                site_prefix: str = "mlp") -> Array:
    dt = cfg.compute_dtype
    x = qat.site(f"{site_prefix}_in", x)
    if cfg.mlp_type == "glu":
        h = _act(x @ p["wg"].astype(dt), cfg.act) * (x @ p["wu"].astype(dt))
    else:
        h = _act(x @ p["wu"].astype(dt) + p["bu"].astype(dt), cfg.act)
    h = constrain(h, rules, "batch", "seq", "mlp")
    h = qat.site(f"{site_prefix}_down_in", h)
    y = h @ p["wd"].astype(dt)
    if cfg.mlp_type != "glu":
        y = y + p["bd"].astype(dt)
    return constrain(y, rules, "batch", "seq", "embed")


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def embed_init(key, cfg: ModelConfig) -> Params:
    ke, kh = jax.random.split(key)
    p = {"embedding": jax.random.normal(ke, (cfg.vocab_size, cfg.d_model),
                                        jnp.float32) * cfg.d_model ** -0.5}
    if not cfg.tie_embeddings:
        p["head"] = _uniform_init(kh, (cfg.d_model, cfg.vocab_size), cfg.d_model)
    return p


def embed_specs(cfg: ModelConfig) -> Params:
    p = {"embedding": Logical("vocab", "embed")}
    if not cfg.tie_embeddings:
        p["head"] = Logical("embed", "vocab")
    return p


def embed_tokens(tokens: Array, p: Params, cfg: ModelConfig,
                 rules: Optional[ShardingRules]) -> Array:
    x = p["embedding"].astype(cfg.compute_dtype)[tokens]
    scale = math.sqrt(cfg.d_model) if cfg.embed_scale is None else cfg.embed_scale
    x = x * jnp.asarray(scale, cfg.compute_dtype)
    return constrain(x, rules, "batch", "seq", "embed")


def lm_head(x: Array, p: Params, cfg: ModelConfig,
            rules: Optional[ShardingRules], qat: LayerQAT) -> Array:
    x = qat.site("head_in", x)
    w = (p["embedding"].T if cfg.tie_embeddings else p["head"])
    logits = scale_logits(x @ w.astype(cfg.compute_dtype), cfg)
    return constrain(logits, rules, "batch", "seq", "vocab")


def scale_logits(logits: Array, cfg: ModelConfig) -> Array:
    """Granite divides the head's output by `logits_scaling`."""
    if cfg.logits_scaling == 1.0:
        return logits
    return logits * jnp.asarray(1.0 / cfg.logits_scaling, logits.dtype)
