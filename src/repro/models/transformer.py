"""Model assembly: stacked-scan layer execution for all 10 architectures.

Layer stacks are grouped by `block_pattern` period (MaxText-style): the
params of every period are stacked along a leading axis and executed with
`jax.lax.scan` (remainder layers unrolled as the "tail").  The scan carries
activations and threads per-layer QAT ranges and recurrent state / KV caches
through the xs/ys, so one compiled period body serves the whole depth —
compile time stays flat in depth, which matters on the 512-device dry-run.

Public API
----------
  init_params(key, cfg)                        parameter pytree
  param_specs(cfg)                             matching Logical tree
  init_ranges(cfg)                             stacked QAT range tree
  forward(params, batch, cfg, ...)             logits (train/prefill path)
  loss_fn(params, batch, cfg, ...)             scalar loss + aux
  init_cache(cfg, batch, max_seq)              decode caches/states
  cache_specs(cfg, ...)                        Logical tree for caches
  decode_step(params, tokens, cache, pos, ...) one-token serve step
  period_apply / tail shapes                   exposed for the roofline harness
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.parallelism import Logical, ShardingRules, constrain
from repro.models import frontend as fe
from repro.models import mamba2 as mamba_mod
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import rwkv6 as rwkv_mod
from repro.models.config import ATTN_GLOBAL, ATTN_LOCAL, MAMBA2, RGLRU, RWKV6, ModelConfig
from repro.models import layers as L

Array = jax.Array
Params = dict[str, Any]


# ---------------------------------------------------------------------------
# per-block init / specs
# ---------------------------------------------------------------------------


def block_sites(cfg: ModelConfig, bt: str) -> tuple[str, ...]:
    if bt in (ATTN_GLOBAL, ATTN_LOCAL):
        return L.MOE_SITES if cfg.is_moe else L.ATTN_SITES
    if bt == RWKV6:
        return L.RWKV_SITES
    if bt == RGLRU:
        return L.RGLRU_SITES
    if bt == MAMBA2:
        return L.MAMBA2_SITES
    raise ValueError(bt)


def block_init(key, cfg: ModelConfig, bt: str) -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    if bt in (ATTN_GLOBAL, ATTN_LOCAL):
        ffn = moe_mod.moe_init(k4, cfg) if cfg.is_moe else L.mlp_init(k4, cfg)
        return {"ln1": L.norm_init(cfg), "attn": L.attn_init(k2, cfg),
                "ln2": L.norm_init(cfg), "ffn": ffn}
    if bt == RWKV6:
        return {"ln1": L.norm_init(cfg), "ln2": L.norm_init(cfg),
                "rwkv": rwkv_mod.rwkv_init(k2, cfg)}
    if bt == RGLRU:
        return {"ln1": L.norm_init(cfg), "rnn": rglru_mod.rglru_init(k2, cfg),
                "ln2": L.norm_init(cfg), "ffn": L.mlp_init(k4, cfg)}
    if bt == MAMBA2:
        return {"ln1": L.norm_init(cfg), "mamba": mamba_mod.mamba2_init(k2, cfg),
                "ln2": L.norm_init(cfg), "ffn": L.mlp_init(k4, cfg)}
    raise ValueError(bt)


def block_specs(cfg: ModelConfig, bt: str) -> Params:
    if bt in (ATTN_GLOBAL, ATTN_LOCAL):
        ffn = moe_mod.moe_specs(cfg) if cfg.is_moe else L.mlp_specs(cfg)
        return {"ln1": L.norm_specs(cfg), "attn": L.attn_specs(cfg),
                "ln2": L.norm_specs(cfg), "ffn": ffn}
    if bt == RWKV6:
        return {"ln1": L.norm_specs(cfg), "ln2": L.norm_specs(cfg),
                "rwkv": rwkv_mod.rwkv_specs(cfg)}
    if bt == RGLRU:
        return {"ln1": L.norm_specs(cfg), "rnn": rglru_mod.rglru_specs(cfg),
                "ln2": L.norm_specs(cfg), "ffn": L.mlp_specs(cfg)}
    if bt == MAMBA2:
        return {"ln1": L.norm_specs(cfg), "mamba": mamba_mod.mamba2_specs(cfg),
                "ln2": L.norm_specs(cfg), "ffn": L.mlp_specs(cfg)}
    raise ValueError(bt)


# ---------------------------------------------------------------------------
# whole-model init / specs
# ---------------------------------------------------------------------------


def init_params(key, cfg: ModelConfig) -> Params:
    kf, ke, *kblocks = jax.random.split(key, 2 + len(cfg.block_pattern) + cfg.n_tail)
    params: Params = {"embed": L.embed_init(ke, cfg),
                      "final_norm": L.norm_init(cfg),
                      "frontend": fe.frontend_init(kf, cfg)}
    scan = []
    for s, bt in enumerate(cfg.block_pattern):
        keys = jax.random.split(jax.random.fold_in(kblocks[s], s), cfg.n_periods)
        scan.append(jax.vmap(lambda k: block_init(k, cfg, bt))(keys))
    params["scan"] = scan
    params["tail"] = [block_init(kblocks[len(cfg.block_pattern) + i], cfg,
                                 cfg.block_pattern[i])
                      for i in range(cfg.n_tail)]
    return params


def _add_leading(spec_tree):
    """Prefix a `layers` (never-sharded) axis for stacked params."""
    return jax.tree.map(lambda l: Logical("layers", *l.axes), spec_tree,
                        is_leaf=lambda x: isinstance(x, Logical))


def param_specs(cfg: ModelConfig) -> Params:
    specs: Params = {"embed": L.embed_specs(cfg),
                     "final_norm": L.norm_specs(cfg),
                     "frontend": fe.frontend_specs(cfg)}
    specs["scan"] = [_add_leading(block_specs(cfg, bt))
                     for bt in cfg.block_pattern]
    specs["tail"] = [block_specs(cfg, cfg.block_pattern[i])
                     for i in range(cfg.n_tail)]
    return specs


def init_ranges(cfg: ModelConfig) -> Params:
    """QAT range trees (stacked for scan slots, scalar for tail/head)."""
    r = {"scan": [L.init_site_ranges(block_sites(cfg, bt), cfg.n_periods)
                  for bt in cfg.block_pattern],
         "tail": [L.init_site_ranges(block_sites(cfg, cfg.block_pattern[i]), 1)
                  for i in range(cfg.n_tail)],
         "head": L.init_site_ranges(L.HEAD_SITES, 1)}
    return r


def ranges_specs(cfg: ModelConfig) -> Params:
    rep = lambda tree: jax.tree.map(lambda _: Logical(None), tree)
    return rep(init_ranges(cfg))


# ---------------------------------------------------------------------------
# block forward (full-sequence)
# ---------------------------------------------------------------------------


def _residual(x: Array, h: Array, cfg: ModelConfig) -> Array:
    """x + h, the branch scaled first where the config says (granite)."""
    if cfg.residual_scale != 1.0:
        h = h * jnp.asarray(cfg.residual_scale, h.dtype)
    return x + h


def _ffn(x: Array, bp: Params, cfg: ModelConfig, rules, qat) -> tuple[Array, Array]:
    """The feed-forward half of a block, with its norm and residual."""
    aux = jnp.float32(0)
    with jax.named_scope("ffn"):
        h = L.apply_norm(x, bp["ln2"], cfg)
        if cfg.is_moe:
            h, aux = moe_mod.moe_forward(h, bp["ffn"], cfg, rules, qat)
        else:
            h = L.mlp_forward(h, bp["ffn"], cfg, rules, qat)
        return _residual(x, h, cfg), aux


def block_forward(x: Array, bp: Params, cfg: ModelConfig, bt: str, *,
                  positions: Array, rules: Optional[ShardingRules],
                  qat: L.LayerQAT, state: Optional[dict] = None,
                  attn_chunk: int = 0, unroll: bool = False,
                  segment_ids: Optional[Array] = None
                  ) -> tuple[Array, Optional[dict], Array]:
    """Returns (x_out, new_state, aux_loss).  Attention blocks lie in the
    `attn` scope, Mamba-2 mixers in `mamba_proj`/`ssd`, feed-forward halves
    in `ffn` (train/step.PHASES)."""
    aux = jnp.float32(0)
    if state is None and _needs_state(bt):
        # training / stateless prefill: fresh zero recurrent state
        state = _block_state_init(cfg, bt, x.shape[0], 0, for_decode=False)
    if bt in (ATTN_GLOBAL, ATTN_LOCAL):
        with jax.named_scope("attn"):
            h = L.apply_norm(x, bp["ln1"], cfg)
            h, state = L.attn_forward(h, bp["attn"], cfg,
                                      local=(bt == ATTN_LOCAL),
                                      positions=positions, rules=rules, qat=qat,
                                      chunk=attn_chunk, unroll=unroll,
                                      cache=state, segment_ids=segment_ids)
            x = _residual(x, h, cfg)
        x, aux = _ffn(x, bp, cfg, rules, qat)
        return x, state, aux
    if bt == MAMBA2:
        if state is not None:
            raise NotImplementedError("Mamba-2 blocks have no decode state")
        with jax.named_scope("mamba_proj"):
            h = L.apply_norm(x, bp["ln1"], cfg)
        h = mamba_mod.mamba2_forward(h, bp["mamba"], cfg, rules, qat, segment_ids)
        with jax.named_scope("mamba_proj"):
            x = _residual(x, h, cfg)
        x, aux = _ffn(x, bp, cfg, rules, qat)
        return x, state, aux
    if bt == RWKV6:
        h = L.apply_norm(x, bp["ln1"], cfg)
        h, state = rwkv_mod.time_mix(h, bp["rwkv"], cfg, state, rules, qat,
                                     unroll=unroll)
        x = x + h
        h = L.apply_norm(x, bp["ln2"], cfg)
        h, state = rwkv_mod.channel_mix(h, bp["rwkv"], cfg, state, rules, qat)
        return x + h, state, aux
    if bt == RGLRU:
        h = L.apply_norm(x, bp["ln1"], cfg)
        h, state = rglru_mod.rglru_forward(h, bp["rnn"], cfg, state, rules, qat)
        x = x + h
        h = L.apply_norm(x, bp["ln2"], cfg)
        h = L.mlp_forward(h, bp["ffn"], cfg, rules, qat)
        return x + h, state, aux
    raise ValueError(bt)


def _needs_state(bt: str) -> bool:
    return bt in (RWKV6, RGLRU)


def _block_state_init(cfg: ModelConfig, bt: str, batch: int, max_seq: int,
                      for_decode: bool):
    """Initial recurrent state / KV cache for one layer of type bt."""
    if bt == RWKV6:
        return rwkv_mod.init_state(cfg, batch)
    if bt == RGLRU:
        return rglru_mod.init_state(cfg, batch)
    if bt == MAMBA2:
        raise NotImplementedError("Mamba-2 blocks have no decode state")
    if for_decode:  # attention KV cache; local layers use a window ring
        t = min(max_seq, cfg.window) if bt == ATTN_LOCAL else max_seq
        return {"k": jnp.zeros((batch, t, cfg.n_kv_heads, cfg.hd),
                               cfg.compute_dtype),
                "v": jnp.zeros((batch, t, cfg.n_kv_heads, cfg.hd),
                               cfg.compute_dtype)}
    return None


def _block_state_specs(cfg: ModelConfig, bt: str, for_decode: bool):
    if bt == RWKV6:
        return rwkv_mod.state_specs(cfg)
    if bt == RGLRU:
        return rglru_mod.state_specs(cfg)
    if for_decode:
        s = Logical("batch", "kv_seq", "kv_heads", "head_dim")
        return {"k": s, "v": s}
    return None


# ---------------------------------------------------------------------------
# full forward (train / prefill)
# ---------------------------------------------------------------------------


def _remat_wrap(fn, cfg: ModelConfig, enable: bool):
    if not enable or cfg.remat in ("none", "block"):
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)


def forward(params: Params, batch: dict[str, Array], cfg: ModelConfig, *,
            rules: Optional[ShardingRules] = None,
            ranges: Optional[Params] = None,
            quant_phase: Optional[Array] = None,
            states: Optional[Params] = None,
            remat: bool = False, attn_chunk: int = 0,
            unroll: bool = False, skip_head: bool = False
            ) -> tuple[Array, dict[str, Any]]:
    """Full-sequence forward. Returns (logits, {"ranges", "states", "aux"}).

    `states` (prefill): {"scan": [stacked per slot], "tail": [...]} —
    when provided, recurrent blocks consume/produce them and attention
    blocks write KV caches (prefill mode).
    """
    qat_on = ranges is not None
    with jax.named_scope("embed"):
        if "tokens" in batch:
            x = L.embed_tokens(batch["tokens"], params["embed"], cfg, rules)
            b, s = batch["tokens"].shape
        else:  # audio frontend: embeddings only
            b, s, _ = batch["frontend"].shape
            x = jnp.zeros((b, s, cfg.d_model), cfg.compute_dtype)
        x = fe.apply_frontend(x, params["frontend"], batch, cfg, rules)
    positions = jnp.arange(s, dtype=jnp.int32)
    segment_ids = batch.get("segment_ids")

    def apply_block(x, bp, bt, stats, st):
        """One block; returns (x, state, aux, its updated QAT ranges)."""
        qat = L.LayerQAT(stats, quant_phase, cfg.qat_bits)
        x, st, a = block_forward(x, bp, cfg, bt, positions=positions, rules=rules,
                                 qat=qat, state=st, attn_chunk=attn_chunk,
                                 unroll=unroll, segment_ids=segment_ids)
        return x, st, a, qat.collect()

    if remat and cfg.remat == "block":
        apply_block = jax.checkpoint(apply_block, static_argnums=(2,))

    m = len(cfg.block_pattern)
    has_states = states is not None
    aux_total = jnp.float32(0)
    new_ranges = {"scan": [], "tail": []} if qat_on else None
    new_states = {"scan": [], "tail": []} if has_states else None

    def make_period(slot_types):
        def period(carry, xs):
            x, aux = carry
            bps, rngs, sts = xs
            new_rngs, new_sts = [], []
            for i, bt in enumerate(slot_types):
                x, st, a, rng = apply_block(x, bps[i], bt,
                                            rngs[i] if qat_on else None, sts[i])
                aux = aux + a
                new_rngs.append(rng)
                new_sts.append(st)
            x = constrain(x, rules, "batch", "seq", "embed")
            ys = (new_rngs if qat_on else None,
                  new_sts if has_states else None)
            return (x, aux), ys
        return period

    # ---- scanned periods ---------------------------------------------------
    if cfg.n_periods > 0:
        period = _remat_wrap(make_period(cfg.block_pattern), cfg, remat)
        xs = (params["scan"],
              ranges["scan"] if qat_on else [None] * m,
              states["scan"] if has_states else [None] * m)
        if unroll:
            carry, ys_list = (x, aux_total), []
            for i in range(cfg.n_periods):
                carry, ys_i = period(carry, jax.tree.map(lambda a: a[i], xs))
                ys_list.append(ys_i)
            (x, aux_total) = carry
            ys = jax.tree.map(lambda *a: jnp.stack(a), *ys_list)
        else:
            (x, aux_total), ys = jax.lax.scan(period, (x, aux_total), xs)
        if qat_on:
            new_ranges["scan"] = ys[0]
        if has_states:
            new_states["scan"] = ys[1]

    # ---- tail layers (unrolled) ---------------------------------------------
    for i in range(cfg.n_tail):
        bt = cfg.block_pattern[i]
        st = states["tail"][i] if has_states else None
        x, st, a, rng = apply_block(
            x, params["tail"][i], bt,
            _index_ranges(ranges["tail"][i], 0) if qat_on else None, st)
        aux_total = aux_total + a
        if qat_on:
            new_ranges["tail"].append(_unindex_ranges(rng))
        if has_states:
            new_states["tail"].append(st)

    # ---- head ----------------------------------------------------------------
    with jax.named_scope("head"):
        x = L.apply_norm(x, params["final_norm"], cfg)
        qat = L.LayerQAT(_index_ranges(ranges["head"], 0) if qat_on else None,
                         quant_phase, cfg.qat_bits)
        if skip_head:
            # chunked-CE path (§Perf-7): the caller fuses head matmul + loss
            # per sequence chunk so the (B,S,V) logits never materialize at
            # once.  The head QAT site still applies to the hidden stream here.
            out = qat.site("head_in", x.reshape(-1, x.shape[-1])).reshape(x.shape)
        else:
            out = L.lm_head(x, params["embed"], cfg, rules, qat)
    if qat_on:
        new_ranges["head"] = _unindex_ranges(qat.collect())
    return out, {"ranges": new_ranges, "states": new_states, "aux": aux_total}


def _index_ranges(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def _unindex_ranges(tree):
    return jax.tree.map(lambda a: a[None], tree)


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------


def loss_fn(params: Params, batch: dict[str, Array], cfg: ModelConfig, *,
            rules: Optional[ShardingRules] = None,
            ranges: Optional[Params] = None,
            quant_phase: Optional[Array] = None,
            remat: bool = True, attn_chunk: int = 0,
            aux_coef: float = 0.01, unroll: bool = False,
            ce_chunk: int = 0) -> tuple[Array, dict[str, Any]]:
    """`ce_chunk > 0` fuses head-matmul + cross-entropy per sequence chunk
    (§Perf-7): the (B, S, V) logits — 2 GiB/dev in bf16 for gemma3 train_4k,
    ×2 again as f32 inside the softmax — exist only one chunk at a time."""
    labels = batch["labels"]
    s = labels.shape[1]
    if ce_chunk and s > ce_chunk and s % ce_chunk == 0:
        hidden, extras = forward(params, batch, cfg, rules=rules,
                                 ranges=ranges, quant_phase=quant_phase,
                                 remat=remat, attn_chunk=attn_chunk,
                                 unroll=unroll, skip_head=True)
        nc = s // ce_chunk
        with jax.named_scope("head"):
            w = (params["embed"]["embedding"].T if cfg.tie_embeddings
                 else params["embed"]["head"]).astype(cfg.compute_dtype)
            hc = hidden.reshape(hidden.shape[0], nc, ce_chunk, -1).swapaxes(0, 1)
            lc = labels.reshape(labels.shape[0], nc, ce_chunk).swapaxes(0, 1)

        @jax.named_scope("head")
        def chunk_nll(carry, xl):
            xc, lab = xl
            logits = constrain(L.scale_logits(xc @ w, cfg), rules, "batch", "seq",
                               "vocab")
            lf = logits.astype(jnp.float32)
            lse = jax.nn.logsumexp(lf, axis=-1)
            tgt = jnp.take_along_axis(
                lf, jnp.maximum(lab, 0)[..., None], axis=-1)[..., 0]
            v = (lab >= 0).astype(jnp.float32)
            nll_sum, v_sum = carry
            return (nll_sum + jnp.sum((lse - tgt) * v),
                    v_sum + jnp.sum(v)), None

        if cfg.remat == "block":   # no chunk's logits outlive it
            chunk_nll = jax.checkpoint(chunk_nll)
        init = (jnp.float32(0), jnp.float32(0))
        if unroll:
            carry = init
            for i in range(nc):
                carry, _ = chunk_nll(carry, (hc[i], lc[i]))
        else:
            carry, _ = jax.lax.scan(chunk_nll, init, (hc, lc))
        loss = carry[0] / jnp.maximum(carry[1], 1.0)
    else:
        logits, extras = forward(params, batch, cfg, rules=rules,
                                 ranges=ranges, quant_phase=quant_phase,
                                 remat=remat, attn_chunk=attn_chunk,
                                 unroll=unroll)
        with jax.named_scope("head"):
            valid = (labels >= 0).astype(jnp.float32)
            lf = logits.astype(jnp.float32)
            lse = jax.nn.logsumexp(lf, axis=-1)
            target = jnp.take_along_axis(
                lf, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
            nll = (lse - target) * valid
            loss = jnp.sum(nll) / jnp.maximum(jnp.sum(valid), 1.0)
    if cfg.is_moe:
        loss = loss + aux_coef * extras["aux"] / max(cfg.n_layers, 1)
    return loss, extras


# ---------------------------------------------------------------------------
# serve: caches + decode
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_seq: int) -> Params:
    stack = lambda tree, n: jax.tree.map(
        lambda a: jnp.zeros((n,) + a.shape, a.dtype), tree)
    scan = []
    for bt in cfg.block_pattern:
        st = _block_state_init(cfg, bt, batch, max_seq, for_decode=True)
        scan.append(stack(st, cfg.n_periods))
    tail = [_block_state_init(cfg, cfg.block_pattern[i], batch, max_seq,
                              for_decode=True)
            for i in range(cfg.n_tail)]
    return {"scan": scan, "tail": tail}


def cache_specs(cfg: ModelConfig) -> Params:
    lead = lambda tree: jax.tree.map(
        lambda l: Logical("layers", *l.axes), tree,
        is_leaf=lambda x: isinstance(x, Logical))
    scan = [lead(_block_state_specs(cfg, bt, for_decode=True))
            for bt in cfg.block_pattern]
    tail = [_block_state_specs(cfg, cfg.block_pattern[i], for_decode=True)
            for i in range(cfg.n_tail)]
    return {"scan": scan, "tail": tail}


def _block_decode(x, bp, cfg, bt, *, cache, pos, rules, qat):
    if bt in (ATTN_GLOBAL, ATTN_LOCAL):
        h = L.apply_norm(x, bp["ln1"], cfg)
        h, cache = L.attn_decode(h, bp["attn"], cfg, local=(bt == ATTN_LOCAL),
                                 cache=cache, pos=pos, rules=rules, qat=qat)
        x = x + h
        h = L.apply_norm(x, bp["ln2"], cfg)
        if cfg.is_moe:
            h, _ = moe_mod.moe_forward(h, bp["ffn"], cfg, rules, qat)
        else:
            h = L.mlp_forward(h, bp["ffn"], cfg, rules, qat)
        return x + h, cache
    if bt == RWKV6:
        h = L.apply_norm(x, bp["ln1"], cfg)
        h, cache = rwkv_mod.decode_step(h, bp["rwkv"], cfg, cache, rules, qat,
                                        "tmix")
        x = x + h
        h = L.apply_norm(x, bp["ln2"], cfg)
        h, cache = rwkv_mod.decode_step(h, bp["rwkv"], cfg, cache, rules, qat,
                                        "cmix")
        return x + h, cache
    if bt == RGLRU:
        h = L.apply_norm(x, bp["ln1"], cfg)
        h, cache = rglru_mod.decode_step(h, bp["rnn"], cfg, cache, rules, qat)
        x = x + h
        h = L.apply_norm(x, bp["ln2"], cfg)
        h = L.mlp_forward(h, bp["ffn"], cfg, rules, qat)
        return x + h, cache
    raise ValueError(f"no decode path for {bt!r} blocks")


def decode_step(params: Params, tokens: Array, cache: Params, pos: Array,
                cfg: ModelConfig, *, rules: Optional[ShardingRules] = None,
                ranges: Optional[Params] = None,
                quant_phase: Optional[Array] = None, unroll: bool = False
                ) -> tuple[Array, Params]:
    """One-token decode. tokens: (B, 1); pos: () int32 current position, or
    (B,) per-row positions for continuously-batched decode (serve/lm) —
    attention layers scatter/mask per lane; recurrent blocks are
    position-independent either way."""
    qat_on = ranges is not None
    x = L.embed_tokens(tokens, params["embed"], cfg, rules)
    m = len(cfg.block_pattern)

    def period(carry, xs):
        x = carry
        bps, rngs, caches = xs
        new_caches = []
        for i, bt in enumerate(cfg.block_pattern):
            qat = L.LayerQAT(rngs[i] if qat_on else None, quant_phase,
                             cfg.qat_bits)
            x, c = _block_decode(x, bps[i], cfg, bt, cache=caches[i], pos=pos,
                                 rules=rules, qat=qat)
            new_caches.append(c)
        return x, new_caches

    if cfg.n_periods > 0:
        xs = (params["scan"],
              ranges["scan"] if qat_on else [None] * m,
              cache["scan"])
        if unroll:
            outs = []
            for i in range(cfg.n_periods):
                x, ci = period(x, jax.tree.map(lambda a: a[i], xs))
                outs.append(ci)
            new_scan = jax.tree.map(lambda *a: jnp.stack(a), *outs)
        else:
            x, new_scan = jax.lax.scan(period, x, xs)
    else:
        new_scan = []
    new_tail = []
    for i in range(cfg.n_tail):
        bt = cfg.block_pattern[i]
        qat = L.LayerQAT(
            _index_ranges(ranges["tail"][i], 0) if qat_on else None,
            quant_phase, cfg.qat_bits)
        x, c = _block_decode(x, params["tail"][i], cfg, bt,
                             cache=cache["tail"][i], pos=pos, rules=rules,
                             qat=qat)
        new_tail.append(c)

    x = L.apply_norm(x, params["final_norm"], cfg)
    qat = L.LayerQAT(_index_ranges(ranges["head"], 0) if qat_on else None,
                     quant_phase, cfg.qat_bits)
    logits = L.lm_head(x, params["embed"], cfg, rules, qat)
    return logits, {"scan": new_scan, "tail": new_tail}


def prefill(params: Params, batch: dict[str, Array], cfg: ModelConfig, *,
            rules: Optional[ShardingRules] = None, attn_chunk: int = 0,
            unroll: bool = False, cache: Optional[Params] = None):
    """Prompt processing; returns last-position logits.

    Without `cache` this is the logits-only path the dry-run cell lowers.
    With `cache` (from `init_cache`), the whole prompt is processed in ONE
    batched pass that also populates the KV caches / recurrent states —
    returns (last_logits, cache) ready for `decode_step` at pos = S."""
    logits, extras = forward(params, batch, cfg, rules=rules, remat=False,
                             states=cache, attn_chunk=attn_chunk,
                             unroll=unroll)
    last = logits[:, -1, :]
    return last if cache is None else (last, extras["states"])
