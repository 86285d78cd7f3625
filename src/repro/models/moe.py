"""Mixture-of-Experts FFN (dbrx 16e/top-4, moonshot 64e/top-6).

Capacity-based GShard-style dispatch implemented with scatter/gather so the
buffers stay O(T·K·d) — compile-friendly at the 1M-token train_4k cell.
Experts are sharded over the `model` mesh axis (expert parallelism); the
expert capacity dim is sharded over `data`, which makes XLA lower the
dispatch as an all-to-all over the token shards — the production EP comm
pattern.  Expert weights additionally shard d_ff over `data` (ZeRO/FSDP
style) so dbrx-132b's optimizer state fits 512 chips (DESIGN.md §5).

QAT: per-expert activations flow through the shared layer sites
("expert_in"/"expert_down_in") — ranges are per layer, not per expert,
matching the paper's per-tensor monitoring granularity.
"""
from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.parallelism import Logical, ShardingRules, constrain
from repro.models.config import ModelConfig
from repro.models.layers import LayerQAT, _act, _uniform_init

Array = jax.Array
Params = dict[str, Any]


def moe_init(key, cfg: ModelConfig) -> Params:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    return {
        "router": _uniform_init(ks[0], (d, e), d),
        "wg": _uniform_init(ks[1], (e, d, f), d),
        "wu": _uniform_init(ks[2], (e, d, f), d),
        "wd": _uniform_init(ks[3], (e, f, d), f),
    }


def moe_specs(cfg: ModelConfig) -> Params:
    return {
        "router": Logical("embed", "experts"),
        "wg": Logical("experts", "embed", "expert_ffn"),
        "wu": Logical("experts", "embed", "expert_ffn"),
        "wd": Logical("experts", "expert_ffn", "embed"),
    }


def _blocked_cumsum(x: Array, n_blocks: int = 4096) -> Array:
    """Exclusive-friendly two-level cumsum along axis 0.

    XLA lowers a flat `jnp.cumsum` over millions of rows to a quadratic
    reduce-window (measured: 1.1e12 flops for a (262k,64) cumsum vs 8.4e7
    for this form — §Perf-1), and scanning across the token shards drags
    collectives in at every level.  Two-level scan: block-local cumsum +
    cumsum of per-block totals; block count chosen so blocks align with the
    data sharding.  Bit-identical to the flat form (integer adds).
    """
    n = x.shape[0]
    nb = n_blocks
    while n % nb != 0:
        nb //= 2
    if nb <= 1:
        return jnp.cumsum(x, axis=0)
    blocks = x.reshape(nb, n // nb, *x.shape[1:])
    local = jnp.cumsum(blocks, axis=1)
    tot = local[:, -1]
    offsets = jnp.cumsum(tot, axis=0) - tot
    return (local + offsets[:, None]).reshape(x.shape)


def capacity(n_tokens: int, cfg: ModelConfig) -> int:
    c = int(math.ceil(n_tokens * cfg.experts_per_token / cfg.n_experts
                      * cfg.moe_capacity_factor))
    return max(8, -(-c // 8) * 8)  # round up to 8 for tiling


def moe_forward(x: Array, p: Params, cfg: ModelConfig,
                rules: Optional[ShardingRules], qat: LayerQAT
                ) -> tuple[Array, Array]:
    """x: (B, S, d) -> (y, aux_loss).  Dispatches to the shard_map
    expert-parallel path when a compatible mesh is active (§Perf-1b);
    falls back to the single-device dense-dispatch path otherwise."""
    mesh = None
    if rules is not None:
        try:
            am = jax.sharding.get_abstract_mesh()
            if not am.empty and "model" in am.axis_names:
                mesh = am
        except (ValueError, RuntimeError):
            mesh = None
    # The sharded path all-gathers the (FSDP-sharded) expert weights once
    # per layer — amortized over tokens.  Below ~64k tokens (decode shapes)
    # the dense path's scatter replication (∝ T·K·d) is cheaper than the
    # weight gather (∝ E_local·d·f), so decode stays on the dense path
    # (measured: sharded dbrx decode_32k collective 1.69 s vs 4 ms dense).
    big_enough = x.shape[0] * x.shape[1] >= 65536
    if mesh is not None and big_enough and cfg.n_experts % dict(
            zip(mesh.axis_names, mesh.axis_sizes))["model"] == 0:
        return _moe_forward_sharded(x, p, cfg, rules, qat, mesh)
    return _moe_forward_dense(x, p, cfg, rules, qat)


def _moe_forward_dense(x: Array, p: Params, cfg: ModelConfig,
                       rules: Optional[ShardingRules], qat: LayerQAT
                       ) -> tuple[Array, Array]:
    """Reference dispatch: capacity scatter/gather under auto-SPMD."""
    b, s, d = x.shape
    t = b * s
    k = cfg.experts_per_token
    e = cfg.n_experts
    c = capacity(t, cfg)
    dt = cfg.compute_dtype

    flat = x.reshape(t, d)
    flat = qat.site("router_in", flat)
    logits = (flat.astype(jnp.float32) @ p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, -1)                      # (T, E)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)         # (T, K)
    gate_vals = gate_vals / jnp.sum(gate_vals, -1, keepdims=True)

    # Switch-style load-balance aux loss
    density = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], e, dtype=jnp.float32), 0)
    density_proxy = jnp.mean(probs, 0)
    aux_loss = jnp.sum(density * density_proxy) * e

    # position of each (token, choice) within its expert buffer
    onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.int32)  # (T, K, E)
    oh_flat = onehot.reshape(t * k, e)
    pos = _blocked_cumsum(oh_flat) - oh_flat                 # exclusive
    pos_in_e = jnp.sum(pos * oh_flat, axis=-1).reshape(t, k)  # (T, K)
    keep = (pos_in_e < c).astype(dt)                         # dropped past capacity
    pos_clip = jnp.minimum(pos_in_e, c - 1)

    # scatter tokens -> (E, C, d); dropped tokens contribute zero
    contrib = flat.astype(dt)[:, None, :] * keep[..., None]  # (T, K, d)
    buf = jnp.zeros((e, c, d), dt).at[
        expert_idx.reshape(-1), pos_clip.reshape(-1)].add(
        contrib.reshape(t * k, d))
    buf = constrain(buf, rules, "experts", "exp_cap", None)

    # expert FFN, batched over E
    buf_q = qat.site("expert_in", buf)
    h = _act(jnp.einsum("ecd,edf->ecf", buf_q, p["wg"].astype(dt)), cfg.act) \
        * jnp.einsum("ecd,edf->ecf", buf_q, p["wu"].astype(dt))
    h = constrain(h, rules, "experts", "exp_cap", "expert_ffn")
    h = qat.site("expert_down_in", h)
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["wd"].astype(dt))
    out_buf = constrain(out_buf, rules, "experts", "exp_cap", None)

    # gather back + weighted combine
    gathered = out_buf[expert_idx.reshape(-1), pos_clip.reshape(-1)]
    gathered = gathered.reshape(t, k, d) * keep[..., None]
    y = jnp.sum(gathered * gate_vals.astype(dt)[..., None], axis=1)
    y = y.reshape(b, s, d)
    return constrain(y, rules, "batch", "seq", "embed"), aux_loss


# ---------------------------------------------------------------------------
# shard_map expert-parallel path (§Perf-1b)
# ---------------------------------------------------------------------------
#
# The auto-SPMD scatter/gather dispatch replicates the (T·K, d) update tensor
# (all-gather) and all-reduces the scattered buffer — measured 33 TB + 35 TB
# per device per step on dbrx train_4k (results/roofline/baseline).  The
# explicit formulation exploits the mesh structure instead:
#
#   * activations are sharded over (pod,)data and REPLICATED over model, so
#     every model-column device can locally select the tokens routed to its
#     own experts — dispatch costs ZERO collective bytes;
#   * per-data-shard capacity (GShard "groups" semantics) keeps dispatch
#     positions shard-local (local blocked cumsum);
#   * expert weights arrive (E/m, d, f/nd) (EP over model × ZeRO over data)
#     and are all-gathered over data per layer — the standard FSDP cost;
#   * the combine is one psum over model of the (T_local, d) partial
#     outputs — the inherent EP combine traffic.
#
# Projected per-device collective bytes for dbrx train_4k: ~0.2 TB vs 69 TB
# baseline; measured numbers in EXPERIMENTS.md §Perf-1.


def _moe_forward_sharded(x: Array, p: Params, cfg: ModelConfig,
                         rules: ShardingRules, qat: LayerQAT, mesh
                         ) -> tuple[Array, Array]:
    from jax.sharding import PartitionSpec as P

    axis_sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    batch_axes = ("pod", "data") if "pod" in axis_sizes else ("data",)
    all_axes = tuple(mesh.axis_names)
    n_model = axis_sizes["model"]
    e, k = cfg.n_experts, cfg.experts_per_token
    e_local = e // n_model
    dt = cfg.compute_dtype
    b, s, d = x.shape

    # QAT: router/expert input sites hoisted onto the (replicated-over-model)
    # token stream — same tensor content as the dispatched buffer.
    x = qat.site("router_in", x)
    x = qat.site("expert_in", x)
    hidden_qat = qat.params_for("expert_down_in")
    use_qat = hidden_qat is not None
    if not use_qat:  # dummy operands keep the shard_map signature static
        hidden_qat = (jnp.float32(-1), jnp.float32(1), jnp.array(False))

    t_global = b * s
    n_batch_shards = 1
    for a in batch_axes:
        n_batch_shards *= axis_sizes[a]
    c_local = capacity(t_global // n_batch_shards, cfg)

    def body(xl, router, wg, wu, wd, qat_in):
        # xl: (B_l, S, d); router: (d, E); w*: (E_l, d, f_l)
        tl = xl.shape[0] * xl.shape[1]
        flat = xl.reshape(tl, d)
        logits = (flat.astype(jnp.float32) @ router).astype(jnp.float32)
        probs = jax.nn.softmax(logits, -1)
        gate_vals, expert_idx = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.sum(gate_vals, -1, keepdims=True)

        # aux load-balance loss (identical across model by construction)
        density = jnp.mean(
            jax.nn.one_hot(expert_idx[:, 0], e, dtype=jnp.float32), 0)
        density_proxy = jnp.mean(probs, 0)
        aux = jnp.sum(density * density_proxy) * e
        aux = jax.lax.pmean(aux, batch_axes)

        # ---- local dispatch (no collectives) ------------------------------
        onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.int32)
        oh_flat = onehot.reshape(tl * k, e)
        pos = _blocked_cumsum(oh_flat, n_blocks=256) - oh_flat
        pos_in_e = jnp.sum(pos * oh_flat, -1).reshape(tl, k)
        keep = (pos_in_e < c_local).astype(dt)
        pos_clip = jnp.minimum(pos_in_e, c_local - 1)

        my_e_lo = jax.lax.axis_index("model") * e_local
        rel_e = expert_idx - my_e_lo
        mine = jnp.logical_and(rel_e >= 0, rel_e < e_local)
        contrib = flat.astype(dt)[:, None, :] * (keep * mine.astype(dt))[..., None]
        rel_clip = jnp.clip(rel_e, 0, e_local - 1)
        buf = jnp.zeros((e_local, c_local, d), dt).at[
            rel_clip.reshape(-1), pos_clip.reshape(-1)].add(
            contrib.reshape(tl * k, d))

        # ---- expert FFN (weights FSDP-gathered over data) ------------------
        wg_full = jax.lax.all_gather(wg, "data", axis=2, tiled=True).astype(dt)
        wu_full = jax.lax.all_gather(wu, "data", axis=2, tiled=True).astype(dt)
        wd_full = jax.lax.all_gather(wd, "data", axis=1, tiled=True).astype(dt)
        h = _act(jnp.einsum("ecd,edf->ecf", buf, wg_full), cfg.act) \
            * jnp.einsum("ecd,edf->ecf", buf, wu_full)

        if use_qat:
            a_min, a_max, quant_phase = qat_in
            from repro.core import fixedpoint as fxp
            h32 = h.astype(jnp.float32)
            h_q = fxp.fake_quant_affine(h32, a_min, a_max, cfg.qat_bits)
            h_full = fxp.fake_quant(h32, fxp.FXP32)
            h = jnp.where(quant_phase, h_q, h_full).astype(dt)
            hsg = jax.lax.stop_gradient(h32)
            h_min = jax.lax.pmin(hsg.min(), all_axes)
            h_max = jax.lax.pmax(hsg.max(), all_axes)
        else:
            h_min = h_max = jnp.float32(0)

        out_buf = jnp.einsum("ecf,efd->ecd", h, wd_full)

        # ---- combine: gather my experts' outputs, psum over model ---------
        gathered = out_buf[rel_clip.reshape(-1), pos_clip.reshape(-1)]
        gathered = gathered.reshape(tl, k, d) \
            * (keep * mine.astype(dt))[..., None]
        y = jnp.sum(gathered * gate_vals.astype(dt)[..., None], 1)
        y = jax.lax.psum(y, "model")
        return y.reshape(xl.shape), aux, h_min, h_max

    bspec = P(batch_axes if len(batch_axes) > 1 else batch_axes[0], None, None)
    y, aux, h_min, h_max = jax.shard_map(
        body, mesh=mesh,
        in_specs=(bspec, P(None, None), P("model", None, "data"),
                  P("model", None, "data"), P("model", "data", None),
                  (P(), P(), P())),
        out_specs=(bspec, P(), P(), P()),
    )(x.astype(dt), p["router"].astype(jnp.float32), p["wg"], p["wu"],
      p["wd"], hidden_qat)
    if use_qat:
        qat.fold_external("expert_down_in", h_min, h_max)
    return y, aux
