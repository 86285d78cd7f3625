import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()

"""Roofline analysis per (arch × shape × mesh) — §Roofline deliverable.

Method
------
XLA's `cost_analysis()` counts a `while` (lax.scan) body ONCE regardless of
trip count (verified: scan-vs-unroll of the same 8-step matmul reports 8×
fewer flops for scan).  The production programs scan over layer periods, so
the dry-run numbers undercount depth.  This harness therefore lowers two
*unrolled* reduced-depth variants of every cell — depth = 1 period + tail
and 2 periods + tail, python-loop instead of lax.scan, algorithm otherwise
identical (same chunking, same shardings, production mesh) — and
extrapolates:

    per_period = cost(2p) - cost(1p)          # exact: no while loops remain
    total      = cost(1p) + (n_periods - 1) * per_period

`cost_analysis` on an SPMD-partitioned module reports PER-DEVICE flops
(verified: 2·M·K·N sharded over 8 devices reports exactly 1/8th), so the
roofline terms divide by single-chip peaks:

    compute_s    = flops_dev / 197e12          (TPU v5e bf16 peak)
    memory_s     = bytes_dev / 819e9           (HBM BW)
    collective_s = coll_bytes_dev / 50e9       (per-link ICI; parsed operand
                   bytes of all-reduce/gather/scatter/all-to-all/permute in
                   the per-device HLO ≈ link traffic, ring-schedule ≈1×)

MODEL_FLOPS = 6·N_active·D (training) or 2·N_active·D (one forward token
batch for serve shapes), compared against flops_dev × n_devices to expose
remat/dispatch waste.
"""
import argparse
import dataclasses
import json
import pathlib
import sys

import jax
import jax.numpy as jnp

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "src"))

from repro.configs import registry                              # noqa: E402
from repro.core.parallelism import rules_for                    # noqa: E402
from repro.launch import specs as S                             # noqa: E402
from repro.launch.dryrun import collective_bytes, skip_reason   # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models.config import ALL_SHAPES, ModelConfig, ShapeConfig  # noqa: E402
from repro.optim import adam                                    # noqa: E402
from repro.serve.engine import make_prefill, make_serve_step    # noqa: E402
from repro.train.step import make_train_step                    # noqa: E402

RESULTS = REPO / "results" / "roofline"

PEAK_FLOPS = 197e12     # bf16 / chip
HBM_BW = 819e9          # B/s
LINK_BW = 50e9          # B/s per ICI link


def _reduced(cfg: ModelConfig, periods: int) -> ModelConfig:
    m = len(cfg.block_pattern)
    return dataclasses.replace(cfg, n_layers=periods * m + cfg.n_tail)


def _serve_layout_hints(cfg, mesh) -> dict:
    """Arch-aware serve-rule knobs (§Perf opt-5): follow the cache layout
    when kv_heads can't TP-shard; keep MoE weights resident when they fit."""
    n_model = dict(zip(mesh.axis_names, mesh.axis_sizes))["model"]
    hints = {}
    if cfg.n_kv_heads % n_model != 0:
        hints["prefer_head_dim"] = True
    if cfg.is_moe:
        bf16_bytes = cfg.total_params() * 2 / n_model
        hints["shard_expert_ffn"] = bf16_bytes > 8e9
    return hints


def _lower_cell(cfg: ModelConfig, shape: ShapeConfig, mesh, *, qat: bool):
    """Unrolled lowering of one cell; returns (flops, bytes, coll_bytes)."""
    if qat and shape.kind == "train":
        cfg = dataclasses.replace(cfg, qat=True, qat_delay=10_000)
    if shape.kind == "train":
        rules = rules_for(mesh, "train")
        st_sh, b_sh = S.train_shardings(cfg, shape, mesh, rules)
        attn_chunk = 4096 if shape.seq_len > 4096 else 0
        fn = make_train_step(cfg, adam.AdamConfig(lr=1e-4, grad_clip_norm=1.0),
                             rules=rules, attn_chunk=attn_chunk, unroll=True)
        jitted = jax.jit(fn, in_shardings=(st_sh, b_sh),
                         out_shardings=(st_sh, None), donate_argnums=0)
        args = (S.state_shapes(cfg), S.input_specs(cfg, shape))
    elif shape.kind == "prefill":
        rules = rules_for(mesh, "serve")
        p_sh, b_sh, _ = S.serve_shardings(cfg, shape, mesh, rules)
        attn_chunk = 4096 if shape.seq_len > 4096 else 0
        fn = make_prefill(cfg, rules=rules, attn_chunk=attn_chunk, unroll=True)
        jitted = jax.jit(fn, in_shardings=(p_sh, b_sh))
        args = (S.params_shapes(cfg), S.input_specs(cfg, shape))
    else:
        shard_kv_seq = shape.global_batch == 1
        rules = rules_for(mesh, "serve", shard_kv_seq=shard_kv_seq,
                          **_serve_layout_hints(cfg, mesh))
        p_sh, b_sh, c_sh = S.serve_shardings(cfg, shape, mesh, rules)
        fn = make_serve_step(cfg, rules=rules, unroll=True)
        jitted = jax.jit(fn, in_shardings=(p_sh, b_sh["tokens"], c_sh, None),
                         donate_argnums=2)
        args = (S.params_shapes(cfg), S.input_specs(cfg, shape)["tokens"],
                S.cache_shapes(cfg, shape.global_batch, shape.seq_len),
                jax.ShapeDtypeStruct((), jnp.int32))
    with jax.set_mesh(mesh):
        compiled = jitted.lower(*args).compile()
        cost = compiled.cost_analysis()
        coll = collective_bytes(compiled.as_text())
    return (cost.get("flops", 0.0), cost.get("bytes accessed", 0.0),
            sum(coll.values()), coll)


def _rwkv_chunk_correction(cfg: ModelConfig, shape: ShapeConfig, mesh,
                           n_layers: int):
    """Analytic correction for rwkv6 cells whose chunk loop stays a scan
    (n_chunks > 64, see rwkv6.time_mix): cost_analysis counts the chunk body
    once per layer, so add (n_chunks-1) x standalone chunk-body cost per
    layer.  Decode cells have no chunk loop."""
    from repro.models import rwkv6 as R
    from repro.models.config import RWKV6
    n_rwkv = sum(1 for t in cfg.layer_types()[:n_layers] if t == RWKV6)
    if n_rwkv == 0 or shape.kind == "decode":
        return 0.0, 0.0
    c = R.CHUNK
    n_chunks = shape.seq_len // c
    if n_chunks <= 64:  # unrolled in the lowering already
        return 0.0, 0.0
    b = shape.global_batch
    h, n = cfg.d_model // cfg.rwkv_head_dim, cfg.rwkv_head_dim
    sds = lambda shp: jax.ShapeDtypeStruct(shp, jnp.float32)
    rules = rules_for(mesh, "train" if shape.kind == "train" else "serve")
    sh4 = jax.sharding.NamedSharding(
        mesh, rules.mesh_axes(("batch", None, "heads_rwkv", None),
                              (b, c, h, n), _shim(mesh)))
    shs = jax.sharding.NamedSharding(
        mesh, rules.mesh_axes(("batch", "heads_rwkv", None, None),
                              (b, h, n, n), _shim(mesh)))

    def chunk_fn(r, k, v, lw, u, s0):
        return R._wkv_chunk(r, k, v, lw, u, s0)

    with jax.set_mesh(mesh):
        compiled = jax.jit(chunk_fn, in_shardings=(sh4, sh4, sh4, sh4, None,
                                                   shs)).lower(
            sds((b, c, h, n)), sds((b, c, h, n)), sds((b, c, h, n)),
            sds((b, c, h, n)), sds((h, n)), sds((b, h, n, n))).compile()
        cost = compiled.cost_analysis()
    mult = (n_chunks - 1) * n_rwkv
    # training backward re-traverses the chunk scan (~2x fwd cost for the
    # matmul-dominated body) + remat replays the forward once more
    if shape.kind == "train":
        mult *= 4
    return (mult * cost.get("flops", 0.0),
            mult * cost.get("bytes accessed", 0.0))


class _shim:
    def __init__(self, mesh):
        self.shape = dict(zip(mesh.axis_names, mesh.axis_sizes))


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    n_active = cfg.params_per_token()
    if shape.kind == "train":
        d = shape.global_batch * shape.seq_len
        return 6.0 * n_active * d
    if shape.kind == "prefill":
        d = shape.global_batch * shape.seq_len
        return 2.0 * n_active * d
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch


def roofline_cell(arch: str, shape: ShapeConfig, *, qat: bool = True) -> dict:
    cfg = registry.get(arch)
    rec = {"arch": cfg.name, "shape": shape.name, "mesh": "pod16x16"}
    reason = skip_reason(cfg, shape)
    if reason:
        rec.update(status="skip", skip_reason=reason)
        return rec
    mesh = make_production_mesh()
    n_dev = mesh.devices.size
    n_periods = cfg.n_periods

    f1, b1, c1, cd1 = _lower_cell(_reduced(cfg, 1), shape, mesh, qat=qat)
    f2, b2, c2, cd2 = _lower_cell(_reduced(cfg, 2), shape, mesh, qat=qat)
    # rwkv6 long-seq cells keep the chunk loop scanned: add analytic body cost
    cf1, cb1 = _rwkv_chunk_correction(_reduced(cfg, 1), shape, mesh,
                                      _reduced(cfg, 1).n_layers)
    cf2, cb2 = _rwkv_chunk_correction(_reduced(cfg, 2), shape, mesh,
                                      _reduced(cfg, 2).n_layers)
    f1, b1, f2, b2 = f1 + cf1, b1 + cb1, f2 + cf2, b2 + cb2

    scale = n_periods - 1
    flops = f1 + scale * (f2 - f1)
    byts = b1 + scale * (b2 - b1)
    coll = c1 + scale * (c2 - c1)
    coll_by_op = {k: cd1.get(k, 0.0) + scale * (cd2.get(k, 0.0) - cd1.get(k, 0.0))
                  for k in set(cd1) | set(cd2)}

    compute_s = flops / PEAK_FLOPS
    memory_s = byts / HBM_BW
    coll_s = coll / LINK_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": coll_s}
    bottleneck = max(terms, key=terms.get)
    mf = model_flops(cfg, shape)
    hlo_global = flops * n_dev
    rec.update(
        status="ok", n_devices=int(n_dev),
        flops_per_device=flops, bytes_per_device=byts,
        collective_bytes_per_device=coll, collective_by_op=coll_by_op,
        compute_s=compute_s, memory_s=memory_s, collective_s=coll_s,
        bottleneck=bottleneck.replace("_s", ""),
        step_time_bound_s=max(terms.values()),
        roofline_fraction=max(terms.values()) and compute_s / max(terms.values()),
        model_flops_global=mf,
        hlo_flops_global=hlo_global,
        useful_flops_ratio=mf / hlo_global if hlo_global else 0.0,
    )
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--no-qat", action="store_true")
    ap.add_argument("--tag", default="baseline")
    args = ap.parse_args(argv)
    archs = registry.lm_archs() if args.arch == "all" else [args.arch]
    shapes = (list(ALL_SHAPES) if args.shape == "all"
              else [s for s in ALL_SHAPES if s.name == args.shape])
    outdir = RESULTS / args.tag
    outdir.mkdir(parents=True, exist_ok=True)
    for arch in archs:
        for shape in shapes:
            try:
                rec = roofline_cell(arch, shape, qat=not args.no_qat)
            except Exception as e:  # noqa: BLE001
                rec = {"arch": arch, "shape": shape.name, "status": "error",
                       "error": f"{type(e).__name__}: {e}"}
            (outdir / f"{rec['arch']}_{rec['shape']}.json").write_text(
                json.dumps(rec, indent=2, default=str))
            brief = {k: rec.get(k) for k in
                     ("arch", "shape", "status", "bottleneck",
                      "skip_reason", "error")}
            if rec.get("status") == "ok":
                brief.update(
                    compute_ms=round(rec["compute_s"] * 1e3, 3),
                    memory_ms=round(rec["memory_s"] * 1e3, 3),
                    coll_ms=round(rec["collective_s"] * 1e3, 3),
                    useful=round(rec["useful_flops_ratio"], 3))
            print(json.dumps(brief), flush=True)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
