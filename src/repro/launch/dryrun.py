import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

Proves the distribution config is coherent without hardware: `jax.jit(...)
.lower(**ShapeDtypeStructs).compile()` must succeed on the single-pod
(16,16)=256-chip mesh and the multi-pod (2,16,16)=512-chip mesh, for every
assigned architecture × input shape.  Outputs memory_analysis (fits-HBM
proof) and cost_analysis (roofline §Roofline inputs) as JSON artifacts under
results/dryrun/.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --multi-pod
"""
import argparse
import dataclasses
import json
import pathlib
import re
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.core.parallelism import rules_for
from repro.launch import specs as S
from repro.launch.mesh import make_debug_mesh, make_production_mesh
from repro.models.config import ALL_SHAPES, ModelConfig, ShapeConfig
from repro.optim import adam
from repro.serve.engine import make_prefill, make_serve_step
from repro.train.step import make_train_step

RESULTS = pathlib.Path(__file__).resolve().parents[3] / "results" / "dryrun"

# cells skipped per task spec (see DESIGN.md §4 table)
FULL_ATTENTION_ONLY = {"internlm2-1.8b", "qwen2-0.5b", "deepseek-7b",
                       "dbrx-132b", "moonshot-v1-16b-a3b",
                       "phi-3-vision-4.2b"}
ENCODER_ONLY = {"hubert-xlarge"}



def skip_reason(cfg: ModelConfig, shape: ShapeConfig) -> str | None:
    if cfg.name in ENCODER_ONLY and shape.kind == "decode":
        return "encoder-only: no decode step"
    if cfg.name in FULL_ATTENTION_ONLY and shape.name == "long_500k":
        return "pure full attention: 500k decode excluded per spec"
    return None


def collective_bytes(hlo_text: str) -> dict[str, float]:
    """Sum operand bytes of every collective op in (optimized) HLO text.

    Parses lines like:
      %all-reduce.1 = f32[256,1024]{1,0} all-reduce(...)
    Counts the OUTPUT shape bytes per op (operand bytes ≈ output bytes for
    all-reduce/permute; all-gather output = gathered size — the conservative
    upper bound we want for link traffic).
    """
    dt_bytes = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
                "u8": 1, "pred": 1, "s64": 8, "u64": 8, "f64": 8, "s16": 2,
                "u16": 2}
    out: dict[str, float] = {}
    pat = re.compile(
        r"=\s+(?:\()?([a-z0-9]+)\[([0-9,]*)\][^=]*?"
        r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)\b")
    for m in pat.finditer(hlo_text):
        dt, dims, op = m.group(1), m.group(2), m.group(3)
        if op.endswith("-start"):
            op = op[:-6]
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out[op] = out.get(op, 0.0) + n * dt_bytes.get(dt, 4)
    return out


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


def build_cell(cfg: ModelConfig, shape: ShapeConfig, mesh, *, qat: bool):
    """Returns (jitted_fn, example_args) for one cell."""
    if qat and shape.kind == "train":
        cfg = dataclasses.replace(cfg, qat=True,
                                  qat_delay=10_000)
    if shape.kind == "train":
        rules = rules_for(mesh, "train")
        st_sh, b_sh = S.train_shardings(cfg, shape, mesh, rules)
        opt_cfg = adam.AdamConfig(lr=1e-4, grad_clip_norm=1.0)
        attn_chunk = 4096 if shape.seq_len > 4096 else 0
        fn = make_train_step(cfg, opt_cfg, rules=rules, attn_chunk=attn_chunk)
        jitted = jax.jit(fn, in_shardings=(st_sh, b_sh),
                         out_shardings=(st_sh, None), donate_argnums=0)
        args = (S.state_shapes(cfg), S.input_specs(cfg, shape))
        return jitted, args
    if shape.kind == "prefill":
        rules = rules_for(mesh, "serve")
        p_sh, b_sh, _ = S.serve_shardings(cfg, shape, mesh, rules)
        attn_chunk = 4096 if shape.seq_len > 4096 else 0
        fn = make_prefill(cfg, rules=rules, attn_chunk=attn_chunk)
        jitted = jax.jit(fn, in_shardings=(p_sh, b_sh))
        args = (S.params_shapes(cfg), S.input_specs(cfg, shape))
        return jitted, args
    # decode
    shard_kv_seq = shape.global_batch == 1  # long_500k: sequence-parallel
    rules = rules_for(mesh, "serve", shard_kv_seq=shard_kv_seq,
                      **_serve_layout_hints(cfg, mesh))
    p_sh, b_sh, c_sh = S.serve_shardings(cfg, shape, mesh, rules)
    fn = make_serve_step(cfg, rules=rules)
    jitted = jax.jit(fn, in_shardings=(p_sh, b_sh["tokens"], c_sh, None),
                     donate_argnums=2)
    args = (S.params_shapes(cfg), S.input_specs(cfg, shape)["tokens"],
            S.cache_shapes(cfg, shape.global_batch, shape.seq_len),
            jax.ShapeDtypeStruct((), jnp.int32))
    return jitted, args


def run_cell(arch: str, shape: ShapeConfig, *, multi_pod: bool, qat: bool,
             debug_mesh: bool = False) -> dict:
    cfg = registry.get(arch)
    reason = skip_reason(cfg, shape)
    mesh_name = "debug" if debug_mesh else ("pod2x16x16" if multi_pod
                                            else "pod16x16")
    rec = {"arch": cfg.name, "shape": shape.name, "mesh": mesh_name,
           "status": "skip", "skip_reason": reason}
    if reason:
        return rec
    mesh = (make_debug_mesh(multi_pod=multi_pod) if debug_mesh
            else make_production_mesh(multi_pod=multi_pod))
    t0 = time.time()
    with jax.set_mesh(mesh):
        jitted, args = build_cell(cfg, shape, mesh, qat=qat)
        lowered = jitted.lower(*args)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        coll = collective_bytes(compiled.as_text())
    n_dev = mesh.devices.size
    rec.update(
        status="ok",
        lower_s=round(t1 - t0, 1), compile_s=round(t2 - t1, 1),
        n_devices=int(n_dev),
        flops=cost.get("flops", 0.0),
        bytes_accessed=cost.get("bytes accessed", 0.0),
        collective_bytes=coll,
        memory={
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        },
    )
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--debug-mesh", action="store_true",
                    help="8-device mesh for fast sharding tests")
    ap.add_argument("--no-qat", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    archs = registry.lm_archs() if args.arch == "all" else [args.arch]
    shapes = (list(ALL_SHAPES) if args.shape == "all"
              else [s for s in ALL_SHAPES if s.name == args.shape])

    RESULTS.mkdir(parents=True, exist_ok=True)
    ok = True
    for arch in archs:
        for shape in shapes:
            try:
                rec = run_cell(arch, shape, multi_pod=args.multi_pod,
                               qat=not args.no_qat,
                               debug_mesh=args.debug_mesh)
            except Exception as e:  # noqa: BLE001 — record and continue
                rec = {"arch": arch, "shape": shape.name,
                       "mesh": "pod2x16x16" if args.multi_pod else "pod16x16",
                       "status": "error", "error": f"{type(e).__name__}: {e}"}
                ok = False
            name = f"{rec['arch']}_{rec['shape']}_{rec['mesh']}.json"
            out = pathlib.Path(args.out) if args.out else RESULTS / name
            out.write_text(json.dumps(rec, indent=2, default=str))
            line = {k: rec.get(k) for k in
                    ("arch", "shape", "mesh", "status", "compile_s",
                     "skip_reason", "error")}
            print(json.dumps(line), flush=True)
            if rec["status"] == "ok":
                print(f"  flops={rec['flops']:.3e} bytes={rec['bytes_accessed']:.3e} "
                      f"coll={ {k: f'{v:.2e}' for k, v in rec['collective_bytes'].items()} }",
                      flush=True)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
