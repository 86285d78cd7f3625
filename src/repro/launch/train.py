"""End-to-end LM training driver.

Wires every substrate together: config registry -> synthetic data ->
QAT-enabled train step -> (fixed-point) Adam -> async checkpointing ->
heartbeat/straggler supervisor -> deterministic restart.

CPU-scale usage (deliverable (b)):
  PYTHONPATH=src python -m repro.launch.train --arch demo_100m --steps 300 \\
      --batch 2 --seq 256 --qat --qat-delay 100 --ckpt-dir /tmp/ckpt_demo

Pod-scale usage (same code path; mesh selected by flag):
  python -m repro.launch.train --arch deepseek-7b --mesh pod16x16 ...
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax

from repro.checkpoint import ckpt
from repro.configs import registry
from repro.core.parallelism import rules_for
from repro.data.synthetic import DataConfig, DataIterator
from repro.models.config import ShapeConfig
from repro.optim import adam, schedule
from repro.runtime.ft import TrainingSupervisor
from repro.train.step import init_state, make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="demo_100m")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=50)
    ap.add_argument("--qat", action="store_true")
    ap.add_argument("--qat-delay", type=int, default=0)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--mesh", default="none", choices=["none", "debug",
                                                       "pod16x16"])
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = registry.get_smoke(args.arch) if args.smoke else registry.get(args.arch)
    if args.qat:
        cfg = dataclasses.replace(cfg, qat=True, qat_delay=args.qat_delay)
    shape = ShapeConfig("train_cli", "train", args.seq, args.batch)

    rules = None
    mesh_ctx = None
    if args.mesh != "none":
        from repro.launch.mesh import make_debug_mesh, make_production_mesh
        mesh = (make_debug_mesh() if args.mesh == "debug"
                else make_production_mesh())
        rules = rules_for(mesh, "train")
        mesh_ctx = jax.set_mesh(mesh)
        mesh_ctx.__enter__()

    opt_cfg = adam.AdamConfig(
        lr=args.lr, grad_clip_norm=1.0,
        schedule=schedule.warmup_cosine(args.warmup, args.steps))
    step_fn = jax.jit(make_train_step(cfg, opt_cfg, rules=rules,
                                      n_microbatches=args.microbatches),
                      donate_argnums=0)

    state = init_state(jax.random.key(args.seed), cfg)
    start_step = 0
    if args.resume and args.ckpt_dir:
        latest = ckpt.latest_step(args.ckpt_dir)
        if latest is not None:
            state, start_step, _ = ckpt.restore(args.ckpt_dir, state)
            print(f"resumed from step {start_step}")

    data = DataIterator(DataConfig(seed=args.seed), cfg, shape,
                        start_step=start_step)
    writer = (ckpt.AsyncCheckpointer(args.ckpt_dir) if args.ckpt_dir
              else None)
    supervisor = TrainingSupervisor(n_hosts=max(jax.process_count(), 1),
                                    devices_per_host=jax.local_device_count())

    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M qat={cfg.qat} "
          f"delay={cfg.qat_delay} steps={args.steps}")

    t_last = time.perf_counter()
    for step in range(start_step, args.steps):
        batch = next(data)
        state, metrics = step_fn(state, batch)
        if (step + 1) % args.log_every == 0 or step == args.steps - 1:
            jax.block_until_ready(metrics["loss"])
            now = time.perf_counter()
            dt = (now - t_last) / args.log_every
            t_last = now
            tokens_s = args.batch * args.seq / dt
            supervisor.step_report(0, dt)
            print(json.dumps({
                "step": step + 1, "loss": round(float(metrics["loss"]), 4),
                "lr": float(metrics["lr"]),
                "grad_norm": round(float(metrics.get("grad_norm", 0)), 3),
                "quant_phase": int(metrics.get("quant_phase", 0)),
                "s_per_step": round(dt, 3),
                "tokens_per_s": round(tokens_s, 1)}), flush=True)
        if writer and (step + 1) % args.ckpt_every == 0:
            writer.save(step + 1, state, extra={"arch": cfg.name})
    if writer:
        writer.save(args.steps, state, extra={"arch": cfg.name})
        writer.close()
    if mesh_ctx:
        mesh_ctx.__exit__(None, None, None)
    print("done")


if __name__ == "__main__":
    main()
