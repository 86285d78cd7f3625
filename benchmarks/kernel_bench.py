"""§V-C microbench — the configurable-datapath PE claim in numbers:
half-precision mode must cost ~half the MAC work of full-precision mode.

Plus the network-resident fused MLP comparison: the whole paper-actor
forward in ONE Pallas call (kernels/fxp_mlp) vs the 3-call per-layer
`fxp_dense` chain, both precision phases, the acting-path IPS for each DDPG
backend at TWO batch sizes (so `CostModel.from_bench` can separate launch
overhead from per-item rate), and the *training*-step comparison — the
Fig. 8-comparable line: `ddpg.update()` through the fused kernel's custom
VJP (fwd + bwd Pallas launches) vs the jnp autodiff backend, in updates/sec
and trained-samples/sec.  Results land in `BENCH_fused_mlp.json` at the
repo root so the perf trajectory is tracked across PRs.

On CPU (interpret) we measure wall time AND verify the structural 2× via
`ref_flops`; on a real TPU the same harness times the Mosaic kernels.
`--smoke` shrinks batches/iterations to CI scale while emitting the same
JSON shape (validated by `benchmarks/schema.py`).
"""
import argparse
import dataclasses
import json
import pathlib
import sys

_REPO = pathlib.Path(__file__).resolve().parents[1]
if str(_REPO) not in sys.path:
    sys.path.insert(0, str(_REPO))

import jax
import jax.numpy as jnp

from benchmarks.common import emit, time_fn

from repro.kernels.fxp_matmul.ops import fxp_dense
from repro.kernels.fxp_matmul.ref import ref_flops

SHAPES = [(256, 400, 300), (512, 1024, 1024), (64, 17, 400)]
SMOKE_SHAPES = [(16, 33, 40)]

FUSED_JSON = _REPO / "BENCH_fused_mlp.json"
# smoke runs must NOT clobber the tracked calibration artifact with tiny
# interpret-mode numbers — they emit the same shape to an untracked path
SMOKE_FUSED_JSON = _REPO / "results" / "bench" / "smoke" / FUSED_JSON.name
ACTOR_BATCHES = (64, 256)        # two points -> slope/intercept separation
SMOKE_ACTOR_BATCHES = (8, 32)
TRAIN_BATCHES = (32, 128)        # same two-point idea for the train fit
SMOKE_TRAIN_BATCHES = (8, 16)


def _count_pallas_calls(fn, *args) -> int:
    """Traced pallas_call count, recursing into cond/pjit sub-jaxprs —
    the per-layer path traces BOTH precision kernels per layer (lax.cond),
    the fused path traces exactly one (plus one backward under grad)."""
    def subs(v):
        vals = v if isinstance(v, (tuple, list)) else [v]
        for item in vals:
            if hasattr(item, "eqns"):            # Jaxpr
                yield item
            elif hasattr(item, "jaxpr"):         # ClosedJaxpr
                yield item.jaxpr

    def count(jx) -> int:
        n = 0
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                n += 1
            for v in eqn.params.values():
                n += sum(count(s) for s in subs(v))
        return n

    return count(jax.make_jaxpr(fn)(*args).jaxpr)


def _dummy_batch(spec, n, key=0):
    k = jax.random.key(key)
    return {
        "obs": jax.random.normal(k, (n, spec.obs_dim)),
        "action": jax.random.uniform(k, (n, spec.act_dim),
                                     minval=-1, maxval=1),
        "reward": jax.random.normal(k, (n,)),
        "next_obs": jax.random.normal(jax.random.fold_in(k, 1),
                                      (n, spec.obs_dim)),
        "done": jnp.zeros((n,), jnp.bool_),
    }


def bench_train_step(report: dict, env, cfg, state, smoke: bool) -> None:
    """Training-step throughput through the fused kernel's custom VJP vs
    jnp autodiff — FIXAR's headline is *training* IPS (Fig. 8).

    Measured at TWO batch sizes (`ips_by_batch`) so
    `CostModel.from_bench` can fit the train-phase affine coefficients
    (slope = per-item rate, intercept = fwd+bwd launch overhead) the same
    way `actor_ips_by_batch` feeds the acting-path fit."""
    from repro.rl import ddpg

    train_batches = SMOKE_TRAIN_BATCHES if smoke else TRAIN_BATCHES
    batch_size = train_batches[-1]
    iters, warmup = (2, 1) if smoke else (5, 2)
    batch = _dummy_batch(env.spec, batch_size)

    res = {"batch": batch_size, "batches": list(train_batches),
           "updates_per_s": {}, "train_ips": {}, "ips_by_batch": {},
           "pallas_calls_traced": {}, "launches_per_update": {}}
    for backend in ("jnp", "pallas", "pallas_fused_step"):
        bcfg = dataclasses.replace(cfg, backend=backend,
                                   batch_size=batch_size)
        calls = _count_pallas_calls(
            lambda s, b, bcfg=bcfg: ddpg.update(s, b, bcfg), state, batch)
        res["pallas_calls_traced"][backend] = calls
        # one update executes every traced call exactly once for all three
        # backends (no lax.cond dual-tracing on the train path), so the
        # traced count IS the launch count — the v4 schema pins it per
        # backend (jnp 0, custom-VJP pair 8, fused step 2)
        res["launches_per_update"][backend] = calls
        upd = jax.jit(lambda s, b, bcfg=bcfg: ddpg.update(s, b, bcfg))
        per_batch = {}
        for tb in train_batches:
            sub = {k: v[:tb] for k, v in batch.items()}
            us = time_fn(lambda: upd(state, sub), iters=iters,
                         warmup=warmup)
            per_batch[str(tb)] = tb / (us * 1e-6)   # trained samples / s
            if tb == batch_size:
                ups = 1e6 / us
        res["ips_by_batch"][backend] = per_batch
        res["updates_per_s"][backend] = ups
        res["train_ips"][backend] = ups * batch_size
        emit(f"kernel/fxp_mlp/train_step/{backend}", 1e6 / ups,
             f"updates_per_s={ups:.2f};train_ips={ups * batch_size:.0f};"
             f"batch={batch_size};launches={calls}")
    res["speedup_vs_jnp"] = {
        backend: res["updates_per_s"][backend] / res["updates_per_s"]["jnp"]
        for backend in ("pallas", "pallas_fused_step")}
    emit("kernel/fxp_mlp/train_step/pallas_calls", 0.0,
         "fused_step={};fused_fwd_bwd={};jnp={}".format(
             res["pallas_calls_traced"]["pallas_fused_step"],
             res["pallas_calls_traced"]["pallas"],
             res["pallas_calls_traced"]["jnp"]))
    report["train"] = res


def bench_fused_mlp(smoke: bool = False) -> dict:
    """Fused whole-network kernel vs the per-layer fxp_dense chain."""
    from repro.rl import ddpg
    from repro.rl.envs.locomotion import make
    from repro.core.qat import QATContext

    env = make("halfcheetah")
    dims = [env.spec.obs_dim, *ddpg.HIDDEN, env.spec.act_dim]
    cfg = ddpg.DDPGConfig()
    state = ddpg.init(jax.random.key(0), env.spec, cfg)
    batches = SMOKE_ACTOR_BATCHES if smoke else ACTOR_BATCHES
    primary = batches[-1]
    fwd_iters, fwd_warmup = (2, 1) if smoke else (5, 2)
    obs = jax.random.normal(jax.random.key(1), (primary, dims[0]))

    def forward(backend, qat_state):
        @jax.jit
        def f(params, x):
            return ddpg.actor_forward(params, x, QATContext(qat_state),
                                      backend=backend)
        return f

    report = {
        "schema": "fixar/fused_mlp_bench/v4",
        "config": {"batch": primary, "batches": list(batches), "net": dims,
                   "backend": jax.default_backend(), "smoke": smoke},
        "pallas_calls_traced": {},
        "phases": {},
        "actor_ips": {},
        "actor_ips_by_batch": {},
    }

    # traced-call structure: fused = 1 kernel for the whole network;
    # per-layer = 2 kernels traced per layer (cond), len(dims)-1 executed
    fused_calls = _count_pallas_calls(forward("pallas", state.qat),
                                      state.actor, obs)
    layer_calls = _count_pallas_calls(forward("pallas_layer", state.qat),
                                      state.actor, obs)
    report["pallas_calls_traced"] = {
        "fused": fused_calls,
        "perlayer": layer_calls,
        "perlayer_executed": len(dims) - 1,
    }
    emit("kernel/fxp_mlp/actor/pallas_calls", 0.0,
         f"fused={fused_calls};perlayer_traced={layer_calls};"
         f"perlayer_executed={len(dims) - 1}")

    # wall-clock, both phases (full precision pre-delay, half after)
    for phase_name, step in (("full", 0), ("half", 10)):
        qat = dataclasses.replace(state.qat, step=jnp.array(step, jnp.int32),
                                  config=dataclasses.replace(
                                      state.qat.config, delay=5))
        res = {}
        for mode, backend in (("fused", "pallas"),
                              ("perlayer", "pallas_layer")):
            f = forward(backend, qat)
            us = time_fn(lambda f=f: f(state.actor, obs),
                         iters=fwd_iters, warmup=fwd_warmup)
            res[f"{mode}_us"] = us
            emit(f"kernel/fxp_mlp/actor/{phase_name}/{mode}", us,
                 f"batch={primary}")
        res["speedup"] = res["perlayer_us"] / res["fused_us"]
        report["phases"][phase_name] = res
        emit(f"kernel/fxp_mlp/actor/{phase_name}/speedup", 0.0,
             f"fused_vs_perlayer={res['speedup']:.2f}x")

    # acting-path IPS (the env-interaction side of the training loop) at
    # two batch sizes: the pair lets CostModel.from_bench fit BOTH the
    # launch overhead (intercept) and the per-item rate (slope)
    for backend in ("jnp", "pallas", "pallas_layer"):
        bcfg = dataclasses.replace(cfg, backend=backend)
        act = jax.jit(lambda s, o: ddpg.act(s, o, cfg=bcfg))
        per_batch = {}
        for b in batches:
            ob = obs[:b]
            us = time_fn(lambda: act(state, ob), iters=fwd_iters,
                         warmup=fwd_warmup)
            per_batch[str(b)] = b / (us * 1e-6)
            emit(f"kernel/fxp_mlp/act_ips/{backend}/b{b}", us,
                 f"ips={per_batch[str(b)]:.0f};batch={b}")
        report["actor_ips_by_batch"][backend] = per_batch
        report["actor_ips"][backend] = per_batch[str(primary)]

    bench_train_step(report, env, cfg, state, smoke)

    target = SMOKE_FUSED_JSON if smoke else FUSED_JSON
    target.parent.mkdir(parents=True, exist_ok=True)
    target.write_text(json.dumps(report, indent=2) + "\n")
    emit("kernel/fxp_mlp/json", 0.0,
         f"wrote={target.relative_to(_REPO)}")
    return report


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny batches/iteration counts (CI schema gate)")
    args = ap.parse_args(argv)
    for (m, k, n) in (SMOKE_SHAPES if args.smoke else SHAPES):
        x = jax.random.normal(jax.random.key(0), (m, k))
        w = jax.random.normal(jax.random.key(1), (k, n)) * 0.1
        res = {}
        for mode, fp in (("full", True), ("half", False)):
            us = time_fn(lambda fp=fp: fxp_dense(x, w, None,
                                                 full_precision=fp),
                         iters=5, warmup=2)
            fl = ref_flops(m, n, k, fp)
            res[mode] = (us, fl)
            emit(f"kernel/fxp_dense/{m}x{k}x{n}/{mode}", us,
                 f"model_flops={fl:.3e};gflops={fl/us*1e-3:.2f}")
        ratio = res["full"][1] / res["half"][1]
        emit(f"kernel/fxp_dense/{m}x{k}x{n}/flop_ratio", 0.0,
             f"full_vs_half={ratio:.1f}x (paper claims 2x)")
    bench_fused_mlp(smoke=args.smoke)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
