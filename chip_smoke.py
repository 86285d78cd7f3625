"""On-chip smoke test of the FIXAR path: fused kernels, the two-launch DDPG
update, device-resident training and policy serving, at the paper's widths
(halfcheetah, 17-400-300-6 actor, 23-400-300-1 critic, batch 128).

    python chip_smoke.py              # one TPU chip: every phase below
    python chip_smoke.py --chips 4    # four chips: sharded serving only

Phases on one chip, each checked against the repo's own references:

  kernel   fused actor forward at B 1, 7, 128, 512 in both QAT phases vs the
           jnp oracle (`kernels/fxp_mlp/ref.py`) at highest matmul precision:
           each layer alone on the oracle's input for it (one Q15.16 quantum
           in the monitor phase, 1e-3 in the quantized phase), and the whole
           network (see NET_TOL);
  update   20 `ddpg.update` steps across the QAT phase flip, two-launch fused
           step vs `backend="jnp"` at highest precision: one-step error from
           the reference state at every step (one Q15.16 quantum in the
           monitor phase, 1e-3 in the quantized phase) and the free-running
           drift (1e-3);
  compile  the compiled act holds 1 Pallas kernel, the update 2;
  train    `rl/loop.train_device` on the paper's job (100k replay, batch
           128) with the fused step, the QAT delay inside the run;
  serve    the trained policy behind `PolicyEngine(force_mode="fused")`:
           threaded requests plus one batch of 512 vs `ddpg.act` through the
           same fused kernel (one quantum) and through jnp at highest
           precision (whole-network bound).

With --chips 4 the script runs only the 4-chip engine (one `data` mesh, each
chip acting on its B/4 rows) against a one-chip engine on the same requests.

The script exits non-zero before any work when JAX finds no TPU, and on any
failed check or exception.  Its last stdout line is one JSON object,
{"ok": true, "device": {"platform", "kind", "count"}}.  Times it prints are
smoke timings of one run, not benchmarks.  Facts also go to
chiprun_out/chip_smoke_<chips>chip.json.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import re
import sys
import threading
import time

REPO = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(REPO / "src"))

QUANTUM = 2.0 ** -16      # one Q15.16 lattice step
QUANT_TOL = 1e-3          # quantized-phase bound (bf16 hi-limb datapath)
# Whole-network forward bounds, at unit scale.  Errors compound across
# layers: a one-ulp dot difference can move the next site's input across a
# Q15.16 lattice point (monitor phase) or, in the quantized phase, across a
# rounding point of the bf16 hi limb (2^-8 relative).  The one-layer checks
# carry the tight bounds; XLA:CPU against the unpadded oracle reaches 2.7e-5
# and 3.1e-3 at 512 rows.
NET_TOL = {"monitor": 1e-4, "quant": 1e-2}
BATCHES = (1, 7, 128, 512)
UPDATE_STEPS, QAT_DELAY = 20, 10
N_REQUESTS, N_CLIENTS = 320, 8
CLIENT_TIMEOUT_S = 300.0
# the device-loop run: three eval windows, updates from step 500, the QAT
# flip after 1000 updates
TRAIN = dict(total_steps=3_000, eval_every=1_000, warmup_steps=500, qat_delay=1_000)

FACTS: dict = {}


def report(phase: str, **facts) -> None:
    FACTS.setdefault(phase, {}).update(facts)
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in facts.items()), flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def max_err(a, b) -> float:
    import jax
    import numpy as np

    return max(float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def count_kernels(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


# --------------------------------------------------------------------------- #
# phases
# --------------------------------------------------------------------------- #

def _rel_err(got, want) -> float:
    """max |got - want| at unit scale: over max(1, max |want|)."""
    import numpy as np

    got, want = np.asarray(got), np.asarray(want)
    return float(np.max(np.abs(got - want)) / max(1.0, float(np.max(np.abs(want)))))


def kernel_phase(spec) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import fixedpoint as fxp
    from repro.kernels.fxp_mlp.ops import fxp_mlp_forward
    from repro.kernels.fxp_mlp.ref import ref_fxp_mlp
    from repro.rl import ddpg

    # seeded uniform(-0.2, 0.2) weights, as the CPU parity tests use: larger
    # than DDPG's init, so every layer and both quantizers see real ranges
    dims = (spec.obs_dim, *ddpg.HIDDEN, spec.act_dim)
    acts = ddpg.ACTOR_ACTS
    n = len(dims) - 1
    keys = jax.random.split(jax.random.key(0), 2 * n)
    ws = tuple(jax.random.uniform(keys[2 * i], (dims[i], dims[i + 1]),
                                  jnp.float32, -0.2, 0.2) for i in range(n))
    bs = tuple(jax.random.uniform(keys[2 * i + 1], (dims[i + 1],),
                                  jnp.float32, -0.2, 0.2) for i in range(n))
    a_mins = jnp.linspace(-1.0, -3.0, n).astype(jnp.float32)
    a_maxs = jnp.linspace(1.5, 3.5, n).astype(jnp.float32)
    params = [fxp.affine_params(a_mins[i], a_maxs[i], 16) for i in range(n)]
    deltas = jnp.stack([d for d, _ in params])
    zs = jnp.stack([z.astype(jnp.float32) for _, z in params])

    def fused(x, lo, hi, quant):
        return fxp_mlp_forward(x, ws[lo:hi], bs[lo:hi], deltas[lo:hi], zs[lo:hi],
                               activations=acts[lo:hi], quant_phase=jnp.array(quant))

    def oracle(x, lo, hi, quant):
        with jax.default_matmul_precision("highest"):
            return ref_fxp_mlp(x, ws[lo:hi], bs[lo:hi], activations=acts[lo:hi],
                               quant_phase=jnp.array(quant),
                               a_mins=a_mins[lo:hi], a_maxs=a_maxs[lo:hi])

    for quant in (False, True):
        name = "quant" if quant else "monitor"
        layer_errs, net_errs = [], []
        for b in BATCHES:
            x = jax.random.normal(jax.random.key(b), (b, spec.obs_dim)) * 3
            t0 = time.perf_counter()
            got = jax.block_until_ready(fused(x, 0, n, quant))
            dt = time.perf_counter() - t0
            want = oracle(x, 0, n, quant)
            net_errs.append(_rel_err(got[0], want[0]))
            np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                                       rtol=2e-5, atol=2e-5, err_msg="site mins")
            np.testing.assert_allclose(np.asarray(got[2]), np.asarray(want[2]),
                                       rtol=2e-5, atol=2e-5, err_msg="site maxs")
            # each layer alone, fed the oracle's own input for that layer
            xl, per_layer = x, []
            for li in range(n):
                want_l = oracle(xl, li, li + 1, quant)[0]
                per_layer.append(_rel_err(fused(xl, li, li + 1, quant)[0], want_l))
                xl = want_l
            layer_errs.append(max(per_layer))
            report("kernel", qat_phase=name, batch=b, layer_err=max(per_layer),
                   net_err=net_errs[-1], first_call_s_smoke=round(dt, 3))
        layer_bound = QUANT_TOL if quant else QUANTUM
        net_bound = NET_TOL[name]
        check(max(layer_errs) <= layer_bound,
              f"fused layer vs oracle, {name} phase: {max(layer_errs)} > {layer_bound}")
        check(max(net_errs) <= net_bound,
              f"fused network vs oracle, {name} phase: {max(net_errs)} > {net_bound}")


def _batches(spec, n: int, b: int = 128):
    import jax
    import jax.numpy as jnp

    out = []
    for t in range(n):
        ks = jax.random.split(jax.random.key(1000 + t), 5)
        out.append({
            "obs": jax.random.normal(ks[0], (b, spec.obs_dim)),
            "action": jax.random.uniform(ks[1], (b, spec.act_dim), minval=-1, maxval=1),
            "reward": jax.random.normal(ks[2], (b,)),
            "next_obs": jax.random.normal(ks[3], (b, spec.obs_dim)),
            "done": (jax.random.uniform(ks[4], (b,)) < 0.05).astype(jnp.float32),
        })
    return out


def update_phase(spec) -> None:
    import jax

    from repro.rl import ddpg

    def params(s):
        return (s.actor, s.critic)

    cfg_ref = ddpg.DDPGConfig(backend="jnp", qat_delay=QAT_DELAY)
    cfg_fused = ddpg.DDPGConfig(backend="pallas_fused_step", qat_delay=QAT_DELAY)
    upd_fused = jax.jit(lambda s, b: ddpg.update(s, b, cfg_fused))
    batches = _batches(spec, UPDATE_STEPS)
    s0 = ddpg.init(jax.random.key(0), spec, cfg_ref)

    ref = [s0]
    with jax.default_matmul_precision("highest"):
        upd_ref = jax.jit(lambda s, b: ddpg.update(s, b, cfg_ref))
        for b in batches:
            ref.append(upd_ref(ref[-1], b)[0])

    one_step = {"monitor": 0.0, "quant": 0.0}
    drift = {"monitor": 0.0, "quant": 0.0}
    s = s0
    t0 = time.perf_counter()
    for t, b in enumerate(batches):
        phase = "quant" if bool(ref[t].qat.quantized_phase) else "monitor"
        one = upd_fused(ref[t], b)[0]
        s = upd_fused(s, b)[0]
        one_step[phase] = max(one_step[phase], max_err(params(one), params(ref[t + 1])))
        drift[phase] = max(drift[phase], max_err(params(s), params(ref[t + 1])))
    dt = time.perf_counter() - t0
    check(bool(ref[-1].qat.quantized_phase) and not bool(s0.qat.quantized_phase),
          "the 20 steps must cross the QAT phase flip")
    report("update", steps=UPDATE_STEPS, qat_delay=QAT_DELAY,
           one_step_err_monitor=one_step["monitor"],
           one_step_err_monitor_quanta=one_step["monitor"] / QUANTUM,
           one_step_err_quant=one_step["quant"],
           drift_monitor=drift["monitor"], drift_quant=drift["quant"],
           wall_s_smoke=round(dt, 3))
    check(one_step["monitor"] <= QUANTUM,
          f"monitor-phase update error {one_step['monitor']} > one Q15.16 quantum")
    check(one_step["quant"] <= QUANT_TOL,
          f"quantized-phase update error {one_step['quant']} > {QUANT_TOL}")
    check(max(drift.values()) <= QUANT_TOL,
          f"free-running drift {max(drift.values())} > {QUANT_TOL}")


def compile_phase(spec) -> None:
    import jax
    import jax.numpy as jnp

    from repro.rl import ddpg

    cfg = ddpg.DDPGConfig(backend="pallas_fused_step")
    state = ddpg.init(jax.random.key(0), spec, cfg)
    obs = jnp.zeros((128, spec.obs_dim), jnp.float32)
    t0 = time.perf_counter()
    act = jax.jit(lambda s, o: ddpg.act(s, o, cfg=cfg)).lower(state, obs).compile()
    t1 = time.perf_counter()
    upd = jax.jit(lambda s, b: ddpg.update(s, b, cfg)).lower(
        state, _batches(spec, 1)[0]).compile()
    t2 = time.perf_counter()
    n_act, n_upd = count_kernels(act), count_kernels(upd)
    report("compile", act_kernels=n_act, update_kernels=n_upd,
           act_compile_s_smoke=round(t1 - t0, 3), update_compile_s_smoke=round(t2 - t1, 3))
    check(n_act == 1, f"compiled act holds {n_act} Pallas kernels, expected 1")
    check(n_upd == 2, f"compiled update holds {n_upd} Pallas kernels, expected 2")


def train_phase(env):
    import jax
    import numpy as np

    from repro.configs.fixar_ddpg import CONFIG
    from repro.rl import ddpg, loop, replay

    dcfg = ddpg.DDPGConfig(backend="pallas_fused_step", batch_size=CONFIG.ddpg.batch_size,
                           qat_delay=TRAIN["qat_delay"])
    cfg = loop.TrainConfig(total_steps=TRAIN["total_steps"], eval_every=TRAIN["eval_every"],
                           warmup_steps=TRAIN["warmup_steps"], replay_capacity=100_000,
                           eval_episodes=4, seed=0)
    t0 = time.perf_counter()
    ts, hist = loop.train_device(env, cfg, dcfg)
    dt = time.perf_counter() - t0
    updates = int(ts.agent.step)
    # the losses of one more update from the trained state, on a replay sample
    batch = replay.sample(ts.buf, jax.random.key(1), dcfg.batch_size)
    _, m = jax.jit(lambda s, b: ddpg.update(s, b, dcfg))(ts.agent, batch)
    losses = {k: float(v) for k, v in m.items()}
    report("train", env=env.spec.name, windows=len(hist["step"]), updates=updates,
           quantized=bool(ts.agent.qat.quantized_phase),
           eval_reward=hist["eval_reward"], train_reward=hist["train_reward"],
           losses=losses, wall_s_smoke_incl_compile=round(dt, 3))
    check(updates > 0, "no update ran")
    check(bool(ts.agent.qat.quantized_phase), "the QAT delay must fall inside the run")
    finite = [*hist["eval_reward"], *hist["train_reward"], *losses.values()]
    check(all(np.isfinite(finite)), f"non-finite loss or return: {finite}")
    check(all(np.all(np.isfinite(np.asarray(p))) for p in jax.tree.leaves(ts.agent)
              if np.issubdtype(np.asarray(p).dtype, np.floating)),
          "non-finite parameter after training")
    return ts.agent


def _serve_threaded(engine, obs) -> list:
    """Submit every row of obs from N_CLIENTS threads; answers in row order.
    An exception in any client, including one a request future relays from
    the serving thread, is raised here."""
    out = [None] * len(obs)
    errors = []

    def client(rows):
        try:
            futs = [(i, engine.submit(obs[i])) for i in rows]
            for i, f in futs:
                out[i] = f.result()
        except Exception as e:  # raised in the main thread below
            errors.append(e)

    threads = [threading.Thread(target=client, args=(range(c, len(obs), N_CLIENTS),))
               for c in range(N_CLIENTS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=CLIENT_TIMEOUT_S)
    if errors:
        raise errors[0]
    check(not any(t.is_alive() for t in threads),
          f"a client thread did not finish within {CLIENT_TIMEOUT_S} s")
    return out


def serve_phase(agent, spec) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.rl import ddpg
    from repro.serve.policy import PolicyEngine

    rng = np.random.default_rng(0)
    obs = rng.normal(size=(N_REQUESTS, spec.obs_dim)).astype(np.float32)
    big = rng.normal(size=(512, spec.obs_dim)).astype(np.float32)
    engine = PolicyEngine.from_ddpg(agent, force_mode="fused")
    t0 = time.perf_counter()
    engine.warmup()
    t1 = time.perf_counter()
    engine.start()
    try:
        answers = np.stack(_serve_threaded(engine, obs))
    finally:
        engine.stop()
    t2 = time.perf_counter()
    batch = engine.run_batch(big)
    ref_cfg = ddpg.DDPGConfig(backend="jnp")
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ddpg.act(agent, jnp.asarray(obs), cfg=ref_cfg))
        want_big = np.asarray(ddpg.act(agent, jnp.asarray(big), cfg=ref_cfg))
    err = max(max_err(answers, want), max_err(batch, want_big))
    # the same policy through the training path's fused kernel (live QAT
    # context instead of the engine's frozen snapshot)
    kernel_cfg = ddpg.DDPGConfig(backend="pallas_fused_step")
    kernel_err = max(
        max_err(answers, ddpg.act(agent, jnp.asarray(obs), cfg=kernel_cfg)),
        max_err(batch, ddpg.act(agent, jnp.asarray(big), cfg=kernel_cfg)))
    st = engine.stats()
    report("serve", requests=st["requests"], batches=st["batches"],
           quantized=bool(engine.frozen.quantized), max_err_vs_jnp=err,
           max_err_vs_kernel_act=kernel_err,
           warmup_s_smoke=round(t1 - t0, 3), threaded_wall_s_smoke=round(t2 - t1, 3))
    check(st["requests"] == N_REQUESTS, "not every request was answered")
    bound = NET_TOL["quant" if engine.frozen.quantized else "monitor"]
    check(err <= bound, f"served actions vs ddpg.act (jnp): {err} > {bound}")
    check(kernel_err <= QUANTUM,
          f"served actions vs ddpg.act (fused kernel): {kernel_err} > {QUANTUM}")


def four_chip_phase(spec) -> None:
    import jax
    import numpy as np

    from repro.launch.mesh import make_serve_mesh
    from repro.rl import ddpg
    from repro.serve.policy import PolicyEngine

    cfg = ddpg.DDPGConfig(qat_delay=0)
    state = ddpg.init(jax.random.key(0), spec, cfg)
    # one update on a seeded batch puts real ranges into the QAT monitors
    state, _ = jax.jit(lambda s, b: ddpg.update(s, b, cfg))(state, _batches(spec, 1)[0])
    mesh = make_serve_mesh()
    sharded = PolicyEngine.from_ddpg(state, mesh=mesh, force_mode="fused")
    single = PolicyEngine.from_ddpg(state, force_mode="fused")
    rng = np.random.default_rng(0)
    obs = rng.normal(size=(N_REQUESTS, spec.obs_dim)).astype(np.float32)
    big = rng.normal(size=(512, spec.obs_dim)).astype(np.float32)

    answers = {}
    for name, eng in (("sharded", sharded), ("single", single)):
        eng.warmup()
        eng.start()
        try:
            threaded = np.stack(_serve_threaded(eng, obs))
        finally:
            eng.stop()
        answers[name] = (threaded, eng.run_batch(big))

    # the per-chip split, from the output's shards and the compiled kernel
    x = jax.device_put(big, sharded._sharding)
    y = sharded._sharded_fns["fused"](sharded.actor, x, sharded.frozen)
    shards = sorted((s.device.id, tuple(s.data.shape)) for s in y.addressable_shards)
    hlo = sharded._sharded_fns["fused"].lower(sharded.actor, x, sharded.frozen).compile()
    kernel_rows = sorted({int(m.group(1)) for m in re.finditer(
        r"= \(f32\[(\d+),\d+\][^\n]*custom_call_target=\"tpu_custom_call\"",
        hlo.as_text())})
    st = sharded.stats()
    same = all(np.array_equal(a, b) for a, b in zip(answers["sharded"], answers["single"]))
    report("serve4", mesh_devices=int(mesh.size), requests=st["requests"],
           batches=st["batches"], unsharded_batches=st["unsharded_batches"],
           identical_to_one_chip=same,
           max_diff=max_err(answers["sharded"], answers["single"]),
           batch512_shards=shards, kernel_rows_per_chip=kernel_rows)
    check(int(mesh.size) == 4, f"expected a 4-chip mesh, got {mesh.size}")
    check(same, "4-chip answers differ from the one-chip engine")
    check([s[1] for s in shards] == [(128, spec.act_dim)] * 4,
          f"512-row batch not split 128 rows per chip: {shards}")


# --------------------------------------------------------------------------- #

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: every phase on one chip; 4: the sharded serving phase only")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {devices[0].platform!r}); nothing run",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devices)} devices",
              file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache
    from repro.rl.envs import make

    cache = enable_compile_cache()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    report("device", **device, jax=jax.__version__, compile_cache=str(cache))
    env = make("halfcheetah")
    if args.chips == 4:
        four_chip_phase(env.spec)
    else:
        kernel_phase(env.spec)
        update_phase(env.spec)
        compile_phase(env.spec)
        agent = train_phase(env)
        serve_phase(agent, env.spec)

    out = REPO / "chiprun_out"
    out.mkdir(exist_ok=True)
    (out / f"chip_smoke_{args.chips}chip.json").write_text(
        json.dumps(FACTS, indent=2, default=str) + "\n")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
