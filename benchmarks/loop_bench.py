"""Device-resident training-loop benchmark — the scan-the-whole-loop payoff.

`rl/loop.train_device` runs an entire eval window of the act → explore →
env-step → store → update chain as ONE jitted `lax.scan` launch over a
vmapped env fleet.  This bench measures what that buys:

  * scaling   — env-steps/s and updates/s as the fleet width `n_envs` grows
    (each timestep still performs exactly one update, so env throughput
    scales with the fleet while update throughput stays flat: the classic
    vmap-amortization curve);
  * host_vs_device — wall updates/s of the scanned window vs the
    paper-faithful `train_host` loop at the learner-bench config
    (halfcheetah, batch 128, quantized-phase QAT), i.e. how much of the
    per-step dispatch/transfer tax the single-launch window removes.

Writes `BENCH_device_loop.json` at the repo root (tracked across PRs, next
to the kernel/serve/learner artifacts) and emits the harness CSV lines.
`--smoke` shrinks fleet sizes/windows to CI scale while emitting the same
JSON shape (validated by `benchmarks/schema.py`); smoke output lands in the
untracked results/bench/smoke/ so tiny interpret-mode numbers never clobber
the tracked artifact.
"""
import json
import pathlib
import sys

_REPO = pathlib.Path(__file__).resolve().parents[1]
if str(_REPO) not in sys.path:
    sys.path.insert(0, str(_REPO))

import argparse
import time

import numpy as np

from benchmarks.common import emit

LOOP_JSON = _REPO / "BENCH_device_loop.json"
SMOKE_DIR = _REPO / "results" / "bench" / "smoke"


def _window_cfg(loop, n_envs, window, capacity, seed=0):
    return loop.TrainConfig(
        total_steps=window,
        warmup_steps=1,
        replay_capacity=capacity,
        eval_every=window,
        eval_episodes=1,
        n_envs=n_envs,
        seed=seed,
        noise_kind="gaussian",
    )


def bench_loop(quick: bool = False, smoke: bool = False) -> dict:
    import jax
    from repro.rl import ddpg, loop
    from repro.rl.envs.locomotion import make

    env = make("halfcheetah")
    # the learner bench's config: quantized-phase training at batch 128
    dcfg = ddpg.DDPGConfig(qat_delay=0, batch_size=16 if smoke else 128)
    dims = [env.spec.obs_dim, *ddpg.HIDDEN, env.spec.act_dim]

    if smoke:
        n_envs_list, window, reps, capacity, host_steps = [1, 4], 8, 1, 1024, 6
    elif quick:
        n_envs_list, window, reps, capacity, host_steps = [1, 16, 128], 64, 2, 16_384, 30
    else:
        n_envs_list, window, reps, capacity, host_steps = (
            [1, 16, 64, 256, 1024], 200, 3, 65_536, 100
        )

    report = {
        "schema": "fixar/device_loop_bench/v1",
        "config": {
            "env": env.spec.name,
            "net": dims,
            "batch": dcfg.batch_size,
            "window": window,
            "n_envs": list(n_envs_list),
            "reps": reps,
            "backend": jax.default_backend(),
            "quick": quick,
            "smoke": smoke,
        },
        "scaling": {},
        "host_vs_device": {},
        "launches": {},
    }

    # ---- device loop: one scanned launch per window, fleet sweep ----------
    traces_per_config = []
    for n in n_envs_list:
        cfg = _window_cfg(loop, n, window, capacity)
        ts = loop.init_train_state(env, cfg, dcfg)
        before = loop._train_window._cache_size()
        # compile + warm launch (not timed)
        ts, stats = loop._train_window(ts, env=env, cfg=cfg, dcfg=dcfg, window=window)
        jax.block_until_ready(stats["reward"])
        walls = []
        for _ in range(reps):
            t0 = time.perf_counter()
            ts, stats = loop._train_window(ts, env=env, cfg=cfg, dcfg=dcfg, window=window)
            jax.block_until_ready(stats["reward"])
            walls.append(time.perf_counter() - t0)
        traces_per_config.append(loop._train_window._cache_size() - before)
        wall = float(np.median(walls))
        ups = window / wall
        sps = window * n / wall
        report["scaling"][str(n)] = {
            "env_steps_per_s": float(sps),
            "updates_per_s": float(ups),
            "wall_s": wall,
        }
        emit(
            f"rl/loop/device/n{n}",
            wall * 1e6 / window,
            f"env_steps_per_s={sps:.0f};updates_per_s={ups:.2f}",
        )

    # every config must have traced its window exactly once (warm launch),
    # with the timed reps hitting the jit cache — the single-launch claim
    report["launches"] = {
        "windows_traced_per_config": max(traces_per_config),
        "timed_reps_per_config": reps,
    }

    # ---- host loop at the same config: the per-step dispatch tax ----------
    host_cfg = _window_cfg(loop, 1, host_steps, capacity)
    # warm pass first so XLA's compile cache absorbs the trace/compile cost
    # (train_host re-jits its helpers per call; the HLO is identical)
    loop.train_host(env, _window_cfg(loop, 1, 3, capacity), dcfg)
    t0 = time.perf_counter()
    ts_h, _ = loop.train_host(env, host_cfg, dcfg)
    host_wall = time.perf_counter() - t0
    host_updates = int(ts_h.agent.step)
    host_ups = host_updates / host_wall
    dev_ups = report["scaling"][str(n_envs_list[0])]["updates_per_s"]
    report["host_vs_device"] = {
        "host_updates_per_s": float(host_ups),
        "host_steps": host_steps,
        "device_updates_per_s": float(dev_ups),
        "speedup": float(dev_ups / host_ups),
    }
    emit(
        "rl/loop/host/updates",
        host_wall * 1e6 / max(host_updates, 1),
        f"updates_per_s={host_ups:.2f};device_updates_per_s={dev_ups:.2f};"
        f"speedup={dev_ups / host_ups:.2f}",
    )

    target = SMOKE_DIR / LOOP_JSON.name if smoke else LOOP_JSON
    target.parent.mkdir(parents=True, exist_ok=True)
    target.write_text(json.dumps(report, indent=2) + "\n")
    emit("rl/loop/json", 0.0, f"wrote={target.relative_to(_REPO)}")
    return report


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="reduced fleet sizes / window (CI-scale)")
    ap.add_argument("--smoke", action="store_true", help="tiny fleets + window (CI schema gate)")
    args = ap.parse_args(argv)
    bench_loop(quick=args.quick, smoke=args.smoke)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
