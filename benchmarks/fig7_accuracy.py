"""Fig. 7 — algorithm accuracy: reward curves for fp32 / fxp32 / fxp16-from-
scratch / FIXAR dynamic (fxp32 -> fxp16 after the quantization delay).

Paper claim: FIXAR's dynamic format tracks fp32 (dips at the switch, then
recovers); starting at 16-bit from scratch fails to train.  MuJoCo is
replaced by the pure-JAX surrogate (DESIGN.md §2), so we validate the
*relative* format behaviour, which is the paper's actual claim.

CPU scaling: `--steps` (default 25k) ~ 1/40th of the paper's 1M but past
the point where the format separation is visible on the surrogate.
"""
import pathlib
import sys

_REPO = pathlib.Path(__file__).resolve().parents[1]
if str(_REPO) not in sys.path:
    sys.path.insert(0, str(_REPO))

import argparse
import json

from benchmarks.common import RESULTS, emit
import time


from repro.rl import ddpg, loop
from repro.rl.envs.locomotion import make

FORMATS = {
    # paper Fig. 7 legend -> DDPGConfig knobs
    "fp32": dict(qat_enabled=False, fxp_weights=False, qat_delay=10 ** 9),
    "fxp32": dict(qat_enabled=True, fxp_weights=True, qat_delay=10 ** 9),
    "fxp16_scratch": dict(qat_enabled=True, fxp_weights=True, qat_delay=0),
    "fixar_dynamic": dict(qat_enabled=True, fxp_weights=True,
                          qat_delay=None),  # set to 40% of steps below
}


def run(env_name: str, steps: int, seed: int = 1) -> dict:
    env = make(env_name)
    curves = {}
    for name, kw in FORMATS.items():
        kw = dict(kw)
        if kw["qat_delay"] is None:
            kw["qat_delay"] = int(0.4 * steps)
        dcfg = ddpg.DDPGConfig(batch_size=64, actor_lr=3e-4, critic_lr=1e-3,
                               exploration_sigma=0.15, **kw)
        cfg = loop.TrainConfig(total_steps=steps, warmup_steps=500,
                               eval_every=max(steps // 8, 1000),
                               replay_capacity=min(steps, 100_000),
                               eval_episodes=4, seed=seed)
        t0 = time.perf_counter()
        _, hist = loop.train_device(env, cfg, dcfg)
        dt = time.perf_counter() - t0
        curves[name] = {"step": hist["step"], "reward": hist["eval_reward"]}
        emit(f"fig7/{env_name}/{name}", dt * 1e6 / steps,
             f"final_reward={hist['eval_reward'][-1]:.1f}")
    return curves


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--env", default="pendulum")
    ap.add_argument("--steps", type=int, default=25_000)
    args = ap.parse_args(argv)
    curves = run(args.env, args.steps)
    RESULTS.mkdir(parents=True, exist_ok=True)
    # short runs get their own artifact so CI-scale sweeps never clobber
    # the full reproduction curves referenced by EXPERIMENTS.md
    suffix = "" if args.steps >= 20_000 else f"_quick{args.steps}"
    out = RESULTS / f"fig7_{args.env}{suffix}.json"
    out.write_text(json.dumps(curves, indent=2))
    print(f"# wrote {out}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
