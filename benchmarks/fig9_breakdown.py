"""Fig. 9 — execution-time breakdown of one timestep: environment / runtime
(transfer+replay) / accelerator (inference+training), in host-loop mode —
the paper's CPU↔FPGA decomposition, with the device boundary standing in
for PCIe."""
import pathlib
import sys

_REPO = pathlib.Path(__file__).resolve().parents[1]
if str(_REPO) not in sys.path:
    sys.path.insert(0, str(_REPO))

import argparse
import json

from benchmarks.common import RESULTS, emit

from repro.rl import ddpg, loop
from repro.rl.envs.locomotion import make

BATCHES = (64, 128, 256, 512)


def run(env_name: str, steps: int) -> dict:
    env = make(env_name)
    out = {}
    for bs in BATCHES:
        dcfg = ddpg.DDPGConfig(batch_size=bs)
        cfg = loop.TrainConfig(total_steps=steps, warmup_steps=20,
                               replay_capacity=8_192, eval_every=10 ** 9)
        _, rep = loop.train_host(env, cfg, dcfg)
        t = rep["times"]
        total = sum(t.values())
        out[bs] = {k: v / steps * 1e3 for k, v in t.items()}  # ms per step
        out[bs]["accel_frac"] = t["accelerator"] / total
        emit(f"fig9/{env_name}/batch{bs}", total / steps * 1e6,
             f"env_ms={out[bs]['env']:.2f};runtime_ms={out[bs]['runtime']:.2f};"
             f"accel_ms={out[bs]['accelerator']:.2f}")
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--env", default="halfcheetah")
    ap.add_argument("--steps", type=int, default=200)
    args = ap.parse_args(argv)
    out = run(args.env, args.steps)
    RESULTS.mkdir(parents=True, exist_ok=True)
    (RESULTS / f"fig9_{args.env}.json").write_text(json.dumps(out, indent=2))


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
