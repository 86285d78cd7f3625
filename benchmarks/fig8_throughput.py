"""Fig. 8 — training throughput (IPS) vs batch size {64,128,256,512}.

IPS = collected samples / end-to-end time of the full timestep loop
(inference + training + environment), the paper's metric.  Absolute numbers
are CPU-bound here; the *scaling shape* (IPS grows with batch size, FPGA-
style fused loop beats the host round-trip loop) is the reproducible claim.
"""
import pathlib
import sys

_REPO = pathlib.Path(__file__).resolve().parents[1]
if str(_REPO) not in sys.path:
    sys.path.insert(0, str(_REPO))

import argparse
import json
import time

from benchmarks.common import RESULTS, emit

from repro.rl import ddpg, loop
from repro.rl.envs.locomotion import make

BATCHES = (64, 128, 256, 512)


def run(env_name: str, steps: int) -> dict:
    env = make(env_name)
    out = {}
    for bs in BATCHES:
        dcfg = ddpg.DDPGConfig(batch_size=bs, qat_delay=steps // 2)
        cfg = loop.LoopConfig(total_steps=steps, warmup_steps=min(600, steps),
                              replay_capacity=20_000, eval_every=10 ** 9)
        t0 = time.perf_counter()
        loop.train_fused(env, cfg, dcfg, chunk=min(500, steps))
        dt = time.perf_counter() - t0
        ips = steps / dt
        out[bs] = ips
        emit(f"fig8/{env_name}/batch{bs}", dt * 1e6 / steps, f"ips={ips:.1f}")
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--env", default="halfcheetah")
    ap.add_argument("--steps", type=int, default=2_000)
    args = ap.parse_args(argv)
    out = run(args.env, args.steps)
    RESULTS.mkdir(parents=True, exist_ok=True)
    (RESULTS / f"fig8_{args.env}.json").write_text(json.dumps(out, indent=2))


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
