"""Which side of a served actor's comparison a gap lies on: the program's
act paths against the reference, and against each other.

    python3 bench/witness.py --config ddpg_hopper --seeds 1,2,3

For each seed and each QAT phase (monitor, quantized) it freezes the
configuration's actor as a serving cell does and answers the same
observations through each mode of `ddpg.act_batch`: `fused` and `layer`
(the Pallas kernels the engine dispatches to) and `jnp` (plain XLA, here
at highest precision).
It prints one JSON line per seed and phase with each mode's widest and
mean gap to the reference, and each kernel's gap to `jnp`.  A gap that
the kernels share and `jnp` does not lies in the kernels' datapath.  Runs
on any backend: on the CPU the kernels run in the interpreter.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(), "tpu_logs"))


def gaps(a, b) -> dict:
    import numpy as np

    d = np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))
    return dict(max=float(d.max()), mean=float(d.mean()))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rows", type=int, default=4096)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from bench import harness
    from bench.generators import serve_open
    from repro.rl import ddpg

    harness.enable_cache()
    cfg = harness._load_json("configs", args.config + ".json")
    platform = jax.devices()[0].platform
    for seed in [int(s) for s in args.seeds.split(",")]:
        start = serve_open.make_start(cfg, args.rows)(jax.random.key(seed))
        keep = dict(actor=jax.device_get(start["actor"]), ranges=jax.device_get(start["ranges"]))
        obs = np.asarray(start["obs"], np.float32)
        for phase in ("monitor", "quantized"):
            state = serve_open.agent_of(start, cfg, phase == "quantized")
            frozen = ddpg.freeze_actor_quant(state)
            want = serve_open.reference_actions(cfg, dict(phase=phase), keep, obs)
            got = {}
            for mode in ("fused", "layer", "jnp"):
                with jax.default_matmul_precision("highest"):
                    got[mode] = np.asarray(ddpg.act_batch(state.actor, obs, frozen, mode=mode))
            row = dict(seed=seed, phase=phase, platform=platform,
                       frozen_quantized=bool(frozen.quantized))
            row.update({f"{m}_vs_reference": gaps(y, want) for m, y in got.items()})
            row.update({f"{m}_vs_jnp": gaps(got[m], got["jnp"]) for m in ("fused", "layer")})
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
