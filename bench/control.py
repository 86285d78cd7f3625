"""The readings a cell's limits are set from, on the chip at the cell's size.

    python3 bench/control.py --workload train_halfcheetah_monitor \
        --program-seeds 1,2,3,4,5,6,7,8,9,10,11,12 --control-seeds 101,102,103

In one process: for each program seed, a run of the cell (set-up, the
shortest measured span, the comparison) and the numbers it compared; for
each control seed, the reference put in the program's place at the next
precision below the configuration's (the traffic file's `control`: `high`,
three bfloat16 passes, below float32 at highest precision) and,
for training cells, with the planted half-batch fault.  The lower reading
of a number is the largest over the program's seeds, the upper the
smallest over the control's.  Prints one JSON line per reading and one
summary line; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(), "tpu_logs"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--program-seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args(argv)

    import jax

    from bench import harness

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("control: no TPU; nothing run", file=sys.stderr)
        return 2
    harness.enable_cache()
    compiles = harness.CompileCounter()
    cell = harness.load_cell(args.workload)
    generator = importlib.import_module(f"bench.generators.{cell.traffic['generator']}")
    seeds = lambda s: [int(x) for x in s.split(",") if x]
    rows: dict[str, list] = {}

    def emit(kind, seed, nums):
        print(json.dumps(dict(kind=kind, seed=seed, **nums)), flush=True)
        rows.setdefault(kind, []).append(nums)

    for seed in seeds(args.program_seeds):
        out = generator.run(cell, seed=seed, seconds=args.seconds, trace=False,
                         t_start=time.perf_counter(), devices=devices[:cell.chips],
                         compiles=compiles)
        emit("program", seed, out.numbers)
    variants = [cell.traffic["control"]] + (
        ["half_batch"] if cell.traffic["generator"] == "train_loop" else [])
    for seed in seeds(args.control_seeds):
        for v in variants:
            emit(v, seed, generator.readings(cell, seed, v))
    summary = {}
    for kind, rs in rows.items():
        agg = max if kind == "program" else min
        summary[kind] = {k: agg(r[k] for r in rs) for k in rs[0]}
    print(json.dumps(dict(summary=summary)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
