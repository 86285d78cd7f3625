"""Find a serving cell's knee: the highest offered rate whose p95 stays
within a latency limit with no growing backlog.

    python3 bench/sweep.py --workload serve_hopper_open_monitor --rates 2000,5000,10000 \
        --seconds 5 --seed 1 --limit-ms 8

One process sets the engine up once and offers each rate in turn, open
loop, with the cell's arrival law.  A rate holds when its p95 is within
the limit, every request was answered, and the p95 of the last fifth of
the requests is within the limit too (a backlog that grows through the
window shows there first).  Prints one JSON line per rate; the rate a
cell offers (about four fifths of the knee) goes into its traffic file.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--limit-ms", type=float, required=True)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from bench import harness, schedule
    from bench.generators import serve_open

    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU; nothing run", file=sys.stderr)
        return 2
    harness.enable_cache()
    cell = harness.load_cell(args.workload)
    rates = [float(r) for r in args.rates.split(",")]
    n_max = int(max(rates) * args.seconds) + 1
    start = serve_open.make_start(cell.config, n_max)(jax.random.key(args.seed))
    obs = np.asarray(start["obs"], np.float32)
    engine = serve_open.engine_of(start, cell.config, serve_open.quantized(cell.traffic))
    knee = None
    for rate in rates:
        due = schedule.poisson(rate, args.seconds, args.seed)
        engine.reset_stats()
        engine.start()
        try:
            res = serve_open.open_loop(engine, obs[: len(due)], due)
        finally:
            engine.stop()
        st = engine.stats()
        lat = res["latency"]
        tail = lat[int(0.8 * len(lat)):]
        row = dict(rate_per_s=rate, requests=len(due),
                   answered=int(np.isfinite(lat).sum()),
                   p50_ms=schedule.percentile(lat, 50) * 1e3,
                   p95_ms=schedule.percentile(lat, 95) * 1e3,
                   p99_ms=schedule.percentile(lat, 99) * 1e3,
                   last_fifth_p95_ms=schedule.percentile(tail, 95) * 1e3,
                   generator_late_p99_ms=float(np.percentile(res["late"], 99) * 1e3),
                   rows_per_call=st["requests"] / max(st["batches"], 1),
                   modes=st["mode_histogram"])
        row["holds"] = (row["answered"] == len(due) and row["p95_ms"] <= args.limit_ms
                        and row["last_fifth_p95_ms"] <= args.limit_ms)
        if row["holds"]:
            knee = rate
        print(json.dumps(row), flush=True)
    print(json.dumps(dict(knee_rate_per_s=knee, limit_ms=args.limit_ms)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
