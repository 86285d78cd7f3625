"""serve/policy benchmark — the end-to-end throughput face of PR 2's kernel.

Measures the batched policy-serving engine the way the paper reports Fig. 8:
instructions (actions) per second, plus the serving-side numbers the paper's
FPGA never had to expose — request p50/p99 latency, batch occupancy, and the
adaptive dispatcher's mode choices per batch size.

Writes `BENCH_serve_policy.json` at the repo root (tracked across PRs, like
BENCH_fused_mlp.json) and emits the harness CSV lines.  The adaptive run
executes with tracing enabled and drops a Chrome trace-event JSONL
(`results/bench/trace_serve.jsonl`, Perfetto-openable) next to the
registry-backed stats; its JSON carries the dispatch predicted-vs-measured
audit and the per-site QAT saturation telemetry.
"""
import json
import pathlib
import sys
import threading

_REPO = pathlib.Path(__file__).resolve().parents[1]
if str(_REPO) not in sys.path:
    sys.path.insert(0, str(_REPO))

import argparse
import time

import numpy as np

from benchmarks.common import emit

SERVE_JSON = _REPO / "BENCH_serve_policy.json"
FUSED_JSON = _REPO / "BENCH_fused_mlp.json"
# smoke outputs live off-tree so the tracked artifacts keep real numbers
SMOKE_DIR = _REPO / "results" / "bench" / "smoke"
DISPATCH_BATCHES = [1, 7, 128, 512]


def bench_serve_policy(quick: bool = False, smoke: bool = False) -> dict:
    import jax
    from repro.rl import ddpg
    from repro.rl.envs.locomotion import make
    from repro.serve.policy import BatcherConfig, CostModel, PolicyEngine
    from repro.serve.policy.dispatch import MODES

    quick = quick or smoke
    env = make("halfcheetah")
    cfg = ddpg.DDPGConfig(qat_delay=0)  # frozen-quantized serving
    state = ddpg.init(jax.random.key(0), env.spec, cfg)
    dims = [env.spec.obs_dim, *ddpg.HIDDEN, env.spec.act_dim]

    big = 64 if smoke else 512
    buckets = (1, 8, 32, big) if smoke else (1, 8, 32, 128, big)
    lat_iters = 5 if smoke else (10 if quick else 30)
    ips_iters = 2 if quick else 5
    rng = np.random.default_rng(0)
    obs_big = rng.standard_normal((big, dims[0])).astype(np.float32)

    report = {
        # v3: adaptive carries dispatch_audit + qat_telemetry, and its
        # mode_histogram is phase-keyed ({"act": {mode: n}})
        "schema": "fixar/serve_policy_bench/v3",
        "config": {"net": dims, "big_batch": big, "quick": quick,
                   "smoke": smoke, "backend": jax.default_backend(),
                   "qat": "frozen_quantized"},
        "modes": {},
        "dispatch": {},
        "adaptive": {},
    }

    # ---- per-mode IPS + latency (forced dispatch) -------------------------
    for mode in MODES:
        eng = PolicyEngine.from_ddpg(
            state, force_mode=mode,
            batcher=BatcherConfig(buckets=buckets))
        eng.warmup(buckets=(1, big))
        eng.reset_stats()
        lat_us = []
        for _ in range(lat_iters):
            t0 = time.perf_counter()
            eng.run_batch(obs_big[:1])
            lat_us.append((time.perf_counter() - t0) * 1e6)
        big_us = []
        for _ in range(ips_iters):
            t0 = time.perf_counter()
            eng.run_batch(obs_big)
            big_us.append((time.perf_counter() - t0) * 1e6)
        ips = big / (float(np.median(big_us)) * 1e-6)
        res = {
            "ips_big": float(ips),
            "p50_ms": float(np.percentile(lat_us, 50) * 1e-3),
            "p99_ms": float(np.percentile(lat_us, 99) * 1e-3),
            "batches": eng.stats()["batches"],
        }
        report["modes"][mode] = res
        emit(f"serve/policy/{mode}/ips_b{big}", 0.0, f"ips={ips:.0f}")
        emit(f"serve/policy/{mode}/latency_b1",
             float(np.percentile(lat_us, 50)),
             f"p99_us={np.percentile(lat_us, 99):.0f}")

    # ---- dispatcher choices: default model vs bench-calibrated ------------
    # smoke calibrates from the smoke kernel bench (run.py orders them)
    cm_default = CostModel.default()
    cm_cal = CostModel.from_bench(
        SMOKE_DIR / FUSED_JSON.name if smoke else FUSED_JSON)
    report["dispatch"] = {
        "default": {str(b): cm_default.choose(b, dims)
                    for b in DISPATCH_BATCHES},
        "calibrated": {str(b): cm_cal.choose(b, dims)
                       for b in DISPATCH_BATCHES},
        "calibration_source": cm_cal.source,
    }
    d = report["dispatch"]["default"]
    emit("serve/policy/dispatch", 0.0,
         ";".join(f"b{b}={d[str(b)]}" for b in DISPATCH_BATCHES))
    assert d["1"] != d["512"], \
        "adaptive dispatcher must pick different modes for batch 1 vs 512"

    # ---- adaptive end-to-end: concurrent clients through the queue --------
    # traced + audited: the registry backs stats(), every batch feeds the
    # predicted-vs-measured audit, and the QAT probe samples saturation
    from repro.obs import Observability
    # trace path decided up front so the tracer self-flushes on close():
    # an aborted bench still leaves its (partial) trace on disk
    trace_path = (SMOKE_DIR if smoke else _REPO / "results" / "bench") \
        / "trace_serve.jsonl"
    trace_path.parent.mkdir(parents=True, exist_ok=True)
    obsb = Observability.tracing(trace_path=str(trace_path),
                                 qat_probe_every=2)
    eng = PolicyEngine.from_ddpg(
        state, batcher=BatcherConfig(buckets=buckets, max_wait_ms=2.0),
        obs=obsb)
    try:
        eng.warmup(buckets=(8, 32), modes=("layer",))
        eng.warmup(buckets=tuple(b for b in (128, big) if b in buckets),
                   modes=("fused",))
        eng.reset_stats()
        n_clients, per_client = (2, 4) if smoke \
            else ((4, 8) if quick else (8, 32))
        eng.start()

        def client(k):
            futs = [eng.submit(obs_big[(k + i) % big])
                    for i in range(per_client)]
            for f in futs:
                f.result(timeout=120.0)

        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        eng.stop()
        # one explicit probe so qat_telemetry is populated even on runs
        # too short for the qat_probe_every cadence to fire
        eng.record_qat_telemetry(obs_big[:buckets[1]], rows=buckets[1])
        st = eng.stats()
    finally:
        eng.close()     # idempotent stop + tracer flush to trace_path
    report["adaptive"] = {
        "requests": st["requests"],
        "ips_wall": st["ips_wall"],
        "p50_ms": st["p50_ms"],
        "p99_ms": st["p99_ms"],
        "batch_occupancy": st["batch_occupancy"],
        "mode_histogram": st["mode_histogram"],
        "dispatch_audit": st["dispatch_audit"],
        "qat_telemetry": st["qat_telemetry"],
    }
    emit("serve/policy/adaptive", 0.0,
         f"requests={st['requests']};ips_wall={st['ips_wall']:.0f};"
         f"p50_ms={st['p50_ms']:.2f};p99_ms={st['p99_ms']:.2f};"
         f"occupancy={st['batch_occupancy']:.2f}")
    drift = st["dispatch_audit"]["drift_factor"]
    emit("serve/policy/dispatch_audit", 0.0,
         f"drift_factor={drift:.2f};stale={st['dispatch_audit']['stale']};"
         f"batches={st['dispatch_audit']['batches']}")

    target = SMOKE_DIR / SERVE_JSON.name if smoke else SERVE_JSON
    target.parent.mkdir(parents=True, exist_ok=True)
    target.write_text(json.dumps(report, indent=2) + "\n")
    emit("serve/policy/json", 0.0, f"wrote={target.relative_to(_REPO)}")
    emit("serve/policy/trace", 0.0,
         f"wrote={trace_path.relative_to(_REPO)}")
    return report


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced iteration counts (CI-scale)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny batch + iteration counts (CI schema gate)")
    args = ap.parse_args(argv)
    bench_serve_policy(quick=args.quick, smoke=args.smoke)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
