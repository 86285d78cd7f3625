"""train/learner benchmark — the training-throughput face of the fused VJP.

Measures the batched learner engine the way the paper reports its headline:
trained samples per second (FIXAR's 25293.3 IPS is *training* throughput,
delivered by intra-batch parallelism), plus the streaming-side numbers the
paper's FPGA never had to expose — update-request p50/p99 latency, batch
occupancy, and the train-phase adaptive dispatcher's mode choices.

Writes `BENCH_learner.json` at the repo root (tracked across PRs, next to
BENCH_fused_mlp.json / BENCH_serve_policy.json) and emits the harness CSV
lines.  `--smoke` shrinks buckets/iterations to CI scale while emitting the
same JSON shape (validated by `benchmarks/schema.py`); smoke output lands in
the untracked results/bench/smoke/ so tiny interpret-mode numbers never
clobber the tracked artifact.
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

LEARNER_JSON = _REPO / "BENCH_learner.json"
FUSED_JSON = _REPO / "BENCH_fused_mlp.json"
SMOKE_DIR = _REPO / "results" / "bench" / "smoke"
DISPATCH_BATCHES = [1, 8, 32, 128, 512]


def _replay_batch(rng, n, obs_dim, act_dim):
    return {
        "obs": rng.standard_normal((n, obs_dim)).astype(np.float32),
        "action": rng.uniform(-1, 1, (n, act_dim)).astype(np.float32),
        "reward": rng.standard_normal((n,)).astype(np.float32),
        "next_obs": rng.standard_normal((n, obs_dim)).astype(np.float32),
        "done": np.zeros((n,), bool),
    }


def bench_learner(quick: bool = False, smoke: bool = False) -> dict:
    import jax
    from repro.rl import ddpg
    from repro.rl.envs.locomotion import make
    from repro.serve.policy import BatcherConfig, CostModel
    from repro.serve.policy.dispatch import TRAIN_MODES
    from repro.train.learner import LearnerEngine

    quick = quick or smoke
    env = make("halfcheetah")
    cfg = ddpg.DDPGConfig(qat_delay=0)   # quantized-phase training
    state = ddpg.init(jax.random.key(0), env.spec, cfg)
    dims = [env.spec.obs_dim, *ddpg.HIDDEN, env.spec.act_dim]

    buckets = (4, 8, 16) if smoke else (8, 32, 128)
    big = buckets[-1]
    lat_iters = 3 if smoke else (5 if quick else 10)
    ups_iters = 2 if quick else 5
    rng = np.random.default_rng(0)
    big_batch = _replay_batch(rng, big, dims[0], dims[-1])

    # the train-phase dispatcher calibrates from the kernel bench (run.py
    # orders kernel -> serve -> learner so this JSON is fresh)
    cm = CostModel.from_bench(
        SMOKE_DIR / FUSED_JSON.name if smoke else FUSED_JSON)

    report = {
        # v2: adaptive carries dispatch_audit + qat_telemetry (the
        # engine's registry-backed stats sections)
        "schema": "fixar/learner_bench/v2",
        "config": {"net": dims, "buckets": list(buckets), "big_batch": big,
                   "quick": quick, "smoke": smoke,
                   "backend": jax.default_backend(),
                   "qat": "quantized_phase"},
        "modes": {},
        "dispatch": {},
        "adaptive": {},
    }

    # ---- per-mode updates/sec + latency (forced dispatch) -----------------
    for mode in TRAIN_MODES:
        eng = LearnerEngine.from_ddpg(
            state, cfg, force_mode=mode,
            batcher=BatcherConfig(buckets=buckets))
        eng.warmup(buckets=(buckets[0], big))
        eng.load_state(state)   # fixed starting state for every mode
        eng.reset_stats()
        lat_us = []
        small = {k: v[:buckets[0]] for k, v in big_batch.items()}
        for _ in range(lat_iters):
            t0 = time.perf_counter()
            eng.run_update(small)
            lat_us.append((time.perf_counter() - t0) * 1e6)
        big_us = []
        for _ in range(ups_iters):
            t0 = time.perf_counter()
            eng.run_update(big_batch)
            big_us.append((time.perf_counter() - t0) * 1e6)
        ups = 1e6 / float(np.median(big_us))
        st = eng.stats()
        res = {
            "updates_per_s": float(ups),
            "train_ips": float(ups * big),
            "p50_ms": float(np.percentile(lat_us, 50) * 1e-3),
            "p99_ms": float(np.percentile(lat_us, 99) * 1e-3),
            "updates": st["updates"],
        }
        report["modes"][mode] = res
        emit(f"train/learner/{mode}/updates_b{big}",
             float(np.median(big_us)),
             f"updates_per_s={ups:.2f};train_ips={ups * big:.0f}")
        emit(f"train/learner/{mode}/latency_b{buckets[0]}",
             float(np.percentile(lat_us, 50)),
             f"p99_us={np.percentile(lat_us, 99):.0f}")

    # ---- dispatcher choices per phase: the phase axis made visible --------
    report["dispatch"] = {
        "act": {str(b): cm.choose(b, dims, phase="act")
                for b in DISPATCH_BATCHES},
        "train": {str(b): cm.choose(b, dims, phase="train")
                  for b in DISPATCH_BATCHES},
        "calibration_source": cm.source,
    }
    d = report["dispatch"]
    emit("train/learner/dispatch", 0.0,
         ";".join(f"b{b}={d['train'][str(b)]}" for b in DISPATCH_BATCHES))

    # ---- adaptive end-to-end: concurrent producers through the queue ------
    # traced + audited: registry-backed stats, predicted-vs-measured
    # audit per update, QAT range/saturation probes off the live state
    from repro.obs import Observability
    # trace path decided up front so the tracer self-flushes on close():
    # an aborted bench still leaves its (partial) trace on disk
    trace_path = (SMOKE_DIR if smoke else _REPO / "results" / "bench") \
        / "trace_learner.jsonl"
    trace_path.parent.mkdir(parents=True, exist_ok=True)
    obsb = Observability.tracing(trace_path=str(trace_path),
                                 qat_probe_every=2)
    eng = LearnerEngine.from_ddpg(
        state, cfg, cost_model=cm,
        batcher=BatcherConfig(buckets=buckets, max_wait_ms=2.0),
        obs=obsb)
    try:
        eng.warmup(padded=True)
        eng.load_state(state)
        eng.reset_stats()
        n_prod, per_prod = (2, 3) if smoke \
            else ((3, 6) if quick else (6, 16))
        eng.start()

        def producer(k):
            prng = np.random.default_rng(k)
            futs = [eng.submit(
                        _replay_batch(prng,
                                      int(prng.integers(2, buckets[1])),
                                      dims[0], dims[-1]))
                    for _ in range(per_prod)]
            for f in futs:
                f.result(timeout=300.0)

        threads = [threading.Thread(target=producer, args=(k,))
                   for k in range(n_prod)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        eng.stop()
        # one explicit range+saturation probe so qat_telemetry is
        # populated even on runs too short for qat_probe_every to fire
        eng.record_qat_telemetry(
            _replay_batch(rng, buckets[0], dims[0], dims[-1]))
        st = eng.stats()
    finally:
        eng.close()     # idempotent stop + tracer flush to trace_path
    report["adaptive"] = {
        "requests": st["requests"],
        "updates": st["updates"],
        "transitions": st["transitions"],
        "updates_per_s_wall": st["updates_per_s_wall"],
        "train_ips_wall": st["train_ips_wall"],
        "p50_ms": st["p50_ms"],
        "p99_ms": st["p99_ms"],
        "batch_occupancy": st["batch_occupancy"],
        "mode_histogram": st["mode_histogram"],   # already phase-keyed
        "dispatch_audit": st["dispatch_audit"],
        "qat_telemetry": st["qat_telemetry"],
    }
    emit("train/learner/adaptive", 0.0,
         f"requests={st['requests']};updates={st['updates']};"
         f"train_ips_wall={st['train_ips_wall']:.0f};"
         f"p50_ms={st['p50_ms']:.2f};p99_ms={st['p99_ms']:.2f};"
         f"occupancy={st['batch_occupancy']:.2f}")
    drift = st["dispatch_audit"]["drift_factor"]
    emit("train/learner/dispatch_audit", 0.0,
         f"drift_factor={drift:.2f};stale={st['dispatch_audit']['stale']};"
         f"batches={st['dispatch_audit']['batches']}")

    target = SMOKE_DIR / LEARNER_JSON.name if smoke else LEARNER_JSON
    target.parent.mkdir(parents=True, exist_ok=True)
    target.write_text(json.dumps(report, indent=2) + "\n")
    emit("train/learner/json", 0.0, f"wrote={target.relative_to(_REPO)}")
    emit("train/learner/trace", 0.0,
         f"wrote={trace_path.relative_to(_REPO)}")
    return report


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced iteration counts (CI-scale)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny buckets + iteration counts (CI schema gate)")
    args = ap.parse_args(argv)
    bench_learner(quick=args.quick, smoke=args.smoke)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
