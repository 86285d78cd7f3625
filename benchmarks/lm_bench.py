"""Continuously-batched LM serving benchmark (serve/lm on the shared runtime).

Measures `LMEngine` the way an LLM-serving system reports itself:
tokens/second, time-to-first-token p50/p99, and decode-batch occupancy —
against a sequential baseline (the same engine pinned to one lane, i.e.
`serve/engine.generate` semantics on the same compiled prefill/decode
functions, so the comparison isolates the scheduler).

Writes `BENCH_serve_lm.json` at the repo root (tracked across PRs,
schema-gated like the other four artifacts) and emits the harness CSV
lines.  The engine run executes with tracing enabled and drops a Chrome
trace-event JSONL (`results/bench/trace_serve_lm.jsonl`) showing the
admission / decode / eviction lifecycle.

Both smoke and full runs use the qwen2_0_5b smoke config: the full LM
checkpoints don't fit a CI CPU, and the scheduler numbers (occupancy,
speedup) are model-size-independent.
"""
import json
import pathlib
import sys
import threading
import time

_REPO = pathlib.Path(__file__).resolve().parents[1]
if str(_REPO) not in sys.path:
    sys.path.insert(0, str(_REPO))

import argparse

import numpy as np

from benchmarks.common import emit

LM_JSON = _REPO / "BENCH_serve_lm.json"
# smoke outputs live off-tree so the tracked artifacts keep real numbers
SMOKE_DIR = _REPO / "results" / "bench" / "smoke"

ARCH = "qwen2_0_5b"


def bench_serve_lm(quick: bool = False, smoke: bool = False) -> dict:
    import jax
    from repro.configs import registry
    from repro.models import transformer as T
    from repro.obs import Observability
    from repro.serve.lm import LMEngine

    quick = quick or smoke
    cfg = registry.get_smoke(ARCH)
    params = T.init_params(jax.random.key(0), cfg)

    lanes = 2 if smoke else 4
    max_seq = 64 if smoke else 128
    max_new = 4 if smoke else (8 if quick else 16)
    requests = lanes * 2 if smoke else lanes * (2 if quick else 4)
    rng = np.random.default_rng(0)
    prompt_lens = [int(5 + (i * 7) % 20) for i in range(requests)]
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in prompt_lens]

    report = {
        "schema": "fixar/serve_lm_bench/v1",
        "config": {"arch": ARCH, "lanes": lanes, "max_seq": max_seq,
                   "max_new": max_new, "requests": requests,
                   "prompt_lens": prompt_lens, "quick": quick,
                   "smoke": smoke, "backend": jax.default_backend()},
        "engine": {},
        "sequential": {},
    }

    # ---- sequential baseline: one lane == generate() semantics ------------
    seq = LMEngine(params, cfg, lanes=1, max_seq=max_seq)
    # warm every prompt length (prefill retraces per length) + decode, so
    # both runs measure steady-state scheduling, not compilation
    seq.generate_batch(prompts, [1] * requests)
    seq.generate_batch(prompts[:1], [2])
    seq.reset_stats()
    t0 = time.perf_counter()
    seq.generate_batch(prompts, [max_new] * requests)
    seq_wall = time.perf_counter() - t0
    seq_tokens = seq.stats()["tokens"]
    report["sequential"] = {
        "tokens": seq_tokens,
        "tokens_per_s_wall": seq_tokens / seq_wall,
    }
    emit("serve/lm/sequential", 0.0,
         f"tokens={seq_tokens};tps={seq_tokens / seq_wall:.1f}")

    # ---- continuous batching: concurrent staggered clients, traced --------
    # trace path decided up front so the tracer self-flushes on close():
    # an aborted bench still leaves its (partial) trace on disk
    trace_path = (SMOKE_DIR if smoke else _REPO / "results" / "bench") \
        / "trace_serve_lm.jsonl"
    trace_path.parent.mkdir(parents=True, exist_ok=True)
    obsb = Observability.tracing(trace_path=str(trace_path))
    eng = LMEngine(params, cfg, lanes=lanes, max_seq=max_seq, obs=obsb)
    try:
        # warm every prompt length (prefill retraces per length) + decode
        eng.generate_batch(prompts, [1] * requests)
        eng.generate_batch(prompts[:lanes], [2] * lanes)
        eng.reset_stats()
        eng.start()
        t0 = time.perf_counter()

        def client(k):
            # staggered arrivals: later clients admit mid-decode
            time.sleep(0.002 * k)
            eng.submit(prompts[k], max_new).result(timeout=300.0)

        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(requests)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        eng.stop()
        st = eng.stats()
    finally:
        eng.close()     # idempotent stop + tracer flush to trace_path
    report["engine"] = {
        "requests": st["requests"],
        "tokens": st["tokens"],
        "decode_steps": st["decode_steps"],
        "tokens_per_s_wall": st["tokens"] / wall,
        "ttft_p50_ms": st["ttft_p50_ms"],
        "ttft_p99_ms": st["ttft_p99_ms"],
        "p50_ms": st["p50_ms"],
        "p99_ms": st["p99_ms"],
        "decode_occupancy": st["decode_occupancy"],
        "lanes": st["lanes"],
        "mode_histogram": st["mode_histogram"],
    }
    report["speedup_vs_sequential"] = (
        report["engine"]["tokens_per_s_wall"]
        / report["sequential"]["tokens_per_s_wall"])
    emit("serve/lm/engine", 0.0,
         f"requests={st['requests']};tokens={st['tokens']};"
         f"tps={report['engine']['tokens_per_s_wall']:.1f};"
         f"ttft_p50_ms={st['ttft_p50_ms']:.2f};"
         f"occupancy={st['decode_occupancy']:.2f}")
    emit("serve/lm/speedup", 0.0,
         f"vs_sequential={report['speedup_vs_sequential']:.2f}")

    target = SMOKE_DIR / LM_JSON.name if smoke else LM_JSON
    target.parent.mkdir(parents=True, exist_ok=True)
    target.write_text(json.dumps(report, indent=2) + "\n")
    emit("serve/lm/json", 0.0, f"wrote={target.relative_to(_REPO)}")
    emit("serve/lm/trace", 0.0, f"wrote={trace_path.relative_to(_REPO)}")
    return report


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced iteration counts (CI-scale)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny batch + iteration counts (CI schema gate)")
    args = ap.parse_args(argv)
    bench_serve_lm(quick=args.quick, smoke=args.smoke)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
