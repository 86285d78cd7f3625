"""One run of one cell, as `bench/run.py` is asked for it.

The harness finds everything by the names in `BENCHMARK.json`:

  cell      an entry of `workloads`: a `config` and a `traffic` name;
  config    `bench/configs/<config>.json` (sizes and the precision stated),
            whose `reference` and `work` keys name `bench/reference/<x>.py`
            and `bench/work/<x>.py`;
  traffic   `bench/traffic/<traffic>.json`, whose `generator` key names the
            general generator in `bench/generators/<generator>.py`;
  limits    `bench/limits/<cell>.json`: the limit of each number that
            `correct` compares;
  per-layer `bench/metrics/<metric>.py`, a reader with `read(reading)`
            that returns a number or None;
  peaks     `bench/peaks.json`, keyed by the device kind JAX reports.

A generator sets the cell up, measures for `--seconds`, or traces with
`--trace 1`, frees the program's state and compares what the timed path
produced with the reference.  The harness prints the numbers compared
with their limits as the last lines on standard error, and one JSON
result as the last line on standard output.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import math
import os
import sys
import time
from typing import Any, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
OUT_DIR = os.path.join(ROOT, ".bench_out")


def _load_json(*parts) -> dict:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """Everything one run needs to know about its cell."""

    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def load_cell(name: str, bench_file: str = os.path.join(ROOT, "BENCHMARK.json")) -> Cell:
    with open(bench_file) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m else m["moves"] in moved)]
    return Cell(name=name, chips=w["chips"], config=_load_json("configs", w["config"] + ".json"),
                traffic=_load_json("traffic", w["traffic"] + ".json"),
                limits=_load_json("limits", name + ".json"), end_to_end=e2e, per_layer=per_layer)


def enable_cache() -> None:
    """JAX's persistent compile cache at a fixed path in the checkout, for
    every program whatever its compile time, so that only a cell's first
    run in a checkout compiles."""
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


class CompileCounter:
    """Counts the backend compiles JAX reports (a cache hit is no compile)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event == self.EVENT:
            self.count += 1


@dataclasses.dataclass
class Reading:
    """What a per-layer reader gets: the reduced trace, the program's
    counters, the work counts, the peaks of this device, and what the
    generator measured over the traced span."""

    trace: Any
    counters: dict
    work: Any
    config: dict
    traffic: dict
    peaks: dict
    measured: dict
    chips: int


@dataclasses.dataclass
class Outcome:
    """What a generator hands back to the harness."""

    metrics: dict                      # end-to-end values, --trace 0
    attempted: int
    failed: int
    checks: list                       # (name, value, limit)
    memory_peak_bytes: Optional[int]
    reading: Optional[Reading] = None  # --trace 1
    notes: dict = dataclasses.field(default_factory=dict)
    numbers: dict = dataclasses.field(default_factory=dict)  # all the check computed


def device_kind_peaks(kind: str) -> dict:
    peaks = _load_json("peaks.json")["devices"]
    if kind not in peaks:
        raise SystemExit(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return peaks[kind]


def work_module(cfg: dict):
    """The work counts of a configuration: `bench/work/<cfg["work"]>.py`."""
    return importlib.import_module(f"bench.work.{cfg['work']}")


def start_trace(cell: str, seed: int) -> str:
    """Start the profiler, without the Python function tracer (it would
    slow the host), into a fresh directory inside the checkout."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    path = trace_dir(cell, seed)
    jax.profiler.start_trace(path, profiler_options=opts)
    return path


def trace_dir(cell: str, seed: int) -> str:
    """A fresh directory for one run's profiler trace, inside the checkout."""
    import shutil

    path = os.path.join(OUT_DIR, "trace", f"{cell}-{seed}")
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


def memory_peak(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def judge(checks: list) -> bool:
    return all(v is not None and math.isfinite(v) and lim is not None and v <= lim
               for _, v, lim in checks)


def run(argv=None, *, t_start: Optional[float] = None, require_chip: bool = True,
        cell: Optional[Cell] = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cell or load_cell(args.workload)

    import jax

    devices = jax.devices()
    if require_chip:
        if devices[0].platform != "tpu":
            say(f"bench: no TPU (JAX platform {devices[0].platform!r}); nothing run")
            return 2
        if len(devices) < cell.chips:
            say(f"bench: the cell needs {cell.chips} chips, JAX sees {len(devices)}")
            return 2
    devices = devices[:cell.chips]
    enable_cache()
    compiles = CompileCounter()
    generator = importlib.import_module(f"bench.generators.{cell.traffic['generator']}")
    out: Outcome = generator.run(cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
                              t_start=t_start, devices=devices, compiles=compiles)

    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind, "count": len(devices),
              "memory_peak_bytes": out.memory_peak_bytes}
    result: dict = {"correct": judge(out.checks), "attempted": out.attempted,
                    "failed": out.failed}
    if args.trace:
        r = out.reading
        from bench import trace as tr

        device["busy_s"] = tr.busy_ns(r.trace) / 1e9
        device["window_s"] = tr.window_ns(r.trace) / 1e9
        metrics = {}
        for m in cell.per_layer:
            reader = importlib.import_module(f"bench.metrics.{m['name']}")
            v = reader.read(r)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = {"device_ops": tr.top_ops(r.trace, 10),
                               "idle_gaps": tr.idle_by_host(r.trace, 10)}
    else:
        result["metrics"] = {m["name"]: {"value": out.metrics[m["name"]], "unit": m["unit"]}
                             for m in cell.end_to_end if m["name"] in out.metrics}
        result["device"] = device
    for k, v in out.notes.items():
        say(f"bench: {k} = {v}")
    checks = {n: {"value": v, "limit": lim} for n, v, lim in out.checks}
    result["check"] = checks
    for n, v, lim in out.checks:
        say(f"check {n} = {v} (limit {lim})")
    print(json.dumps(result), flush=True)
    return 0
