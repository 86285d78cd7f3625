"""Serving cells: open-loop requests to `repro.serve.policy.PolicyEngine`.

The traffic file gives the offered `rate_per_s`, the `arrival` law
(see `bench/schedule.py`) and the QAT `phase` the served actor was frozen
in; every request is one observation from an independent controller.  A
run:

  1. makes a frozen actor from the seed, in one jitted call: weights, the
     16-bit ranges a monitor phase would have captured on observations of
     the served distribution (used past the QAT delay), and the
     observations to serve;
  2. set-up: `PolicyEngine.from_ddpg(state)` with its default batcher and
     dispatcher, as a user gets it; warms each bucket in the mode the
     dispatcher picks for it, and starts the engine;
  3. sends each request at its due time from one thread while a second
     collects the answers in order.  A request's latency runs from its due
     time to its answer, so a late generator counts against the system;
     one that fails or never comes is +inf;
  4. stops the engine, frees it, and compares every answer with the
     reference actor on the same observation.
"""
from __future__ import annotations

import gc
import queue
import threading
import time

import numpy as np

from bench import harness, schedule
from bench import trace as tr
from bench.harness import Outcome, Reading

GRACE_S = 60.0


def make_start(cfg: dict, n_obs: int):
    import jax

    from bench.reference import ddpg as ref

    @jax.jit
    def start(key):
        ka, ko, kc = jax.random.split(key, 3)
        actor = ref.init_layers(ka, ref.actor_sizes(cfg), None)
        cal = jax.random.normal(kc, (16384, cfg["obs_dim"]))
        mon = ref.Net(tuple(cfg["actor_activations"]), quantized=False)
        return dict(actor=actor, ranges=ref.site_extrema(actor, cal, mon),
                    obs=jax.random.normal(ko, (n_obs, cfg["obs_dim"])))

    return start


def agent_of(start: dict, cfg: dict, quantized: bool):
    """The program's state of an agent whose actor is the start's, before
    (monitor phase) or past (quantized) the QAT delay."""
    import dataclasses

    import jax.numpy as jnp

    from repro.core.qat import QATState
    from repro.core.ranges import RangeStat
    from repro.optim import adam
    from repro.rl import ddpg

    actor = {f"l{i}": {"w": l["w"], "b": l["b"]} for i, l in enumerate(start["actor"])}
    qat = QATState.init(delay=cfg["qat_delay"], sites=ddpg.ACTOR_SITES + ddpg.CRITIC_SITES,
                        n_bits=cfg["qat_bits"])
    if quantized:
        ranges = dict(qat.ranges)
        for site, (mn, mx) in zip(ddpg.ACTOR_SITES, start["ranges"]):
            ranges[site] = RangeStat(a_min=mn, a_max=mx, count=jnp.array(1, jnp.int32))
        qat = dataclasses.replace(qat, ranges=ranges,
                                  step=jnp.array(cfg["qat_delay"], jnp.int32))
    return ddpg.DDPGState(actor=actor, critic=actor, actor_target=actor, critic_target=actor,
                          actor_opt=adam.init(actor), critic_opt=adam.init(actor), qat=qat,
                          step=jnp.zeros((), jnp.int32))


def engine_of(start: dict, cfg: dict, quantized: bool):
    """The frozen actor behind the engine, as from_ddpg builds it."""
    from repro.serve.policy import PolicyEngine

    engine = PolicyEngine.from_ddpg(agent_of(start, cfg, quantized))
    # every batch size a drain can produce: each pads to its bucket, runs the
    # mode the dispatcher picks there, and slices its own rows back out
    rows = np.zeros((engine.batcher_config.max_batch, cfg["obs_dim"]), np.float32)
    for n in range(1, engine.batcher_config.max_batch + 1):
        engine.run_batch(rows[:n])
    engine.reset_stats()
    return engine


def open_loop(engine, obs: np.ndarray, due: np.ndarray, annotate: bool = False) -> dict:
    """Send request i at t0 + due[i]; returns answers, latencies from the
    due time (+inf where none came) and how late each send was."""
    import jax

    n = len(due)
    answers = np.full((n, engine.dims[-1]), np.nan, np.float32)
    done_at = np.full(n, np.inf)
    sent = queue.Queue()
    errors = []       # (request, exception); each one counts as a miss
    mark = jax.profiler.TraceAnnotation if annotate else None

    def collect():
        for _ in range(n):
            i, fut = sent.get()
            if isinstance(fut, Exception):
                errors.append((i, fut))
                continue
            try:
                left = t_end - time.perf_counter()
                answers[i] = fut.result(timeout=max(left, 0.0))
                done_at[i] = time.perf_counter()
            except Exception as e:  # noqa: BLE001 - the engine relays any failure
                errors.append((i, e))

    late = np.zeros(n)
    t0 = time.perf_counter() + 0.05
    t_end = t0 + float(due[-1]) + GRACE_S
    collector = threading.Thread(target=collect, name="bench-collect")
    collector.start()
    for i in range(n):
        wait = t0 + due[i] - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        try:
            if mark:
                with mark("bench.submit"):
                    fut = engine.submit(obs[i])
            else:
                fut = engine.submit(obs[i])
        except Exception as e:  # noqa: BLE001 - a refused request counts as a miss
            fut = e
        late[i] = time.perf_counter() - (t0 + due[i])
        sent.put((i, fut))
    collector.join()
    return dict(answers=answers, latency=done_at - (t0 + due), late=late, errors=errors)


def run(cell, *, seed: int, seconds: float, trace: bool, t_start: float, devices,
        compiles) -> Outcome:
    import jax

    cfg, traffic = cell.config, cell.traffic
    due = schedule.poisson(traffic["rate_per_s"], seconds, seed)
    start = make_start(cfg, len(due))(jax.random.key(seed))
    keep = dict(actor=jax.device_get(start["actor"]), ranges=jax.device_get(start["ranges"]))
    obs = np.asarray(start["obs"], np.float32)
    engine = engine_of(start, cfg, quantized(traffic))
    del start
    engine.start()
    setup_s = time.perf_counter() - t_start
    compiles_before = compiles.count

    log_dir = None
    if trace:
        inner = engine.run_batch

        def run_batch(x):
            with jax.profiler.TraceAnnotation("bench.run_batch"):
                return inner(x)

        engine.run_batch = run_batch
        log_dir = harness.start_trace(cell.name, seed)
    try:
        with jax.profiler.TraceAnnotation("bench.traced"):
            res = open_loop(engine, obs, due, annotate=trace)
    finally:
        engine.stop()
        if trace:
            jax.profiler.stop_trace()
    in_window = compiles.count - compiles_before
    stats = engine.stats()
    mem = harness.memory_peak(devices)
    del engine
    gc.collect()

    lat = res["latency"]
    p95_ms = schedule.percentile(lat, 95.0) * 1e3
    answered = np.isfinite(lat)
    notes = dict(compiles_in_window=in_window, requests=len(due),
                 answered=int(answered.sum()), failed=len(res["errors"]),
                 first_error=repr(res["errors"][0]) if res["errors"] else None,
                 generator_late_p50_ms=float(np.median(res["late"]) * 1e3),
                 generator_late_p99_ms=float(np.percentile(res["late"], 99) * 1e3),
                 generator_late_max_ms=float(res["late"].max() * 1e3),
                 p50_ms=schedule.percentile(lat, 50.0) * 1e3, p95_ms=p95_ms,
                 p99_ms=schedule.percentile(lat, 99.0) * 1e3,
                 engine_requests=stats["requests"], engine_batches=stats["batches"],
                 mode_histogram=stats["mode_histogram"])
    checks, numbers = check(cfg, traffic, cell.limits, keep, obs[answered],
                            res["answers"][answered], missing=len(due) - int(answered.sum()))
    reading = None
    if trace:
        reading = Reading(trace=tr.load_and_remove(log_dir), counters=dict(stats),
                          work=harness.work_module(cfg), config=cfg, traffic=traffic,
                          peaks=harness.device_kind_peaks(devices[0].device_kind),
                          measured={},
                          chips=len(devices))
    return Outcome(metrics=dict(setup_s=setup_s, serve_p95_ms=p95_ms),
                   attempted=len(due), failed=len(due) - int(answered.sum()), checks=checks,
                   memory_peak_bytes=mem, reading=reading, notes=notes, numbers=numbers)


def quantized(traffic: dict) -> bool:
    return traffic["phase"] == "quantized"


def reference_actions(cfg: dict, traffic: dict, keep: dict, obs: np.ndarray,
                      precision: str = "highest"):
    """The reference actor, frozen in the traffic's phase, on every row."""
    import jax
    import jax.numpy as jnp

    from bench.reference import ddpg as ref

    net = ref.Net(tuple(cfg["actor_activations"]), quantized(traffic), cfg["qat_bits"],
                  precision)
    f = jax.jit(lambda a, x, r: jnp.clip(ref.mlp(a, x, net, r), -1.0, 1.0))
    out = [np.asarray(f(keep["actor"], jnp.asarray(obs[i:i + 65536]), keep["ranges"]))
           for i in range(0, len(obs), 65536)]
    return np.concatenate(out) if out else np.zeros((0, cfg["act_dim"]), np.float32)


def compare(want: np.ndarray, got: np.ndarray) -> dict:
    gap = np.abs(want.astype(np.float64) - got.astype(np.float64))
    return dict(max_gap=float(gap.max()) if gap.size else 0.0,
                mean_gap=float(gap.mean()) if gap.size else 0.0)


def check(cfg, traffic, limits, keep, obs, answers, missing: int) -> tuple[list, dict]:
    nums = compare(reference_actions(cfg, traffic, keep, obs), answers)
    nums["missing"] = float(missing)
    harness.say(f"bench: check numbers {nums}")
    return [(k, nums[k], limits[k]) for k in limits], nums


def readings(cell, seed: int, variant: str, n: int = 65536) -> dict:
    """The numbers of the reference put in the engine's place, for
    `bench/control.py`: `variant` is the control's precision."""
    import jax

    cfg, traffic = cell.config, cell.traffic
    start = make_start(cfg, n)(jax.random.key(seed))
    keep = dict(actor=jax.device_get(start["actor"]), ranges=jax.device_get(start["ranges"]))
    obs = np.asarray(start["obs"], np.float32)
    return compare(reference_actions(cfg, traffic, keep, obs),
                   reference_actions(cfg, traffic, keep, obs, variant))
