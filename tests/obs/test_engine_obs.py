"""Engine-level observability: stats()/snapshot() under concurrent load,
PR-5 stats key compatibility with obs disabled, engine trace JSONL, and
the engines' dispatch-audit + QAT-telemetry sections.
"""
import json
import threading

import jax
import numpy as np

from repro.obs import Observability, read_jsonl
from repro.rl import ddpg
from repro.rl.envs.base import EnvSpec
from repro.serve.policy import BatcherConfig, PolicyEngine
from repro.train.learner import LearnerEngine

SPEC = EnvSpec(name="obs-test", obs_dim=9, act_dim=3, episode_length=50)
_CACHE: dict = {}


def _state():
    if "state" not in _CACHE:
        cfg = ddpg.DDPGConfig(qat_delay=0)
        _CACHE["state"] = (ddpg.init(jax.random.key(0), SPEC, cfg), cfg)
    return _CACHE["state"]


def _batch(rng, rows):
    return {"obs": rng.standard_normal((rows, SPEC.obs_dim))
            .astype(np.float32),
            "action": rng.uniform(-1, 1, (rows, SPEC.act_dim))
            .astype(np.float32),
            "reward": rng.standard_normal((rows,)).astype(np.float32),
            "next_obs": rng.standard_normal((rows, SPEC.obs_dim))
            .astype(np.float32),
            "done": np.zeros((rows,), bool)}


# --------------------------------------------------------------------- #
# stats() key compatibility (the tier-1 overhead guard)
# --------------------------------------------------------------------- #

# the exact pre-obs (PR 5) stats surfaces: every key must survive the
# registry port with a compatible type — consumers (benches, harnesses)
# parse these blind
SERVE_KEYS_PRE_OBS = {
    "requests": int, "actions": int, "batches": int,
    "ips_device": (float, type(None)), "ips_wall": (float, type(None)),
    "p50_ms": (float, type(None)), "p99_ms": (float, type(None)),
    "batch_occupancy": (float, type(None)), "mode_histogram": dict,
    "cost_model": str,
}
# the learner trains through one backend: it has no cost model to report
LEARNER_KEYS_PRE_OBS = {
    "requests": int, "updates": int, "transitions": int,
    "updates_per_s_device": (float, type(None)),
    "updates_per_s_wall": (float, type(None)),
    "train_ips_device": (float, type(None)),
    "train_ips_wall": (float, type(None)),
    "p50_ms": (float, type(None)), "p99_ms": (float, type(None)),
    "batch_occupancy": (float, type(None)), "mode_histogram": dict,
}


def test_serve_stats_keys_compatible_with_obs_disabled():
    state, _ = _state()
    eng = PolicyEngine.from_ddpg(state, force_mode="jnp",
                                 batcher=BatcherConfig(buckets=(1, 8)))
    # default Observability: registry live, tracer the shared no-op
    assert eng.obs.tracer.enabled is False
    eng.run_batch(np.zeros((5, SPEC.obs_dim), np.float32))
    st = eng.stats()
    for key, types in SERVE_KEYS_PRE_OBS.items():
        assert key in st, f"stats() lost pre-obs key {key!r}"
        assert isinstance(st[key], types), \
            f"stats()[{key!r}] changed type: {type(st[key]).__name__}"
    # phase-keyed histogram counts every batch
    assert sum(st["mode_histogram"]["act"].values()) == st["batches"] == 1
    # no trace events were recorded anywhere on the disabled path
    assert eng.obs.tracer.events() == []
    json.dumps(st)


def test_learner_stats_keys_compatible_with_obs_disabled():
    state, cfg = _state()
    eng = LearnerEngine.from_ddpg(
        state, cfg,
        batcher=BatcherConfig(buckets=(4, 8)))
    assert eng.obs.tracer.enabled is False
    eng.run_update(_batch(np.random.default_rng(0), 4))
    st = eng.stats()
    for key, types in LEARNER_KEYS_PRE_OBS.items():
        assert key in st, f"stats() lost pre-obs key {key!r}"
        assert isinstance(st[key], types), \
            f"stats()[{key!r}] changed type: {type(st[key]).__name__}"
    assert sum(st["mode_histogram"]["train"].values()) == st["updates"] == 1
    assert eng.obs.tracer.events() == []
    json.dumps(st)


# --------------------------------------------------------------------- #
# concurrency: hammer stats()/snapshot() during threaded submits
# --------------------------------------------------------------------- #

def test_serve_stats_hammered_during_threaded_submits():
    state, _ = _state()
    obsb = Observability()
    eng = PolicyEngine.from_ddpg(
        state, force_mode="jnp",
        batcher=BatcherConfig(buckets=(1, 4, 16), max_wait_ms=0.5),
        obs=obsb)
    eng.warmup(buckets=(1, 4, 16))
    eng.reset_stats()
    n_clients, per_client = 4, 12
    stop = threading.Event()
    errors = []

    def reader():
        try:
            while not stop.is_set():
                st = eng.stats()
                assert st["requests"] >= 0
                assert st["actions"] >= st["batches"] >= 0
                hist = st["mode_histogram"]
                if hist:
                    # the calls counter incs BEFORE the per-mode counter,
                    # so a batches value read AFTER summing the histogram
                    # is an upper bound however the reads interleave (the
                    # st["batches"] captured above may predate mode incs)
                    assert sum(hist["act"].values()) <= \
                        eng.stats()["batches"]
                snap = obsb.registry.snapshot()
                json.dumps(snap)
                json.dumps(st)
        except Exception as err:  # noqa: BLE001 — surface in main thread
            errors.append(err)

    def client(k):
        rng = np.random.default_rng(k)
        futs = [eng.submit(rng.standard_normal(SPEC.obs_dim)
                           .astype(np.float32))
                for _ in range(per_client)]
        for f in futs:
            f.result(timeout=60.0)

    eng.start()
    readers = [threading.Thread(target=reader) for _ in range(2)]
    clients = [threading.Thread(target=client, args=(k,))
               for k in range(n_clients)]
    for t in readers + clients:
        t.start()
    for t in clients:
        t.join()
    eng.stop()
    stop.set()
    for t in readers:
        t.join()
    assert not errors, errors
    st = eng.stats()
    assert st["requests"] == n_clients * per_client
    assert sum(st["mode_histogram"]["act"].values()) == st["batches"]
    assert st["dispatch_audit"]["batches"] == st["batches"]
    assert st["p50_ms"] is not None and st["p99_ms"] >= st["p50_ms"]


def test_learner_stats_hammered_during_threaded_submits():
    state, cfg = _state()
    obsb = Observability()
    eng = LearnerEngine.from_ddpg(
        state, cfg,
        batcher=BatcherConfig(buckets=(4, 8, 16), max_wait_ms=0.5),
        obs=obsb)
    eng.warmup(padded=True)
    eng.load_state(state)
    eng.reset_stats()
    stop = threading.Event()
    errors = []

    def reader():
        try:
            while not stop.is_set():
                st = eng.stats()
                assert st["transitions"] >= 0
                json.dumps(st)
        except Exception as err:  # noqa: BLE001
            errors.append(err)

    def producer(k):
        rng = np.random.default_rng(k)
        futs = [eng.submit(_batch(rng, int(rng.integers(2, 8))))
                for _ in range(4)]
        for f in futs:
            f.result(timeout=120.0)

    eng.start()
    readers = [threading.Thread(target=reader) for _ in range(2)]
    producers = [threading.Thread(target=producer, args=(k,))
                 for k in range(3)]
    for t in readers + producers:
        t.start()
    for t in producers:
        t.join()
    eng.stop()
    stop.set()
    for t in readers:
        t.join()
    assert not errors, errors
    st = eng.stats()
    assert st["requests"] == 12
    assert sum(st["mode_histogram"]["train"].values()) == st["updates"] > 0


# --------------------------------------------------------------------- #
# engine traces: lifecycle spans land in well-formed JSONL
# --------------------------------------------------------------------- #

def test_serve_trace_lifecycle_jsonl(tmp_path):
    state, _ = _state()
    obsb = Observability.tracing()
    eng = PolicyEngine.from_ddpg(
        state, force_mode="jnp",
        batcher=BatcherConfig(buckets=(1, 4, 16), max_wait_ms=0.5),
        obs=obsb)
    eng.warmup(buckets=(1, 4, 16))
    eng.start()
    futs = [eng.submit(np.zeros(SPEC.obs_dim, np.float32))
            for _ in range(10)]
    for f in futs:
        f.result(timeout=60.0)
    eng.stop()
    path = tmp_path / "trace_serve.jsonl"
    obsb.tracer.write(path)
    evs = read_jsonl(path)
    names = {e["name"] for e in evs}
    assert {"serve.coalesce", "serve.dispatch", "serve.launch",
            "serve.block_until_ready", "serve.reply",
            "serve.request"} <= names
    # well-formed: complete events only, closed by construction, sorted
    assert all(e["ph"] in ("X", "i") for e in evs)
    assert all(e.get("dur", 0) >= 0 for e in evs)
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts)
    # one request-lifetime span per resolved request
    reqs = [e for e in evs if e["name"] == "serve.request"]
    assert len(reqs) == 10
    # dispatch spans carry the decision args
    disp = next(e for e in evs if e["name"] == "serve.dispatch")
    assert disp["args"]["mode"] == "jnp" and "bucket" in disp["args"]


def test_learner_trace_and_qat_sections(tmp_path):
    state, cfg = _state()
    obsb = Observability.tracing(qat_probe_every=1)
    eng = LearnerEngine.from_ddpg(
        state, cfg,
        batcher=BatcherConfig(buckets=(4, 8)), obs=obsb)
    rng = np.random.default_rng(0)
    for _ in range(2):
        eng.run_update(_batch(rng, 4))
    st = eng.stats()
    # QAT telemetry live: per-site ranges (live QATState) + probe results
    sites = st["qat_telemetry"]
    assert sites, "expected per-site QAT telemetry"
    probed = [s for s in sites.values() if s.get("probes")]
    assert probed, "qat_probe_every=1 must have produced probes"
    for entry in probed:
        assert 0.0 <= entry["saturation"] <= 1.0
        assert entry["act_min"] <= entry["act_max"]
    assert st["mode_histogram"] == {"train": {"jnp": 2}}
    path = tmp_path / "trace_learner.jsonl"
    obsb.tracer.write(path)
    evs = read_jsonl(path)
    names = {e["name"] for e in evs}
    assert {"learner.dispatch", "learner.launch",
            "learner.block_until_ready"} <= names


def test_shared_registry_across_engines_and_reset():
    """One registry can back both engines; prefixes keep them apart and
    reset_stats() on one engine leaves the other untouched."""
    state, cfg = _state()
    obsb = Observability()
    serve = PolicyEngine.from_ddpg(state, force_mode="jnp",
                                   batcher=BatcherConfig(buckets=(1, 8)),
                                   obs=obsb)
    learner = LearnerEngine.from_ddpg(state, cfg,
                                      batcher=BatcherConfig(buckets=(4, 8)),
                                      obs=obsb)
    serve.run_batch(np.zeros((3, SPEC.obs_dim), np.float32))
    learner.run_update(_batch(np.random.default_rng(0), 4))
    names = obsb.registry.names()
    assert any(n.startswith("serve.") for n in names)
    assert any(n.startswith("learner.") for n in names)
    serve.reset_stats()
    assert serve.stats()["batches"] == 0
    assert learner.stats()["updates"] == 1


# --------------------------------------------------------------------- #
# engine shutdown: close() flushes traces, context managers serve
# --------------------------------------------------------------------- #

def test_serve_engine_close_flushes_trace_and_is_reusable(tmp_path):
    state, _ = _state()
    path = tmp_path / "serve.jsonl"
    obsb = Observability.tracing(trace_path=str(path))
    eng = PolicyEngine.from_ddpg(
        state, force_mode="jnp",
        batcher=BatcherConfig(buckets=(1, 4), max_wait_ms=0.5), obs=obsb)
    with eng:                               # __enter__ starts serving
        eng.submit(np.zeros(SPEC.obs_dim, np.float32)).result(timeout=60.0)
    # __exit__ closed: loop stopped, trace flushed to the bundle's path
    evs = read_jsonl(path)
    assert any(e["name"] == "serve.request" for e in evs)
    assert all(e["ph"] in ("X", "i") for e in evs)
    eng.close()                             # idempotent
    with eng:                               # restartable after close
        eng.submit(np.zeros(SPEC.obs_dim, np.float32)).result(timeout=60.0)
    assert len(read_jsonl(path)) > len(evs)


def test_learner_engine_close_flushes_trace(tmp_path):
    state, cfg = _state()
    path = tmp_path / "learner.jsonl"
    obsb = Observability.tracing(trace_path=str(path))
    eng = LearnerEngine.from_ddpg(
        state, cfg,
        batcher=BatcherConfig(buckets=(4, 8), max_wait_ms=0.5), obs=obsb)
    rng = np.random.default_rng(0)
    with eng:
        eng.submit(_batch(rng, 4)).result(timeout=120.0)
    names = {e["name"] for e in read_jsonl(path)}
    assert "learner.launch" in names


def test_engine_health_reflects_audit_staleness():
    state, _ = _state()
    # threshold below 1.0 means any drift at all reads as stale
    obsb = Observability(audit_threshold=1e-6)
    eng = PolicyEngine.from_ddpg(
        state, force_mode="jnp", batcher=BatcherConfig(buckets=(1, 4)),
        obs=obsb)
    assert eng.health()["ok"]               # no batches yet: healthy
    eng.run_batch(np.zeros((2, SPEC.obs_dim), np.float32))
    h = eng.health()
    assert not h["ok"] and h["drift_factor"] > 1e-6
    # the registry mirror the fleet/SLO layers read
    assert obsb.registry.gauge("serve.dispatch_audit.stale").value == 1.0
    eng.reset_stats()
    assert eng.health()["ok"]
    assert obsb.registry.gauge("serve.dispatch_audit.stale").value == 0.0
