"""serve/policy — parity pins and engine behavior.

The acceptance contract: for batch sizes 1/7/128/512, engine output must
equal the reference (`ddpg.act`, or for the `layer` mode the per-layer
kernel chain under the live QAT context) under every dispatch mode, with
QAT frozen and off; and the adaptive dispatcher must pick different modes for batch 1
vs batch 512 under the default cost model.
"""
import dataclasses
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.qat import QATContext, freeze_quant
from repro.kernels.fxp_matmul.ops import fxp_dense
from repro.launch.mesh import make_serve_mesh
from repro.rl import ddpg
from repro.rl.envs.locomotion import make
from repro.serve.policy import BatcherConfig, CostModel, MicroBatcher, \
    PolicyEngine
from repro.serve.policy.dispatch import DEFAULT_COSTS, MODES, flops_per_item

BATCHES = [1, 7, 128, 512]
REF_BACKEND = {"fused": "pallas", "jnp": "jnp"}
ACTOR_DIMS = [17, 400, 300, 6]  # halfcheetah actor

_STATES: dict = {}
_ENGINES: dict = {}


def _state(regime: str):
    """DDPG states per QAT regime: frozen-quantized / monitor-phase /
    QAT-off (module-cached — init is the expensive part)."""
    if regime not in _STATES:
        env = make("halfcheetah")
        cfg = {"frozen": ddpg.DDPGConfig(qat_delay=0),
               "monitor": ddpg.DDPGConfig(qat_delay=10 ** 9),
               "off": ddpg.DDPGConfig(qat_enabled=False)}[regime]
        _STATES[regime] = (ddpg.init(jax.random.key(0), env.spec, cfg), cfg)
    return _STATES[regime]


def _engine(regime: str, mode: str) -> PolicyEngine:
    key = (regime, mode)
    if key not in _ENGINES:
        state, _ = _state(regime)
        _ENGINES[key] = PolicyEngine.from_ddpg(state, force_mode=mode)
    return _ENGINES[key]


def _reference_act(state, obs, cfg, mode: str):
    """`ddpg.act` through the DDPG backend a serve mode mirrors; for
    "layer", the per-layer kernel chain under the live QAT context (each
    site through `QATContext.site`, the half-precision datapath in the
    quantized phase)."""
    if mode != "layer":
        return ddpg.act(state, obs, cfg=dataclasses.replace(
            cfg, backend=REF_BACKEND[mode]))
    ctx = QATContext(state.qat) if state.qat.config.enabled else None
    full = ctx is None or not bool(state.qat.quantized_phase)
    x = obs
    for i, act in enumerate(ddpg.ACTOR_ACTS):
        if ctx is not None:
            x = ctx.site(ddpg.ACTOR_SITES[i], x)
        layer = state.actor[f"l{i}"]
        x = fxp_dense(x, layer["w"], layer["b"], full_precision=full,
                      activation=act)
    return jnp.clip(x, -1.0, 1.0)


def _obs(batch: int):
    return np.asarray(
        jax.random.normal(jax.random.key(batch), (batch, 17))) * 2


# --------------------------------------------------------------------- #
# parity: engine ≡ reference ddpg.act
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("mode", list(MODES))
@pytest.mark.parametrize("batch", BATCHES)
@pytest.mark.parametrize("regime", ["frozen", "off"])
def test_engine_matches_reference_act(batch, mode, regime):
    state, cfg = _state(regime)
    obs = _obs(batch)
    got = _engine(regime, mode).run_batch(obs)
    want = np.asarray(_reference_act(state, jnp.asarray(obs), cfg, mode))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6,
                               err_msg=f"{mode}/{regime}/b{batch}")


@pytest.mark.parametrize("mode", list(MODES))
def test_engine_matches_reference_act_monitor_phase(mode):
    """Frozen snapshot taken pre-delay serves the full-precision datapath."""
    state, cfg = _state("monitor")
    obs = _obs(7)
    got = _engine("monitor", mode).run_batch(obs)
    want = np.asarray(_reference_act(state, jnp.asarray(obs), cfg, mode))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_oversized_batch_is_chunked():
    state, cfg = _state("off")
    eng = PolicyEngine.from_ddpg(state, force_mode="jnp",
                                 batcher=BatcherConfig(buckets=(1, 8, 32)))
    obs = _obs(81)  # 32 + 32 + 17
    got = eng.run_batch(obs)
    want = np.asarray(ddpg.act(state, jnp.asarray(obs), cfg=cfg))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert eng.stats()["batches"] == 3


def test_mesh_sharded_batch_parity():
    """Batch-axis scale-out through launch/mesh keeps outputs identical
    (1-device degenerate mesh on CPU; same code path as a pod)."""
    state, cfg = _state("frozen")
    eng = PolicyEngine.from_ddpg(state, mesh=make_serve_mesh())
    obs = _obs(128)
    want = np.asarray(ddpg.act(
        state, jnp.asarray(obs),
        cfg=dataclasses.replace(cfg, backend="pallas")))
    np.testing.assert_allclose(eng.run_batch(obs), want, rtol=1e-5,
                               atol=1e-6)


# --------------------------------------------------------------------- #
# frozen-QAT serving: no live QAT state on the serve path
# --------------------------------------------------------------------- #

def test_freeze_quant_none_when_disabled():
    state, _ = _state("off")
    assert freeze_quant(state.qat, ddpg.ACTOR_SITES) is None


def test_frozen_matches_context_site_params():
    state, _ = _state("frozen")
    from repro.core.qat import QATContext
    frozen = ddpg.freeze_actor_quant(state)
    deltas, zs = QATContext(state.qat).site_quant_params(ddpg.ACTOR_SITES)
    np.testing.assert_allclose(np.asarray(frozen.deltas), np.asarray(deltas))
    np.testing.assert_allclose(np.asarray(frozen.zs), np.asarray(zs))
    assert frozen.quantized is True  # delay=0 -> quantized phase, static


def test_serve_path_is_stateless():
    """Repeated engine calls are bit-identical (no range evolution), and
    the engine holds no QATState at all — frozen-QAT by construction."""
    eng = _engine("frozen", "fused")
    obs = _obs(7)
    first = eng.run_batch(obs)
    for _ in range(3):
        np.testing.assert_array_equal(eng.run_batch(obs), first)
    from repro.core.qat import QATState
    assert not any(isinstance(v, QATState) for v in vars(eng).values())


# --------------------------------------------------------------------- #
# adaptive dispatcher
# --------------------------------------------------------------------- #

def test_dispatcher_adapts_to_batch_size():
    """The acceptance pin: different dataflows for batch 1 vs batch 512
    (paper §V-B — intra-layer for one vector, intra-batch for a big
    batch)."""
    cm = CostModel.default()
    assert cm.choose(1, ACTOR_DIMS) == "layer"
    assert cm.choose(512, ACTOR_DIMS) == "fused"
    assert cm.choose(1, ACTOR_DIMS) != cm.choose(512, ACTOR_DIMS)


def test_cost_model_estimates_are_sane():
    cm = CostModel.default()
    for mode in MODES:
        # monotone in batch, positive, launch count from the kernel hints
        assert 0 < cm.estimate_us(mode, 1, ACTOR_DIMS) \
            < cm.estimate_us(mode, 512, ACTOR_DIMS)
    assert CostModel.launches("fused", ACTOR_DIMS) == 1
    assert CostModel.launches("layer", ACTOR_DIMS) == 3
    assert flops_per_item(ACTOR_DIMS) == 2 * (17 * 400 + 400 * 300 + 300 * 6)


def test_cost_model_calibrates_from_bench_json(tmp_path):
    bench = {"config": {"batch": 256, "net": ACTOR_DIMS},
             "actor_ips": {"jnp": 200_000.0, "pallas": 50_000.0,
                           "pallas_layer": 25_000.0}}
    path = tmp_path / "BENCH_fused_mlp.json"
    path.write_text(json.dumps(bench))
    cm = CostModel.from_bench(path)
    assert cm.source == str(path)
    # measured jnp is fastest at the bench batch -> it must win there
    assert cm.choose(256, ACTOR_DIMS) == "jnp"
    # missing or corrupt files fall back to defaults (dispatcher stays
    # total — a truncated bench write must never break serving)
    cm2 = CostModel.from_bench(tmp_path / "missing.json")
    assert cm2.costs == DEFAULT_COSTS
    bad = tmp_path / "truncated.json"
    bad.write_text('{"config": {"batch": 256}, "actor_ips": {"jnp": 1')
    cm3 = CostModel.from_bench(bad)
    assert cm3.costs == DEFAULT_COSTS and "default" in cm3.source
    bad.write_text(json.dumps({"actor_ips": {"jnp": "not-a-number"}}))
    assert CostModel.from_bench(bad).costs == DEFAULT_COSTS


def test_cost_model_two_point_fit_recovers_both_coefficients(tmp_path):
    """Two batch sizes separate slope from intercept: synthesize IPS from a
    known affine model and check from_bench recovers BOTH the per-launch
    overhead and the per-item rate (the single-point path could only refit
    the rate and kept default overheads)."""
    from repro.serve.policy.dispatch import cost_hint

    truth = {"pallas": (80.0, 0.002), "pallas_layer": (4.0, 0.006),
             "jnp": (30.0, 0.010)}
    mode_of = {"pallas": "fused", "pallas_layer": "layer", "jnp": "jnp"}
    by_batch = {}
    for backend, (per_launch, rate) in truth.items():
        hint = cost_hint(mode_of[backend], ACTOR_DIMS)
        by_batch[backend] = {}
        for b in (64, 512):
            t_us = (per_launch * hint["launches"]
                    + b * hint["flops_per_item"] / 1e3 * rate)
            by_batch[backend][str(b)] = b / (t_us * 1e-6)
    bench = {"config": {"batch": 512, "net": ACTOR_DIMS},
             "actor_ips": {k: v["512"] for k, v in by_batch.items()},
             "actor_ips_by_batch": by_batch}
    path = tmp_path / "BENCH_fused_mlp.json"
    path.write_text(json.dumps(bench))
    cm = CostModel.from_bench(path)
    for backend, (per_launch, rate) in truth.items():
        got = cm.costs[mode_of[backend]]
        np.testing.assert_allclose(got.per_launch_us, per_launch, rtol=1e-6,
                                   err_msg=f"{backend} overhead")
        np.testing.assert_allclose(got.us_per_kflop, rate, rtol=1e-6,
                                   err_msg=f"{backend} rate")


def test_cost_model_duplicate_batch_keys_stay_total(tmp_path):
    """Two JSON keys parsing to the same int batch ("64", " 64") must not
    divide by zero — the model stays total and falls back to the
    single-point path / defaults."""
    bench = {"config": {"batch": 64, "net": ACTOR_DIMS},
             "actor_ips": {"pallas": 50_000.0},
             "actor_ips_by_batch": {"pallas": {"64": 1000.0, " 64": 900.0}}}
    path = tmp_path / "bench.json"
    path.write_text(json.dumps(bench))
    cm = CostModel.from_bench(path)
    fused = cm.costs["fused"]
    assert fused.per_launch_us > 0 and fused.us_per_kflop > 0


def test_cost_model_two_point_without_single_point_entry(tmp_path):
    """actor_ips_by_batch alone (backend absent from actor_ips) must still
    drive the two-point fit."""
    bench = {"config": {"batch": 512, "net": ACTOR_DIMS},
             "actor_ips": {},
             "actor_ips_by_batch": {"pallas": {"64": 60_000.0,
                                               "512": 90_000.0}}}
    path = tmp_path / "bench.json"
    path.write_text(json.dumps(bench))
    cm = CostModel.from_bench(path)
    assert cm.costs["fused"] != DEFAULT_COSTS["fused"]


def test_cost_model_malformed_backend_entry_keeps_other_fits(tmp_path):
    """A broken entry for one backend must not discard another backend's
    successful calibration (per-mode fallback, not file-level)."""
    bench = {"config": {"batch": 512, "net": ACTOR_DIMS},
             "actor_ips": {"jnp": "not-a-number"},
             "actor_ips_by_batch": {
                 "pallas": {"64": 60_000.0, "512": 90_000.0},
                 "jnp": {"b64": "junk"}}}
    path = tmp_path / "bench.json"
    path.write_text(json.dumps(bench))
    cm = CostModel.from_bench(path)
    assert cm.source == str(path)
    assert cm.costs["fused"] != DEFAULT_COSTS["fused"]   # pallas fit kept
    assert cm.costs["jnp"] == DEFAULT_COSTS["jnp"]       # jnp -> default


def test_cost_model_two_point_degenerate_falls_back(tmp_path):
    """A noise-degenerate pair (flat or inverted timings -> non-positive
    slope/intercept) must fall back to the single-point recalibration, not
    produce negative costs."""
    b1, b2 = 64, 512
    # identical per-batch latency => slope 0 after converting IPS->time
    ips1, ips2 = b1 / 100e-6, b2 / 100e-6
    bench = {"config": {"batch": b2, "net": ACTOR_DIMS},
             "actor_ips": {"pallas": ips2},
             "actor_ips_by_batch": {"pallas": {str(b1): ips1,
                                               str(b2): ips2}}}
    path = tmp_path / "bench.json"
    path.write_text(json.dumps(bench))
    cm = CostModel.from_bench(path)
    fused = cm.costs["fused"]
    assert fused.per_launch_us > 0 and fused.us_per_kflop > 0
    # single-point fallback keeps the default launch overhead
    assert fused.per_launch_us == DEFAULT_COSTS["fused"].per_launch_us


# --------------------------------------------------------------------- #
# micro-batcher
# --------------------------------------------------------------------- #

def test_bucket_rounding():
    bc = BatcherConfig(buckets=(1, 8, 32, 128, 512))
    assert [bc.bucket_for(n) for n in (1, 2, 8, 9, 128, 512)] == \
        [1, 8, 8, 32, 128, 512]
    with pytest.raises(ValueError):
        bc.bucket_for(513)
    assert BatcherConfig(buckets=[1, 8, 32]).buckets == (1, 8, 32)  # list ok
    with pytest.raises(ValueError):
        BatcherConfig(buckets=(8, 1))
    # duplicates pass a plain sorted() check but would compile a redundant
    # executable per (bucket, mode) — rejected
    with pytest.raises(ValueError, match="strictly increasing"):
        BatcherConfig(buckets=(8, 8, 32))
    with pytest.raises(ValueError, match="strictly increasing"):
        BatcherConfig(buckets=(0, 8))


def test_close_rejects_submits_but_keeps_queue_for_draining():
    mb = MicroBatcher(BatcherConfig(buckets=(1, 64), max_wait_ms=10_000.0))
    futs = [mb.submit(np.zeros(3)) for _ in range(5)]
    mb.close()
    with pytest.raises(RuntimeError):   # no request may enter a dying queue
        mb.submit(np.zeros(3))
    assert len(mb) == 5                 # queued work survives for the loop
    reqs = mb.drain()
    assert len(reqs) == 5 and len(mb) == 0
    for r in reqs:
        r.future.set_exception(RuntimeError("stopped"))
    for f in futs:
        with pytest.raises(RuntimeError):
            f.result(timeout=1.0)
    mb.reopen()
    assert mb.submit(np.zeros(3)) is not None


def test_submit_requires_running_engine():
    """No dangling futures: submit on a never-started or stopped engine
    fails loudly instead of queueing work nothing will drain."""
    state, _ = _state("off")
    eng = PolicyEngine.from_ddpg(state, force_mode="jnp")
    with pytest.raises(RuntimeError, match="not serving"):
        eng.submit(np.zeros(17))
    eng.start()
    eng.submit(np.zeros(17)).result(timeout=60.0)
    eng.stop()
    with pytest.raises(RuntimeError, match="not serving"):
        eng.submit(np.zeros(17))


def test_force_mode_must_be_enabled():
    state, _ = _state("off")
    with pytest.raises(ValueError, match="force_mode"):
        PolicyEngine.from_ddpg(state, modes=("fused", "jnp"),
                               force_mode="layer")


def test_full_batch_flushes_immediately():
    mb = MicroBatcher(BatcherConfig(buckets=(1, 4), max_wait_ms=10_000.0))
    for i in range(5):
        mb.submit(np.full(3, i))
    batch = mb.next_batch(timeout=0.5)
    assert [int(r.obs[0]) for r in batch] == [0, 1, 2, 3]  # FIFO, capped
    assert len(mb) == 1


def test_max_wait_flushes_partial_batch():
    mb = MicroBatcher(BatcherConfig(buckets=(1, 64), max_wait_ms=20.0))
    mb.submit(np.zeros(3))
    batch = mb.next_batch(timeout=5.0)  # returns at the ~20ms deadline
    assert len(batch) == 1
    assert mb.next_batch(timeout=0.01) == []  # empty queue -> timeout


# --------------------------------------------------------------------- #
# threaded request lifecycle
# --------------------------------------------------------------------- #

def test_threaded_serving_parity_and_stats():
    state, cfg = _state("frozen")
    eng = PolicyEngine.from_ddpg(
        state, batcher=BatcherConfig(buckets=(1, 8, 32), max_wait_ms=5.0))
    eng.warmup(buckets=(8, 32), modes=("layer",))
    obs = _obs(16)
    want = np.asarray(_reference_act(state, jnp.asarray(obs), cfg, "layer"))
    eng.start()
    try:
        futs = {}

        def client(lo, hi):
            for i in range(lo, hi):
                futs[i] = eng.submit(obs[i])

        threads = [threading.Thread(target=client, args=(k * 4, k * 4 + 4))
                   for k in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i, fut in futs.items():
            np.testing.assert_allclose(fut.result(timeout=60.0), want[i],
                                       rtol=1e-5, atol=1e-6)
    finally:
        eng.stop()
    stats = eng.stats()
    assert stats["requests"] == 16
    assert stats["p50_ms"] is not None and stats["p99_ms"] >= stats["p50_ms"]
    assert 0 < stats["batch_occupancy"] <= 1.0
    assert sum(stats["mode_histogram"]["act"].values()) == stats["batches"]
    assert stats["ips_device"] > 0
