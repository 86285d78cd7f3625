"""train/learner — parity pins and engine behavior.

The acceptance contract: learner-engine results bit-match direct
`ddpg.update` per training backend and bucket size, and the engine trains
through exactly the one backend its `DDPGConfig` names — no cost model, no
dispatch audit.
"""
import dataclasses
import threading

import jax
import numpy as np
import pytest

from repro.rl import ddpg
from repro.rl.envs.locomotion import make
from repro.serve.policy import BatcherConfig
from repro.train.learner import LearnerEngine, UpdateBatcher

BUCKETS = (8, 16, 32)

_STATE = {}


def _state():
    if not _STATE:
        env = make("halfcheetah")
        cfg = ddpg.DDPGConfig(qat_delay=0)
        _STATE["v"] = (ddpg.init(jax.random.key(0), env.spec, cfg), cfg)
    return _STATE["v"]


def _batch(n, key=0):
    k = jax.random.key(key)
    return {
        "obs": np.asarray(jax.random.normal(k, (n, 17))),
        "action": np.asarray(jax.random.uniform(k, (n, 6),
                                                minval=-1, maxval=1)),
        "reward": np.asarray(jax.random.normal(k, (n,))),
        "next_obs": np.asarray(jax.random.normal(jax.random.fold_in(k, 1),
                                                 (n, 17))),
        "done": np.zeros((n,), bool),
    }


def _assert_trees_equal(got, want, msg=""):
    for la, lb in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb),
                                      err_msg=msg)


# --------------------------------------------------------------------- #
# parity: streamed update ≡ direct ddpg.update (the acceptance pin)
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("backend", list(ddpg.BACKENDS))
@pytest.mark.parametrize("bucket", list(BUCKETS))
def test_streamed_update_bitmatches_direct(backend, bucket):
    """A bucket-sized request streams through the SAME jitted executable a
    direct call uses — params, targets, and metrics are bit-identical.
    Covers 3 bucket sizes through each of the three training backends."""
    state, cfg = _state()
    bcfg = dataclasses.replace(cfg, backend=backend)
    eng = LearnerEngine.from_ddpg(state, bcfg,
                                  batcher=BatcherConfig(buckets=BUCKETS))
    batch = _batch(bucket, key=bucket)
    got_metrics = eng.run_update(batch)
    want, want_metrics = jax.jit(
        lambda s, b: ddpg.update(s, b, bcfg))(state, batch)
    _assert_trees_equal(
        (eng.state.actor, eng.state.critic, eng.state.actor_target),
        (want.actor, want.critic, want.actor_target),
        msg=f"{backend}/b{bucket}")
    for k, v in want_metrics.items():
        assert got_metrics[k] == float(v), f"{backend}/b{bucket}/{k}"
    assert got_metrics["mode"] == backend
    assert int(eng.state.step) == int(state.step) + 1


@pytest.mark.parametrize("backend", ["pallas_fused_step", "pallas", "jnp"])
def test_from_ddpg_trains_through_the_configured_backend(backend):
    """`from_ddpg` builds one update, for `cfg.backend`: the engine has
    that one mode at every bucket, reports no cost model and no dispatch
    audit, and its mode histogram names only that backend."""
    state, cfg = _state()
    eng = LearnerEngine.from_ddpg(state, dataclasses.replace(cfg, backend=backend),
                                  batcher=BatcherConfig(buckets=BUCKETS))
    assert eng.modes == (backend,)
    assert [eng.choose_mode(b) for b in BUCKETS] == [backend] * len(BUCKETS)
    assert eng.run_update(_batch(5, key=5))["mode"] == backend
    st = eng.stats()
    assert "cost_model" not in st and "dispatch_audit" not in st
    assert st["mode_histogram"] == {"train": {backend: 1}}


def test_padded_update_bitmatches_direct_masked_call():
    """A short request pads to the bucket with a zero-weight mask; the
    result bit-matches a direct ddpg.update on the identically padded
    batch, and numerically matches the unpadded direct update (pad rows
    carry zero loss weight)."""
    state, cfg = _state()
    eng = LearnerEngine.from_ddpg(state, cfg,
                                  batcher=BatcherConfig(buckets=BUCKETS))
    batch = _batch(5, key=3)
    eng.run_update(batch)
    padded = {k: np.concatenate([v, np.zeros((3,) + v.shape[1:], v.dtype)])
              for k, v in batch.items()}
    padded["mask"] = np.asarray([1.0] * 5 + [0.0] * 3, np.float32)
    want, _ = jax.jit(lambda s, b: ddpg.update(s, b, cfg))(state, padded)
    _assert_trees_equal((eng.state.actor, eng.state.critic),
                        (want.actor, want.critic))
    # padded ≡ unpadded up to reduction order (same math, fewer rows)
    direct, _ = jax.jit(lambda s, b: ddpg.update(s, b, cfg))(state, batch)
    for l in ("l0", "l1", "l2"):
        np.testing.assert_allclose(
            np.asarray(eng.state.actor[l]["w"]),
            np.asarray(direct.actor[l]["w"]), rtol=2e-5, atol=1e-7)


def test_oversized_request_chunks_sequentially():
    """A whole-trajectory chunk larger than the top bucket splits into
    top-bucket updates applied in order — same final state as manually
    feeding the chunks."""
    state, cfg = _state()
    eng = LearnerEngine.from_ddpg(state, cfg,
                                  batcher=BatcherConfig(buckets=BUCKETS))
    traj = _batch(70, key=7)
    metrics = eng.run_update(traj)
    assert metrics["chunks"] == 3  # 32 + 32 + 6
    assert int(eng.state.step) == int(state.step) + 3
    upd = jax.jit(lambda s, b: ddpg.update(s, b, cfg))
    want = state
    for lo in (0, 32, 64):
        n = min(70 - lo, 32)
        part = {k: v[lo:lo + n] for k, v in traj.items()}
        bucket = eng.batcher_config.bucket_for(n)
        want, _ = upd(want, eng._pad(part, n, bucket))
    _assert_trees_equal(eng.state.actor, want.actor)


def test_update_mask_all_ones_matches_no_mask():
    """ddpg.update's weighted-loss contract degenerates exactly: an
    all-ones mask reproduces the unmasked update bit for bit would be
    reduction-order dependent, so pin allclose at f32 resolution."""
    state, cfg = _state()
    batch = _batch(16, key=11)
    plain, pm = ddpg.update(state, batch, cfg)
    masked, mm = ddpg.update(state,
                             dict(batch, mask=np.ones(16, np.float32)), cfg)
    for l in ("l0", "l1", "l2"):
        np.testing.assert_allclose(np.asarray(masked.critic[l]["w"]),
                                   np.asarray(plain.critic[l]["w"]),
                                   rtol=1e-6, atol=1e-8)
    np.testing.assert_allclose(float(mm["critic_loss"]),
                               float(pm["critic_loss"]), rtol=1e-6)


# --------------------------------------------------------------------- #
# batching / engine lifecycle
# --------------------------------------------------------------------- #

def test_update_batcher_coalesces_by_rows():
    ub = UpdateBatcher(BatcherConfig(buckets=BUCKETS, max_wait_ms=10_000.0))
    for i in range(5):
        ub.submit(_batch(8, key=i))
    reqs = ub.next_batch(timeout=0.5)   # 32-row cap -> 4 x 8-row requests
    assert [r.rows for r in reqs] == [8, 8, 8, 8]
    assert len(ub) == 1
    with pytest.raises(ValueError, match="exceeds the top bucket"):
        ub.submit(_batch(33))
    with pytest.raises(ValueError, match="missing"):
        UpdateBatcher(BatcherConfig(buckets=BUCKETS),
                      required_keys=("obs", "action", "reward", "next_obs",
                                     "done")).submit({"obs": np.zeros((4, 17))})


def test_threaded_streaming_applies_all_requests_sequentially():
    state, cfg = _state()
    eng = LearnerEngine.from_ddpg(
        state, cfg,
        batcher=BatcherConfig(buckets=BUCKETS, max_wait_ms=5.0))
    eng.warmup(padded=True)
    eng.start()
    try:
        futs = []

        def producer(k):
            futs.append(eng.submit(_batch(8, key=k)))

        threads = [threading.Thread(target=producer, args=(k,))
                   for k in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        results = [f.result(timeout=120.0) for f in futs]
    finally:
        eng.stop()
    assert all("critic_loss" in r for r in results)
    st = eng.stats()
    assert st["requests"] == 6
    assert st["transitions"] == 48
    # coalescing means fewer updates than requests, all accounted
    assert st["updates"] == int(eng.state.step) - int(state.step)
    assert sum(st["mode_histogram"]["train"].values()) == st["updates"]
    assert st["p99_ms"] >= st["p50_ms"]
    assert 0 < st["batch_occupancy"] <= 1.0
    assert st["updates_per_s_device"] > 0 and st["train_ips_device"] > 0


def test_submit_requires_running_engine_and_splits_oversize():
    state, cfg = _state()
    eng = LearnerEngine.from_ddpg(state, cfg,
                                  batcher=BatcherConfig(buckets=BUCKETS))
    with pytest.raises(RuntimeError, match="not streaming"):
        eng.submit(_batch(8))
    eng.start()
    try:
        fut = eng.submit(_batch(70, key=2))   # 3 chunks, aggregate future
        res = fut.result(timeout=120.0)
        assert res["chunks"] == 3
        assert "critic_loss" in res
    finally:
        eng.stop()
    assert int(eng.state.step) == int(state.step) + 3
    with pytest.raises(RuntimeError, match="not streaming"):
        eng.submit(_batch(8))


def test_force_mode_and_pad_policy_validation():
    """The learner takes no mode to force or choose among: `force_mode=`
    and `modes=` are refused, as is an update family of more than one
    function; a bad pad policy is refused by name."""
    state, cfg = _state()
    with pytest.raises(TypeError, match="force_mode"):
        LearnerEngine.from_ddpg(state, cfg, force_mode="jnp")
    with pytest.raises(TypeError, match="modes"):
        LearnerEngine.from_ddpg(state, cfg, modes=("jnp",))
    with pytest.raises(ValueError, match="one update function"):
        LearnerEngine(state, {"jnp": None, "pallas": None})
    with pytest.raises(ValueError, match="pad_policy"):
        LearnerEngine.from_ddpg(state, cfg, pad_policy="truncate")
    eng = LearnerEngine.from_ddpg(state, cfg,
                                  batcher=BatcherConfig(buckets=BUCKETS),
                                  pad_policy="exact")
    with pytest.raises(ValueError, match="exact"):
        eng.run_update(_batch(5))
    eng.run_update(_batch(8))   # exact fit passes
    assert int(eng.state.step) == int(state.step) + 1


def test_warmup_compiles_without_advancing_state():
    state, cfg = _state()
    eng = LearnerEngine.from_ddpg(state, cfg,
                                  batcher=BatcherConfig(buckets=BUCKETS))
    n = eng.warmup(padded=True)
    assert n == len(BUCKETS) * 2  # exact + masked variant per bucket
    assert int(eng.state.step) == int(state.step)
    _assert_trees_equal(eng.state.actor, state.actor)


def test_generic_update_family_contract():
    """The engine drives any update_fn(state, batch) -> (state, metrics)
    family — the train/step LM adapter shape — with pad_policy='exact'.
    Chunking is key-agnostic (no DDPG 'obs' assumption), and warmup
    without a batch template fails loudly instead of feeding transition
    shapes to a non-DDPG family."""
    calls = []

    def update(state, batch):
        calls.append(batch["x"].shape[0])
        return state + batch["x"].sum(), {"loss": batch["x"].mean()}

    eng = LearnerEngine(np.float64(0.0), {"jnp": update},
                        batcher=BatcherConfig(buckets=(4, 8)),
                        pad_policy="exact")
    m = eng.run_update({"x": np.ones((8, 2))})
    assert m["loss"] == 1.0 and m["mode"] == "jnp"
    assert eng.state == 16.0
    assert calls == [8]
    # oversized generic request: chunks by the top bucket on its own keys
    m2 = eng.run_update({"x": np.full((16, 2), 2.0)})
    assert m2["chunks"] == 2 and m2["loss"] == 2.0
    assert calls == [8, 8, 8]
    assert eng.state == 16.0 + 64.0
    with pytest.raises(RuntimeError, match="warmup_template"):
        eng.warmup()
    # a template makes warmup family-aware
    eng.warmup_template = lambda rows: {"x": np.zeros((rows, 2))}
    assert eng.warmup(buckets=(4,)) == 1
    assert eng.state == 80.0   # zero batch: warmup adds nothing


def test_learner_update_fns_adapter_shape():
    """The LM train step adapts into the engine's update-family contract
    (single jnp mode; the engine's queue/metrics machinery is reusable)."""
    from repro.models.config import ModelConfig
    from repro.optim import adam
    from repro.train import step as train_step

    cfg = ModelConfig(name="tiny", family="dense", n_layers=1, d_model=8,
                      n_heads=2, n_kv_heads=2, d_ff=16, vocab_size=32)
    fns = train_step.learner_update_fns(cfg, adam.AdamConfig())
    assert set(fns) == {"jnp"} and callable(fns["jnp"])
