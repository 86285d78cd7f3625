"""rl/loop — retrace regression, single-launch windows, learner streaming.

`evaluate` must not re-trace its episode scan on every call (the jit is
hoisted to module level with env/dcfg as static keys), every window of
`train_device` reuses one trace, and `train_host` optionally streams its
updates through a `train/learner.LearnerEngine`.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.rl import ddpg, loop
from repro.rl.envs.locomotion import make
from repro.serve.policy import BatcherConfig
from repro.train.learner import LearnerEngine


# --------------------------------------------------------------------- #
# evaluate: hoisted jit, no per-call retrace
# --------------------------------------------------------------------- #

def test_evaluate_does_not_retrace_across_calls():
    """The bug: a closure-defined `@jax.jit one_episode` is a fresh
    function object — and a fresh full-episode trace/compile — on every
    eval call.  Hoisted, repeat calls must hit the jit cache."""
    if not hasattr(loop._eval_episodes, "_cache_size"):
        pytest.skip("jit cache introspection unavailable")
    env = make("pendulum")
    dcfg = ddpg.DDPGConfig(qat_enabled=False)
    agent = ddpg.init(jax.random.key(0), env.spec, dcfg)
    before = loop._eval_episodes._cache_size()
    r1 = loop.evaluate(env, agent, dcfg, jax.random.key(1), n_episodes=2)
    after_first = loop._eval_episodes._cache_size()
    assert after_first == before + 1
    # different key, different agent VALUES (same shapes): cache hit
    agent2 = dataclasses.replace(
        agent, step=agent.step + 1,
        actor=jax.tree.map(lambda x: x + 0.01, agent.actor))
    r2 = loop.evaluate(env, agent2, dcfg, jax.random.key(2), n_episodes=2)
    r3 = loop.evaluate(env, agent, dcfg, jax.random.key(3), n_episodes=2)
    assert loop._eval_episodes._cache_size() == after_first
    assert np.isfinite(float(r1) + float(r2) + float(r3))


def test_evaluate_matches_paper_protocol_shape():
    env = make("pendulum")
    dcfg = ddpg.DDPGConfig(qat_enabled=False)
    agent = ddpg.init(jax.random.key(0), env.spec, dcfg)
    r = loop.evaluate(env, agent, dcfg, jax.random.key(1), n_episodes=3)
    assert r.shape == () and np.isfinite(float(r))


# --------------------------------------------------------------------- #
# train_device: single-launch windows + host/device parity
# --------------------------------------------------------------------- #

_SMALL = dict(total_steps=24, warmup_steps=8, replay_capacity=64,
              eval_every=12, eval_episodes=2, seed=3)


def test_train_window_traces_once_across_windows_and_drivers():
    """The tentpole claim, pinned: an entire eval window is ONE jitted
    launch, and every window — across `train_device` calls at the same
    shapes, with or without an evaluation function — reuses the single
    trace."""
    if not hasattr(loop._train_window, "_cache_size"):
        pytest.skip("jit cache introspection unavailable")
    env = make("pendulum")
    dcfg = ddpg.DDPGConfig(qat_enabled=False, batch_size=8)
    cfg = loop.TrainConfig(n_envs=2, **_SMALL)
    before = loop._train_window._cache_size()
    _, hist = loop.train_device(env, cfg, dcfg,
                                eval_fn=lambda *a: jnp.float32(0.0))
    assert len(hist["step"]) == 2                 # two windows ran...
    after = loop._train_window._cache_size()
    assert after == before + 1                    # ...through one trace
    loop.train_device(env, cfg, dcfg, eval_fn=lambda *a: jnp.float32(0.0))
    loop.train_device(env, cfg, dcfg)
    assert loop._train_window._cache_size() == after


def test_train_device_matches_train_host_jnp():
    """Host loop (eager env boundary) vs device loop (scanned window) run
    the same act→explore→step→store→update program from the same seed.
    The env steps eagerly on the host and inside the scanned launch on the
    device, so XLA op fusion makes trajectories differ by ~1ulp; through
    the Q15.16 weight projection that occasionally moves a parameter a few
    lattice quanta (2^-16 ≈ 1.5e-5).  Anything beyond a handful of quanta
    means the two drivers ran different programs."""
    env = make("pendulum")
    dcfg = ddpg.DDPGConfig(qat_enabled=False, batch_size=8)
    cfg = loop.TrainConfig(n_envs=1, **_SMALL)
    ts_h, _ = loop.train_host(env, cfg, dcfg)
    ts_d, _ = loop.train_device(env, cfg, dcfg,
                                eval_fn=lambda *a: jnp.float32(0.0))
    assert int(ts_h.agent.step) == int(ts_d.agent.step) > 0
    for name in ("actor", "critic", "actor_target", "critic_target"):
        h, d = getattr(ts_h.agent, name), getattr(ts_d.agent, name)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=0, atol=8 * 2.0 ** -16), h, d)
    np.testing.assert_allclose(np.asarray(ts_h.obs), np.asarray(ts_d.obs),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ts_h.buf.reward),
                               np.asarray(ts_d.buf.reward),
                               rtol=1e-4, atol=1e-5)
    assert int(ts_h.buf.size) == int(ts_d.buf.size)


def test_train_device_fleet_runs_and_reports():
    """n_envs > 1: every step stores a whole fleet row-batch and performs
    at most one update; history reports env-step and update throughput."""
    env = make("pendulum")
    dcfg = ddpg.DDPGConfig(qat_enabled=False, batch_size=8)
    cfg = loop.TrainConfig(n_envs=4, **_SMALL)
    ts, hist = loop.train_device(env, cfg, dcfg,
                                 eval_fn=lambda *a: jnp.float32(0.0))
    assert ts.obs.shape == (4, env.spec.obs_dim)
    # 24 steps x 4 lanes = 96 transitions through a 64-slot ring
    assert int(ts.buf.size) == 64
    # updates start once buf.size >= warmup: 4 lanes/step fills the 8-slot
    # warmup after step 1, so steps 1..23 each apply one update
    assert int(ts.agent.step) == 23
    assert set(hist) == {"step", "eval_reward", "train_reward", "ips",
                         "updates_per_s"}
    assert all(v > 0 for v in hist["ips"])
    assert all(np.isfinite(v) for v in hist["train_reward"])


def test_train_host_streams_updates_through_learner():
    env = make("pendulum")
    cfg = loop.TrainConfig(total_steps=6, warmup_steps=2,
                           replay_capacity=32, eval_every=10 ** 6)
    dcfg = ddpg.DDPGConfig(qat_enabled=False, batch_size=8, backend="jnp")
    seed_state = ddpg.init(jax.random.key(0), env.spec, dcfg)
    learner = LearnerEngine.from_ddpg(
        seed_state, dcfg, batcher=BatcherConfig(buckets=(8, 16)))
    ts, info = loop.train_host(env, cfg, dcfg, learner=learner)
    # every post-warmup step streamed one update through the engine
    st = learner.stats()
    assert st["updates"] == int(ts.agent.step) > 0
    assert st["transitions"] == st["updates"] * dcfg.batch_size
    assert st["mode_histogram"] == {"train": {"jnp": st["updates"]}}
    # the loop's final agent IS the engine's state (one source of truth)
    assert ts.agent is learner.state
    assert info["times"]["accelerator"] > 0
