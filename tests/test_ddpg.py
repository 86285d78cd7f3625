"""DDPG behaviour tests — the paper's workload."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.rl import ddpg, loop
from repro.rl.envs.locomotion import make


def _dummy_batch(spec, n=32, key=0):
    k = jax.random.key(key)
    return {
        "obs": jax.random.normal(k, (n, spec.obs_dim)),
        "action": jax.random.uniform(k, (n, spec.act_dim), minval=-1, maxval=1),
        "reward": jax.random.normal(k, (n,)),
        "next_obs": jax.random.normal(jax.random.fold_in(k, 1),
                                      (n, spec.obs_dim)),
        "done": jnp.zeros((n,), jnp.bool_),
    }


def test_network_shapes_match_paper():
    """actor 400-300, critic state+action->400->300->1 (§VI-B)."""
    env = make("halfcheetah")
    st = ddpg.init(jax.random.key(0), env.spec, ddpg.DDPGConfig())
    assert st.actor["l0"]["w"].shape == (17, 400)
    assert st.actor["l1"]["w"].shape == (400, 300)
    assert st.actor["l2"]["w"].shape == (300, 6)
    assert st.critic["l0"]["w"].shape == (17 + 6, 400)
    assert st.critic["l2"]["w"].shape == (300, 1)


def test_actions_bounded():
    env = make("halfcheetah")
    cfg = ddpg.DDPGConfig()
    st = ddpg.init(jax.random.key(0), env.spec, cfg)
    obs = 100 * jax.random.normal(jax.random.key(1), (16, 17))
    a = ddpg.act(st, obs, cfg=cfg, noise_key=jax.random.key(2))
    assert float(jnp.abs(a).max()) <= 1.0


def test_update_moves_params_and_targets_slowly():
    env = make("swimmer")
    cfg = ddpg.DDPGConfig(batch_size=32, tau=0.01)
    st = ddpg.init(jax.random.key(0), env.spec, cfg)
    batch = _dummy_batch(env.spec)
    st2, metrics = jax.jit(lambda s, b: ddpg.update(s, b, cfg))(st, batch)
    d_main = sum(float(jnp.abs(a - b).max()) for a, b in
                 zip(jax.tree.leaves(st.actor), jax.tree.leaves(st2.actor)))
    d_tgt = sum(float(jnp.abs(a - b).max()) for a, b in
                zip(jax.tree.leaves(st.actor_target),
                    jax.tree.leaves(st2.actor_target)))
    assert d_main > 0 and d_tgt > 0
    assert d_tgt < d_main  # soft update lags
    assert bool(jnp.isfinite(metrics["critic_loss"]))


def test_fxp_weights_on_lattice():
    """After an update with fxp enabled, weights sit on the Q15.16 grid."""
    env = make("swimmer")
    cfg = ddpg.DDPGConfig(batch_size=16, fxp_weights=True)
    st = ddpg.init(jax.random.key(0), env.spec, cfg)
    st, _ = jax.jit(lambda s, b: ddpg.update(s, b, cfg))(
        st, _dummy_batch(env.spec, 16))
    w = np.asarray(st.actor["l0"]["w"]) * 2.0 ** 16
    assert np.allclose(w, np.round(w), atol=1e-2)


def test_qat_delay_controls_phase():
    env = make("swimmer")
    cfg = ddpg.DDPGConfig(batch_size=16, qat_delay=2)
    st = ddpg.init(jax.random.key(0), env.spec, cfg)
    upd = jax.jit(lambda s, b: ddpg.update(s, b, cfg))
    batch = _dummy_batch(env.spec, 16)
    assert not bool(st.qat.quantized_phase)
    for _ in range(3):
        st, _ = upd(st, batch)
    assert bool(st.qat.quantized_phase)


def test_pallas_backend_matches_jnp():
    """AAP-core kernel backend produces the same actions as the jnp path."""
    env = make("swimmer")
    st = ddpg.init(jax.random.key(0), env.spec, ddpg.DDPGConfig())
    obs = jax.random.normal(jax.random.key(1), (4, env.spec.obs_dim))
    a_jnp = ddpg.act(st, obs, cfg=ddpg.DDPGConfig(backend="jnp"))
    a_pal = ddpg.act(st, obs, cfg=ddpg.DDPGConfig(backend="pallas"))
    np.testing.assert_allclose(np.asarray(a_jnp), np.asarray(a_pal),
                               rtol=1e-4, atol=1e-4)


def test_fused_backend_matches_perlayer_backend():
    """Network-resident fused kernel (backend="pallas") == the per-layer
    AAP-core chain (serving's `act_batch(mode="layer")`): same actions in
    the quantized phase and with QAT off; and the fused kernel's range
    monitors evolve as the jnp backend's QAT sites do."""
    from repro.core.qat import QATContext, QATState

    env = make("halfcheetah")
    st = ddpg.init(jax.random.key(0), env.spec, ddpg.DDPGConfig())
    assert bool(st.qat.quantized_phase)
    obs = jax.random.normal(jax.random.key(1), (8, env.spec.obs_dim)) * 2
    a_fused = ddpg.act(st, obs, cfg=ddpg.DDPGConfig(backend="pallas"))
    a_layer = ddpg.act_batch(st.actor, obs, ddpg.freeze_actor_quant(st),
                             mode="layer")
    np.testing.assert_allclose(np.asarray(a_fused), np.asarray(a_layer),
                               rtol=1e-5, atol=1e-5)

    # with QAT off neither path may flip to the half-precision datapath
    cfg_off = ddpg.DDPGConfig(qat_enabled=False)
    st_off = ddpg.init(jax.random.key(0), env.spec, cfg_off)
    a_f = ddpg.act(st_off, obs, cfg=dataclasses.replace(cfg_off, backend="pallas"))
    a_l = ddpg.act_batch(st_off.actor, obs, None, mode="layer")
    np.testing.assert_allclose(np.asarray(a_f), np.asarray(a_l),
                               rtol=1e-6, atol=1e-6)

    qat = QATState.init(delay=100, sites=ddpg.ACTOR_SITES + ddpg.CRITIC_SITES)
    finals = {}
    for backend in ("pallas", "jnp"):
        ctx = QATContext(qat)
        ddpg.actor_forward(st.actor, obs, ctx, backend=backend)
        finals[backend] = ctx.finalize().ranges
    for site in ddpg.ACTOR_SITES:
        for attr in ("a_min", "a_max", "count"):
            np.testing.assert_allclose(
                np.asarray(getattr(finals["pallas"][site], attr)),
                np.asarray(getattr(finals["jnp"][site], attr)),
                rtol=1e-6, err_msg=f"{site}.{attr}")


@pytest.mark.parametrize("backend", ["pallas_layer", "fused_step", "fused"])
def test_config_rejects_a_backend_without_an_update(backend):
    """Only the three backends `update` trains through are accepted, and
    the refusal names them: the retired per-layer backend and the learner
    dispatcher's old mode names fail at construction."""
    with pytest.raises(ValueError, match="'jnp', 'pallas' or 'pallas_fused_step'"):
        ddpg.DDPGConfig(backend=backend)


def test_act_qat_off_touches_no_qat_state(monkeypatch):
    """Pure inference with QAT disabled must not build a QATContext (which
    copies the range tree and re-derives quant params every call) — the
    no-QAT fast path is hoisted in `act`."""
    env = make("swimmer")
    cfg = ddpg.DDPGConfig(qat_enabled=False)
    st = ddpg.init(jax.random.key(0), env.spec, cfg)
    obs = jax.random.normal(jax.random.key(1), (4, env.spec.obs_dim))

    instantiated = []

    class SpyContext(ddpg.QATContext):
        def __init__(self, state):
            instantiated.append(state)
            super().__init__(state)

    monkeypatch.setattr(ddpg, "QATContext", SpyContext)
    for backend in ddpg.BACKENDS:
        a = ddpg.act(st, obs, cfg=dataclasses.replace(cfg, backend=backend))
        assert a.shape == (4, env.spec.act_dim)
    assert instantiated == [], "QAT state touched during no-QAT inference"

    # with QAT enabled the context is still built exactly once per act
    cfg_on = ddpg.DDPGConfig()
    st_on = ddpg.init(jax.random.key(0), env.spec, cfg_on)
    ddpg.act(st_on, obs, cfg=cfg_on)
    assert len(instantiated) == 1


@pytest.mark.slow
def test_learns_pendulum():
    """Reward improves substantially within 12k fused steps (pure float —
    the fixed-point learning curves are benchmarks/fig7)."""
    env = make("pendulum")
    dcfg = ddpg.DDPGConfig(qat_enabled=False, fxp_weights=False,
                           batch_size=64, actor_lr=3e-4, critic_lr=1e-3,
                           exploration_sigma=0.15, qat_delay=10 ** 9)
    cfg = loop.TrainConfig(total_steps=12_000, warmup_steps=500,
                           eval_every=4_000, replay_capacity=20_000,
                           eval_episodes=4, seed=1)
    _, hist = loop.train_device(env, cfg, dcfg)
    assert hist["eval_reward"][-1] > hist["eval_reward"][0] + 150
    assert hist["eval_reward"][-1] > -900
