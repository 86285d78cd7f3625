"""Fused 2-launch training step (`fxp_mlp_train_step`) parity pins.

Acceptance contract for the whole-update kernel:
  * tracks the 8-launch `backend="pallas"` path tightly in the monitor
    phase: both paths contract the same padded shapes (the in-kernel
    actions are lane-rotated into the critic's concat input rather than
    fed through a split first-layer weight, whose two partial dots round
    differently on XLA:CPU), so the bound is one Q15.16 lattice quantum;
  * ~1e-3 rel tolerance in the quantized phase over multi-step runs (the
    same STE/bf16-hi rationale as the fused-VJP parity pins — in practice
    the lattice re-snap keeps it bit-exact, see the drift test);
  * zero-weight (pad-mask) rows contribute EXACTLY zero gradient;
  * launch-count regression: one `ddpg.update` traces ≤ 2 pallas calls;
  * in-kernel Adam (the epilogue's `leaf_update`) bit-matches host Adam
    over 50 steps;
  * the agent carried in the kernels' padded layout from update to update
    (`fxp_mlp_train_step_padded`, the training window's scan) gives the
    bits of padding and unpadding it around every update, and its padded
    regions stay exactly 0.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fixedpoint as fxp
from repro.kernels.fxp_mlp.ops import (fxp_mlp_train_step,
                                       fxp_mlp_train_step_padded, pad_wb,
                                       unpad_wb)
from repro.optim import adam, fxp_adam
from repro.rl import ddpg, envs, loop
from repro.rl.envs.base import EnvSpec

SPEC = EnvSpec(name="step_test", obs_dim=17, act_dim=6)


def _count_pallas_calls(fn, *args) -> int:
    def subs(v):
        vals = v if isinstance(v, (tuple, list)) else [v]
        for item in vals:
            if hasattr(item, "eqns"):
                yield item
            elif hasattr(item, "jaxpr"):
                yield item.jaxpr

    def count(jx) -> int:
        n = 0
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                n += 1
            for v in eqn.params.values():
                n += sum(count(s) for s in subs(v))
        return n

    return count(jax.make_jaxpr(fn)(*args).jaxpr)


def _batch(key, n, mask_rows=None):
    ks = jax.random.split(jax.random.key(key), 5)
    b = {
        "obs": jax.random.normal(ks[0], (n, SPEC.obs_dim)),
        "action": jax.random.uniform(ks[1], (n, SPEC.act_dim),
                                     minval=-1, maxval=1),
        "reward": jax.random.normal(ks[2], (n,)),
        "next_obs": jax.random.normal(ks[3], (n, SPEC.obs_dim)),
        "done": (jax.random.uniform(ks[4], (n,)) < 0.1).astype(jnp.float32),
    }
    if mask_rows is not None:
        b["mask"] = (jnp.arange(n) < mask_rows).astype(jnp.float32)
    return b


def _run(backend, steps, *, delay, batch=32, mask_rows=None, qat=True,
         fxp_weights=True, seed=0):
    cfg = ddpg.DDPGConfig(backend=backend, qat_delay=delay,
                          qat_enabled=qat, fxp_weights=fxp_weights)
    state = ddpg.init(jax.random.key(seed), SPEC, cfg)
    metrics = {}
    for t in range(steps):
        state, metrics = ddpg.update(state, _batch(100 + t, batch,
                                                   mask_rows), cfg)
    return state, metrics


def _max_err(a, b):
    return max(float(jnp.max(jnp.abs(x - y)))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _assert_state_close(sf, sp, *, params_tol, targets_tol):
    for name in ("actor", "critic"):
        assert _max_err(getattr(sf, name), getattr(sp, name)) <= params_tol
    for name in ("actor_target", "critic_target"):
        assert _max_err(getattr(sf, name), getattr(sp, name)) <= targets_tol
    for name in ("actor_opt", "critic_opt"):
        of, op = getattr(sf, name), getattr(op_ := sp, name)
        assert int(of.step) == int(op.step)
    np.testing.assert_allclose(
        np.asarray(jax.tree.leaves(sf.qat.ranges)),
        np.asarray(jax.tree.leaves(sp.qat.ranges)), rtol=0, atol=1e-6,
        err_msg="QAT range monitors must evolve identically (~1 ulp)")


# --------------------------------------------------------------------- #
# parity vs the 8-launch custom-VJP path
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("batch,mask_rows", [(32, None), (8, None),
                                             (200, None), (32, 20)])
def test_monitor_phase_tracks_pallas_path(batch, mask_rows):
    """3 monitor-phase steps: params within one Q15.16 quantum (2^-16) of
    the 8-launch path, targets within interpret-mode FMA noise, QAT
    ranges bit-identical (incl. multi-block batches and masked rows)."""
    sf, mf = _run("pallas_fused_step", 3, delay=100, batch=batch,
                  mask_rows=mask_rows)
    sp, mp = _run("pallas", 3, delay=100, batch=batch, mask_rows=mask_rows)
    _assert_state_close(sf, sp, params_tol=2.0 ** -16, targets_tol=1e-6)
    for k in mp:
        np.testing.assert_allclose(np.asarray(mf[k]), np.asarray(mp[k]),
                                   rtol=1e-5, atol=1e-6, err_msg=k)


def test_quant_phase_tracks_pallas_path():
    """5 steps crossing the QAT delay into the quantized phase: ~1e-3 rel
    contract (in practice the affine/Q15.16 re-snap keeps params on the
    same lattice points)."""
    sf, mf = _run("pallas_fused_step", 5, delay=1)
    sp, mp = _run("pallas", 5, delay=1)
    _assert_state_close(sf, sp, params_tol=1e-3, targets_tol=1e-3)
    for k in mp:
        np.testing.assert_allclose(np.asarray(mf[k]), np.asarray(mp[k]),
                                   rtol=1e-3, atol=1e-5, err_msg=k)


def test_no_qat_no_fxp_float_path():
    """qat=False + float weights: the pure-float fused step still tracks
    the 8-launch path (no lattice to absorb drift, hence looser tol)."""
    sf, _ = _run("pallas_fused_step", 2, delay=0, qat=False,
                 fxp_weights=False)
    sp, _ = _run("pallas", 2, delay=0, qat=False, fxp_weights=False)
    for name in ("actor", "critic", "actor_target", "critic_target"):
        assert _max_err(getattr(sf, name), getattr(sp, name)) < 5e-4


# --------------------------------------------------------------------- #
# pad-mask rows: exactly zero gradient
# --------------------------------------------------------------------- #

def test_masked_rows_contribute_exactly_zero():
    """A padded batch (mask marking the pad rows invalid) must produce the
    BIT-IDENTICAL weight update of the unpadded batch: w=0 rows enter the
    loss cotangent as exact zeros, so every dW/db contribution they make
    is exactly zero (QAT off so range monitors can't see the pad rows
    either — with QAT on, monitors intentionally include them, same as
    the 8-launch path's contract)."""
    cfg = ddpg.DDPGConfig(backend="pallas_fused_step", qat_enabled=False)
    state = ddpg.init(jax.random.key(0), SPEC, cfg)
    small = _batch(7, 20)
    padded = {k: jnp.concatenate(
        [v, 1e6 * jnp.ones((12,) + v.shape[1:], v.dtype)]) for k, v in
        small.items()}
    padded["mask"] = (jnp.arange(32) < 20).astype(jnp.float32)
    small["mask"] = jnp.ones((20,), jnp.float32)
    s_small, m_small = ddpg.update(state, small, cfg)
    s_pad, m_pad = ddpg.update(state, padded, cfg)
    for name in ("actor", "critic", "actor_target", "critic_target"):
        la = jax.tree.leaves(getattr(s_small, name))
        lb = jax.tree.leaves(getattr(s_pad, name))
        for a, b in zip(la, lb):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for k in m_small:
        np.testing.assert_array_equal(np.asarray(m_small[k]),
                                      np.asarray(m_pad[k]), err_msg=k)


# --------------------------------------------------------------------- #
# launch-count regression: the tentpole number
# --------------------------------------------------------------------- #

def test_fused_step_traces_at_most_two_pallas_calls():
    """THE perf contract: one `ddpg.update(backend='pallas_fused_step')`
    lowers to ≤ 2 pallas_call primitives (critic step + actor step); the
    8-launch custom-VJP path stays at its 8 for contrast."""
    cfg = ddpg.DDPGConfig(backend="pallas_fused_step")
    state = ddpg.init(jax.random.key(0), SPEC, cfg)
    batch = _batch(0, 32)
    n_fused = _count_pallas_calls(
        lambda s, b: ddpg.update(s, b, cfg), state, batch)
    assert n_fused <= 2, f"fused step must stay ≤2 launches, got {n_fused}"
    cfg8 = dataclasses.replace(cfg, backend="pallas")
    n_pair = _count_pallas_calls(
        lambda s, b: ddpg.update(s, b, cfg8), state, batch)
    assert n_fused < n_pair


# --------------------------------------------------------------------- #
# in-kernel Adam ≡ host Adam, 50 steps
# --------------------------------------------------------------------- #

def test_in_kernel_adam_bitmatches_host_50_steps():
    """The epilogue's Adam (StepConstants via SMEM + `leaf_update` inside a
    Pallas body) against `adam.update` on the host: bit-identical params
    and moments over 50 steps."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from repro.kernels.fxp_mlp.kernel import (
        _H_B1, _H_B2, _H_BC1, _H_BC2, _H_EPS, _H_LR, _H_OMB1, _H_OMB2,
        HYPER_LEN)

    def kernel(hyper_ref, p_ref, g_ref, m_ref, v_ref, op_ref, om_ref,
               ov_ref):
        c = adam.StepConstants(
            lr=hyper_ref[_H_LR], b1=hyper_ref[_H_B1],
            one_minus_b1=hyper_ref[_H_OMB1], b2=hyper_ref[_H_B2],
            one_minus_b2=hyper_ref[_H_OMB2], eps=hyper_ref[_H_EPS],
            bc1=hyper_ref[_H_BC1], bc2=hyper_ref[_H_BC2])
        p2, m2, v2 = adam.leaf_update(p_ref[...], g_ref[...], m_ref[...],
                                      v_ref[...], c)
        op_ref[...] = p2
        om_ref[...] = m2
        ov_ref[...] = v2

    shape = (8, 128)
    sds = jax.ShapeDtypeStruct(shape, jnp.float32)

    @jax.jit
    def kernel_step(hyper, p, g, m, v):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(1,),
                in_specs=[pl.BlockSpec(shape, lambda i, h: (0, 0))] * 4,
                out_specs=[pl.BlockSpec(shape, lambda i, h: (0, 0))] * 3),
            out_shape=[sds, sds, sds], interpret=True)(hyper, p, g, m, v)

    cfg = adam.AdamConfig(lr=3e-3)
    key = jax.random.key(3)
    p_host = p_kern = jax.random.normal(key, shape)
    st_host = adam.init(p_host)
    m_kern = jnp.zeros(shape)
    v_kern = jnp.zeros(shape)
    # jit the host reference too: both sides then see the same XLA FMA
    # contractions, which is the bit-parity contract the fused step relies on
    host_update = jax.jit(adam.update, static_argnums=0)
    for t in range(50):
        g = jax.random.normal(jax.random.fold_in(key, t), shape)
        p_host, st_host, _ = host_update(cfg, g, st_host, p_host)
        c = adam.step_constants(cfg, jnp.asarray(t + 1, jnp.int32))
        hyper = jnp.stack([jnp.float32(0.0)] * (HYPER_LEN - 8)
                          + [c.lr, c.b1, c.one_minus_b1, c.b2,
                             c.one_minus_b2, c.eps, c.bc1, c.bc2])
        p_kern, m_kern, v_kern = kernel_step(hyper, p_kern, g, m_kern,
                                             v_kern)
    np.testing.assert_array_equal(np.asarray(p_host), np.asarray(p_kern))
    np.testing.assert_array_equal(np.asarray(st_host.mu),
                                  np.asarray(m_kern))
    np.testing.assert_array_equal(np.asarray(st_host.nu),
                                  np.asarray(v_kern))


def test_fused_step_backend_guard_message():
    """The train-backend guard names all three trainable backends."""
    cfg = ddpg.DDPGConfig(backend="pallas_layer")
    state = ddpg.init(jax.random.key(0), SPEC, cfg)
    with pytest.raises(ValueError, match="pallas_fused_step"):
        ddpg.update(state, _batch(0, 8), cfg)


# --------------------------------------------------------------------- #
# the agent carried in the kernels' padded layout
# --------------------------------------------------------------------- #

def _bits(a):
    return np.asarray(a, np.float32).view(np.uint32)


def _assert_same_bits(a, b, what):
    np.testing.assert_array_equal(_bits(a), _bits(b), err_msg=what)


def _nets(state):
    """The eight parameter sets in `fxp_mlp_train_step`'s order, each
    ((w per layer), (b per layer))."""
    n = len(ddpg.ACTOR_ACTS)
    wb = lambda p: (tuple(p[f"l{i}"]["w"] for i in range(n)),
                    tuple(p[f"l{i}"]["b"] for i in range(n)))
    return [wb(p) for p in (state.actor, state.critic, state.actor_target,
                            state.critic_target, state.actor_opt.mu,
                            state.actor_opt.nu, state.critic_opt.mu,
                            state.critic_opt.nu)]


@pytest.mark.parametrize("quantized", [False, True],
                         ids=["monitor", "quantized"])
def test_padded_carry_matches_the_unpadded_wrapper_bit_for_bit(quantized):
    """4 updates at B = 128 through `fxp_mlp_train_step_padded` with all
    eight parameter sets carried padded from update to update, against the
    same updates through `fxp_mlp_train_step`, which pads and unpads them
    every call: every unpadded leaf (params, targets, both moments), the
    loss partials and the site extrema are the same bits, and every padded
    row and column of every carried leaf is exactly +0."""
    state = ddpg.init(jax.random.key(1), SPEC, ddpg.DDPGConfig())
    a = (SPEC.obs_dim, *ddpg.HIDDEN, SPEC.act_dim)
    c = (SPEC.obs_dim + SPEC.act_dim, *ddpg.HIDDEN, 1)
    dims = [a, c, a, c, a, a, c, c]
    # per-site affine params of ranges no site input stays inside, so the
    # quantized phase clips as well as rounds
    deltas, zs = fxp.affine_params(jnp.full((6,), -1.5), jnp.full((6,), 2.5),
                                   16)
    kw = dict(actor_acts=ddpg.ACTOR_ACTS, critic_acts=ddpg.CRITIC_ACTS,
              gamma=0.99, tau=0.005)
    unpadded = _nets(state)
    carried = [pad_wb(*wb) for wb in unpadded]
    for t in range(4):
        b = _batch(200 + t, 128)
        consts = adam.step_constants(fxp_adam.FxpAdamConfig(lr=1e-3),
                                     jnp.asarray(t + 1, jnp.int32))
        args = (b["obs"], b["action"], b["reward"], b["done"],
                b["next_obs"], jnp.ones((128,), jnp.float32))
        tail = (deltas, zs, consts, consts, jnp.array(quantized))
        # the wrapper's body outside its own jit: its pads and slices run op
        # by op around the very program the carried path runs, so XLA:CPU
        # cannot fuse the interpreted kernel's arithmetic differently in
        # the two (which moves moments by an ulp or so)
        ref = fxp_mlp_train_step.__wrapped__(
            *args, *unpadded, *tail, obs_dim=SPEC.obs_dim,
            act_dim=SPEC.act_dim, **kw)
        out = fxp_mlp_train_step_padded(*args, *carried, *tail,
                                        actor_dims=a, critic_dims=c, **kw)
        unpadded, carried = list(ref[:8]), list(out[:8])
        for k, (wbp, d, (ws, bs)) in enumerate(zip(carried, dims, unpadded)):
            got_ws, got_bs = unpad_wb(wbp, d)
            for i in range(len(ws)):
                _assert_same_bits(got_ws[i], ws[i], f"step {t} set {k} w{i}")
                _assert_same_bits(got_bs[i], bs[i], f"step {t} set {k} b{i}")
            for i, leaf in enumerate(wbp):
                rows, cols = ((d[i // 2], d[i // 2 + 1]) if i % 2 == 0
                              else (1, d[i // 2 + 1]))
                pad = np.asarray(leaf).copy()
                pad[:rows, :cols] = 0.0
                assert not _bits(pad).any(), f"step {t} set {k} leaf {i}"
        for name in ("closs_sum", "y_sum", "q_sum", "c_mins", "c_maxs",
                     "a_mins", "a_maxs"):
            _assert_same_bits(getattr(out, name), getattr(ref, name),
                              f"step {t} {name}")
    # the updates moved every set, so the carry was exercised
    assert float(jnp.max(jnp.abs(unpadded[4][0][1]))) > 0.0


def test_train_window_carries_the_agent_padded_bit_for_bit():
    """The scanned window with the fused update carries the agent padded
    (one pad at entry, one unpad at exit); the same window with the agent
    carried unpadded, each update padding and unpadding it, returns the
    same `TrainState` bit for bit (agent, replay rows, env, noise, key)
    and the same window metrics, across the QAT delay into the quantized
    phase."""
    env = envs.make("halfcheetah")
    dcfg = ddpg.DDPGConfig(batch_size=32, backend="pallas_fused_step",
                           qat_delay=5)
    cfg = loop.TrainConfig(total_steps=14, warmup_steps=4,
                           replay_capacity=64, eval_every=14, seed=3)
    ts = loop.init_train_state(env, cfg, dcfg)

    @jax.jit
    def unpadded_window(ts):
        def body(carry, _):
            carry, m = loop._one_timestep(carry, env, cfg, dcfg)
            return carry, (m["reward"], m["did_update"])

        ts, (rewards, updates) = jax.lax.scan(body, ts, None, length=14)
        return ts, {"reward": jnp.mean(rewards), "updates": jnp.sum(updates)}

    ref_ts, ref_stats = unpadded_window(jax.tree.map(jnp.copy, ts))
    got_ts, got_stats = loop._train_window(ts, env=env, cfg=cfg, dcfg=dcfg,
                                           window=14)
    assert int(got_stats["updates"]) == 11
    assert bool(got_ts.agent.qat.quantized_phase)

    def leaves(tree):
        out = []
        for leaf in jax.tree.leaves(tree):
            if jnp.issubdtype(leaf.dtype, jax.dtypes.prng_key):
                leaf = jax.random.key_data(leaf)
            out.append(np.asarray(leaf))
        return out

    got, ref = leaves((got_ts, got_stats)), leaves((ref_ts, ref_stats))
    assert len(got) == len(ref)
    for i, (a, b) in enumerate(zip(got, ref)):
        assert a.shape == b.shape and a.dtype == b.dtype, i
        assert a.tobytes() == b.tobytes(), i
