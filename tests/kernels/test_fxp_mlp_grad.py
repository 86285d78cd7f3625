"""Gradient parity for the fused MLP kernel's custom VJP.

The fused backward kernel (kernels/fxp_mlp/kernel.fxp_mlp_bwd_pallas) must
produce the same gradients `jax.grad` derives through the differentiable
references:

  * the pure-jnp oracle `ref_fxp_mlp` (same limb semantics as the kernel) —
    tight tolerance in the full-precision phase; the quantized phase is
    looser because the oracle's autodiff *rounds the cotangent* through the
    bf16 hi-limb cast while the fused backward keeps the straight-through
    f32 cotangent (a deliberate STE choice, not an approximation error);
  * the plain-jnp DDPG training path (`backend="jnp"`) for full update()
    gradients;

plus a 50-step `ddpg.update()` smoke run asserting the pallas-backend loss
trajectory tracks the jnp backend within fixed-point tolerance.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fixedpoint as fxp
from repro.kernels.fxp_mlp.ops import fxp_mlp_train, pad_bias, pad_weight
from repro.kernels.fxp_mlp.ref import ref_fxp_mlp
from repro.rl import ddpg
from repro.rl.envs.locomotion import make

# actor/critic shapes of the paper workload + a ragged net for padding paths
NETS = [
    ("actor_halfcheetah", [17, 400, 300, 6], ("relu", "relu", "tanh")),
    ("critic_halfcheetah", [23, 400, 300, 1], ("relu", "relu", "none")),
    ("tiny_ragged", [5, 33, 7], ("relu", "tanh")),
]


def _make_net(dims, seed=0):
    keys = jax.random.split(jax.random.key(seed), 2 * (len(dims) - 1))
    ws = tuple(jax.random.uniform(keys[2 * i], (dims[i], dims[i + 1]),
                                  jnp.float32, -0.2, 0.2)
               for i in range(len(dims) - 1))
    bs = tuple(jax.random.uniform(keys[2 * i + 1], (dims[i + 1],),
                                  jnp.float32, -0.2, 0.2)
               for i in range(len(dims) - 1))
    return ws, bs


def _site_params(n_layers, n_bits=16):
    a_mins = jnp.linspace(-1.0, -3.0, n_layers).astype(jnp.float32)
    a_maxs = jnp.linspace(1.5, 3.5, n_layers).astype(jnp.float32)
    ds, zs = [], []
    for i in range(n_layers):
        d, z = fxp.affine_params(a_mins[i], a_maxs[i], n_bits)
        ds.append(d)
        zs.append(z.astype(jnp.float32))
    return a_mins, a_maxs, jnp.stack(ds), jnp.stack(zs)


def _assert_tree_close(got, want, *, rtol, atol, err_msg=""):
    for i, (g, w) in enumerate(zip(jax.tree.leaves(got),
                                   jax.tree.leaves(want))):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=rtol, atol=atol,
                                   err_msg=f"{err_msg} leaf {i}")


@pytest.mark.parametrize("net", NETS, ids=[n[0] for n in NETS])
@pytest.mark.parametrize("quant", [False, True])
def test_fused_vjp_matches_oracle_autodiff(net, quant):
    """grad(fused custom VJP) == grad(jnp oracle) for x, W, and b."""
    _, dims, acts = net
    ws, bs = _make_net(dims)
    x = jax.random.normal(jax.random.key(11), (32, dims[0])) * 2
    a_mins, a_maxs, deltas, zs = _site_params(len(ws))
    qp = jnp.array(quant)

    def loss_fused(ws, bs, x):
        y, _, _ = fxp_mlp_train(x, ws, bs, deltas, zs, activations=acts,
                                quant_phase=qp)
        return jnp.sum(jnp.sin(y))  # nonlinear head: exercises dy != const

    def loss_ref(ws, bs, x):
        y, _, _ = ref_fxp_mlp(x, ws, bs, activations=acts, quant_phase=qp,
                              a_mins=a_mins, a_maxs=a_maxs)
        return jnp.sum(jnp.sin(y))

    got = jax.grad(loss_fused, argnums=(0, 1, 2))(ws, bs, x)
    want = jax.grad(loss_ref, argnums=(0, 1, 2))(ws, bs, x)
    # quant phase: the oracle's bf16 cast rounds the cotangent (rel 2^-9);
    # the fused bwd keeps f32 STE — tolerance covers that gap
    tol = dict(rtol=5e-3, atol=2e-2) if quant else dict(rtol=2e-4, atol=2e-5)
    _assert_tree_close(got, want, **tol, err_msg=f"quant={quant}")


@pytest.mark.parametrize("net", NETS, ids=[n[0] for n in NETS])
@pytest.mark.parametrize("quant", [False, True])
def test_prepadded_weights_give_the_same_bits(net, quant):
    """`fxp_mlp_train(..., dims=...)` on weights already in the kernels'
    layout (how acting reads the training window's padded carry): the same
    y, site extrema and gradients as on the unpadded weights, the weight
    gradients in the padded layout with their padding exactly 0."""
    _, dims, acts = net
    ws, bs = _make_net(dims)
    wps = tuple(pad_weight(w) for w in ws)
    bps = tuple(pad_bias(b) for b in bs)
    x = jax.random.normal(jax.random.key(12), (32, dims[0])) * 2
    _, _, deltas, zs = _site_params(len(ws))
    kw = dict(activations=acts, quant_phase=jnp.array(quant))

    def run(ws, bs, x, dims=None):
        def loss(ws, bs, x):
            y, mns, mxs = fxp_mlp_train(x, ws, bs, deltas, zs, dims=dims, **kw)
            return jnp.sum(jnp.sin(y)), (y, mns, mxs)
        return jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(
            ws, bs, x)

    (_, want), (gw, gb, gx) = run(ws, bs, x)
    (_, got), (pgw, pgb, pgx) = run(wps, bps, x, tuple(dims))
    bits = lambda a: np.asarray(a).view(np.uint32)
    for g, w in zip(got + (pgx,), want + (gx,)):
        np.testing.assert_array_equal(bits(g), bits(w))
    for i, (k, n) in enumerate(zip(dims[:-1], dims[1:])):
        np.testing.assert_array_equal(bits(pgw[i][:k, :n]), bits(gw[i]))
        np.testing.assert_array_equal(bits(pgb[i][0, :n]), bits(gb[i]))
        assert pgw[i].shape == wps[i].shape and pgb[i].shape == bps[i].shape
        pad_w, pad_b = np.array(pgw[i]), np.array(pgb[i])
        pad_w[:k, :n], pad_b[0, :n] = 0.0, 0.0
        assert not pad_w.any() and not pad_b.any()


@pytest.mark.parametrize("quant", [False, True])
def test_fused_vjp_value_and_grad_consistent(quant):
    """The custom-VJP fwd rule must return the same primal as the plain
    fused forward (jax checks nothing here — pin it ourselves)."""
    _, dims, acts = NETS[0]
    ws, bs = _make_net(dims, seed=2)
    x = jax.random.normal(jax.random.key(3), (16, dims[0]))
    _, _, deltas, zs = _site_params(len(ws))
    qp = jnp.array(quant)

    def f(ws):
        y, mns, mxs = fxp_mlp_train(x, ws, bs, deltas, zs, activations=acts,
                                    quant_phase=qp)
        return jnp.sum(y), (y, mns, mxs)

    (_, (y_grad, mns_g, mxs_g)), _ = jax.value_and_grad(f, has_aux=True)(ws)
    y_plain, mns_p, mxs_p = fxp_mlp_train(x, ws, bs, deltas, zs,
                                          activations=acts, quant_phase=qp)
    _assert_tree_close((y_grad, mns_g, mxs_g), (y_plain, mns_p, mxs_p),
                       rtol=1e-6, atol=1e-6)


def test_range_monitor_outputs_are_stop_gradient():
    """site_mins/site_maxs are observations, not a differentiable head:
    grads through them must be zero BY CONTRACT (the oracle's mins/maxs do
    differentiate — pinning the intended asymmetry here)."""
    _, dims, acts = NETS[2]
    ws, bs = _make_net(dims, seed=4)
    x = jax.random.normal(jax.random.key(5), (8, dims[0]))
    _, _, deltas, zs = _site_params(len(ws))

    def monitor_loss(ws, x):
        _, mns, mxs = fxp_mlp_train(x, ws, bs, deltas, zs, activations=acts,
                                    quant_phase=jnp.array(False))
        return jnp.sum(mxs - mns)

    gws, gx = jax.grad(monitor_loss, argnums=(0, 1))(ws, x)
    for leaf in jax.tree.leaves((gws, gx)):
        assert float(jnp.abs(leaf).max()) == 0.0


def test_site_clip_gradient_is_zero_outside_range():
    """STE clip mask: cotangents must vanish where the quantizer saturates
    (the standard QAT clipping gradient), exactly like the jnp site."""
    dims, acts = [8, 16], ("none",)
    ws, bs = _make_net(dims, seed=5)
    a_mins = jnp.array([-1.0])
    a_maxs = jnp.array([1.0])
    d, z = fxp.affine_params(a_mins[0], a_maxs[0], 16)
    deltas, zs = jnp.stack([d]), jnp.stack([z.astype(jnp.float32)])
    # half the inputs far outside the captured [-1, 1] range
    x = jnp.concatenate([jnp.full((4, 8), 7.0), jnp.zeros((4, 8))])

    def loss(x):
        y, _, _ = fxp_mlp_train(x, ws, bs, deltas, zs, activations=acts,
                                quant_phase=jnp.array(True))
        return jnp.sum(y)

    gx = jax.grad(loss)(x)
    assert float(jnp.abs(gx[:4]).max()) == 0.0, "saturated rows must not flow"
    assert float(jnp.abs(gx[4:]).max()) > 0.0, "in-range rows must flow"


@pytest.mark.parametrize("qat_enabled", [True, False])
def test_update_gradient_parity_vs_jnp_backend(qat_enabled):
    """One full `ddpg.update()` (critic BP/WU + actor BP/WU) per backend
    from identical state: losses and updated params must agree within
    fixed-point tolerance (full-precision phase — the plain-jnp dense has
    no limb split, so only f32-rounding-level drift is expected)."""
    env = make("halfcheetah")
    spec = env.spec
    k = jax.random.key(0)
    batch = {
        "obs": jax.random.normal(k, (32, spec.obs_dim)),
        "action": jax.random.uniform(k, (32, spec.act_dim),
                                     minval=-1, maxval=1),
        "reward": jax.random.normal(k, (32,)),
        "next_obs": jax.random.normal(jax.random.fold_in(k, 1),
                                      (32, spec.obs_dim)),
        "done": jnp.zeros((32,), jnp.bool_),
    }
    outs = {}
    for backend in ("jnp", "pallas"):
        cfg = ddpg.DDPGConfig(batch_size=32, backend=backend,
                              qat_enabled=qat_enabled, qat_delay=1000)
        st = ddpg.init(jax.random.key(0), spec, cfg)
        st2, metrics = jax.jit(lambda s, b: ddpg.update(s, b, cfg))(st, batch)
        outs[backend] = (st2, metrics)
    stj, mj = outs["jnp"]
    stp, mp = outs["pallas"]
    for name in metrics:
        np.testing.assert_allclose(float(mj[name]), float(mp[name]),
                                   rtol=1e-4, atol=1e-5, err_msg=name)
    for attr in ("actor", "critic", "actor_target", "critic_target"):
        _assert_tree_close(getattr(stp, attr), getattr(stj, attr),
                           rtol=1e-4, atol=2e-5, err_msg=attr)


def test_training_smoke_50_steps_matches_jnp_trajectory():
    """50 update() steps crossing the QAT delay: the fused-kernel training
    path must track the jnp backend's loss/Q trajectory within fixed-point
    tolerance (weights live on the Q15.16 lattice, so tiny gradient diffs
    mostly snap away; the quantized phase adds bf16-datapath drift)."""
    env = make("swimmer")
    spec = env.spec
    n_steps, bs = 50, 16
    k = jax.random.key(7)
    batches = [
        {
            "obs": jax.random.normal(jax.random.fold_in(k, 3 * i),
                                     (bs, spec.obs_dim)),
            "action": jax.random.uniform(jax.random.fold_in(k, 3 * i + 1),
                                         (bs, spec.act_dim),
                                         minval=-1, maxval=1),
            "reward": jax.random.normal(jax.random.fold_in(k, 3 * i + 2),
                                        (bs,)),
            "next_obs": jax.random.normal(jax.random.fold_in(k, 3 * i + 1),
                                          (bs, spec.obs_dim)),
            "done": jnp.zeros((bs,), jnp.bool_),
        }
        for i in range(n_steps)
    ]
    hist = {}
    for backend in ("jnp", "pallas"):
        cfg = ddpg.DDPGConfig(batch_size=bs, backend=backend, qat_delay=25)
        st = ddpg.init(jax.random.key(0), spec, cfg)
        upd = jax.jit(lambda s, b: ddpg.update(s, b, cfg))
        traj = {"critic_loss": [], "actor_loss": [], "q_mean": []}
        for b in batches:
            st, m = upd(st, b)
            for name in traj:
                traj[name].append(float(m[name]))
        hist[backend] = (st, traj)
    stj, tj = hist["jnp"]
    stp, tp = hist["pallas"]
    for name in tj:
        np.testing.assert_allclose(
            np.array(tp[name]), np.array(tj[name]), rtol=5e-3, atol=5e-3,
            err_msg=f"{name} trajectory diverged")
    # end-state parity: the two training paths land on nearby params
    _assert_tree_close(stp.actor, stj.actor, rtol=5e-3, atol=1e-3,
                       err_msg="actor after 50 steps")
    # both backends advanced the same QAT state machine
    assert int(stp.step) == int(stj.step) == n_steps
    assert bool(stp.qat.quantized_phase) and bool(stj.qat.quantized_phase)


def test_act_after_pallas_training_matches_jnp():
    """Policy parity after training: actions from the two trained states
    agree (the serving path consumes pallas-trained weights)."""
    env = make("swimmer")
    spec = env.spec
    k = jax.random.key(1)
    batch = {
        "obs": jax.random.normal(k, (16, spec.obs_dim)),
        "action": jax.random.uniform(k, (16, spec.act_dim),
                                     minval=-1, maxval=1),
        "reward": jax.random.normal(k, (16,)),
        "next_obs": jax.random.normal(jax.random.fold_in(k, 1),
                                      (16, spec.obs_dim)),
        "done": jnp.zeros((16,), jnp.bool_),
    }
    states = {}
    for backend in ("jnp", "pallas"):
        cfg = ddpg.DDPGConfig(batch_size=16, backend=backend, qat_delay=2)
        st = ddpg.init(jax.random.key(0), spec, cfg)
        upd = jax.jit(lambda s, b: ddpg.update(s, b, cfg))
        for _ in range(4):
            st, _ = upd(st, batch)
        states[backend] = (st, cfg)
    obs = jax.random.normal(jax.random.key(9), (8, spec.obs_dim))
    a_j = ddpg.act(states["jnp"][0], obs, cfg=states["jnp"][1])
    a_p = ddpg.act(states["pallas"][0], obs, cfg=states["pallas"][1])
    np.testing.assert_allclose(np.asarray(a_p), np.asarray(a_j),
                               rtol=5e-3, atol=2e-3)
