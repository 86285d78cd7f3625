"""Fused network-resident MLP kernel vs the per-layer kernel chain.

Parity targets:
  * the REAL per-layer path — QAT site projection + `fxp_dense` (the
    dual-precision Pallas dense kernel) chained per layer, both phases;
  * the pure-jnp oracle `ref_fxp_mlp`;
  * the range monitor of `kernels/quantize` (`monitor_quant`), site by site.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fixedpoint as fxp
from repro.kernels._compat import round_up
from repro.kernels.fxp_matmul.ops import fxp_dense
from repro.kernels.fxp_mlp.ops import fxp_mlp_forward
from repro.kernels.fxp_mlp.ref import ref_fxp_mlp
from repro.kernels.quantize.ops import monitor_quant

# (name, layer dims, activations) — odd/unpadded obs/act dims on purpose
NETS = [
    ("actor_halfcheetah", [17, 400, 300, 6], ("relu", "relu", "tanh")),
    ("critic_halfcheetah", [23, 400, 300, 1], ("relu", "relu", "none")),
    ("tiny_ragged", [5, 33, 7], ("relu", "tanh")),
]
BATCHES = [1, 128, 512]


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
    """Captured ranges + the affine params the fused kernel consumes."""
    a_mins = jnp.linspace(-1.0, -3.0, n_layers).astype(jnp.float32)
    a_maxs = jnp.linspace(1.5, 3.5, n_layers).astype(jnp.float32)
    ds, zs = [], []
    for i in range(n_layers):
        d, z = fxp.affine_params(a_mins[i], a_maxs[i], n_bits)
        ds.append(d)
        zs.append(z.astype(jnp.float32))
    return a_mins, a_maxs, jnp.stack(ds), jnp.stack(zs)


def _perlayer_chain(x, ws, bs, acts, quant: bool, a_mins, a_maxs, n_bits=16):
    """The per-layer reference path: inline QAT site + fxp_dense kernel."""
    for i in range(len(ws)):
        if quant:
            x = fxp.fake_quant_affine(x, a_mins[i], a_maxs[i], n_bits)
        else:
            x = fxp.fake_quant(x, fxp.FXP32)
        x = fxp_dense(x, ws[i], bs[i], full_precision=not quant,
                      activation=acts[i])
    return x


@pytest.mark.parametrize("net", NETS, ids=[n[0] for n in NETS])
@pytest.mark.parametrize("batch", BATCHES)
@pytest.mark.parametrize("quant", [False, True])
def test_fused_matches_perlayer_kernel_chain(net, batch, quant):
    _, dims, acts = net
    ws, bs = _make_net(dims)
    x = jax.random.normal(jax.random.key(batch), (batch, dims[0])) * 2
    a_mins, a_maxs, deltas, zs = _site_params(len(ws))
    got, _, _ = fxp_mlp_forward(x, ws, bs, deltas, zs, activations=acts,
                                quant_phase=jnp.array(quant))
    want = _perlayer_chain(x, ws, bs, acts, quant, a_mins, a_maxs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def _lane_padded(x, ws, bs):
    """The same network with every feature dim zero-padded to 128 lanes,
    as the kernel lays it out: padded columns stay exactly zero through
    both quantizers and every activation, so the function is unchanged."""
    dims = [x.shape[-1]] + [w.shape[1] for w in ws]
    padded = [round_up(d, 128) for d in dims]
    xp = jnp.pad(x, ((0, 0), (0, padded[0] - dims[0])))
    wp = tuple(jnp.pad(w, ((0, padded[i] - dims[i]),
                           (0, padded[i + 1] - dims[i + 1])))
               for i, w in enumerate(ws))
    bp = tuple(jnp.pad(b, (0, padded[i + 1] - dims[i + 1]))
               for i, b in enumerate(bs))
    return xp, wp, bp


@pytest.mark.parametrize("net", NETS, ids=[n[0] for n in NETS])
@pytest.mark.parametrize("quant", [False, True])
def test_fused_matches_oracle(net, quant):
    """y against the oracle run at the kernel's lane-padded widths, and
    (monitor phase) at the unpadded widths too; site extrema against the
    unpadded oracle.

    The padded run is there because XLA's CPU dot (jax 0.9) rounds a
    zero-padded contraction differently from the unpadded one, by about
    one f32 ulp.  In the quantized phase each dense input is the bf16 hi
    limb of an affine-lattice value, so that ulp can cross a lattice or
    bf16 rounding boundary and move y by ~2e-4.  With equal contraction
    shapes the two sides must agree to the original 2e-5.
    """
    _, dims, acts = net
    ws, bs = _make_net(dims, seed=3)
    x = jax.random.normal(jax.random.key(7), (64, dims[0])) * 3
    a_mins, a_maxs, deltas, zs = _site_params(len(ws))
    got = fxp_mlp_forward(x, ws, bs, deltas, zs, activations=acts,
                          quant_phase=jnp.array(quant))
    oracle = functools.partial(ref_fxp_mlp, activations=acts,
                               quant_phase=jnp.array(quant),
                               a_mins=a_mins, a_maxs=a_maxs)
    want = oracle(x, ws, bs)
    want_padded = oracle(*_lane_padded(x, ws, bs))[0][:, :dims[-1]]
    tol = dict(rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want_padded),
                               err_msg="y vs padded oracle", **tol)
    if not quant:
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                                   err_msg="y", **tol)
    for g, w, name in zip(got[1:], want[1:], ["mins", "maxs"]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   err_msg=name, **tol)


@pytest.mark.parametrize("batch", BATCHES)
def test_range_monitor_matches_quantize_kernel(batch):
    """Fused in-pipeline monitor == kernels/quantize's monitor_quant, fed
    the exact per-layer site inputs (monitoring phase)."""
    _, dims, acts = NETS[0]
    ws, bs = _make_net(dims, seed=5)
    x = jax.random.normal(jax.random.key(11), (batch, dims[0])) * 4
    a_mins, a_maxs, deltas, zs = _site_params(len(ws))
    _, mins, maxs = fxp_mlp_forward(x, ws, bs, deltas, zs, activations=acts,
                                    quant_phase=jnp.array(False))
    # walk the reference chain to recover each layer's site input
    xi = x
    for i in range(len(ws)):
        _, nmin, nmax = monitor_quant(xi, jnp.float32(jnp.inf),
                                      jnp.float32(-jnp.inf),
                                      jnp.array(False))
        np.testing.assert_allclose(float(mins[i]), float(nmin), rtol=1e-6,
                                   err_msg=f"site {i} min")
        np.testing.assert_allclose(float(maxs[i]), float(nmax), rtol=1e-6,
                                   err_msg=f"site {i} max")
        xi = fxp_dense(fxp.fake_quant(xi, fxp.FXP32), ws[i], bs[i],
                       full_precision=True, activation=acts[i])


def test_padding_never_leaks_into_ranges():
    """Padded rows/cols (batch 1, odd dims) must not contaminate min/max:
    all-positive activations keep a positive min even though padding is 0."""
    dims, acts = [5, 33, 7], ("relu", "tanh")
    ws, bs = _make_net(dims, seed=9)
    x = jnp.abs(jax.random.normal(jax.random.key(1), (1, 5))) + 0.5
    a_mins, a_maxs, deltas, zs = _site_params(len(ws))
    _, mins, _ = fxp_mlp_forward(x, ws, bs, deltas, zs, activations=acts,
                                 quant_phase=jnp.array(False))
    assert float(mins[0]) >= 0.5  # zero padding would have dragged this to 0


def test_no_qat_path_matches_dense_chain():
    """qat=False: pure dual-precision dense pipeline, no site projection."""
    dims, acts = [17, 400, 300, 6], ("relu", "relu", "tanh")
    ws, bs = _make_net(dims, seed=13)
    x = jax.random.normal(jax.random.key(17), (32, dims[0]))
    got, _, _ = fxp_mlp_forward(x, ws, bs, activations=acts,
                                quant_phase=jnp.array(False), qat=False)
    want = x
    for i in range(len(ws)):
        want = fxp_dense(want, ws[i], bs[i], full_precision=True,
                         activation=acts[i])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
