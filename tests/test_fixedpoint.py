"""Property tests for the fixed-point core (hypothesis)."""
try:
    import hypothesis
    import hypothesis.extra.numpy as hnp
    import hypothesis.strategies as st
except ImportError:  # fall back to the local deterministic shim
    from _hyp import hypothesis, hnp, st
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fixedpoint as fxp

SETTINGS = dict(max_examples=50, deadline=None)

floats = st.floats(min_value=-1e4, max_value=1e4, allow_nan=False,
                   width=32)
arrays = hnp.arrays(np.float32, hnp.array_shapes(max_dims=2, max_side=32),
                    elements=floats)


@hypothesis.given(arrays)
@hypothesis.settings(**SETTINGS)
def test_roundtrip_error_half_ulp(x):
    """quantize->dequantize error bounded by delta/2 inside the range."""
    r = fxp.quantize(x, fxp.FXP32)
    back = np.asarray(fxp.dequantize(r, fxp.FXP32))
    clipped = np.clip(x, fxp.FXP32.min_value, fxp.FXP32.max_value)
    assert np.all(np.abs(back - clipped)
                  <= fxp.quantization_error_bound(fxp.FXP32) + 1e-7)


@hypothesis.given(arrays)
@hypothesis.settings(**SETTINGS)
def test_fake_quant_matches_raw(x):
    """fake_quant == dequantize(quantize(x)) bit-exactly."""
    fq = np.asarray(fxp.fake_quant(jnp.asarray(x), fxp.FXP32))
    rq = np.asarray(fxp.dequantize(fxp.quantize(x, fxp.FXP32), fxp.FXP32))
    assert np.array_equal(fq, rq)


@hypothesis.given(arrays)
@hypothesis.settings(**SETTINGS)
def test_quantize_idempotent(x):
    """Quantizing a lattice point is the identity."""
    once = fxp.fake_quant(jnp.asarray(x), fxp.FXP16)
    twice = fxp.fake_quant(once, fxp.FXP16)
    assert np.array_equal(np.asarray(once), np.asarray(twice))


@hypothesis.given(st.floats(-100, 0, allow_nan=False, width=32),
                  st.floats(0, 100, allow_nan=False, width=32))
@hypothesis.settings(**SETTINGS)
def test_affine_contains_zero(a_min, a_max):
    """Affine grid represents 0 exactly (required so ReLU zeros survive)."""
    delta, z = fxp.affine_params(jnp.float32(a_min), jnp.float32(a_max), 16)
    zero = fxp.affine_dequantize(fxp.affine_quantize(jnp.zeros(()), delta, z, 16),
                                 delta, z)
    assert abs(float(zero)) < 1e-6


@hypothesis.given(arrays, st.floats(-50, -1, width=32), st.floats(1, 50, width=32))
@hypothesis.settings(**SETTINGS)
def test_affine_roundtrip_in_range(x, a_min, a_max):
    delta, z = fxp.affine_params(jnp.float32(a_min), jnp.float32(a_max), 16)
    q = fxp.affine_quantize(jnp.asarray(x), delta, z, 16)
    back = np.asarray(fxp.affine_dequantize(q, delta, z))
    # exclude a one-delta boundary band: z rounding can shift the grid's
    # edges by up to delta/2, clipping edge values by up to delta
    d = float(delta)
    inside = (x >= a_min + d) & (x <= a_max - d)
    assert np.all(np.abs(back[inside] - x[inside]) <= d / 2 + 1e-6)


def test_fxp_matmul_raw_exact_vs_int64():
    """Raw int path matches a NumPy int64 oracle bit-exactly."""
    rng = np.random.default_rng(0)
    a = rng.uniform(-4, 4, (8, 21)).astype(np.float32)
    w = rng.uniform(-2, 2, (21, 5)).astype(np.float32)
    ar = np.asarray(fxp.quantize(a, fxp.FXP32), np.int64)
    wr = np.asarray(fxp.quantize(w, fxp.FXP32), np.int64)
    acc = ar @ wr
    shift = fxp.FXP32.frac_bits
    oracle = np.clip((acc + (1 << (shift - 1))) >> shift,
                     fxp.FXP32.raw_min, fxp.FXP32.raw_max).astype(np.int32)
    with jax.enable_x64(True):
        got = np.asarray(fxp.fxp_matmul_raw(
            jnp.asarray(ar, jnp.int32), jnp.asarray(wr, jnp.int32),
            fxp.FXP32, fxp.FXP32, fxp.FXP32))
    assert np.array_equal(got, oracle)


def test_ste_gradient_identity():
    """Straight-through estimator passes gradients unchanged in-range."""
    g = jax.grad(lambda x: jnp.sum(fxp.fake_quant(x, fxp.FXP32)))(
        jnp.array([0.5, -1.25, 3.7]))
    assert np.allclose(np.asarray(g), 1.0)


def test_fake_quant_affine_clips_gradient():
    """Outside the captured range, the clipped fake-quant has zero grad."""
    a_min, a_max = jnp.float32(-1.0), jnp.float32(1.0)
    g = jax.grad(lambda x: jnp.sum(
        fxp.fake_quant_affine(x, a_min, a_max, 16)))(
        jnp.array([0.5, 5.0, -7.0]))
    assert np.allclose(np.asarray(g), [1.0, 0.0, 0.0])
