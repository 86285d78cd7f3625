"""The reference keeps what the configuration states: a 16-bit affine code
enters the contraction with its 16 bits, and the control is the only
place where bfloat16 limbs appear."""
import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import ddpg as ref


def _net(quantized, precision="highest"):
    return ref.Net(("relu", "relu", "tanh"), quantized, 16, precision)


def _actor_and_ranges():
    layers = ref.init_layers(jax.random.key(0), [11, 400, 300, 3], None)
    x = jax.random.normal(jax.random.key(1), (256, 11))
    ranges = ref.site_extrema(layers, x, _net(False))
    return layers, x, ranges


def test_quantized_layer_takes_the_whole_code():
    layers, x, ranges = _actor_and_ranges()
    delta, z = ref.affine_params(*ranges[0], 16)
    code = ref.affine_site(x, delta, z, 16)
    want = jax.nn.relu(jnp.dot(code, layers[0]["w"], precision=jax.lax.Precision.HIGHEST)
                       + layers[0]["b"])
    got = ref.mlp(layers[:1], x, ref.Net(("relu",), True), ranges[:1])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # a 16-bit code is not a bfloat16 number: its hi limb alone loses bits
    assert float(jnp.max(jnp.abs(code - ref._bf16(code)))) > 0.0


def test_control_departs_from_the_reference_in_both_phases():
    layers, x, ranges = _actor_and_ranges()
    for quantized in (False, True):
        best = ref.mlp(layers, x, _net(quantized), ranges)
        high = ref.mlp(layers, x, _net(quantized, "high"), ranges)
        assert float(jnp.max(jnp.abs(best - high))) > 0.0
