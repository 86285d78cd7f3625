"""Jitted wrapper for the fused monitor+quantize kernel."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels._compat import interpret_mode
from repro.kernels.quantize.kernel import _BC, _BR, monitor_quant_pallas

Array = jax.Array


@functools.partial(jax.jit, static_argnames=("n_bits", "interpret"))
def monitor_quant(x: Array, a_min: Array, a_max: Array, quant_phase: Array,
                  *, n_bits: int = 16, interpret: Optional[bool] = None
                  ) -> tuple[Array, Array, Array]:
    """Fused Algorithm-1 activation stage.

    Returns (y, new_min, new_max): y is the phase-selected projection of x,
    ranges update only while quant_phase is False.
    """
    if interpret is None:
        interpret = interpret_mode()
    shape = x.shape
    n = x.size
    flat = x.astype(jnp.float32).reshape(-1)
    cols = _BC
    rows = (n + cols - 1) // cols
    rows = (rows + _BR - 1) // _BR * _BR
    pad = rows * cols - n
    x2 = jnp.pad(flat, (0, pad)).reshape(rows, cols)

    y2, nmin, nmax = monitor_quant_pallas(
        x2,
        jnp.asarray(a_min, jnp.float32).reshape(1),
        jnp.asarray(a_max, jnp.float32).reshape(1),
        jnp.asarray(quant_phase, jnp.int32).reshape(1),
        jnp.asarray(n, jnp.int32).reshape(1),
        n_bits=n_bits, interpret=interpret)
    y = y2.reshape(-1)[:n].reshape(shape)
    return y, nmin, nmax
