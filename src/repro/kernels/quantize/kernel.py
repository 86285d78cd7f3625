"""Pallas kernel: fused activation monitor + quantizer (FIXAR Algorithm 1).

Single sweep over the activation tensor producing the (de)quantized view and
the updated running min/max — the software image of the BRAM-side range
monitor sitting between the accumulator and the activation memory.

Layout: x is reshaped to (R, 128) rows (lane-aligned); the grid walks row
blocks of 8 sequentially ("arbitrary"), min/max accumulate in (8, 128)
output tiles revisited by every step, every entry holding the running
value (Mosaic stores vectors, not scalars, into VMEM).  Tail padding is
masked with the running extrema so it never contaminates the ranges.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.fixedpoint import FXP32

Array = jax.Array

_BR, _BC = 8, 128  # f32 TPU tile


def _mq_kernel(x_ref, amin_ref, amax_ref, phase_ref, nvalid_ref,
               y_ref, nmin_ref, nmax_ref, *, n_bits: int, n_rows: int):
    i = pl.program_id(0)
    x = x_ref[...]

    # ---- tail mask: global element index < n_valid --------------------------
    base = (i * _BR) * _BC
    ridx = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    cidx = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    gidx = base + ridx * _BC + cidx
    valid = gidx < nvalid_ref[0]

    block_min = jnp.min(jnp.where(valid, x, jnp.inf))
    block_max = jnp.max(jnp.where(valid, x, -jnp.inf))

    @pl.when(i == 0)
    def _init():
        nmin_ref[...] = jnp.full(nmin_ref.shape, amin_ref[0], jnp.float32)
        nmax_ref[...] = jnp.full(nmax_ref.shape, amax_ref[0], jnp.float32)

    quant = phase_ref[0] > 0
    # freeze monitoring once quantization starts (Algorithm 1)
    nmin_ref[...] = jnp.where(quant, nmin_ref[...],
                              jnp.minimum(nmin_ref[...], block_min))
    nmax_ref[...] = jnp.where(quant, nmax_ref[...],
                              jnp.maximum(nmax_ref[...], block_max))

    # ---- projection, selected by phase --------------------------------------
    # full phase: Q15.16 lattice
    s32 = jnp.float32(2.0 ** FXP32.frac_bits)
    y_full = jnp.round(jnp.clip(x * s32, jnp.float32(FXP32.raw_min),
                                jnp.float32(FXP32.raw_max))) / s32
    # quant phase: affine Q_n with the *captured* (incoming) ranges
    # (2^n - 1 intervals, matching fixedpoint.affine_params' zero-exactness
    # correction — see that docstring)
    a_min = jnp.minimum(amin_ref[0], 0.0)
    a_max = jnp.maximum(amax_ref[0], 0.0)
    span = jnp.abs(a_min) + jnp.abs(a_max)
    delta = jnp.where(span > 0, span / (2.0 ** n_bits - 1.0), 1.0)
    z = jnp.round(-a_min / delta)
    q = jnp.clip(jnp.round(x / delta) + z, 0.0, float((1 << n_bits) - 1))
    y_quant = (q - z) * delta

    y_ref[...] = jnp.where(quant, y_quant, y_full)


def monitor_quant_pallas(x2: Array, a_min: Array, a_max: Array,
                         phase: Array, n_valid: Array, *, n_bits: int,
                         interpret: bool) -> tuple[Array, Array, Array]:
    """x2: (R, 128) f32 with R % 8 == 0. Scalars passed as shape-(1,) arrays."""
    r = x2.shape[0]
    assert x2.shape[1] == _BC and r % _BR == 0
    grid = (r // _BR,)

    y, nmin, nmax = pl.pallas_call(
        functools.partial(_mq_kernel, n_bits=n_bits, n_rows=r),
        grid=grid,
        in_specs=[
            pl.BlockSpec((_BR, _BC), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((_BR, _BC), lambda i: (i, 0)),
            pl.BlockSpec((_BR, _BC), lambda i: (0, 0)),
            pl.BlockSpec((_BR, _BC), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, _BC), jnp.float32),
            jax.ShapeDtypeStruct((_BR, _BC), jnp.float32),
            jax.ShapeDtypeStruct((_BR, _BC), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(x2, a_min, a_max, phase, n_valid)
    return y, nmin[0, 0], nmax[0, 0]
