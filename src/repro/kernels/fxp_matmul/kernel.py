"""Pallas TPU kernel: dual-precision dense layer (FIXAR AAP core, §V).

Maps the AAP core onto the TPU memory hierarchy:

  * weight memory (BRAM, shared by all cores)  -> w tile resident in VMEM,
    reused across the M grid (the grid iterates M fastest over a fixed w
    block, mirroring the weight-stationary PE array);
  * activation line buffer (512-bit broadcast)  -> x tile in VMEM, rows
    broadcast to the MXU;
  * per-column accumulators + output activation -> f32 VMEM scratch
    accumulator + fused bias/ReLU/tanh epilogue (the paper's accumulator ->
    activation-unit pipeline);
  * dual-precision datapath                      -> full mode issues TWO MXU
    passes per (m,n,k) tile (hi and lo activation limbs), half mode ONE.
    Grid and FLOPs halve exactly as the PE throughput doubles.

Block shapes default to 128x128x512 — MXU-aligned (128 lanes), and the
working set  bm*bk + 2*bk*bn + bm*bn  floats ≈ 0.9 MB « 16 MB VMEM, leaving
room for double buffering.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _dot(a, b):
    """f32 MXU contraction at full f32 precision: the default TPU precision
    would round the f32 weights to bf16."""
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _epilogue(acc, b_ref, activation: str):
    out = acc
    if b_ref is not None:
        out = out + b_ref[...]
    if activation == "relu":
        out = jnp.maximum(out, 0.0)
    elif activation == "tanh":
        out = jnp.tanh(out)
    return out


def _dense_kernel_full(x_hi_ref, x_lo_ref, w_ref, b_ref, o_ref, acc_ref, *,
                       activation: str, n_k: int):
    """Full-precision: two MAC passes per tile (the two DSP multipliers)."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = w_ref[...]
    acc_ref[...] += _dot(x_hi_ref[...], w)
    acc_ref[...] += _dot(x_lo_ref[...], w)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _done():
        o_ref[...] = _epilogue(acc_ref[...], b_ref, activation)


def _dense_kernel_half(x_ref, w_ref, b_ref, o_ref, acc_ref, *,
                       activation: str, n_k: int):
    """Half-precision: one MAC pass per tile (quantized activations)."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _dot(x_ref[...], w_ref[...])

    @pl.when(pl.program_id(2) == n_k - 1)
    def _done():
        o_ref[...] = _epilogue(acc_ref[...], b_ref, activation)


def fxp_dense_pallas(x_hi: Array, x_lo: Optional[Array], w: Array,
                     b: Optional[Array], *, full_precision: bool,
                     activation: str = "none",
                     bm: int = 128, bn: int = 128, bk: int = 512,
                     interpret: bool = False) -> Array:
    """Raw pallas_call; shapes must already be padded to block multiples.

    x_hi/x_lo: (M, K) f32 limbs. w: (K, N) f32. b: (1, N) f32 or None —
    2-D so its block is a legal (1, bn) lane-major Mosaic tile.
    """
    m, k = x_hi.shape
    k2, n = w.shape
    assert k == k2 and m % bm == 0 and n % bn == 0 and k % bk == 0, (
        f"unpadded shapes M{m} K{k} N{n} for blocks {bm}x{bn}x{bk}")
    n_k = k // bk
    grid = (m // bm, n // bn, n_k)

    x_spec = pl.BlockSpec((bm, bk), lambda i, j, s: (i, s))
    w_spec = pl.BlockSpec((bk, bn), lambda i, j, s: (s, j))
    o_spec = pl.BlockSpec((bm, bn), lambda i, j, s: (i, j))
    b_spec = pl.BlockSpec((1, bn), lambda i, j, s: (0, j))

    if full_precision:
        kern = functools.partial(_dense_kernel_full, activation=activation,
                                 n_k=n_k)
        in_specs = [x_spec, x_spec, w_spec]
        args = [x_hi, x_lo, w]
    else:
        kern = functools.partial(_dense_kernel_half, activation=activation,
                                 n_k=n_k)
        in_specs = [x_spec, w_spec]
        args = [x_hi, w]
    if b is not None:
        in_specs.append(b_spec)
        args.append(b)
    else:
        kern = functools.partial(_with_none_bias, kern)

    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*args)


def _with_none_bias(kern, *refs_and_scratch):
    """Adapt a kernel expecting (…, b_ref, o_ref, acc_ref) to bias-less call."""
    *in_refs, o_ref, acc_ref = refs_and_scratch
    return kern(*in_refs, None, o_ref, acc_ref)
