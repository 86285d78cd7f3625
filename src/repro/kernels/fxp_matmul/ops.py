"""Jitted public wrapper for the dual-precision dense kernel.

`fxp_dense` pads arbitrary (M, K, N) up to block multiples, performs the
limb split, dispatches the Pallas kernel, and unpads — so callers (DDPG
networks, LM MLPs) can use it as a drop-in `x @ w + b` with a precision
switch.  On CPU we run interpret mode; on TPU the same code emits the real
Mosaic kernel (`interpret` defaults from `kernels._compat.interpret_mode`).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels._compat import interpret_mode, mlp_flops, round_up as _round_up
from repro.kernels.fxp_matmul.kernel import fxp_dense_pallas
from repro.kernels.fxp_matmul.ref import limb_split

Array = jax.Array


def _auto_blocks(m: int, k: int, n: int) -> tuple[int, int, int]:
    """MXU-aligned blocks, shrunk for small problems (DDPG layers are tiny:
    K<=421, N<=400 — one block holds the whole weight, the FPGA's
    'entire model on-chip' regime)."""
    bm = min(128, _round_up(m, 8))
    bn = min(128, _round_up(n, 128))
    bk = min(512, _round_up(k, 128))
    return bm, bn, bk


@functools.partial(jax.jit, static_argnames=("full_precision", "activation",
                                             "interpret"))
def fxp_dense(x: Array, w: Array, b: Optional[Array] = None, *,
              full_precision: bool = True, activation: str = "none",
              interpret: Optional[bool] = None) -> Array:
    """Dual-precision dense layer: act(x @ w + b) via the AAP-core kernel.

    x: (..., K) f32 — flattened to (M, K).  w: (K, N).  b: (N,) or None.
    full_precision=True  -> two-pass limb datapath (pre-delay, fxp32 regime)
    full_precision=False -> one-pass (post-delay, quantized activations)
    """
    if interpret is None:
        interpret = interpret_mode()
    orig_shape = x.shape
    k = orig_shape[-1]
    n = w.shape[-1]
    x2 = x.reshape(-1, k).astype(jnp.float32)
    m = x2.shape[0]

    bm, bn, bk = _auto_blocks(m, k, n)
    mp, kp, np_ = _round_up(m, bm), _round_up(k, bk), _round_up(n, bn)
    x2 = jnp.pad(x2, ((0, mp - m), (0, kp - k)))
    wp = jnp.pad(w.astype(jnp.float32), ((0, kp - k), (0, np_ - n)))
    bp = (None if b is None
          else jnp.pad(b.astype(jnp.float32), (0, np_ - n)).reshape(1, np_))

    # half mode only consumes the hi limb — skip the dead lo computation
    hi, lo = limb_split(x2, with_lo=full_precision)
    out = fxp_dense_pallas(hi, lo, wp, bp,
                           full_precision=full_precision,
                           activation=activation,
                           bm=bm, bn=bn, bk=bk, interpret=interpret)
    return out[:m, :n].reshape(*orig_shape[:-1], n)


def fxp_dense_chain(x: Array, weights: tuple, biases: tuple, *,
                    activations: tuple, full_precision: bool = True,
                    site_fn=None,
                    interpret: Optional[bool] = None) -> Array:
    """Serving entry point: the per-layer AAP-core kernel chain with a
    STATIC precision phase — intra-layer parallelism, one launch per layer.

    Unlike the training path (`lax.cond` on the runtime QAT phase, both
    precision kernels traced), frozen inference knows its phase at build
    time, so exactly one datapath per layer is traced and launched.
    `site_fn(i, x)`, when given, applies the frozen quantizer in front of
    layer `i` (see `core.qat.FrozenQuant.site`).
    """
    for i, (w, b, act) in enumerate(zip(weights, biases, activations)):
        if site_fn is not None:
            x = site_fn(i, x)
        x = fxp_dense(x, w, b, full_precision=full_precision,
                      activation=act, interpret=interpret)
    return x


def chain_cost_hint(dims) -> dict:
    """Dispatcher hook: launch/FLOP shape of the per-layer chain for an MLP
    with layer dims `dims` — intra-layer parallelism (each launch spreads
    one layer's output columns across the array)."""
    return {"launches": len(dims) - 1, "flops_per_item": mlp_flops(dims),
            "parallelism": "intra_layer"}
