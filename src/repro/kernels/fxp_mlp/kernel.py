"""Pallas TPU kernel: N-layer dual-precision MLP forward in one call.

See the package docstring (`kernels/fxp_mlp/__init__.py`) for the design
rationale.  Layout summary:

  grid            (M_padded // bm,)        "parallel" — batch blocks
  scalar prefetch phase: (1,) i32          QAT phase flag (0 = full, 1 = quant)
  inputs          x (M, K0) blocked by row; per-layer w (Kp, Np) and
                  b (1, Np) with constant index maps (VMEM-resident);
                  deltas/zs (L,) f32 in SMEM (per-site affine params)
  outputs         y (M, NL); per-block stats (n_blocks * 8, 128): one
                  lane-dense tile per grid step, site mins in row 0 and
                  maxs in row 1 (see `_stats_tile`)
  scratch         f32 accumulator (bm, max Np)

Shapes must be pre-padded: rows to bm, every feature dim to 128 lanes.
Padding is engineered to be self-preserving: padded weight columns and bias
entries are zero, so padded activations stay exactly 0 through ReLU/tanh and
both quantizers (the affine grid contains 0 exactly — see
core/fixedpoint.affine_params), and padded rows/cols are masked out of the
range monitor with static index arithmetic.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.fixedpoint import FXP32
from repro.optim import adam as fadam
from repro.optim import fxp_adam

Array = jax.Array

# SMEM hyper-vector layout shared by the fused training-step kernels: the
# loss/soft-update scalars followed by the Adam StepConstants fields, all
# precomputed host-side (the (1-x) complements in double precision) so the
# in-kernel epilogue is bit-compatible with the host optimizer path.
_H_INVW = 0     # 1 / max(sum(w), 1) — weighted-mean denominator
_H_GAMMA = 1    # discount (critic step only)
_H_TAU = 2      # soft-update rate
_H_OMTAU = 3    # 1 - tau, double-precision-then-f32
_H_LR = 4
_H_B1 = 5
_H_OMB1 = 6     # 1 - b1
_H_B2 = 7
_H_OMB2 = 8     # 1 - b2
_H_EPS = 9
_H_BC1 = 10     # 1 - b1**t
_H_BC2 = 11     # 1 - b2**t
HYPER_LEN = 12

# Per-grid-step scalars (site extrema, loss partials) leave the kernels in
# one lane-dense f32 tile per batch block: Mosaic cannot store a scalar into
# VMEM, and a (1, L) block per step breaks the (8, 128) tiling rule once
# there is more than one block.  Row r, lane j of block b's tile holds the
# j-th value of row r; `block_stats` undoes the packing on the host side.
STATS_TILE = (8, 128)
_ROW_MIN, _ROW_MAX, _ROW_PART = 0, 1, 2


def _stats_tile(rows):
    """Pack rows of scalars into one STATS_TILE (unused entries are 0)."""
    flat = (jax.lax.broadcasted_iota(jnp.int32, STATS_TILE, 0) * STATS_TILE[1]
            + jax.lax.broadcasted_iota(jnp.int32, STATS_TILE, 1))
    tile = jnp.zeros(STATS_TILE, jnp.float32)
    for r, vals in enumerate(rows):
        for j, v in enumerate(vals):
            tile = jnp.where(flat == r * STATS_TILE[1] + j, v, tile)
    return tile


def _stats_spec():
    return pl.BlockSpec(STATS_TILE, lambda i, ph: (i, 0))


def _stats_shape(n_blocks: int):
    return jax.ShapeDtypeStruct((n_blocks * STATS_TILE[0], STATS_TILE[1]),
                                jnp.float32)


def block_stats(stats: Array, n_mins: int, n_parts: int = 0):
    """Reduce the per-block stats tiles: (site mins over blocks, site maxs
    over blocks, loss partials summed over blocks)."""
    t = stats.reshape(-1, *STATS_TILE)
    return (jnp.min(t[:, _ROW_MIN, :n_mins], axis=0),
            jnp.max(t[:, _ROW_MAX, :n_mins], axis=0),
            jnp.sum(t[:, _ROW_PART, :n_parts], axis=0))


def _dot(a, b, dims=None):
    """f32 MXU contraction at full f32 precision.  The limb datapath needs
    exact f32 products: the default TPU precision would round the f32
    weights to bf16 and break parity with the f32 reference."""
    if dims is None:
        dims = (((a.ndim - 1,), (0,)), ((), ()))
    return jax.lax.dot_general(a, b, dims,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


# contraction layouts of the backward chains
_TN = (((0,), (0,)), ((), ()))   # a^T @ b: dW = q^T g
_NT = (((1,), (1,)), ((), ()))   # a @ b^T: dx = g W^T


def _site_project(x, quant, delta, z, *, n_bits: int, fxp32_phase1: bool):
    """Algorithm-1 activation projection, selected by the phase flag.

    Matches `kernels/quantize` / `QATContext.site` value semantics exactly:
    quant phase  -> affine n-bit fake-quant with the captured ranges,
    monitor phase-> Q15.16 lattice projection (or identity if disabled).
    """
    q_max = jnp.float32((1 << n_bits) - 1)
    q = jnp.clip(jnp.round(x / delta) + z, 0.0, q_max)
    y_quant = (q - z) * delta
    if fxp32_phase1:
        s32 = jnp.float32(2.0 ** FXP32.frac_bits)
        y_full = jnp.round(jnp.clip(x * s32, jnp.float32(FXP32.raw_min),
                                    jnp.float32(FXP32.raw_max))) / s32
    else:
        y_full = x
    return jnp.where(quant, y_quant, y_full)


def _ste_site_mask(g, x_in, quant, delta, z, *, n_bits: int,
                   fxp32_phase1: bool):
    """Quantize-site backward: the straight-through clip mask of the
    phase's quantizer on the site input, shared by every backward chain.

    Each branch masks the gradient itself and the phase picks between the
    two f32 results: Mosaic cannot select between boolean masks."""
    lo = -z * delta
    hi = (jnp.float32((1 << n_bits) - 1) - z) * delta
    g_q = jnp.where(jnp.logical_and(x_in >= lo, x_in <= hi), g, 0.0)
    if fxp32_phase1:
        xs = x_in * jnp.float32(2.0 ** FXP32.frac_bits)
        g_f = jnp.where(jnp.logical_and(xs >= jnp.float32(FXP32.raw_min),
                                        xs <= jnp.float32(FXP32.raw_max)),
                        g, 0.0)
    else:
        g_f = g
    return jnp.where(quant, g_q, g_f)


class _Sites:
    """The QAT sites of one launch: the phase flag and the SMEM per-site
    affine params, indexed by global site number (actor sites first, then
    critic sites).  With qat=False every site is a pass-through."""

    def __init__(self, quant, deltas_ref, zs_ref, *, qat: bool, n_bits: int,
                 fxp32_phase1: bool):
        self.quant = quant
        self._deltas, self._zs = deltas_ref, zs_ref
        self._qat = qat
        self._kw = dict(n_bits=n_bits, fxp32_phase1=fxp32_phase1)

    def project(self, site: int, x):
        if not self._qat:
            return x
        return _site_project(x, self.quant, self._deltas[site],
                             self._zs[site], **self._kw)

    def ste(self, site: int, g, x_in):
        if not self._qat:
            return g
        return _ste_site_mask(g, x_in, self.quant, self._deltas[site],
                              self._zs[site], **self._kw)


def _monitor_minmax(x, in_dim: int, row0, m_valid: int):
    """Padding-masked (min, max) of a site input block whose first row is
    global row `row0`: pad rows (>= m_valid) and pad lanes (>= in_dim) are
    excluded."""
    row = row0 + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    valid = jnp.logical_and(row < m_valid, col < in_dim)
    return (jnp.min(jnp.where(valid, x, jnp.inf)),
            jnp.max(jnp.where(valid, x, -jnp.inf)))


def _act_fwd(out, actn: str):
    if actn == "relu":
        return jnp.maximum(out, 0.0)
    if actn == "tanh":
        return jnp.tanh(out)
    return out


def _act_bwd(g, h, actn: str):
    """Activation backward from the saved post-activation output:
    `h > 0` for ReLU, `1 - h^2` for tanh."""
    if actn == "relu":
        return jnp.where(h > 0.0, g, 0.0)
    if actn == "tanh":
        return g * (1.0 - h * h)
    return g


def _dense_fwd(x, w_ref, b_ref, acc_ref, *, actn: str, quant):
    """Dual-precision dense layer: the hi-limb dot always issues, the
    lo-limb dot is predicated off in the quantized phase.  Returns (the
    effective dense input the MACs consumed — hi only in the quantized
    phase, hi + lo == x in full precision — and the post-activation
    output block)."""
    n_out_p = w_ref.shape[1]
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    acc_ref[:, :n_out_p] = _dot(hi, w_ref[...])

    def _lo_pass():
        acc_ref[:, :n_out_p] += _dot(x - hi, w_ref[...])
    pl.when(jnp.logical_not(quant))(_lo_pass)
    out = _act_fwd(acc_ref[:, :n_out_p] + b_ref[...], actn)
    return jnp.where(quant, hi, x), out


def _net_fwd(x, wb, acc_ref, acts, sites: _Sites, site0: int, monitor=None):
    """One network's forward over a batch block: per layer, the QAT site
    (global number site0 + layer) then the dual-precision dense layer.

    wb: interleaved (w0, b0, w1, b1, ...) refs.  monitor, when given, is
    (in_dims, row0, m_valid): record each site input's padding-masked
    extrema.  Returns (y, site inputs, effective dense inputs, layer
    outputs, mins, maxs) — the backward chain's residuals.
    """
    ss, qeffs, hs, mins, maxs = [], [], [], [], []
    for li, actn in enumerate(acts):
        if monitor is not None:
            in_dims, row0, m_valid = monitor
            mn, mx = _monitor_minmax(x, in_dims[li], row0, m_valid)
            mins.append(mn)
            maxs.append(mx)
        ss.append(x)
        x = sites.project(site0 + li, x)
        qe, x = _dense_fwd(x, wb[2 * li], wb[2 * li + 1], acc_ref, actn=actn,
                           quant=sites.quant)
        qeffs.append(qe)
        hs.append(x)
    return x, ss, qeffs, hs, mins, maxs


def _net_bwd(g, w_refs, ss, qeffs, hs, acts, sites: _Sites, site0: int,
             dw_refs=None, db_refs=None):
    """One network's backward over a batch block, layers last to first:
    activation backward, dW/db accumulated into dw_refs/db_refs (when
    given), g @ W^T, then the STE clip mask of the layer's site.  Returns
    the cotangent of the network input."""
    for li in reversed(range(len(acts))):
        g = _act_bwd(g, hs[li], acts[li])
        if dw_refs is not None:
            db_refs[li][...] += jnp.sum(g, axis=0, keepdims=True)
            dw_refs[li][...] += _dot(qeffs[li], g, _TN)
        g = _dot(g, w_refs[li][...], _NT)
        g = sites.ste(site0 + li, g, ss[li])
    return g


def _mlp_kernel(phase_ref, *refs, n_layers: int, bm: int, m_valid: int,
                in_dims: Sequence[int], activations: Sequence[str],
                n_bits: int, qat: bool, fxp32_phase1: bool,
                save_residuals: bool = False):
    x_ref = refs[0]
    wb_refs = refs[1:1 + 2 * n_layers]
    deltas_ref = refs[1 + 2 * n_layers]
    zs_ref = refs[2 + 2 * n_layers]
    y_ref, stats_ref = refs[3 + 2 * n_layers:5 + 2 * n_layers]
    acc_ref = refs[-1]

    sites = _Sites(phase_ref[0] > 0, deltas_ref, zs_ref, qat=qat,
                   n_bits=n_bits, fxp32_phase1=fxp32_phase1)
    monitor = (in_dims, pl.program_id(0) * bm, m_valid)
    y, _, qeffs, hs, mins, maxs = _net_fwd(x_ref[...], wb_refs, acc_ref,
                                           activations, sites, 0, monitor)
    y_ref[...] = y
    stats_ref[...] = _stats_tile([mins, maxs])
    if save_residuals:
        # training-mode extra outputs: per-layer effective dense inputs and
        # the intermediate layer outputs (the backward kernel's residuals)
        q_refs = refs[5 + 2 * n_layers:5 + 3 * n_layers]
        h_refs = refs[5 + 3 * n_layers:4 + 4 * n_layers]
        for ref, v in zip(q_refs, qeffs):
            ref[...] = v
        for ref, v in zip(h_refs, hs):
            ref[...] = v


def fxp_mlp_pallas(phase: Array, x: Array, weights: Sequence[Array],
                   biases: Sequence[Array], deltas: Array, zs: Array, *,
                   activations: Sequence[str], in_dims: Sequence[int],
                   m_valid: int, bm: int, n_bits: int, qat: bool,
                   fxp32_phase1: bool, interpret: bool,
                   save_residuals: bool = False):
    """Raw pallas_call; shapes must already be padded (see module docstring).

    phase: (1,) i32 scalar-prefetch flag.  x: (Mp, K0p) f32.
    weights[i]: (Kp_i, Np_i) f32, biases[i]: (1, Np_i) f32.
    deltas/zs: (L,) f32 per-site affine params (ignored when qat=False).
    Returns (y (Mp, NLp), stats (n_blocks * 8, 128)) — reduce stats with
    `block_stats(stats, L)` for the per-site extrema; with
    save_residuals=True additionally the per-layer effective dense inputs
    qs[i] (Mp, Kp_i) and intermediate outputs hs[i] (Mp, Np_i), i < L-1 —
    the VMEM-resident residuals `fxp_mlp_bwd_pallas` consumes.
    """
    n_layers = len(weights)
    mp, k0p = x.shape
    assert mp % bm == 0 and k0p == weights[0].shape[0]
    for i in range(n_layers - 1):
        assert weights[i].shape[1] == weights[i + 1].shape[0], (
            f"layer {i}->{i + 1} padded dims disagree")
    n_blocks = mp // bm
    nlp = weights[-1].shape[1]
    max_np = max(w.shape[1] for w in weights)

    in_specs = [pl.BlockSpec((bm, k0p), lambda i, ph: (i, 0))]
    args = [x]
    for w, b in zip(weights, biases):
        # constant index maps: weight/bias blocks revisit (0, 0) every grid
        # step, so Pallas keeps them VMEM-resident across the whole call
        in_specs.append(pl.BlockSpec(w.shape, lambda i, ph: (0, 0)))
        in_specs.append(pl.BlockSpec(b.shape, lambda i, ph: (0, 0)))
        args.extend((w, b))
    in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))  # deltas
    in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))  # zs
    args.extend((deltas, zs))

    out_specs = [pl.BlockSpec((bm, nlp), lambda i, ph: (i, 0)), _stats_spec()]
    out_shape = [jax.ShapeDtypeStruct((mp, nlp), jnp.float32),
                 _stats_shape(n_blocks)]
    if save_residuals:
        for w in weights:                                   # qs
            out_specs.append(pl.BlockSpec((bm, w.shape[0]),
                                          lambda i, ph: (i, 0)))
            out_shape.append(jax.ShapeDtypeStruct((mp, w.shape[0]),
                                                  jnp.float32))
        for w in weights[:-1]:                              # hs (mid layers)
            out_specs.append(pl.BlockSpec((bm, w.shape[1]),
                                          lambda i, ph: (i, 0)))
            out_shape.append(jax.ShapeDtypeStruct((mp, w.shape[1]),
                                                  jnp.float32))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_blocks,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((bm, max_np), jnp.float32)],
    )
    kern = functools.partial(
        _mlp_kernel, n_layers=n_layers, bm=bm, m_valid=m_valid,
        in_dims=tuple(in_dims), activations=tuple(activations),
        n_bits=n_bits, qat=qat, fxp32_phase1=fxp32_phase1,
        save_residuals=save_residuals)
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(phase, *args)


def _mlp_bwd_kernel(phase_ref, *refs, n_layers: int,
                    activations: Sequence[str], n_bits: int, qat: bool,
                    fxp32_phase1: bool):
    """Whole-network backward in one launch: the dx/dW/db chain, layers
    unrolled last-to-first, weights and saved activations VMEM-resident.

    Gradient semantics mirror what `jax.grad` produces through the oracle
    forward (`kernels/fxp_mlp/ref.ref_fxp_mlp`): straight-through estimators
    across the quantize sites (identity inside the clip range, zero outside —
    the `fake_quant*` clip gradient), STE across the bf16 hi-limb rounding,
    `h > 0` for ReLU and `1 - h^2` for tanh from the saved post-activation
    outputs.  dW contracts the cotangent against the *effective* dense input
    the MACs consumed (hi limb only in the quantized phase), saved by the
    forward as `qs`.
    """
    g_ref = refs[0]
    x0_ref = refs[1]
    w_refs = refs[2:2 + n_layers]
    q_refs = refs[2 + n_layers:2 + 2 * n_layers]
    h_refs = refs[2 + 2 * n_layers:2 + 3 * n_layers]  # h[L-1] is padded y
    deltas_ref = refs[2 + 3 * n_layers]
    zs_ref = refs[3 + 3 * n_layers]
    dx_ref = refs[4 + 3 * n_layers]
    dw_refs = refs[5 + 3 * n_layers:5 + 4 * n_layers]
    db_refs = refs[5 + 4 * n_layers:5 + 5 * n_layers]

    @pl.when(pl.program_id(0) == 0)
    def _zero_accumulators():
        for li in range(n_layers):
            dw_refs[li][...] = jnp.zeros_like(dw_refs[li])
            db_refs[li][...] = jnp.zeros_like(db_refs[li])

    sites = _Sites(phase_ref[0] > 0, deltas_ref, zs_ref, qat=qat,
                   n_bits=n_bits, fxp32_phase1=fxp32_phase1)
    hs = [r[...] for r in h_refs]
    ss = [x0_ref[...]] + hs[:-1]   # each site's input: the previous output
    dx_ref[...] = _net_bwd(g_ref[...], w_refs, ss,
                           [r[...] for r in q_refs], hs, activations, sites,
                           0, dw_refs, db_refs)


def fxp_mlp_bwd_pallas(phase: Array, g: Array, x0: Array,
                       weights: Sequence[Array], qs: Sequence[Array],
                       hs: Sequence[Array], deltas: Array, zs: Array, *,
                       activations: Sequence[str], bm: int, n_bits: int,
                       qat: bool, fxp32_phase1: bool, interpret: bool
                       ) -> tuple[Array, list, list]:
    """Raw backward pallas_call over pre-padded shapes.

    phase: (1,) i32 prefetch flag.  g: (Mp, NLp) cotangent of the padded y
    (zero in padded rows/cols, so padding self-preserves through the whole
    backward chain).  x0: (Mp, K0p) padded layer-0 site input.
    qs[i]/hs[i]: the forward's saved residuals (hs[L-1] = padded y).
    Returns (dx (Mp, K0p), [dW_i (Kp_i, Np_i)], [db_i (1, Np_i)]).

    dW/db are accumulated across batch blocks into constant-index output
    blocks, so the grid dimension is "arbitrary" (sequential), not parallel.
    """
    n_layers = len(weights)
    mp, k0p = x0.shape
    assert mp % bm == 0 and g.shape == (mp, weights[-1].shape[1])
    n_blocks = mp // bm

    in_specs = [
        pl.BlockSpec((bm, g.shape[1]), lambda i, ph: (i, 0)),
        pl.BlockSpec((bm, k0p), lambda i, ph: (i, 0)),
    ]
    args = [g, x0]
    for w in weights:
        in_specs.append(pl.BlockSpec(w.shape, lambda i, ph: (0, 0)))
        args.append(w)
    for q in qs:
        in_specs.append(pl.BlockSpec((bm, q.shape[1]), lambda i, ph: (i, 0)))
        args.append(q)
    for h in hs:
        in_specs.append(pl.BlockSpec((bm, h.shape[1]), lambda i, ph: (i, 0)))
        args.append(h)
    in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))  # deltas
    in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))  # zs
    args.extend((deltas, zs))

    out_specs = [pl.BlockSpec((bm, k0p), lambda i, ph: (i, 0))]
    out_shape = [jax.ShapeDtypeStruct((mp, k0p), jnp.float32)]
    for w in weights:   # dW accumulators: constant index map, VMEM-resident
        out_specs.append(pl.BlockSpec(w.shape, lambda i, ph: (0, 0)))
        out_shape.append(jax.ShapeDtypeStruct(w.shape, jnp.float32))
    for w in weights:   # db accumulators
        out_specs.append(pl.BlockSpec((1, w.shape[1]), lambda i, ph: (0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((1, w.shape[1]), jnp.float32))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_blocks,),
        in_specs=in_specs,
        out_specs=out_specs,
    )
    kern = functools.partial(
        _mlp_bwd_kernel, n_layers=n_layers,
        activations=tuple(activations), n_bits=n_bits, qat=qat,
        fxp32_phase1=fxp32_phase1)
    outs = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(phase, *args)
    dx = outs[0]
    dws = list(outs[1:1 + n_layers])
    dbs = list(outs[1 + n_layers:1 + 2 * n_layers])
    return dx, dws, dbs


# ---------------------------------------------------------------------------
# Fused DDPG training step: fwd + bwd + Adam + soft update, two launches
# ---------------------------------------------------------------------------


def _append_lanes(x, a, offset: int):
    """In-kernel lane concat [x, a]: a's leading lanes moved to start at
    lane `offset`.  Exact when x is zero from `offset` on and a is zero past
    its own width (the padding contract), so the critic's first layer sees
    the same (bm, 128) input — and runs the same dot — as the host-side
    concat of the custom-VJP path."""
    return x + pltpu.roll(a, offset, 1)


def _take_lanes(g, offset: int, width: int):
    """Inverse of `_append_lanes`: lanes offset..offset+width-1 of g moved
    to lanes 0..width-1, every other lane zero."""
    lanes = g.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, g.shape, 1)
    return jnp.where(col < width, pltpu.roll(g, lanes - offset, 1), 0.0)


def _adam_soft_epilogue(hyper_ref, p_ref, g, m_ref, v_ref, t_ref,
                        out_p_ref, out_m_ref, out_v_ref, out_t_ref, *,
                        fxp_weights: bool):
    """One parameter leaf of the in-kernel weight update: Adam from the
    SMEM-shipped StepConstants (grad + param projected onto Q15.16 when
    fxp_weights, via the optimizer's own `leaf_update` — one source of
    truth with the host path), then the target net's soft update from the
    freshly written param.  Padding self-preserves: pad entries have
    p = g = m = v = t = 0, and Adam/soft-update map zeros to zeros.
    """
    c = fadam.StepConstants(
        lr=hyper_ref[_H_LR], b1=hyper_ref[_H_B1],
        one_minus_b1=hyper_ref[_H_OMB1], b2=hyper_ref[_H_B2],
        one_minus_b2=hyper_ref[_H_OMB2], eps=hyper_ref[_H_EPS],
        bc1=hyper_ref[_H_BC1], bc2=hyper_ref[_H_BC2])
    if fxp_weights:
        # ste=False: the value-identical projection without the custom_vjp
        # wrapper (which cannot lower inside a kernel body)
        p2, m2, v2 = fxp_adam.leaf_update(p_ref[...], g, m_ref[...],
                                          v_ref[...], c, ste=False)
    else:
        p2, m2, v2 = fadam.leaf_update(p_ref[...], g, m_ref[...],
                                       v_ref[...], c)
    out_p_ref[...] = p2
    out_m_ref[...] = m2
    out_v_ref[...] = v2
    out_t_ref[...] = (hyper_ref[_H_OMTAU] * t_ref[...]
                      + hyper_ref[_H_TAU] * p2)


def _step_epilogue(hyper_ref, p_wb, dw_refs, db_refs, m_wb, v_wb, t_wb,
                   outs, *, fxp_weights: bool):
    """Adam + target soft update over every leaf of one network, from the
    grads accumulated across all batch blocks."""
    out_p, out_m, out_v, out_t = outs
    grads = [r[...] for pair in zip(dw_refs, db_refs) for r in pair]
    for k, g in enumerate(grads):   # interleaved (w0, b0, w1, b1, ...)
        _adam_soft_epilogue(hyper_ref, p_wb[k], g, m_wb[k], v_wb[k], t_wb[k],
                            out_p[k], out_m[k], out_v[k], out_t[k],
                            fxp_weights=fxp_weights)


def _ddpg_critic_step_kernel(phase_ref, *refs, n_layers: int, bm: int,
                             m_valid: int, obs_dim: int, actor_acts,
                             critic_acts, critic_in_dims, n_bits: int,
                             qat: bool, fxp32_phase1: bool,
                             fxp_weights: bool, n_blocks: int):
    """Launch 1 of the fused DDPG step: the whole critic BP/WU.

    Per batch block: target-actor fwd on next_obs (no monitors — the host
    update discards target-pass observations), target-critic fwd on the
    in-kernel concat (next_obs, next_a), TD target y, online-critic fwd with
    range monitors and VMEM-local residuals, the weighted-MSE cotangent, and
    the full dx/dW/db backward chain with dW/db accumulated in VMEM scratch
    across blocks ("arbitrary" grid).  On the LAST block the epilogue runs
    Adam over the accumulated grads and soft-updates the target critic —
    params never leave the launch between BP and WU.
    """
    L = n_layers
    pos = 0

    def take(k):
        nonlocal pos
        out = refs[pos:pos + k]
        pos += k
        return out

    xc_ref, nobs_ref, aux_ref = take(3)
    at_wb = take(2 * L)
    ct_wb = take(2 * L)
    c_wb = take(2 * L)
    m_wb = take(2 * L)
    v_wb = take(2 * L)
    deltas_ref, zs_ref, hyper_ref = take(3)
    outs = [take(2 * L) for _ in range(4)]   # params, m, v, targets
    stats_ref, = take(1)
    acc_ref, = take(1)
    dw_refs = take(L)
    db_refs = take(L)
    assert pos == len(refs)

    i = pl.program_id(0)
    sites = _Sites(phase_ref[0] > 0, deltas_ref, zs_ref, qat=qat,
                   n_bits=n_bits, fxp32_phase1=fxp32_phase1)

    @pl.when(i == 0)
    def _zero_accumulators():
        for li in range(L):
            dw_refs[li][...] = jnp.zeros_like(dw_refs[li])
            db_refs[li][...] = jnp.zeros_like(db_refs[li])

    nobs = nobs_ref[...]
    reward = aux_ref[:, 0:1]
    done = aux_ref[:, 1:2]
    w = aux_ref[:, 2:3]

    # ---- targets: target actor on next_obs, target critic on the concat ---
    next_a, *_ = _net_fwd(nobs, at_wb, acc_ref, actor_acts, sites, 0)
    q_next, *_ = _net_fwd(_append_lanes(nobs, next_a, obs_dim), ct_wb,
                          acc_ref, critic_acts, sites, L)
    y = reward + (hyper_ref[_H_GAMMA] * (1.0 - done)) * q_next[:, 0:1]

    # ---- online critic forward: monitors + VMEM-local residuals -----------
    q, ss, qeffs, hs, mins, maxs = _net_fwd(
        xc_ref[...], c_wb, acc_ref, critic_acts, sites, L,
        monitor=(critic_in_dims, i * bm, m_valid))
    q = q[:, 0:1]

    # ---- loss partials (host divides by sum(w) once) ----------------------
    diff = q - y
    parts = [jnp.sum(w * (diff * diff)),   # sum w * (q - y)^2
             jnp.sum(w * y)]               # sum w * y  (q_mean)
    stats_ref[...] = _stats_tile([mins, maxs, parts])

    # ---- backward: weighted-mean MSE cotangent, then the dW/db/dx chain ---
    # d closs / dq = (w / sum_w) * 2 (q - y) — exactly XLA's transpose of
    # _wmean(square(q - y), w); pad rows carry w = 0 so their gradient
    # contribution is exactly zero
    dval = (hyper_ref[_H_INVW] * w) * (2.0 * diff)
    col_l = jax.lax.broadcasted_iota(jnp.int32, hs[-1].shape, 1)
    g = jnp.where(col_l == 0, dval, 0.0)
    _net_bwd(g, c_wb[0::2], ss, qeffs, hs, critic_acts, sites, L,
             dw_refs, db_refs)

    # ---- epilogue on the last block: Adam + target soft update ------------
    @pl.when(i == n_blocks - 1)
    def _epilogue():
        _step_epilogue(hyper_ref, c_wb, dw_refs, db_refs, m_wb, v_wb, ct_wb,
                       outs, fxp_weights=fxp_weights)


def _ddpg_actor_step_kernel(phase_ref, *refs, n_layers: int, bm: int,
                            m_valid: int, obs_dim: int, act_dim: int,
                            actor_acts, critic_acts, actor_in_dims,
                            critic_in_dims, n_bits: int, qat: bool,
                            fxp32_phase1: bool, fxp_weights: bool,
                            n_blocks: int):
    """Launch 2 of the fused DDPG step: the whole actor BP/WU.

    Actor fwd with monitors/residuals, the UPDATED critic's fwd on the
    in-kernel concat (obs, actor(obs)) with critic-site monitors, the
    policy-gradient cotangent dq = -w/sum_w, a dx-only backward through the
    critic (STE at its sites), then the actor's dW/db chain accumulated
    across blocks and the same Adam + soft-update epilogue on the last
    block.
    """
    L = n_layers
    pos = 0

    def take(k):
        nonlocal pos
        out = refs[pos:pos + k]
        pos += k
        return out

    obs_ref, aux_ref = take(2)
    a_wb = take(2 * L)
    m_wb = take(2 * L)
    v_wb = take(2 * L)
    at_wb = take(2 * L)                  # actor target (soft-update operand)
    c_wb = take(2 * L)                   # the critic launch 1 just updated
    deltas_ref, zs_ref, hyper_ref = take(3)
    outs = [take(2 * L) for _ in range(4)]   # params, m, v, targets
    stats_ref, = take(1)
    acc_ref, = take(1)
    dw_refs = take(L)
    db_refs = take(L)
    assert pos == len(refs)

    i = pl.program_id(0)
    sites = _Sites(phase_ref[0] > 0, deltas_ref, zs_ref, qat=qat,
                   n_bits=n_bits, fxp32_phase1=fxp32_phase1)

    @pl.when(i == 0)
    def _zero_accumulators():
        for li in range(L):
            dw_refs[li][...] = jnp.zeros_like(dw_refs[li])
            db_refs[li][...] = jnp.zeros_like(db_refs[li])

    obs = obs_ref[...]
    w = aux_ref[:, 2:3]

    # ---- actor forward, then the updated critic on (obs, a) ---------------
    a, a_ss, a_qs, a_hs, mins, maxs = _net_fwd(
        obs, a_wb, acc_ref, actor_acts, sites, 0,
        monitor=(actor_in_dims, i * bm, m_valid))
    q, c_ss, _, c_hs, c_mins, c_maxs = _net_fwd(
        _append_lanes(obs, a, obs_dim), c_wb, acc_ref, critic_acts, sites, L,
        monitor=(critic_in_dims, i * bm, m_valid))
    # aloss = -(sum w q) / sum_w, on host
    stats_ref[...] = _stats_tile([mins + c_mins, maxs + c_maxs,
                                  [jnp.sum(w * q[:, 0:1])]])

    # ---- backward: policy-gradient cotangent, dx-only through the critic --
    dval = (-hyper_ref[_H_INVW]) * w
    col_l = jax.lax.broadcasted_iota(jnp.int32, c_hs[-1].shape, 1)
    g = jnp.where(col_l == 0, dval, 0.0)
    g = _net_bwd(g, c_wb[0::2], c_ss, None, c_hs, critic_acts, sites, L)
    # da: the action lanes of the concat input's cotangent
    g = _take_lanes(g, obs_dim, act_dim)

    # ---- actor backward with dW/db accumulation ---------------------------
    _net_bwd(g, a_wb[0::2], a_ss, a_qs, a_hs, actor_acts, sites, 0,
             dw_refs, db_refs)

    @pl.when(i == n_blocks - 1)
    def _epilogue():
        _step_epilogue(hyper_ref, a_wb, dw_refs, db_refs, m_wb, v_wb, at_wb,
                       outs, fxp_weights=fxp_weights)


def _const_spec(a):
    return pl.BlockSpec(a.shape, lambda i, ph: (0, 0))


def _batch_spec(bm, a):
    return pl.BlockSpec((bm, a.shape[1]), lambda i, ph: (i, 0))


def _step_call(kern, phase, batch, consts, smem, p_wb, updated, *, bm: int,
               interpret: bool, name: str):
    """The pallas_call shared by both step launches: batch arrays blocked
    by row, every parameter leaf VMEM-resident (constant index map), the
    SMEM scalars, and as outputs the new params, moments and targets of
    the trained net plus the per-block stats tiles.  `consts` is groups of
    len(p_wb) leaves; `updated` names, for the new params, moments and
    targets in turn, the group each one replaces, and each output is
    aliased to its group's buffers: updated in place, so a caller that
    carries them (as the training loop's scan does) gets no copies around
    the launch.  Every input leaf is read before its aliased output is
    written (the epilogue reads a leaf's p, m, v, t and then writes them).
    `name` names the launch's instruction in the compiled program, and so
    in a device trace: `%fxp_mlp_train_step_critic.N`,
    `%fxp_mlp_train_step_actor.N`."""
    n_blocks = batch[0].shape[0] // bm
    n = len(p_wb)
    first = 1 + len(batch)    # the scalar-prefetch phase, then the batch
    aliases = {first + g * n + j: k * n + j
               for k, g in enumerate(updated) for j in range(n)}
    max_np = max(a.shape[1] for a in consts)
    args = [*batch, *consts, *smem]
    in_specs = ([_batch_spec(bm, a) for a in batch]
                + [_const_spec(a) for a in consts]
                + [pl.BlockSpec(memory_space=pltpu.SMEM)] * len(smem))
    out_specs = [_const_spec(a) for _ in range(4) for a in p_wb]
    out_shape = [jax.ShapeDtypeStruct(a.shape, jnp.float32)
                 for _ in range(4) for a in p_wb]
    out_specs.append(_stats_spec())
    out_shape.append(_stats_shape(n_blocks))
    ws = p_wb[0::2]
    scratch = ([pltpu.VMEM((bm, max_np), jnp.float32)]
               + [pltpu.VMEM(w.shape, jnp.float32) for w in ws]
               + [pltpu.VMEM((1, w.shape[1]), jnp.float32) for w in ws])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_blocks,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    outs = pl.pallas_call(
        functools.partial(kern, n_blocks=n_blocks),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=32 * 2**20),
        interpret=interpret,
        name=name,
        input_output_aliases=aliases,
    )(phase, *args)
    return ([list(outs[k * n:(k + 1) * n]) for k in range(4)]
            + [outs[4 * n]])


def ddpg_critic_step_pallas(phase, xc, nobs, aux, at_wb, ct_wb, c_wb, m_wb,
                            v_wb, deltas, zs, hyper, *, obs_dim: int,
                            actor_acts, critic_acts, critic_in_dims,
                            m_valid: int, bm: int, n_bits: int, qat: bool,
                            fxp32_phase1: bool, fxp_weights: bool,
                            interpret: bool):
    """Launch 1 pallas_call: fused critic fwd+bwd+Adam+soft-update.

    All shapes pre-padded.  xc (Mp, 128) concat(obs, act); nobs (Mp, 128);
    aux (Mp, 128) with [reward, done, w] in cols 0..2.  at_wb / ct_wb /
    c_wb / m_wb / v_wb: interleaved (w0, b0, w1, b1, ...) padded leaves of
    the target actor, target critic, critic and its Adam moments.
    deltas/zs: (2L,) f32 SMEM (actor sites then critic sites); hyper:
    (HYPER_LEN,) f32 SMEM (see the layout constants above).

    Returns (new_c_wb, new_m_wb, new_v_wb, new_ct_wb, stats): the stats
    tiles hold the L critic-site extrema and the partials
    [sum w*(q-y)^2, sum w*y] (`block_stats(stats, L, 2)`).
    """
    kern = functools.partial(
        _ddpg_critic_step_kernel, n_layers=len(c_wb) // 2, bm=bm,
        m_valid=m_valid, obs_dim=obs_dim, actor_acts=tuple(actor_acts),
        critic_acts=tuple(critic_acts), critic_in_dims=tuple(critic_in_dims),
        n_bits=n_bits, qat=qat, fxp32_phase1=fxp32_phase1,
        fxp_weights=fxp_weights)
    return _step_call(kern, phase, (xc, nobs, aux),
                      (*at_wb, *ct_wb, *c_wb, *m_wb, *v_wb),
                      (deltas, zs, hyper), c_wb, (2, 3, 4, 1), bm=bm,
                      interpret=interpret,
                      name="fxp_mlp_train_step_critic")


def ddpg_actor_step_pallas(phase, obs, aux, a_wb, m_wb, v_wb, at_wb, c_wb,
                           deltas, zs, hyper, *, obs_dim: int, act_dim: int,
                           actor_acts, critic_acts, actor_in_dims,
                           critic_in_dims, m_valid: int, bm: int,
                           n_bits: int, qat: bool, fxp32_phase1: bool,
                           fxp_weights: bool, interpret: bool):
    """Launch 2 pallas_call: fused actor fwd+bwd+Adam+soft-update through
    the freshly updated critic (c_wb: launch 1's new params).

    Returns (new_a_wb, new_m_wb, new_v_wb, new_at_wb, stats): the stats
    tiles hold 2L site extrema (lanes 0..L-1 actor sites, L..2L-1 the
    critic sites as seen by the actor-loss pass) and the partial sum w*q
    (`block_stats(stats, 2 * L, 1)`).
    """
    kern = functools.partial(
        _ddpg_actor_step_kernel, n_layers=len(a_wb) // 2, bm=bm,
        m_valid=m_valid, obs_dim=obs_dim, act_dim=act_dim,
        actor_acts=tuple(actor_acts), critic_acts=tuple(critic_acts),
        actor_in_dims=tuple(actor_in_dims),
        critic_in_dims=tuple(critic_in_dims), n_bits=n_bits, qat=qat,
        fxp32_phase1=fxp32_phase1, fxp_weights=fxp_weights)
    return _step_call(kern, phase, (obs, aux),
                      (*a_wb, *m_wb, *v_wb, *at_wb, *c_wb),
                      (deltas, zs, hyper), a_wb, (0, 1, 2, 3), bm=bm,
                      interpret=interpret,
                      name="fxp_mlp_train_step_actor")
