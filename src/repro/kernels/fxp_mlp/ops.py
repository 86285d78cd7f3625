"""Jitted public wrappers for the network-resident fused MLP kernel.

`fxp_mlp_forward` pads the batch and every feature dimension to TPU tiles,
dispatches the single fused Pallas kernel, unpads the result, and reduces the
per-block range-monitor outputs to one (min, max) pair per QAT site — so a
caller gets the whole actor/critic forward, QAT sites included, from ONE
kernel launch instead of 2L+ (L dense + L quantize sweeps).

`fxp_mlp_train` is the differentiable face of the same kernel: a
`jax.custom_vjp` whose primal IS the fused forward (one launch, no residual
traffic when nothing differentiates through it), whose fwd rule re-runs the
kernel with `save_residuals=True` (per-layer effective dense inputs + saved
activations stay network-resident), and whose bwd rule is a SECOND
network-resident Pallas launch (`fxp_mlp_bwd_pallas`) running the whole
dW/db/dx chain with straight-through estimators at the fused QAT sites.  So
one DDPG loss evaluation trains through exactly two launches: fwd + bwd.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.kernels._compat import interpret_mode, mlp_flops, round_up as _round_up
from repro.kernels.fxp_mlp.kernel import (
    HYPER_LEN, block_stats, ddpg_actor_step_pallas, ddpg_critic_step_pallas,
    fxp_mlp_bwd_pallas, fxp_mlp_pallas)

Array = jax.Array


def _row_block(m: int) -> int:
    """Batch row-block policy — the ONE place fwd padding and the bwd
    launch must agree on (the VJP bwd re-derives bm from the cotangent's
    row count with this same function)."""
    return min(128, _round_up(m, 8))


def pad_weight(w: Array) -> Array:
    """A (K, N) weight in the kernels' layout: (round_up(K, 128),
    round_up(N, 128)) float32, zero-filled."""
    k, n = w.shape
    return jnp.pad(w.astype(jnp.float32),
                   ((0, _round_up(k, 128) - k), (0, _round_up(n, 128) - n)))


def pad_bias(b: Array) -> Array:
    """An (N,) bias in the kernels' layout: (1, round_up(N, 128)) float32,
    zero-filled."""
    n = b.shape[0]
    return jnp.pad(b.astype(jnp.float32),
                   (0, _round_up(n, 128) - n)).reshape(1, -1)


def _pad_x(x: Array):
    """Pad the batch to bm rows and its features to 128 lanes.

    Returns (x2 padded (Mp, K0p), m valid rows, bm row-block)."""
    k0 = x.shape[-1]
    x2 = x.reshape(-1, k0).astype(jnp.float32)
    m = x2.shape[0]
    bm = _row_block(m)
    mp = _round_up(m, bm)
    return jnp.pad(x2, ((0, mp - m), (0, _round_up(k0, 128) - k0))), m, bm


def _pad_net(x: Array, weights: Sequence[Array], biases: Sequence[Array]):
    """Pad the batch to bm rows and every feature dim to 128 lanes.

    Returns (x2 padded (Mp, K0p), padded weights, padded (1, Np) biases,
    m valid rows, bm row-block).
    """
    x2, m, bm = _pad_x(x)
    return (x2, tuple(pad_weight(w) for w in weights),
            tuple(pad_bias(b) for b in biases), m, bm)


def _norm_quant_params(deltas, zs, n_layers: int, qat: bool):
    if not qat:
        return (jnp.ones((n_layers,), jnp.float32),
                jnp.zeros((n_layers,), jnp.float32))
    if deltas is None or zs is None:
        raise ValueError(
            "qat=True requires both deltas and zs (from "
            "QATContext.site_quant_params); pass qat=False for the "
            "site-free pipeline")
    return (jnp.asarray(deltas, jnp.float32).reshape(n_layers),
            jnp.asarray(zs, jnp.float32).reshape(n_layers))


@functools.partial(jax.jit, static_argnames=("activations", "n_bits", "qat",
                                             "fxp32_phase1", "interpret"))
def fxp_mlp_forward(x: Array, weights: tuple, biases: tuple,
                    deltas: Optional[Array] = None,
                    zs: Optional[Array] = None, *,
                    activations: Sequence[str], quant_phase: Array,
                    n_bits: int = 16, qat: bool = True,
                    fxp32_phase1: bool = True,
                    interpret: Optional[bool] = None
                    ) -> tuple[Array, Array, Array]:
    """Fused L-layer MLP forward with inline QAT sites.

    x: (..., K0) f32.  weights[i]: (K_i, N_i), biases[i]: (N_i,).
    activations[i] in {"relu", "tanh", "none"} — fused epilogue per layer.
    quant_phase: boolean scalar, the Algorithm-1 phase flag (False = monitor/
    full precision, True = quantized/half precision).
    deltas/zs: (L,) f32 per-site affine quantization params (from
    `QATContext.site_quant_params`); ignored when qat=False.

    Returns (y, site_mins, site_maxs): y is (..., N_L); site_mins/maxs are
    (L,) exact extrema of each layer's (pre-quantization) input — feed them
    to `QATContext.observe` to keep range monitoring identical to the
    per-layer path.
    """
    n_layers = len(weights)
    assert n_layers == len(biases) == len(activations), (
        f"{n_layers} weights vs {len(biases)} biases vs "
        f"{len(activations)} activations")
    if interpret is None:
        interpret = interpret_mode()

    orig_shape = x.shape
    n_out = weights[-1].shape[-1]
    in_dims = tuple(int(w.shape[0]) for w in weights)
    assert in_dims[0] == orig_shape[-1]
    x2, wp, bp, m, bm = _pad_net(x, weights, biases)
    deltas, zs = _norm_quant_params(deltas, zs, n_layers, qat)
    phase = jnp.asarray(quant_phase, jnp.int32).reshape(1)

    y, stats = fxp_mlp_pallas(
        phase, x2, wp, bp, deltas, zs,
        activations=tuple(activations), in_dims=in_dims, m_valid=m, bm=bm,
        n_bits=n_bits, qat=qat, fxp32_phase1=fxp32_phase1,
        interpret=interpret)

    y = y[:m, :n_out].reshape(*orig_shape[:-1], n_out)
    mins, maxs, _ = block_stats(stats, n_layers)
    return y, mins, maxs


class _TrainSpec(NamedTuple):
    """Hashable statics threaded through the custom VJP as a nondiff arg."""

    activations: tuple
    dims: tuple          # unpadded layer dims (K0, N1, ..., NL)
    n_bits: int
    qat: bool
    fxp32_phase1: bool
    interpret: bool
    padded: bool         # weights and biases arrive in the kernels' layout


def _train_fwd_call(spec: _TrainSpec, phase_f, x, weights, biases,
                    deltas, zs, save_residuals: bool):
    if spec.padded:
        (x2, m, bm), wp, bp = _pad_x(x), tuple(weights), tuple(biases)
    else:
        x2, wp, bp, m, bm = _pad_net(x, weights, biases)
    phase = (phase_f > 0).astype(jnp.int32).reshape(1)
    outs = fxp_mlp_pallas(
        phase, x2, wp, bp, deltas, zs,
        activations=spec.activations, in_dims=spec.dims[:-1],
        m_valid=m, bm=bm, n_bits=spec.n_bits, qat=spec.qat,
        fxp32_phase1=spec.fxp32_phase1, interpret=spec.interpret,
        save_residuals=save_residuals)
    yp, stats = outs[:2]
    n_out = spec.dims[-1]
    y = yp[:m, :n_out].reshape(*x.shape[:-1], n_out)
    site_mins, site_maxs, _ = block_stats(stats, len(weights))
    return y, site_mins, site_maxs, yp, x2, wp, outs[2:], m, bm


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mlp_train_core(spec: _TrainSpec, phase_f, x, weights, biases,
                    deltas, zs):
    y, site_mins, site_maxs, *_ = _train_fwd_call(
        spec, phase_f, x, weights, biases, deltas, zs, save_residuals=False)
    return y, site_mins, site_maxs


def _mlp_train_core_fwd(spec: _TrainSpec, phase_f, x, weights, biases,
                        deltas, zs):
    y, site_mins, site_maxs, yp, x2, wp, res_outs, m, bm = _train_fwd_call(
        spec, phase_f, x, weights, biases, deltas, zs, save_residuals=True)
    n_layers = len(weights)
    qs = tuple(res_outs[:n_layers])
    hs = tuple(res_outs[n_layers:]) + (yp,)   # h[L-1] is the padded output
    res = (phase_f, x2, wp, qs, hs, deltas, zs)
    return (y, site_mins, site_maxs), res


def _mlp_train_core_bwd(spec: _TrainSpec, res, cts):
    gy = cts[0]  # mins/maxs are range-monitor outputs: observed stop-grad
    phase_f, x2, wp, qs, hs, deltas, zs = res
    dims = spec.dims
    n_layers = len(wp)

    gy2 = jnp.asarray(gy, jnp.float32).reshape(-1, dims[-1])
    m = gy2.shape[0]
    mp, nlp = hs[-1].shape
    bm = _row_block(m)
    gyp = jnp.pad(gy2, ((0, mp - m), (0, nlp - dims[-1])))
    phase = (phase_f > 0).astype(jnp.int32).reshape(1)

    dxp, dwps, dbps = fxp_mlp_bwd_pallas(
        phase, gyp, x2, wp, qs, hs, deltas, zs,
        activations=spec.activations, bm=bm, n_bits=spec.n_bits,
        qat=spec.qat, fxp32_phase1=spec.fxp32_phase1,
        interpret=spec.interpret)

    dx = dxp[:m, :dims[0]].reshape(*gy.shape[:-1], dims[0])
    if spec.padded:   # cotangents in the layout the primal came in
        dws, dbs = tuple(dwps), tuple(dbps)
    else:
        dws = tuple(dwps[i][:dims[i], :dims[i + 1]] for i in range(n_layers))
        dbs = tuple(dbps[i][0, :dims[i + 1]] for i in range(n_layers))
    return (jnp.zeros_like(phase_f), dx, dws, dbs,
            jnp.zeros_like(deltas), jnp.zeros_like(zs))


_mlp_train_core.defvjp(_mlp_train_core_fwd, _mlp_train_core_bwd)


@functools.partial(jax.jit, static_argnames=("activations", "n_bits", "qat",
                                             "fxp32_phase1", "interpret",
                                             "dims"))
def fxp_mlp_train(x: Array, weights: tuple, biases: tuple,
                  deltas: Optional[Array] = None,
                  zs: Optional[Array] = None, *,
                  activations: Sequence[str], quant_phase: Array,
                  n_bits: int = 16, qat: bool = True,
                  fxp32_phase1: bool = True,
                  interpret: Optional[bool] = None,
                  dims: Optional[tuple] = None
                  ) -> tuple[Array, Array, Array]:
    """Differentiable fused forward — `fxp_mlp_forward` with a custom VJP.

    Same signature and return value as `fxp_mlp_forward`.  `dims`, when
    given, is the unpadded layer widths (K0, N1, ..., NL) and says that
    `weights`/`biases` already come in the kernels' layout ((Kp, Np) and
    (1, Np), zero-padded: `pad_weight`/`pad_bias`), so only `x` is padded
    here and weight cotangents come back in that layout.  Under `jax.grad`
    the fwd rule saves per-layer residuals in the same single launch and the
    bwd rule runs the whole dW/db/dx chain as ONE network-resident backward
    Pallas kernel; without differentiation the primal is the plain fused
    forward (no residual outputs materialized).  Gradients flow to x,
    weights, and biases; `quant_phase`, `deltas`, and `zs` get zero
    cotangents (quant params derive from stop-gradient'd range monitors),
    and the returned site_mins/site_maxs are stop-gradient'd — they are
    range-monitor observations, not a differentiable head (the oracle's
    mins/maxs DO carry gradients; parity is on y only).
    """
    n_layers = len(weights)
    assert n_layers == len(biases) == len(activations), (
        f"{n_layers} weights vs {len(biases)} biases vs "
        f"{len(activations)} activations")
    if interpret is None:
        interpret = interpret_mode()
    padded = dims is not None
    if not padded:
        assert weights[0].shape[0] == x.shape[-1], (
            f"layer-0 input dim {weights[0].shape[0]} != x feature dim "
            f"{x.shape[-1]}")
        dims = (int(x.shape[-1]),) + tuple(int(w.shape[-1]) for w in weights)
    assert dims[0] == x.shape[-1] and len(dims) == n_layers + 1, (
        f"dims {dims} do not describe {n_layers} layers on x {x.shape}")
    spec = _TrainSpec(activations=tuple(activations), dims=tuple(dims),
                      n_bits=int(n_bits), qat=bool(qat),
                      fxp32_phase1=bool(fxp32_phase1),
                      interpret=bool(interpret), padded=padded)
    deltas, zs = _norm_quant_params(deltas, zs, n_layers, qat)
    # float carrier so the custom_vjp boundary has a float (zero) cotangent
    phase_f = jnp.asarray(quant_phase).astype(jnp.float32).reshape(())
    y, site_mins, site_maxs = _mlp_train_core(
        spec, phase_f, x, tuple(weights), tuple(biases), deltas, zs)
    # the bwd rule discards the min/max cotangents; make that explicit so a
    # range-monitor loss errs toward zero grads *visibly* (stop_gradient)
    # instead of looking differentiable
    return (y, jax.lax.stop_gradient(site_mins),
            jax.lax.stop_gradient(site_maxs))


def fxp_mlp_infer(x: Array, weights: tuple, biases: tuple,
                  deltas: Optional[Array] = None,
                  zs: Optional[Array] = None, *,
                  activations: Sequence[str], quant_phase: Array,
                  n_bits: int = 16, fxp32_phase1: bool = True,
                  interpret: Optional[bool] = None) -> Array:
    """Serving entry point: fused forward, range monitors discarded.

    The inference-phase face of the fused kernel for `serve/policy` — same
    single Pallas launch, but the per-site (min, max) outputs are dropped at
    the wrapper so nothing downstream can fold them back into a live
    `QATState` (frozen-QAT serving).  Pass `deltas/zs=None` for the
    QAT-free pipeline.
    """
    qat = deltas is not None and zs is not None
    y, _, _ = fxp_mlp_forward(x, weights, biases, deltas, zs,
                              activations=activations,
                              quant_phase=quant_phase, n_bits=n_bits,
                              qat=qat, fxp32_phase1=fxp32_phase1,
                              interpret=interpret)
    return jax.lax.stop_gradient(y)


def fused_cost_hint(dims: Sequence[int], phase: str = "act") -> dict:
    """Dispatcher hook: launch/FLOP shape of the fused path for an MLP with
    layer dims `dims` — intra-batch parallelism, the whole network in ONE
    launch (batch is the only grid axis).

    phase="act" is the forward/acting path; phase="train" is a
    forward+backward step through `fxp_mlp_train`: 2 launches (fused fwd +
    fused bwd) and ~3x the MACs (fwd, plus dx and dW matmuls per layer).
    """
    if phase == "train":
        return {"launches": 2, "flops_per_item": 3 * mlp_flops(dims),
                "parallelism": "intra_batch"}
    if phase != "act":
        raise ValueError(f"unknown cost phase {phase!r}; 'act' | 'train'")
    return {"launches": 1, "flops_per_item": mlp_flops(dims),
            "parallelism": "intra_batch"}


# ---------------------------------------------------------------------------
# Fused DDPG training step (2 launches: critic BP/WU, then actor BP/WU)
# ---------------------------------------------------------------------------


def pad_wb(ws: Sequence[Array], bs: Sequence[Array]) -> list:
    """Pad per-layer (w, b) leaves to lane tiles, interleaved
    [w0, b0, w1, b1, ...] — the layout the fused-step kernels consume."""
    return [a for w, b in zip(ws, bs) for a in (pad_weight(w), pad_bias(b))]


def unpad_wb(wbp: Sequence[Array], dims: Sequence[int]) -> tuple:
    """Inverse of `pad_wb`: ((w per layer), (b per layer)) cut back to the
    unpadded widths `dims` (K0, N1, ..., NL)."""
    n = len(dims) - 1
    return (tuple(wbp[2 * i][:dims[i], :dims[i + 1]] for i in range(n)),
            tuple(wbp[2 * i + 1][0, :dims[i + 1]] for i in range(n)))


def _pad_batch(a: Array, mp: int) -> Array:
    """Pad a (B, k) batch array to (mp, 128) — rows AND lanes zero-filled."""
    b, k = a.shape
    return jnp.pad(a.astype(jnp.float32),
                   ((0, mp - b), (0, _round_up(k, 128) - k)))


class TrainStepOut(NamedTuple):
    """Everything `ddpg.update` needs back from the 2 launches.

    The eight parameter fields are, from `fxp_mlp_train_step_padded`, the
    interleaved [w0, b0, w1, b1, ...] leaves in the kernels' layout
    (zero-padded, exactly 0 outside the unpadded widths), and from
    `fxp_mlp_train_step`, ((w per layer), (b per layer)) unpadded."""

    actor: tuple
    critic: tuple
    actor_t: tuple
    critic_t: tuple
    actor_m: tuple        # Adam's moments, laid out like the params
    actor_v: tuple
    critic_m: tuple
    critic_v: tuple
    closs_sum: Array      # sum w * (q - y)^2
    y_sum: Array          # sum w * y
    q_sum: Array          # sum w * q(obs, actor(obs))
    c_mins: Array         # (L,)  critic-site extrema, critic-loss pass
    c_maxs: Array
    a_mins: Array         # (2L,) actor sites then critic sites, actor pass
    a_maxs: Array


@functools.partial(jax.jit, static_argnames=(
    "actor_acts", "critic_acts", "actor_dims", "critic_dims", "gamma", "tau",
    "n_bits", "qat", "fxp32_phase1", "fxp_weights", "interpret"))
def fxp_mlp_train_step_padded(obs, action, reward, done, next_obs, w,
                              actor_wb, critic_wb, actor_t_wb, critic_t_wb,
                              actor_m, actor_v, critic_m, critic_v,
                              deltas, zs, consts_c, consts_a, quant_phase, *,
                              actor_acts, critic_acts, actor_dims: tuple,
                              critic_dims: tuple, gamma: float, tau: float,
                              n_bits: int = 16, qat: bool = True,
                              fxp32_phase1: bool = True,
                              fxp_weights: bool = True,
                              interpret: Optional[bool] = None
                              ) -> TrainStepOut:
    """One whole DDPG update in TWO Pallas launches, on and to the
    kernels' layout.

    Launch 1 (critic step): target-actor fwd, target-critic fwd, TD target,
    online-critic fwd with monitors, weighted-MSE backward, Adam, target
    soft update — params, residuals, and grad accumulators all
    network-resident.  Launch 2 (actor step): actor fwd, updated-critic fwd,
    policy-gradient backward (dx-only through the critic), Adam, target soft
    update.  Every *_wb / moment argument is the interleaved
    [w0, b0, w1, b1, ...] leaves of one net zero-padded to lane tiles
    (`pad_weight`, `pad_bias`), and the eight parameter fields of the
    result come back the same way: the padded regions stay exactly 0
    (zero inputs there give zero grads, and Adam and the soft update map
    zeros to zeros), so a caller can carry them from update to update.
    `actor_dims` / `critic_dims` are the unpadded widths (K0, N1, ..., NL);
    the batch arrays are unpadded.  `consts_c` / `consts_a` are
    `adam.StepConstants` for the post-increment critic/actor optimizer
    steps; `w` is the (B,) sample weight vector (ones when the batch carries
    no mask).  `gamma`/`tau` are static floats so their complements fold in
    double precision, matching the host path bit-for-bit.
    """
    assert HYPER_LEN == 12
    if interpret is None:
        interpret = interpret_mode()

    L = len(actor_wb) // 2
    obs_dim, act_dim = actor_dims[0], actor_dims[-1]
    b_rows = obs.shape[0]
    bm = _row_block(b_rows)
    mp = _round_up(b_rows, bm)

    obs_p = _pad_batch(obs.astype(jnp.float32), mp)
    nobs_p = _pad_batch(next_obs.astype(jnp.float32), mp)
    xc_p = _pad_batch(
        jnp.concatenate([obs, action], axis=-1).astype(jnp.float32), mp)
    aux_p = _pad_batch(
        jnp.stack([reward.reshape(-1), done.reshape(-1),
                   w.reshape(-1)], axis=-1), mp)

    inv_w = 1.0 / jnp.maximum(jnp.sum(w.astype(jnp.float32)), 1.0)
    # (1 - tau) folded in Python double then cast, exactly like the host
    # tree.map soft update's weak-typed constant
    loss_scalars = [inv_w, jnp.float32(gamma), jnp.float32(tau),
                    jnp.float32(1 - tau)]
    hyper_c = jnp.stack(loss_scalars + [
        consts_c.lr, consts_c.b1, consts_c.one_minus_b1, consts_c.b2,
        consts_c.one_minus_b2, consts_c.eps, consts_c.bc1, consts_c.bc2])
    hyper_a = jnp.stack(loss_scalars + [
        consts_a.lr, consts_a.b1, consts_a.one_minus_b1, consts_a.b2,
        consts_a.one_minus_b2, consts_a.eps, consts_a.bc1, consts_a.bc2])

    deltas2, zs2 = _norm_quant_params(deltas, zs, 2 * L, qat)
    phase = jnp.asarray(quant_phase, jnp.int32).reshape(1)

    ncp, ncm, ncv, nct, stats1 = ddpg_critic_step_pallas(
        phase, xc_p, nobs_p, aux_p, actor_t_wb, critic_t_wb, critic_wb,
        critic_m, critic_v, deltas2, zs2, hyper_c, obs_dim=obs_dim,
        actor_acts=actor_acts, critic_acts=critic_acts,
        critic_in_dims=critic_dims[:-1], m_valid=b_rows, bm=bm,
        n_bits=n_bits, qat=qat, fxp32_phase1=fxp32_phase1,
        fxp_weights=fxp_weights, interpret=interpret)

    # launch 2 sees the UPDATED critic
    nap, nam, nav, nat, stats2 = ddpg_actor_step_pallas(
        phase, obs_p, aux_p, actor_wb, actor_m, actor_v, actor_t_wb, ncp,
        deltas2, zs2, hyper_a, obs_dim=obs_dim,
        act_dim=act_dim, actor_acts=actor_acts, critic_acts=critic_acts,
        actor_in_dims=actor_dims[:-1], critic_in_dims=critic_dims[:-1],
        m_valid=b_rows, bm=bm, n_bits=n_bits, qat=qat,
        fxp32_phase1=fxp32_phase1, fxp_weights=fxp_weights,
        interpret=interpret)

    c_mins, c_maxs, part1 = block_stats(stats1, L, 2)
    a_mins, a_maxs, part2 = block_stats(stats2, 2 * L, 1)
    return TrainStepOut(
        actor=tuple(nap), critic=tuple(ncp), actor_t=tuple(nat),
        critic_t=tuple(nct), actor_m=tuple(nam), actor_v=tuple(nav),
        critic_m=tuple(ncm), critic_v=tuple(ncv),
        closs_sum=part1[0], y_sum=part1[1], q_sum=part2[0],
        c_mins=c_mins, c_maxs=c_maxs, a_mins=a_mins, a_maxs=a_maxs)


@functools.partial(jax.jit, static_argnames=(
    "actor_acts", "critic_acts", "obs_dim", "act_dim", "gamma", "tau",
    "n_bits", "qat", "fxp32_phase1", "fxp_weights", "interpret"))
def fxp_mlp_train_step(obs, action, reward, done, next_obs, w,
                       actor_wb, critic_wb, actor_t_wb, critic_t_wb,
                       actor_m, actor_v, critic_m, critic_v,
                       deltas, zs, consts_c, consts_a, quant_phase, *,
                       actor_acts, critic_acts, obs_dim: int, act_dim: int,
                       gamma: float, tau: float, n_bits: int = 16,
                       qat: bool = True, fxp32_phase1: bool = True,
                       fxp_weights: bool = True,
                       interpret: Optional[bool] = None) -> TrainStepOut:
    """`fxp_mlp_train_step_padded` on unpadded leaves: every *_wb / moment
    argument, and each of the eight parameter fields of the result, is
    ((w per layer), (b per layer)) of UNPADDED leaves.  It pads all eight
    on the way in and slices them back out on the way out, every call; a
    caller that runs many updates carries the padded leaves instead
    (`ddpg.pad_state`)."""
    a_ws, a_bs = actor_wb
    c_ws, c_bs = critic_wb
    actor_dims = (obs_dim,) + tuple(int(x.shape[1]) for x in a_ws)
    critic_dims = (obs_dim + act_dim,) + tuple(int(x.shape[1]) for x in c_ws)
    assert actor_dims[-1] == act_dim, (actor_dims, act_dim)

    out = fxp_mlp_train_step_padded(
        obs, action, reward, done, next_obs, w,
        *(pad_wb(*wb) for wb in (actor_wb, critic_wb, actor_t_wb,
                                  critic_t_wb, actor_m, actor_v, critic_m,
                                  critic_v)),
        deltas, zs, consts_c, consts_a, quant_phase,
        actor_acts=actor_acts, critic_acts=critic_acts,
        actor_dims=actor_dims, critic_dims=critic_dims, gamma=gamma, tau=tau,
        n_bits=n_bits, qat=qat, fxp32_phase1=fxp32_phase1,
        fxp_weights=fxp_weights, interpret=interpret)

    a, c = actor_dims, critic_dims
    return out._replace(
        actor=unpad_wb(out.actor, a), critic=unpad_wb(out.critic, c),
        actor_t=unpad_wb(out.actor_t, a), critic_t=unpad_wb(out.critic_t, c),
        actor_m=unpad_wb(out.actor_m, a), actor_v=unpad_wb(out.actor_v, a),
        critic_m=unpad_wb(out.critic_m, c), critic_v=unpad_wb(out.critic_v, c))
