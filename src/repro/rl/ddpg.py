"""DDPG (Lillicrap et al. '15) with FIXAR fixed-point QAT — the paper's workload.

Actor : state → 400 → 300 → act_dim, ReLU hidden, tanh output   (§VI-B)
Critic: [state; action] → 400 → 300 → 1, ReLU hidden
Both optimized with Adam, lr 1e-4 (paper), weights/grads projected onto the
Q15.16 lattice every step (fixed-point weight & gradient memories, §III),
activations run through QAT sites (Algorithm 1).

Backends:
  * `backend="jnp"` (default, training) — dense layers via jnp.dot on
    fake-quantized values; differentiable, fast on CPU.
  * `backend="pallas"` — the network-resident fused kernel
    (kernels/fxp_mlp): ONE Pallas call runs the whole actor/critic forward
    with all weights VMEM-resident, QAT sites fused between layers and the
    dual-precision datapath flipped by a scalar-prefetch phase flag (no
    lax.cond double-trace).  Trainable: the fused forward carries a custom
    VJP whose backward pass is a second network-resident Pallas launch
    (whole dW/db/dx chain, STE at the QAT sites), so `update()` runs the
    paper's BP/WU sequence through the fused kernel too.
  * `backend="pallas_layer"` — the per-layer dual-precision AAP-core kernel
    chain (kernels/fxp_matmul), precision switched by the QAT phase at
    runtime via lax.cond; kept as the reference/fallback for the fused path.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core import fixedpoint as fxp
from repro.core.qat import (FrozenQuant, QATContext, QATState, freeze_quant,
                            quantize_grads)
from repro.kernels.fxp_matmul.ops import fxp_dense, fxp_dense_chain
from repro.kernels.fxp_mlp.ops import (fxp_mlp_infer, fxp_mlp_train,
                                       fxp_mlp_train_step_padded, pad_bias,
                                       pad_weight, unpad_wb)
from repro.optim import adam, fxp_adam
from repro.rl.envs.base import EnvSpec

Array = jax.Array
Params = dict[str, Any]

ACTOR_SITES = ["actor/l0", "actor/l1", "actor/l2"]
CRITIC_SITES = ["critic/l0", "critic/l1", "critic/l2"]
ACTOR_ACTS = ("relu", "relu", "tanh")
CRITIC_ACTS = ("relu", "relu", "none")
HIDDEN = (400, 300)  # paper §VI-B


@dataclasses.dataclass(frozen=True)
class DDPGConfig:
    gamma: float = 0.99
    tau: float = 0.005
    actor_lr: float = 1e-4      # paper: Adam lr 1e-4
    critic_lr: float = 1e-4
    batch_size: int = 128
    qat_delay: int = 0          # optimizer steps before 16-bit switch
    qat_bits: int = 16
    qat_enabled: bool = True
    fxp_weights: bool = True    # project weights/grads to Q15.16
    backend: str = "jnp"        # "jnp" | "pallas" (fused) | "pallas_layer"
    exploration_sigma: float = 0.1


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DDPGState:
    actor: Params
    critic: Params
    actor_target: Params
    critic_target: Params
    actor_opt: adam.AdamState
    critic_opt: adam.AdamState
    qat: QATState
    step: Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PaddedDDPGState:
    """A `DDPGState` in the fused kernels' layout: each weight zero-padded
    to (round_up(K, 128), round_up(N, 128)) float32 and each bias to
    (1, round_up(N, 128)), for the params, both targets and all four Adam
    moments, with the unpadded widths (K0, N1, ..., NL) of each net.

    The fused update (backend "pallas_fused_step") keeps the padded regions
    exactly 0, so a loop that runs many updates pads once (`pad_state`),
    carries this through `act` and `update`, and unpads once
    (`unpad_state`): no update pads or slices the eight parameter sets."""

    agent: DDPGState
    actor_dims: tuple = dataclasses.field(metadata=dict(static=True))
    critic_dims: tuple = dataclasses.field(metadata=dict(static=True))


def _init_linear(key, fan_in: int, fan_out: int, final: bool = False):
    """DDPG init: uniform(±1/sqrt(fan_in)); final layer uniform(±3e-3)."""
    kw, kb = jax.random.split(key)
    bound = 3e-3 if final else float(fan_in) ** -0.5
    w = jax.random.uniform(kw, (fan_in, fan_out), jnp.float32, -bound, bound)
    b = jax.random.uniform(kb, (fan_out,), jnp.float32, -bound, bound)
    return {"w": w, "b": b}


def _init_mlp(key, sizes: list[int]) -> Params:
    keys = jax.random.split(key, len(sizes) - 1)
    return {f"l{i}": _init_linear(keys[i], sizes[i], sizes[i + 1],
                                  final=(i == len(sizes) - 2))
            for i in range(len(sizes) - 1)}


def _dense(x, layer, activation: str, *, backend: str, quant_phase) -> Array:
    if backend == "pallas_layer":
        full = partial(fxp_dense, full_precision=True, activation=activation)
        half = partial(fxp_dense, full_precision=False, activation=activation)
        return jax.lax.cond(quant_phase,
                            lambda a: half(a, layer["w"], layer["b"]),
                            lambda a: full(a, layer["w"], layer["b"]), x)
    y = x @ layer["w"] + layer["b"]
    if activation == "relu":
        y = jax.nn.relu(y)
    elif activation == "tanh":
        y = jnp.tanh(y)
    return y


def _fused_mlp(params: Params, x: Array, ctx: Optional[QATContext],
               *, sites: list[str], activations: tuple[str, ...],
               dims: Optional[tuple[int, ...]] = None) -> Array:
    """Whole-network forward through the fused kernel (kernels/fxp_mlp):
    one Pallas call, weights VMEM-resident, QAT sites fused in-pipeline.
    Range observations flow back into `ctx` via `observe`, so QAT state
    evolves identically to the per-layer path.  `fxp_mlp_train` carries the
    custom VJP: pure inference runs the plain fused forward, while under
    `jax.grad` the backward chain is one more network-resident launch.
    `dims` (the unpadded widths) says `params` is in the kernels' layout."""
    n = len(activations)
    ws = tuple(params[f"l{i}"]["w"] for i in range(n))
    bs = tuple(params[f"l{i}"]["b"] for i in range(n))
    if ctx is None or not ctx.state.config.enabled:
        y, _, _ = fxp_mlp_train(x, ws, bs, activations=activations,
                                quant_phase=jnp.array(False), qat=False,
                                dims=dims)
        return y
    cfg = ctx.state.config
    deltas, zs = ctx.site_quant_params(sites)
    y, mns, mxs = fxp_mlp_train(
        x, ws, bs, deltas, zs, activations=activations,
        quant_phase=ctx.state.quantized_phase, n_bits=cfg.n_bits,
        fxp32_phase1=cfg.fxp32_phase1, dims=dims)
    for j, site in enumerate(sites):
        ctx.observe(site, mns[j], mxs[j])
    return y


def _mlp_forward(params: Params, x: Array, ctx: Optional[QATContext],
                 *, sites: list[str], activations: tuple[str, ...],
                 backend: str) -> Array:
    if backend in ("pallas", "pallas_fused_step"):
        # the fused-step backend only changes how update() runs BP/WU; any
        # plain forward (acting, evaluation) is the fused kernel either way
        return _fused_mlp(params, x, ctx, sites=sites, activations=activations)
    # half-precision dense is tied to activation quantization: with QAT off
    # there is no quantized phase, so the datapath stays full precision
    # (keeps this path bit-comparable with the fused kernel's qat=False mode)
    qp = (ctx.state.quantized_phase
          if ctx is not None and ctx.state.config.enabled
          else jnp.array(False))
    for i, act in enumerate(activations):
        if ctx is not None:
            x = ctx.site(sites[i], x)
        x = _dense(x, params[f"l{i}"], act, backend=backend, quant_phase=qp)
    return x


def actor_forward(params: Params, obs: Array, ctx: Optional[QATContext],
                  *, backend: str = "jnp") -> Array:
    return _mlp_forward(params, obs, ctx, sites=ACTOR_SITES,
                        activations=ACTOR_ACTS, backend=backend)


def critic_forward(params: Params, obs: Array, action: Array,
                   ctx: Optional[QATContext], *, backend: str = "jnp") -> Array:
    x = jnp.concatenate([obs, action], axis=-1)
    x = _mlp_forward(params, x, ctx, sites=CRITIC_SITES,
                     activations=CRITIC_ACTS, backend=backend)
    return jnp.squeeze(x, -1)


def init(key: Array, spec: EnvSpec, cfg: DDPGConfig) -> DDPGState:
    ka, kc = jax.random.split(key)
    actor = _init_mlp(ka, [spec.obs_dim, *HIDDEN, spec.act_dim])
    critic = _init_mlp(kc, [spec.obs_dim + spec.act_dim, *HIDDEN, 1])
    if cfg.fxp_weights:  # weight memory is Q15.16 from step 0
        project = lambda t: jax.tree.map(lambda p: fxp.fake_quant(p, fxp.FXP32), t)
        actor, critic = project(actor), project(critic)
    qat = QATState.init(delay=cfg.qat_delay, sites=ACTOR_SITES + CRITIC_SITES,
                        n_bits=cfg.qat_bits, enabled=cfg.qat_enabled)
    return DDPGState(
        actor=actor, critic=critic,
        actor_target=jax.tree.map(jnp.copy, actor),
        critic_target=jax.tree.map(jnp.copy, critic),
        actor_opt=adam.init(actor), critic_opt=adam.init(critic),
        qat=qat, step=jnp.zeros((), jnp.int32))


def act(state: DDPGState | PaddedDDPGState, obs: Array, *, cfg: DDPGConfig,
        noise_key: Optional[Array] = None,
        noise: Optional[Array] = None) -> Array:
    """Actor inference (+ the PRNG exploration-noise unit of Fig. 2).

    Exploration comes in two equivalent spellings: `noise_key` draws
    Gaussian noise internally at `cfg.exploration_sigma` (the legacy
    surface), while `noise` adds a caller-supplied perturbation — the hook
    `rl/loop` uses to thread `rl/noise.NoiseProcess` samples (Gaussian or
    OU, explicit `NoiseState` carry) through the scanned device loop.
    Either way the perturbation lands pre-clip.  A `PaddedDDPGState` acts
    through the fused kernel on its padded weights as they are.
    """
    padded = isinstance(state, PaddedDDPGState)
    agent = state.agent if padded else state
    # no-QAT fast path: don't materialize a context (which re-derives quant
    # params from the range tree) when every site would be a pass-through
    ctx = QATContext(agent.qat) if agent.qat.config.enabled else None
    if padded:
        a = _fused_mlp(agent.actor, obs, ctx, sites=ACTOR_SITES,
                       activations=ACTOR_ACTS, dims=state.actor_dims)
    else:
        a = actor_forward(agent.actor, obs, ctx, backend=cfg.backend)
    if noise_key is not None:
        a = a + cfg.exploration_sigma * jax.random.normal(noise_key, a.shape)
    elif noise is not None:
        a = a + noise
    return jnp.clip(a, -1.0, 1.0)


def freeze_actor_quant(state: DDPGState) -> Optional[FrozenQuant]:
    """Snapshot the actor's site quant params for serving (None if QAT off)."""
    return freeze_quant(state.qat, ACTOR_SITES)


def act_batch(actor: Params, obs: Array,
              frozen: Optional[FrozenQuant] = None, *,
              mode: str = "fused") -> Array:
    """Pure batched greedy policy — the function `serve/policy` lowers once
    per (bucket, mode) and then drains micro-batches through.

    Unlike `act`, this takes only the actor params and a `FrozenQuant`
    snapshot (no `DDPGState`, no `QATContext`), so the serve path cannot
    touch live QAT range monitors by construction.  `mode` mirrors the AAP
    core's configurable dataflow:

      * "fused" — ONE network-resident Pallas launch, batch as the grid
        axis (intra-batch parallelism; the training-phase dataflow);
      * "layer" — the per-layer dual-precision kernel chain, one launch per
        layer with its columns spread across the array (intra-layer
        parallelism; the paper's inference dataflow for tiny batches);
      * "jnp"   — pure-XLA reference fallback.

    Parity with `act(state, obs, cfg)` (per backend, no noise) is pinned in
    tests/serve/test_policy_engine.py.
    """
    n = len(ACTOR_ACTS)
    ws = tuple(actor[f"l{i}"]["w"] for i in range(n))
    bs = tuple(actor[f"l{i}"]["b"] for i in range(n))
    if mode == "fused":
        if frozen is None:
            y = fxp_mlp_infer(obs, ws, bs, activations=ACTOR_ACTS,
                              quant_phase=jnp.array(False))
        else:
            y = fxp_mlp_infer(obs, ws, bs, frozen.deltas, frozen.zs,
                              activations=ACTOR_ACTS,
                              quant_phase=jnp.array(frozen.quantized),
                              n_bits=frozen.n_bits,
                              fxp32_phase1=frozen.fxp32_phase1)
    elif mode == "layer":
        y = fxp_dense_chain(
            obs, ws, bs, activations=ACTOR_ACTS,
            full_precision=not (frozen is not None and frozen.quantized),
            site_fn=frozen.site if frozen is not None else None)
    elif mode == "jnp":
        x = obs
        for i, act_name in enumerate(ACTOR_ACTS):
            if frozen is not None:
                x = frozen.site(i, x)
            x = _dense(x, {"w": ws[i], "b": bs[i]}, act_name,
                       backend="jnp", quant_phase=None)
        y = x
    else:
        raise ValueError(f"unknown serve mode {mode!r}; expected "
                         "'fused' | 'layer' | 'jnp'")
    return jnp.clip(y, -1.0, 1.0)


def actor_site_telemetry(actor: Params, obs: Array,
                         frozen: Optional[FrozenQuant] = None,
                         mask: Optional[Array] = None
                         ) -> tuple[Array, Array, Array]:
    """Per-site activation extrema + quantizer saturation rates (obs hook).

    Runs the actor's jnp reference forward and captures, at each QAT site,
    the pre-quantization input extrema and the fraction of elements at or
    beyond the site's clip boundaries ``[a_min, a_max]`` — the
    paper-grounded overflow signal `repro.obs.qat` aggregates: a site whose
    saturation climbs is a layer whose captured range no longer covers its
    activations at the current bitwidth.  Saturation is 0 when `frozen` is
    None or not in the quantized phase (nothing clips there).

    `mask` is an optional (B,) row-validity vector so engines can probe
    their *padded* bucket batches (one trace per bucket, not per row
    count): masked-out rows are excluded from extrema and saturation.

    Returns ``(mins, maxs, saturations)``, each ``(n_sites,)`` f32.
    """
    valid = None if mask is None else (mask > 0)[:, None]
    x = obs
    mns, mxs, sats = [], [], []
    for i, act_name in enumerate(ACTOR_ACTS):
        x_lo = x if valid is None else jnp.where(valid, x, jnp.inf)
        x_hi = x if valid is None else jnp.where(valid, x, -jnp.inf)
        mns.append(jnp.min(x_lo))
        mxs.append(jnp.max(x_hi))
        if frozen is not None and frozen.quantized:
            out = ((x <= frozen.a_mins[i]) |
                   (x >= frozen.a_maxs[i])).astype(jnp.float32)
            if valid is None:
                sats.append(jnp.mean(out))
            else:
                w = valid.astype(jnp.float32)
                sats.append(jnp.sum(out * w) /
                            jnp.maximum(jnp.sum(w) * x.shape[-1], 1.0))
        else:
            sats.append(jnp.float32(0.0))
        if frozen is not None:
            x = frozen.site(i, x)
        x = _dense(x, actor[f"l{i}"], act_name, backend="jnp",
                   quant_phase=None)
    return jnp.stack(mns), jnp.stack(mxs), jnp.stack(sats)


def _wmean(x: Array, w: Optional[Array]) -> Array:
    """Mean over valid rows: plain `jnp.mean` when `w` is None (the
    unweighted path is kept verbatim so existing update programs are
    untouched), else sum(w*x)/sum(w) — padded rows carry w=0 and contribute
    exactly zero to the loss and its gradients."""
    if w is None:
        return jnp.mean(x)
    w = w.astype(jnp.float32)
    return jnp.sum(x * w) / jnp.maximum(jnp.sum(w), 1.0)


def _wb_to_params(wb: tuple[tuple, tuple]) -> Params:
    ws, bs = wb
    return {f"l{i}": {"w": w, "b": b} for i, (w, b) in enumerate(zip(ws, bs))}


def _net_dims(params: Params) -> tuple[int, ...]:
    """The unpadded widths (K0, N1, ..., NL) of a net's params."""
    ws = [params[f"l{i}"]["w"] for i in range(len(params))]
    return (int(ws[0].shape[0]),) + tuple(int(w.shape[1]) for w in ws)


def _interleaved(params: Params) -> list:
    return [params[f"l{i}"][k] for i in range(len(params)) for k in ("w", "b")]


def _from_interleaved(wbp) -> Params:
    return {f"l{i}": {"w": wbp[2 * i], "b": wbp[2 * i + 1]}
            for i in range(len(wbp) // 2)}


def _map_nets(state: DDPGState, actor_fn, critic_fn) -> DDPGState:
    """`state` with each actor-shaped tree through `actor_fn` and each
    critic-shaped tree through `critic_fn` (params, target, both moments)."""
    def opt(o, fn):
        return adam.AdamState(step=o.step, mu=fn(o.mu), nu=fn(o.nu))
    return dataclasses.replace(
        state, actor=actor_fn(state.actor), critic=critic_fn(state.critic),
        actor_target=actor_fn(state.actor_target),
        critic_target=critic_fn(state.critic_target),
        actor_opt=opt(state.actor_opt, actor_fn),
        critic_opt=opt(state.critic_opt, critic_fn))


def pad_state(state: DDPGState) -> PaddedDDPGState:
    """`state` in the fused kernels' layout."""
    def pad(p):
        return {k: {"w": pad_weight(l["w"]), "b": pad_bias(l["b"])}
                for k, l in p.items()}
    return PaddedDDPGState(agent=_map_nets(state, pad, pad),
                           actor_dims=_net_dims(state.actor),
                           critic_dims=_net_dims(state.critic))


def unpad_state(state: PaddedDDPGState) -> DDPGState:
    """Inverse of `pad_state`."""
    def unpad(dims):
        return lambda p: _wb_to_params(unpad_wb(_interleaved(p), dims))
    return _map_nets(state.agent, unpad(state.actor_dims),
                     unpad(state.critic_dims))


def _update_padded(padded: PaddedDDPGState, batch: dict[str, Array],
                   cfg: DDPGConfig) -> tuple[PaddedDDPGState, dict[str, Array]]:
    """The whole update in TWO Pallas launches
    (`fxp_mlp_train_step_padded`), on and to the fused kernels' layout:
    critic fwd+bwd+Adam+soft-update resident in launch 1, actor ditto in
    launch 2 — residuals in VMEM, gradients accumulated across batch
    blocks in-kernel, moments/params/targets updated in place in the
    epilogue, and no padding or slicing of the eight parameter sets around
    them.  Value semantics (losses, QAT range evolution, optimizer
    trajectory) track `backend="pallas"`; parity is pinned in
    tests/kernels/test_fxp_mlp_step.py.
    """
    state = padded.agent
    obs, action = batch["obs"], batch["action"]
    reward, next_obs = batch["reward"], batch["next_obs"]
    done = batch["done"].astype(jnp.float32)
    mask = batch.get("mask")
    w = (jnp.ones((obs.shape[0],), jnp.float32) if mask is None
         else mask.astype(jnp.float32))

    qat_on = state.qat.config.enabled
    if qat_on:
        deltas, zs = QATContext(state.qat).site_quant_params(
            ACTOR_SITES + CRITIC_SITES)
    else:
        deltas = zs = None

    opt_cfg_c = (fxp_adam.FxpAdamConfig(lr=cfg.critic_lr) if cfg.fxp_weights
                 else adam.AdamConfig(lr=cfg.critic_lr))
    opt_cfg_a = (fxp_adam.FxpAdamConfig(lr=cfg.actor_lr) if cfg.fxp_weights
                 else adam.AdamConfig(lr=cfg.actor_lr))
    consts_c = adam.step_constants(opt_cfg_c, state.critic_opt.step + 1)
    consts_a = adam.step_constants(opt_cfg_a, state.actor_opt.step + 1)

    out = fxp_mlp_train_step_padded(
        obs, action, reward, done, next_obs, w,
        _interleaved(state.actor), _interleaved(state.critic),
        _interleaved(state.actor_target), _interleaved(state.critic_target),
        _interleaved(state.actor_opt.mu), _interleaved(state.actor_opt.nu),
        _interleaved(state.critic_opt.mu), _interleaved(state.critic_opt.nu),
        deltas, zs, consts_c, consts_a, state.qat.quantized_phase,
        actor_acts=ACTOR_ACTS, critic_acts=CRITIC_ACTS,
        actor_dims=padded.actor_dims, critic_dims=padded.critic_dims,
        gamma=cfg.gamma, tau=cfg.tau, n_bits=state.qat.config.n_bits,
        qat=qat_on, fxp32_phase1=state.qat.config.fxp32_phase1,
        fxp_weights=cfg.fxp_weights)

    # range-monitor evolution mirrors the two-context sequence of update():
    # critic-loss pass observes the critic sites (-> qat1), actor-loss pass
    # observes actor sites and the critic sites again on top of qat1
    if qat_on:
        ctx1 = QATContext(state.qat)
        for j, site in enumerate(CRITIC_SITES):
            ctx1.observe(site, out.c_mins[j], out.c_maxs[j])
        ctx2 = QATContext(dataclasses.replace(ctx1.finalize()))
        for j, site in enumerate(ACTOR_SITES + CRITIC_SITES):
            ctx2.observe(site, out.a_mins[j], out.a_maxs[j])
        qat_final = ctx2.finalize().tick()
    else:
        qat_final = state.qat.tick()

    sum_w = jnp.maximum(jnp.sum(w), 1.0)
    new_state = DDPGState(
        actor=_from_interleaved(out.actor),
        critic=_from_interleaved(out.critic),
        actor_target=_from_interleaved(out.actor_t),
        critic_target=_from_interleaved(out.critic_t),
        actor_opt=adam.AdamState(step=state.actor_opt.step + 1,
                                 mu=_from_interleaved(out.actor_m),
                                 nu=_from_interleaved(out.actor_v)),
        critic_opt=adam.AdamState(step=state.critic_opt.step + 1,
                                  mu=_from_interleaved(out.critic_m),
                                  nu=_from_interleaved(out.critic_v)),
        qat=qat_final, step=state.step + 1)
    metrics = {"critic_loss": out.closs_sum / sum_w,
               "actor_loss": -(out.q_sum / sum_w),
               "q_mean": out.y_sum / sum_w}
    return dataclasses.replace(padded, agent=new_state), metrics


def _update_fused_step(state: DDPGState, batch: dict[str, Array],
                       cfg: DDPGConfig) -> tuple[DDPGState, dict[str, Array]]:
    """`_update_padded` on an unpadded agent: pad all eight parameter sets,
    update, unpad — every call."""
    new_state, metrics = _update_padded(pad_state(state), batch, cfg)
    return unpad_state(new_state), metrics


def update(state: DDPGState | PaddedDDPGState, batch: dict[str, Array],
           cfg: DDPGConfig) -> tuple[DDPGState | PaddedDDPGState,
                                     dict[str, Array]]:
    """One FIXAR timestep's training work: critic BP/WU then actor BP/WU
    (operation sequence of Fig. 3), QAT-aware, fixed-point weights.

    Trains with `backend="jnp"` (XLA autodiff) or `backend="pallas"` (the
    fused kernel's custom VJP: fwd + bwd are one network-resident Pallas
    launch each).  The per-layer chain has no autodiff rule and stays
    inference-only.

    `batch` may carry an optional `"mask"` row — (B,) validity weights, the
    contract `train/learner` uses to pad update streams to its batching
    buckets: masked-out rows get zero loss weight (zero gradient), so a
    bucket-padded update computes the same BP/WU as the unpadded batch.
    The padded rows do still flow through the QAT range monitors (min/max
    extrema; all-zero pad rows only widen a range that excludes 0, which
    mid-training activations essentially never do).

    A `PaddedDDPGState` (backend "pallas_fused_step" only) is updated and
    returned in the fused kernels' layout.
    """
    if isinstance(state, PaddedDDPGState):
        if cfg.backend != "pallas_fused_step":
            raise ValueError("a PaddedDDPGState trains only with "
                             "backend='pallas_fused_step'")
        return _update_padded(state, batch, cfg)
    if cfg.backend == "pallas_fused_step":
        return _update_fused_step(state, batch, cfg)
    if cfg.backend not in ("jnp", "pallas"):
        raise ValueError(
            f"backend={cfg.backend!r} is forward/inference-only (the "
            "per-layer kernel chain has no autodiff rule); train with "
            "backend='jnp', backend='pallas', or "
            "backend='pallas_fused_step'")
    obs, action = batch["obs"], batch["action"]
    reward, next_obs = batch["reward"], batch["next_obs"]
    done = batch["done"].astype(jnp.float32)
    mask = batch.get("mask")

    # ---- targets (inference on target nets, no range updates) -------------
    tctx = QATContext(state.qat)
    next_a = actor_forward(state.actor_target, next_obs, tctx, backend=cfg.backend)
    q_next = critic_forward(state.critic_target, next_obs, next_a, tctx,
                            backend=cfg.backend)
    y = reward + cfg.gamma * (1.0 - done) * q_next
    y = jax.lax.stop_gradient(y)

    # ---- critic BP + WU ----------------------------------------------------
    def critic_loss(cp):
        ctx = QATContext(state.qat)
        q = critic_forward(cp, obs, action, ctx, backend=cfg.backend)
        return _wmean(jnp.square(q - y), mask), ctx.finalize()

    (closs, qat1), cgrads = jax.value_and_grad(critic_loss, has_aux=True)(
        state.critic)
    opt_cfg_c = (fxp_adam.FxpAdamConfig(lr=cfg.critic_lr) if cfg.fxp_weights
                 else adam.AdamConfig(lr=cfg.critic_lr))
    upd_fn = fxp_adam.update if cfg.fxp_weights else adam.update
    if cfg.fxp_weights:
        cgrads = quantize_grads(cgrads)  # gradient memory is fxp32
    critic, critic_opt, _ = upd_fn(opt_cfg_c, cgrads, state.critic_opt,
                                   state.critic)

    # ---- actor BP + WU (through the *updated* critic, Fig. 3) -------------
    def actor_loss(ap):
        ctx = QATContext(dataclasses.replace(qat1))
        a = actor_forward(ap, obs, ctx, backend=cfg.backend)
        q = critic_forward(critic, obs, a, ctx, backend=cfg.backend)
        return -_wmean(q, mask), ctx.finalize()

    (aloss, qat2), agrads = jax.value_and_grad(actor_loss, has_aux=True)(
        state.actor)
    opt_cfg_a = (fxp_adam.FxpAdamConfig(lr=cfg.actor_lr) if cfg.fxp_weights
                 else adam.AdamConfig(lr=cfg.actor_lr))
    if cfg.fxp_weights:
        agrads = quantize_grads(agrads)
    actor, actor_opt, _ = upd_fn(opt_cfg_a, agrads, state.actor_opt,
                                 state.actor)

    # ---- soft target update -------------------------------------------------
    soft = lambda t, o: jax.tree.map(
        lambda a, b: (1 - cfg.tau) * a + cfg.tau * b, t, o)
    new_state = DDPGState(
        actor=actor, critic=critic,
        actor_target=soft(state.actor_target, actor),
        critic_target=soft(state.critic_target, critic),
        actor_opt=actor_opt, critic_opt=critic_opt,
        qat=qat2.tick(), step=state.step + 1)
    metrics = {"critic_loss": closs, "actor_loss": aloss,
               "q_mean": _wmean(y, mask)}
    return new_state, metrics
