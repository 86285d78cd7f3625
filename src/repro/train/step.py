"""LM training step: loss + grads + (fixed-point) Adam + QAT threading.

The FIXAR technique rides along as a first-class feature: when cfg.qat is
set, every activation site fake-quantizes per Algorithm 1 (32-bit lattice
pre-delay with range monitoring, 16-bit affine after), gradients and weights
are projected onto the Q15.16 lattice (the fixed-point gradient/weight
memories), and the per-layer ranges thread through the layer scan.

Microbatching (gradient accumulation) runs as a `lax.scan` over microbatch
slices with an f32 grad accumulator — the standard large-batch recipe.

The step's phases are `jax.named_scope`s (`PHASES`), disjoint, so each
compiled instruction belongs to one (`repro.obs.phases.op_phases`): the
embedding, the Mamba-2 mixers' projections, conv and gated norm
(`mamba_proj`) apart from their SSD scan (`ssd`), attention, the
feed-forward halves (`ffn`), the final norm, head and loss (`head`), and the
gradients' lattice, clip, Adam and the weight lattice (`optimizer`).  A
block's norm and residual add fall in its sublayer's phase; the backward
and rematerialized ops keep their forward phase.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.parallelism import ShardingRules
from repro.core.qat import quantize_grads, quantize_weights
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.optim import adam

Array = jax.Array
Params = dict[str, Any]

PHASES = ("embed", "mamba_proj", "ssd", "attn", "ffn", "head", "optimizer")


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    params: Params
    opt: adam.AdamState
    ranges: Params          # QAT range trees (present even when qat off)
    step: Array             # i32


def init_state(key, cfg: ModelConfig) -> TrainState:
    params = T.init_params(key, cfg)
    return TrainState(params=params, opt=adam.init(params),
                      ranges=T.init_ranges(cfg),
                      step=jnp.zeros((), jnp.int32))


def make_train_step(cfg: ModelConfig, opt_cfg: adam.AdamConfig, *,
                    rules: Optional[ShardingRules] = None,
                    n_microbatches: int = 1, attn_chunk: int = 0,
                    unroll: bool = False, ce_chunk: int = 0):
    """Returns train_step(state, batch) -> (state, metrics)."""

    def loss_and_ranges(params, ranges, batch, quant_phase):
        loss, extras = T.loss_fn(
            params, batch, cfg, rules=rules,
            ranges=ranges if cfg.qat else None,
            quant_phase=quant_phase,
            remat=(cfg.remat != "none"), attn_chunk=attn_chunk,
            unroll=unroll, ce_chunk=ce_chunk)
        return loss, extras

    def train_step(state: TrainState, batch: dict[str, Array]
                   ) -> tuple[TrainState, dict[str, Array]]:
        quant_phase = state.step >= cfg.qat_delay

        if n_microbatches == 1:
            (loss, extras), grads = jax.value_and_grad(
                loss_and_ranges, has_aux=True)(
                state.params, state.ranges, batch, quant_phase)
            new_ranges = extras["ranges"] if cfg.qat else state.ranges
        else:
            mb = lambda x: x.reshape((n_microbatches,
                                      x.shape[0] // n_microbatches)
                                     + x.shape[1:])
            batch_mb = jax.tree.map(mb, batch)

            def body(carry, b):
                acc, ranges = carry
                (l, ex), g = jax.value_and_grad(
                    loss_and_ranges, has_aux=True)(
                    state.params, ranges, b, quant_phase)
                acc = jax.tree.map(
                    lambda a, gg: a + gg.astype(jnp.float32), acc, g)
                return (acc, ex["ranges"] if cfg.qat else ranges), l

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            (gsum, new_ranges), losses = jax.lax.scan(
                body, (zeros, state.ranges), batch_mb)
            grads = jax.tree.map(lambda g: g / n_microbatches, gsum)
            loss = jnp.mean(losses)

        with jax.named_scope("optimizer"):
            if cfg.qat:  # fxp32 gradient memory
                grads = quantize_grads(grads)
            new_params, new_opt, metrics = adam.update(
                opt_cfg, grads, state.opt, state.params)
            if cfg.qat:  # fxp32 weight memory
                new_params = quantize_weights(new_params)

        metrics = dict(metrics, loss=loss,
                       quant_phase=quant_phase.astype(jnp.int32))
        return TrainState(params=new_params, opt=new_opt, ranges=new_ranges,
                          step=state.step + 1), metrics

    return train_step


def learner_update_fns(cfg: ModelConfig, opt_cfg: adam.AdamConfig,
                       **kwargs) -> dict:
    """The LM train step in `train/learner.LearnerEngine`'s update-family
    contract: {mode: update_fn(state, batch) -> (state, metrics)}.

    The LM step has one trainable path (XLA autodiff), so the family is the
    single "jnp" mode; the engine's queueing/coalescing/metrics machinery
    applies unchanged.  LM
    batches carry no per-row loss mask, so pair this with
    `LearnerEngine(pad_policy="exact")` and buckets matching the batch
    shapes (`kwargs` forward to `make_train_step`).
    """
    return {"jnp": jax.jit(make_train_step(cfg, opt_cfg, **kwargs))}
