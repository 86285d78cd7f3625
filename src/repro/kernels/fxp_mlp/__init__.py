"""Network-resident fused MLP kernel — whole actor/critic forward in ONE
Pallas call (FIXAR's "entire model on-chip" regime, §V).

Why
---
FIXAR's headline throughput comes from keeping the *whole* DDPG network in
BRAM: weights never leave the chip and activations pipeline layer-to-layer
without a memory round-trip.  The per-layer path (`kernels/fxp_matmul` +
`kernels/quantize`) instead pays, per layer: a pad/unpad, an HBM activation
round-trip, a limb split, a separate range-monitor sweep, and a launch of
its own.  For DDPG's tiny layers (K <= 421) that launch overhead dominates;
this module removes all of it.

Design
------
* **VMEM residency**: every layer's weight block uses a constant index map
  `(0, 0)`, so Pallas keeps all weights resident in VMEM for the whole grid
  (= the BRAM weight memory).  Budget for the paper's actor
  (obs->400->300->act, padded to 128 lanes): 512x512 + 512x384 + 384x128
  f32 weights ~ 2.0 MB, plus a 128-row activation block (256 KB) and the
  (128, 512) f32 accumulator scratch (256 KB) — < 3 MB of the ~16 MB VMEM,
  leaving room for double buffering.
* **Grid layout**: a 1-D grid over batch blocks (`bm = min(128,
  round_up(M, 8))` rows each), declared `parallel` — the paper's intra-batch
  dataflow.  Each grid step runs the ENTIRE L-layer forward for its rows;
  inter-layer activations live in registers/VMEM and never touch HBM.
* **Fused QAT sites**: the Algorithm-1 range monitor + phase-selected
  quantizer (`kernels/quantize` semantics) runs inline on each layer input:
  per-block masked min/max are written to one lane-dense (8, 128) stats
  tile per batch block (reduced
  to per-site scalars by the wrapper, then folded into `QATState` ranges by
  `QATContext.observe`), and the activation is projected onto the Q15.16
  lattice (monitor phase) or the captured n-bit affine lattice (quantized
  phase).
* **Dual precision via scalar-prefetch phase flag**: the QAT phase bit rides
  in as the scalar-prefetch argument (SMEM, available before the body runs).
  The hi-limb MAC pass always issues; the lo-limb pass is predicated on
  `pl.when(phase == 0)` — full precision costs two MXU passes per layer,
  the quantized phase one, inside a single traced kernel.  This replaces the
  `lax.cond` over two whole `pallas_call`s.
* **Fused epilogue**: bias + ReLU/tanh happen on the accumulator before the
  next layer consumes it (the paper's accumulator -> activation-unit
  pipeline).

* **Trainable via custom VJP** (`fxp_mlp_train`): the same forward wrapped
  in `jax.custom_vjp`.  Under differentiation the fwd launch additionally
  writes per-layer residuals (the *effective* dense inputs the MACs consumed
  and the post-activation outputs), and the backward pass is a SECOND
  network-resident launch (`fxp_mlp_bwd_pallas`): layers unrolled
  last-to-first, weights + saved activations VMEM-resident, dW/db
  accumulated across batch blocks into constant-index output blocks
  (sequential "arbitrary" grid), straight-through estimators at the fused
  QAT sites.  `rl/ddpg.py` trains through it with `backend="pallas"`.

* **Whole-update fused step** (`fxp_mlp_train_step_padded`): the endpoint
  of the launch-count trajectory — one `ddpg.update` in exactly TWO
  launches (critic step, actor step) instead of the custom-VJP path's
  eight.  The contract that makes it work is *residuals stay in VMEM*: each
  launch runs forward AND backward for its loss in one kernel body, so the
  per-layer effective inputs / pre-STE site inputs / post-activation
  outputs are plain VMEM values consumed by the backward sweep in the same
  grid step — they are never written to HBM, never padded into residual
  outputs, never re-read.  dW/db accumulate across batch blocks in VMEM
  scratch (sequential "arbitrary" grid), and the LAST block runs the
  epilogue in-kernel: Adam moment/param update (`optim/adam.leaf_update` /
  `optim/fxp_adam.leaf_update(ste=False)` against SMEM-shipped
  `StepConstants`) followed by the Polyak soft-update of the target nets.
  The kernel-computed actions (launch 2) and target actions (launch 1) are
  lane-rotated next to the observations (`pltpu.roll`), so the critic's
  first layer runs the same concat-input dot as the custom-VJP path.  The
  step runs on the kernels' own layout (every leaf zero-padded to lane
  tiles, and left exactly 0 there), each launch updating its net's params,
  moments and target in place.  `rl/ddpg` owns that layout: the scanned
  training window carries the agent padded (`ddpg.to_carry`), and a single
  `ddpg.update` on an unpadded agent pads and unpads around the step.

Which update runs is `ddpg.DDPGConfig.backend`'s choice alone:
"pallas_fused_step" (the two launches above), "pallas" (the 8-launch
custom-VJP pair, kept as the fused step's parity reference) or "jnp"
(XLA autodiff).

Files: `kernel.py` (pallas_call + grid spec, fwd + bwd + whole-update
step), `ops.py` (jitted public wrappers, padding + range reduction +
custom VJP + the step's wrapper), `ref.py` (pure-jnp per-layer oracle).
The per-layer `fxp_dense` chain (kernels/fxp_matmul) is serving's `layer`
mode (`ddpg.act_batch(mode="layer")`); forward parity is asserted in
tests/kernels/test_fxp_mlp.py, gradient parity in
tests/kernels/test_fxp_mlp_grad.py, whole-step parity + the ≤2-launch
regression in tests/kernels/test_fxp_mlp_step.py.
"""
from repro.kernels.fxp_mlp.ops import (fxp_mlp_forward, fxp_mlp_infer,
                                       fxp_mlp_train, fxp_mlp_train_step_padded)
from repro.kernels.fxp_mlp.ref import ref_fxp_mlp

__all__ = ["fxp_mlp_forward", "fxp_mlp_infer", "fxp_mlp_train",
           "fxp_mlp_train_step_padded", "ref_fxp_mlp"]
