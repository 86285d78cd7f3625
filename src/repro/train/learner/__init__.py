"""Batched fixed-point learner engine — `serve/policy`'s training-side twin.

FIXAR's headline number is *training* throughput: 25293.3 IPS delivered by
intra-batch parallelism on the adaptive array (Fig. 8), with QuaRL
(arXiv:1910.01055) showing that quantized *training* is where RL
quantization pays off and Sakr & Shanbhag (arXiv:1812.11732) grounding the
fixed-point back-prop path.  `serve/policy` (PR 3) productized the acting
path; this package does the same for the update path:

    producers ──submit(replay batch / trajectory chunk)──▶ UpdateBatcher
                                  │  coalesce FIFO requests to ≤ max rows,
                                  │  pad to a bucket (+ zero-weight mask)
                                  ▼
                     ONE ddpg.update per micro-batch, through the
                     backend its DDPGConfig names
                     (sequential: the learner owns the DDPGState)
                                  │
                 futures resolve ◀── per-request metrics

Design decisions, mirroring `serve/policy`'s engine doc:

  * **One state, sequential updates.**  Unlike serving (stateless actor
    snapshot, embarrassingly parallel), training mutates a single
    `DDPGState`.  The engine owns it; micro-batches apply in FIFO order on
    one drain thread (or under a lock for synchronous `run_update`), so a
    streamed run is a *deterministic* sequence of `ddpg.update` calls.
  * **Coalescing, not splitting.**  The throughput win is combining many
    small update requests (per-actor replay batches, trajectory chunks)
    into one bucket-padded batch for ONE update — intra-batch
    parallelism, the paper's training dataflow.  Oversized
    requests are chunked to the top bucket at submit time.
  * **Bit-exact streaming.**  A request whose row count hits a bucket
    exactly runs the *same jitted `ddpg.update` executable* a direct call
    would — results are bit-identical (pinned in
    tests/train/test_learner.py).  Padded batches carry a zero-weight
    `mask` row (`ddpg.update`'s weighted-loss contract), so pad rows
    contribute exactly zero gradient.
  * **One update, no dispatch.**  `ddpg.DDPGConfig.backend` alone decides
    how DDPG trains; `LearnerEngine.from_ddpg` jits `ddpg.update` for it
    and names the engine's one mode after it.
  * **Generic update family.**  The engine drives any one
    `update_fn(state, batch) -> (state, metrics)`, keyed by its mode name;
    `train/step.learner_update_fns` adapts the LM train step.

`benchmarks/learner_bench.py` turns this into the Fig. 9-comparable
training-throughput line (`BENCH_learner.json`: updates/sec, train IPS,
p50/p99, per-phase mode histogram), schema-gated in CI next to the kernel
and serving artifacts.

Public API:
  LearnerEngine   — queue + micro-batch + one update function + metrics
  UpdateBatcher   — multi-row request queue (reuses serve/policy machinery)
"""
from repro.train.learner.batcher import UpdateBatcher, UpdateRequest
from repro.train.learner.engine import LearnerEngine

__all__ = ["LearnerEngine", "UpdateBatcher", "UpdateRequest"]
