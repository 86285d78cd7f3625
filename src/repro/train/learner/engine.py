"""Batched learner engine (the tentpole of train/learner — see __init__).

`LearnerEngine` owns one training state and streams update requests
through it: coalesce → pad to bucket → ONE call of its update function per
micro-batch, applied sequentially.

The queue, serve thread, and observability wiring are the shared
`repro.runtime.engine.StreamEngine`; this module keeps only the
learner-specific parts: sequential state mutation under `_ulock`, the
mask/exact pad policy, oversized-request chunking, and the live-QAT
telemetry probe.

Observability runs through `repro.obs` (pass an `Observability` bundle):
the shared registry carries the training-throughput story end to end —
updates/sec, trained-samples/sec (train IPS, the Fig. 8 headline axis),
p50/p99 request latency via the streaming histogram, batch occupancy, the
phase-keyed mode histogram — plus the per-site QAT range/saturation
telemetry pulled straight off the live `QATState` between updates
(`benchmarks/learner_bench` lands it all in `BENCH_learner.json`).  An
enabled tracer gets per-update spans (dispatch → launch →
block_until_ready).
"""

from __future__ import annotations

import threading
import time
from functools import partial
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import Observability
from repro.rl import ddpg
from repro.runtime.engine import BatcherConfig, StreamEngine
from repro.train.learner.batcher import (
    TRANSITION_KEYS,
    JoinedFuture,
    UpdateBatcher,
    as_transition_batch,
    concat_batches,
    merge_chunk_metrics,
)

# learner-shaped default buckets: update batches are replay-sized (tens to
# hundreds of rows), never single observations
DEFAULT_BUCKETS = (8, 32, 128)

UpdateFn = Callable[[Any, dict], tuple[Any, dict]]


class LearnerEngine(StreamEngine):
    """Streams batched updates through one update function.

    Synchronous use: `run_update(batch)` — one (or, for oversized batches,
    a chunked sequence of) padded, sequentially applied update(s).
    Threaded use: `start()`, then `submit(batch).result()` from any number
    of producer threads; `stop()` to drain and join.

    The engine is generic over the update family: `update_fns` is
    `{mode: update_fn(state, batch) -> (new_state, metrics)}` with exactly
    one entry, the mode naming it in the metrics.  `from_ddpg` builds the
    DDPG update for the config's backend; `train/step.learner_update_fns`
    adapts the LM train step.

    `pad_policy`:
      * "mask"  — pad short batches to the bucket with zero rows plus a
        zero-weight `batch["mask"]` (the `ddpg.update` weighted-loss
        contract: pad rows contribute exactly zero gradient);
      * "exact" — reject row counts that miss every bucket (for update
        families without a mask contract, e.g. the LM step).
    """

    not_running_msg = (
        "learner not streaming; call start() first (or use run_update for synchronous updates)"
    )
    already_started_msg = "learner already started"
    stopped_msg = "learner stopped before applying this update"
    health_running_key = "training"
    thread_name = "learner"

    def __init__(
        self,
        state,
        update_fns: dict[str, UpdateFn],
        *,
        batcher: Optional[BatcherConfig] = None,
        pad_policy: str = "mask",
        required_keys: Optional[Sequence[str]] = None,
        warmup_template: Optional[Callable[[int], dict]] = None,
        obs: Optional[Observability] = None,
    ):
        if len(update_fns) != 1:
            raise ValueError(
                f"a learner trains through one update function; got modes {sorted(update_fns)}"
            )
        self._state = state
        ((mode, self._update_fn),) = update_fns.items()
        self.batcher_config = batcher or BatcherConfig(buckets=DEFAULT_BUCKETS)
        if pad_policy not in ("mask", "exact"):
            raise ValueError(f"pad_policy {pad_policy!r}; 'mask' | 'exact'")
        self.pad_policy = pad_policy
        self.required_keys = required_keys
        self.warmup_template = warmup_template
        # one lock serializes state mutation (sync callers + drain thread):
        # updates are sequential by construction
        self._ulock = threading.Lock()
        obs = obs if obs is not None else Observability()
        super().__init__(
            prefix="learner",
            phase="train",
            items_name="transitions",
            calls_name="updates",
            queue=UpdateBatcher(
                self.batcher_config,
                required_keys=required_keys,
                registry=obs.registry,
                prefix="learner.batcher",
            ),
            modes=(mode,),
            obs=obs,
        )

    @classmethod
    def from_ddpg(
        cls,
        state: "ddpg.DDPGState",
        cfg: "ddpg.DDPGConfig",
        **kwargs,
    ) -> "LearnerEngine":
        """The DDPG learner: one jitted `ddpg.update` through `cfg.backend`,
        which also names the engine's mode (executables per bucket come
        from the jit cache, so a bucket-sized stream and a direct call
        share the SAME program — that is what makes streamed results
        bit-identical)."""
        fns = {cfg.backend: jax.jit(partial(ddpg.update, cfg=cfg))}
        n = len(ddpg.ACTOR_ACTS)
        dims = [int(state.actor["l0"]["w"].shape[0])] + [
            int(state.actor[f"l{i}"]["w"].shape[1]) for i in range(n)
        ]

        def transitions(rows: int) -> dict:
            return {
                "obs": np.zeros((rows, dims[0]), np.float32),
                "action": np.zeros((rows, dims[-1]), np.float32),
                "reward": np.zeros((rows,), np.float32),
                "next_obs": np.zeros((rows, dims[0]), np.float32),
                "done": np.zeros((rows,), bool),
            }

        kwargs.setdefault("required_keys", TRANSITION_KEYS)
        kwargs.setdefault("warmup_template", transitions)
        return cls(state, fns, **kwargs)

    # ------------------------------------------------------------------ #
    # state
    # ------------------------------------------------------------------ #

    @property
    def state(self):
        return self._state

    def load_state(self, state) -> None:
        """Install a (fresh or checkpointed) training state; subsequent
        updates stream onto it."""
        with self._ulock:
            self._state = state

    # ------------------------------------------------------------------ #
    # device call
    # ------------------------------------------------------------------ #

    def _pad(self, batch: dict[str, np.ndarray], rows: int, bucket: int) -> dict[str, np.ndarray]:
        """Pad `rows` transitions up to `bucket` (zero rows + zero-weight
        mask).  Exact fits pass through untouched — no mask key, so the
        program is byte-for-byte the direct-call executable."""
        if rows == bucket:
            return batch
        if self.pad_policy == "exact":
            raise ValueError(
                f"pad_policy='exact': batch of {rows} rows must hit a "
                f"bucket exactly ({self.batcher_config.buckets})"
            )
        pad = bucket - rows
        out = {
            k: np.concatenate([v, np.zeros((pad,) + v.shape[1:], v.dtype)])
            for k, v in batch.items()
        }
        out["mask"] = np.concatenate([np.ones(rows, np.float32), np.zeros(pad, np.float32)])
        return out

    def warmup(
        self,
        buckets: Optional[Sequence[int]] = None,
        padded: bool = False,
    ) -> int:
        """Lower + compile the per-bucket update executables ahead of
        traffic without advancing the training state.  `padded=True` also
        warms the masked variants (bucket-1 rows).  Returns the number of
        executables warmed.

        Dummy batches come from the engine's `warmup_template` (a
        `rows -> batch` callable; `from_ddpg` installs the DDPG transition
        shape).  Generic engines must pass one at construction to warm up.
        """
        if self.warmup_template is None:
            raise RuntimeError(
                "no warmup_template: this engine's update family has no "
                "known batch shape — pass warmup_template=rows->batch at "
                "construction (from_ddpg installs the DDPG one)"
            )
        n = 0
        for bucket in buckets or self.batcher_config.buckets:
            rows_list = [bucket] + ([bucket - 1] if padded and bucket > 1 else [])
            for rows in rows_list:
                batch = self._pad(self.warmup_template(rows), rows, bucket)
                with self._ulock:
                    jax.block_until_ready(self._update_fn(self._state, batch))
                n += 1
        return n

    def _apply(self, batch: dict[str, np.ndarray], rows: int) -> dict[str, float]:
        """One micro-batch through the update function and onto the state."""
        tracer = self.obs.tracer
        bucket = self.batcher_config.bucket_for(rows)
        with tracer.span("learner.dispatch", bucket=bucket, rows=rows) as sp:
            mode = self.choose_mode(bucket)
            sp.set(mode=mode)
        padded = self._pad(batch, rows, bucket)
        with self._ulock:
            t0 = time.perf_counter()
            with tracer.span("learner.launch", bucket=bucket, mode=mode):
                new_state, metrics = self._update_fn(self._state, padded)
            with tracer.span("learner.block_until_ready", bucket=bucket, mode=mode):
                jax.block_until_ready((new_state, metrics))
            device_s = time.perf_counter() - t0
            self._state = new_state
        if self._finish_call(rows, bucket, mode, device_s):
            self.record_qat_telemetry(batch)
        out = {k: float(v) for k, v in metrics.items()}
        out["mode"] = mode
        return out

    def _chunks(self, arrs: dict[str, np.ndarray], rows: int):
        """Top-bucket-sized (chunk, rows) slices of an oversized request
        — key-agnostic (the update family defines the batch schema)."""
        cap = self.batcher_config.max_batch
        for lo in range(0, rows, cap):
            yield ({k: v[lo : lo + cap] for k, v in arrs.items()}, min(cap, rows - lo))

    def run_update(self, batch) -> dict[str, float]:
        """Synchronously stream one update request: chunk to the top
        bucket if oversized, pad, apply sequentially.  Returns
        the update metrics (row-weighted means across chunks)."""
        arrs, rows = as_transition_batch(batch, self.required_keys)
        if rows <= self.batcher_config.max_batch:
            return self._apply(arrs, rows)
        return merge_chunk_metrics(
            [(self._apply(part, n), n) for part, n in self._chunks(arrs, rows)]
        )

    # ------------------------------------------------------------------ #
    # threaded streaming
    # ------------------------------------------------------------------ #

    def submit(self, batch):
        """Enqueue one update request (replay batch or trajectory chunk);
        resolve via `.result()` to the update metrics.  Oversized requests
        split into top-bucket chunks behind one aggregate future."""
        self._require_running()
        arrs, rows = as_transition_batch(batch, self.required_keys)
        if rows <= self.batcher_config.max_batch:
            return self._batcher.submit(arrs)
        return JoinedFuture(
            [(self._batcher.submit(part), n) for part, n in self._chunks(arrs, rows)]
        )

    def _process(self, reqs: list) -> list:
        rows = sum(r.rows for r in reqs)
        metrics = self._apply(concat_batches([r.batch for r in reqs]), rows)
        # coalesced requests share one update: metrics are joint
        return [dict(metrics, rows=r.rows) for r in reqs]

    # ------------------------------------------------------------------ #
    # telemetry
    # ------------------------------------------------------------------ #

    def record_qat_telemetry(self, batch: Optional[dict] = None) -> dict:
        """Snapshot the live `QATState`'s per-site ranges into the
        registry, and — when `batch` carries observations — probe per-site
        activation extrema + saturation against a frozen snapshot of the
        current quant params.  No-op (returns the current view) for
        non-DDPG states or QAT-off training.  Returns the per-site
        `qat_telemetry` stats view.
        """
        qat = getattr(self._state, "qat", None)
        if qat is None or not qat.config.enabled:
            return self._qat.stats()
        self._qat.record_state(qat)
        if batch is not None and "obs" in batch:
            # eager probe (replay batches vary in row count; jit would
            # retrace per shape) against the would-freeze-now quant params
            frozen = ddpg.freeze_actor_quant(self._state)
            mns, mxs, sats = ddpg.actor_site_telemetry(
                self._state.actor, jnp.asarray(batch["obs"], jnp.float32), frozen
            )
            mns, mxs, sats = (np.asarray(mns), np.asarray(mxs), np.asarray(sats))
            for i in range(mns.shape[0]):
                self._qat.record_probe(f"act{i}", float(mns[i]), float(mxs[i]), float(sats[i]))
        return self._qat.stats()

    # ------------------------------------------------------------------ #
    # metrics
    # ------------------------------------------------------------------ #

    def stats(self) -> dict:
        """Training-throughput metrics so far, read off the shared
        registry: exact lifetime totals, streaming-histogram latency
        quantiles, the phase-keyed mode histogram, and the QAT telemetry
        section."""
        m = self._metrics
        device_s = m.device_s
        wall = m.wall_s()
        return {
            "requests": m.requests,
            "updates": m.calls,
            "transitions": m.items,
            "updates_per_s_device": (m.calls / device_s if device_s > 0 else None),
            "updates_per_s_wall": (m.calls / wall if wall else None),
            "train_ips_device": (m.items / device_s if device_s > 0 else None),
            "train_ips_wall": (m.items / wall if wall else None),
            "p50_ms": m.latency_ms(0.50),
            "p99_ms": m.latency_ms(0.99),
            "batch_occupancy": m.occupancy(),
            "mode_histogram": m.mode_histogram(),
            "qat_telemetry": self._qat.stats(),
        }


__all__ = ["LearnerEngine", "DEFAULT_BUCKETS"]
