"""Batched fixed-point policy-serving engine (the tentpole of serve/policy).

Request lifecycle::

    client threads ──submit(obs)──▶ MicroBatcher (queue, flush deadline)
                                        │ drain: ≤ max_batch, pad → bucket
                                        ▼
                                  adaptive dispatcher (dispatch.CostModel)
                                        │ fused / layer / jnp per batch
                                        ▼
                                  ONE device call (ddpg.act_batch,
                                  lowered once per (bucket, mode))
                                        │ optional mesh batch-sharding
                                        │ (shard_map: B/n rows per device)
                                        ▼
                    futures resolve ◀── scatter rows back to requests

The queue, serve thread, dispatch hook, and observability wiring are the
shared `repro.runtime.engine.StreamEngine`; this module keeps only the
policy-specific parts: the actor device call, bucket padding, mesh
sharding, and the QAT saturation probe.

The engine is frozen-QAT by construction: it holds only the actor params
and a `core.qat.FrozenQuant` snapshot — there is no `QATState` anywhere on
the serve path, so no range-monitor write can happen (QuaRL/QForce-RL's
"deploy the quantized policy" framing).

Observability runs through `repro.obs` (pass an `Observability` bundle):
metrics land in the shared registry (IPS, p50/p99 request latency via the
streaming histogram, batch occupancy, phase-keyed dispatch-mode histogram
— the Fig. 8-comparable numbers land in `BENCH_serve_policy.json` via
benchmarks/serve_bench); every batch feeds the dispatch predicted-vs-
measured audit; an enabled tracer gets the full request lifecycle
(enqueue → coalesce → dispatch → launch → block_until_ready → reply) as
Chrome trace events; and `record_qat_telemetry` (or the
`qat_probe_every` cadence) probes per-site activation saturation against
the frozen quantization ranges.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.obs import Observability
from repro.rl import ddpg
from repro.runtime.engine import StreamEngine
from repro.serve.policy.batcher import BatcherConfig, MicroBatcher, PolicyFuture
from repro.serve.policy.dispatch import MODES, CostModel

Array = jax.Array
Params = dict[str, Any]


class PolicyEngine(StreamEngine):
    """Drains concurrent act requests into batched device calls.

    Synchronous use: `run_batch(obs)` — one padded, dispatched device call.
    Threaded use: `start()`, then `submit(obs).result()` from any number of
    client threads; `stop()` to drain and join.
    """

    not_running_msg = (
        "engine not serving; call start() first (or use run_batch for synchronous batches)"
    )
    already_started_msg = "engine already started"
    stopped_msg = "policy engine stopped before serving this request"
    health_running_key = "serving"
    thread_name = "policy-serve"

    def __init__(
        self,
        actor: Params,
        frozen=None,
        *,
        cost_model: Optional[CostModel] = None,
        batcher: BatcherConfig = BatcherConfig(),
        modes: Sequence[str] = MODES,
        force_mode: Optional[str] = None,
        mesh=None,
        obs: Optional[Observability] = None,
    ):
        self.actor = actor
        self.frozen = frozen
        self.batcher_config = batcher
        self.mesh = mesh
        self._sharding = NamedSharding(mesh, P("data")) if mesh is not None else None
        # batches whose bucket does not divide by the mesh size run whole on
        # one device; counted so a deployment can see its idle devices
        self.unsharded_batches = 0
        n = len(ddpg.ACTOR_ACTS)
        dims = [int(actor["l0"]["w"].shape[0])]
        dims += [int(actor[f"l{i}"]["w"].shape[1]) for i in range(n)]
        self._fns, self._sharded_fns = {}, {}
        for mode in modes:
            fn = functools.partial(ddpg.act_batch, mode=mode)
            self._fns[mode] = jax.jit(fn)
            if mesh is not None:
                # the compiler cannot partition a Pallas (Mosaic) kernel, so
                # each device runs the act on its own B/n rows; weights and
                # quant params are replicated
                self._sharded_fns[mode] = jax.jit(
                    jax.shard_map(
                        fn,
                        mesh=mesh,
                        in_specs=(P(), P("data"), P()),
                        out_specs=P("data"),
                        check_vma=False,
                    )
                )
        self._qat_probe_fn = None
        self._qat_ranges_recorded = False
        obs = obs if obs is not None else Observability()
        super().__init__(
            prefix="serve",
            phase="act",
            items_name="actions",
            calls_name="batches",
            queue=MicroBatcher(batcher, registry=obs.registry, prefix="serve.batcher"),
            modes=modes,
            dims=dims,
            cost_model=cost_model or CostModel.default(),
            force_mode=force_mode,
            obs=obs,
        )

    @classmethod
    def from_ddpg(cls, state: "ddpg.DDPGState", **kwargs) -> "PolicyEngine":
        """Snapshot a trained DDPG state into a serving engine (freezes the
        actor's site quant params; QAT-off states serve unquantized)."""
        return cls(state.actor, ddpg.freeze_actor_quant(state), **kwargs)

    # ------------------------------------------------------------------ #
    # dispatch + device call
    # ------------------------------------------------------------------ #

    def warmup(
        self, buckets: Optional[Sequence[int]] = None, modes: Optional[Sequence[str]] = None
    ) -> int:
        """Lower + compile the (bucket, mode) executables ahead of traffic.
        Returns the number of executables warmed."""
        n = 0
        dummy = np.zeros((1, self.dims[0]), np.float32)
        for bucket in buckets or self.batcher_config.buckets:
            for mode in modes or ([self.force_mode] if self.force_mode else self.modes):
                x = np.broadcast_to(dummy, (bucket, self.dims[0]))
                self._call(np.ascontiguousarray(x), mode)
                n += 1
        return n

    def _call(self, x_padded: np.ndarray, mode: str) -> Array:
        if mode not in self._fns:
            raise ValueError(f"mode {mode!r} not in enabled modes {self.modes}")
        x = jnp.asarray(x_padded)
        if self._sharding is None:
            return self._fns[mode](self.actor, x, self.frozen)
        if x.shape[0] % self.mesh.size:
            self.unsharded_batches += 1
            return self._fns[mode](self.actor, x, self.frozen)
        x = jax.device_put(x, self._sharding)
        return self._sharded_fns[mode](self.actor, x, self.frozen)

    def run_batch(self, obs) -> np.ndarray:
        """One engine pass over (n, obs_dim) observations: pad to a bucket,
        dispatch adaptively, call the device once, unpad.  Batches larger
        than the top bucket are chunked."""
        obs = np.asarray(obs, np.float32)
        n = obs.shape[0]
        cap = self.batcher_config.max_batch
        if n > cap:
            return np.concatenate([self.run_batch(obs[i : i + cap]) for i in range(0, n, cap)])
        tracer = self.obs.tracer
        bucket = self.batcher_config.bucket_for(n)
        with tracer.span("serve.dispatch", bucket=bucket, rows=n) as sp:
            mode = self.choose_mode(bucket)
            sp.set(mode=mode)
        x = np.zeros((bucket, self.dims[0]), np.float32)
        x[:n] = obs
        t0 = time.perf_counter()
        with tracer.span("serve.launch", bucket=bucket, mode=mode):
            y = self._call(x, mode)
        with tracer.span("serve.block_until_ready", bucket=bucket, mode=mode):
            y = jax.block_until_ready(y)
        if self._finish_call(n, bucket, mode, time.perf_counter() - t0):
            self.record_qat_telemetry(x, rows=n)
        return np.asarray(y[:n])

    # ------------------------------------------------------------------ #
    # threaded serving
    # ------------------------------------------------------------------ #

    def submit(self, obs) -> PolicyFuture:
        """Enqueue one observation (obs_dim,); resolve via .result().
        Raises RuntimeError once the engine is stopped (never leaves a
        future dangling in a queue nothing drains)."""
        self._require_running()
        return self._batcher.submit(obs)

    def _process(self, reqs: list) -> list:
        return list(self.run_batch(np.stack([r.obs for r in reqs])))

    # ------------------------------------------------------------------ #
    # telemetry
    # ------------------------------------------------------------------ #

    def record_qat_telemetry(self, obs, rows: Optional[int] = None) -> dict:
        """Probe per-site activation ranges + saturation on one (possibly
        padded) observation batch and fold them into the registry.

        `rows` masks out padding rows (a bucket-padded batch's zero rows
        would otherwise drag act_min to 0 and dilute the saturation rate).
        The probe is one extra jitted forward per call — it retraces per
        bucket shape, which the engine's fixed bucket set bounds.  Returns
        the per-site `qat_telemetry` stats view.
        """
        if not self._qat_ranges_recorded and self.frozen is not None and self.frozen.quantized:
            for i in range(len(self.frozen.a_mins)):
                self._qat.record_range(
                    f"act{i}", float(self.frozen.a_mins[i]), float(self.frozen.a_maxs[i])
                )
            self._qat_ranges_recorded = True
        if self._qat_probe_fn is None:
            self._qat_probe_fn = jax.jit(ddpg.actor_site_telemetry)
        x = np.asarray(obs, np.float32)
        mask = None
        if rows is not None and rows < x.shape[0]:
            mask = np.zeros((x.shape[0],), np.float32)
            mask[:rows] = 1.0
        mns, mxs, sats = jax.block_until_ready(
            self._qat_probe_fn(
                self.actor,
                jnp.asarray(x),
                self.frozen,
                mask if mask is None else jnp.asarray(mask),
            )
        )
        mns, mxs, sats = np.asarray(mns), np.asarray(mxs), np.asarray(sats)
        for i in range(mns.shape[0]):
            self._qat.record_probe(f"act{i}", float(mns[i]), float(mxs[i]), float(sats[i]))
        return self._qat.stats()

    # ------------------------------------------------------------------ #
    # metrics
    # ------------------------------------------------------------------ #

    def stats(self) -> dict:
        """Serving metrics so far, read off the shared registry: exact
        lifetime totals, streaming-histogram latency quantiles, the
        phase-keyed dispatch histogram, and the two audit sections."""
        m = self._metrics
        device_s = m.device_s
        wall = m.wall_s()
        return {
            "requests": m.requests,
            "actions": m.items,
            "batches": m.calls,
            "ips_device": m.items / device_s if device_s > 0 else None,
            "ips_wall": (m.requests / wall if wall else None),
            "p50_ms": m.latency_ms(0.50),
            "p99_ms": m.latency_ms(0.99),
            "batch_occupancy": m.occupancy(),
            "unsharded_batches": self.unsharded_batches,
            "mode_histogram": m.mode_histogram(),
            "cost_model": self.cost_model.source,
            "dispatch_audit": self._audit.snapshot(),
            "qat_telemetry": self._qat.stats(),
        }


__all__ = ["PolicyEngine"]
