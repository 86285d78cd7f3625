"""Production mesh builders.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state — required because the dry-run force-creates 512
host devices via XLA_FLAGS *before* any jax import, while tests and benches
must keep seeing 1 CPU device.
"""
from __future__ import annotations

import jax


def make_auto_mesh(shape, axes):
    """jax.make_mesh with every axis in Auto mode (the sharding rules leave
    partitioning to the compiler)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
    Multi-pod:  (pod=2, data=16, model=16) = 512 chips; `pod` composes with
    `data` for hierarchical data parallelism (DESIGN.md §5)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_serve_mesh(n_data=None):
    """Policy-serving mesh: one `data` axis over the local devices.

    The DDPG policy net is tiny (fits in a single core's VMEM), so scale-out
    is pure data parallelism — `serve/policy` shards the micro-batch axis
    across this mesh and keeps the weights replicated.  Defaults to every
    visible device; on a 1-CPU test host this degenerates to a 1-device
    mesh (sharding becomes a no-op, same code path)."""
    n = n_data if n_data is not None else len(jax.devices())
    return make_auto_mesh((n,), ("data",))


def make_debug_mesh(n_data: int = 2, n_model: int = 4, *, multi_pod: bool = False):
    """Small mesh for subprocess sharding tests (8 host devices)."""
    if multi_pod:
        shape, axes = (2, n_data, n_model), ("pod", "data", "model")
    else:
        shape, axes = (n_data, n_model), ("data", "model")
    return make_auto_mesh(shape, axes)
