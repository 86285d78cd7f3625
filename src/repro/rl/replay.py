"""On-device ring-buffer replay (the host-side transition store of Fig. 2,
moved on-device for the fused loop; the host loop keeps it on CPU arrays).

Every function here is pure in its array arguments and shape-static, so the
buffer composes with ``jit``/``vmap``/``lax.scan``: ``rl/loop.train_device``
carries the whole ``ReplayBuffer`` through its scanned act→store→update
chain and the buffer never leaves the device.  ``add``/``add_batch`` store a
batch of transitions (``add_batch`` takes the same dict layout ``sample``
returns and ``ddpg.update`` consumes, making store/sample symmetric);
``sample`` draws a uniform random batch.

A single-row add writes each field with a slice write at ``ptr`` rather than
a scatter: a scatter pins the ring row-major (``{1,0}``), while the sample's
batch gather reads it column-major (``{0,1}``), so on a TPU XLA would copy
every 10^6-row field into that layout each timestep just to pick the batch.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

Array = jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ReplayBuffer:
    obs: Array        # (cap, obs_dim)
    action: Array     # (cap, act_dim)
    reward: Array     # (cap,)
    next_obs: Array   # (cap, obs_dim)
    done: Array       # (cap,)
    ptr: Array        # i32 — next write slot
    size: Array       # i32 — valid entries


def init(capacity: int, obs_dim: int, act_dim: int) -> ReplayBuffer:
    return ReplayBuffer(
        obs=jnp.zeros((capacity, obs_dim), jnp.float32),
        action=jnp.zeros((capacity, act_dim), jnp.float32),
        reward=jnp.zeros((capacity,), jnp.float32),
        next_obs=jnp.zeros((capacity, obs_dim), jnp.float32),
        done=jnp.zeros((capacity,), jnp.bool_),
        ptr=jnp.zeros((), jnp.int32),
        size=jnp.zeros((), jnp.int32),
    )


def add(buf: ReplayBuffer, obs, action, reward, next_obs, done) -> ReplayBuffer:
    """Add a batch of B transitions (B may be 1). Wraps modulo capacity.

    B > capacity is handled FIFO-correctly: only the trailing `cap` rows
    can survive the ring, so the leading rows are dropped *before* the
    scatter — `(ptr + arange(B)) % cap` would contain duplicate indices,
    and `.at[idx].set` leaves the winner among duplicate writes
    unspecified, i.e. the surviving rows would be arbitrary, not the
    newest.  `ptr` still advances by the full B (mod cap), so the write
    cursor lands exactly past the newest retained row.  One kept row sits
    at `ptr < cap` and never wraps, so it is a slice write (module doc).
    """
    b = obs.shape[0]
    cap = buf.obs.shape[0]
    keep = min(b, cap)                       # static: shapes are concrete
    if keep == 1:
        def put(field, x):
            row = jnp.asarray(x[b - 1 :], field.dtype)
            return lax.dynamic_update_slice_in_dim(field, row, buf.ptr, 0)
    else:
        idx = (buf.ptr + (b - keep) + jnp.arange(keep)) % cap
        put = lambda field, x: field.at[idx].set(x[b - keep :])  # newest `keep` rows win
    return ReplayBuffer(
        obs=put(buf.obs, obs),
        action=put(buf.action, action),
        reward=put(buf.reward, reward),
        next_obs=put(buf.next_obs, next_obs),
        done=put(buf.done, done),
        ptr=(buf.ptr + b) % cap,
        size=jnp.minimum(buf.size + b, cap),
    )


def add_batch(buf: ReplayBuffer, batch: dict[str, Array]) -> ReplayBuffer:
    """`add` in the dict transition layout (`obs`/`action`/`reward`/
    `next_obs`/`done`, each with a leading batch axis) — the layout `sample`
    returns and `ddpg.update` consumes.  Pure and jit/scan-safe; the scanned
    device loop stores its per-step fleet transitions through this."""
    return add(
        buf, batch["obs"], batch["action"], batch["reward"], batch["next_obs"], batch["done"]
    )


def sample(buf: ReplayBuffer, key: Array, batch: int) -> dict[str, Array]:
    """Uniform random batch of B transitions (paper: 'a random batch of B
    transitions ... sampled in order to send to FPGA')."""
    idx = jax.random.randint(key, (batch,), 0, jnp.maximum(buf.size, 1))
    return {
        "obs": buf.obs[idx],
        "action": buf.action[idx],
        "reward": buf.reward[idx],
        "next_obs": buf.next_obs[idx],
        "done": buf.done[idx],
    }
