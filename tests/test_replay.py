import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.rl import replay


def test_add_and_sample():
    buf = replay.init(16, 3, 2)
    obs = jnp.arange(12, dtype=jnp.float32).reshape(4, 3)
    act = jnp.ones((4, 2))
    buf = replay.add(buf, obs, act, jnp.ones((4,)), obs + 1,
                     jnp.zeros((4,), jnp.bool_))
    assert int(buf.size) == 4 and int(buf.ptr) == 4
    batch = replay.sample(buf, jax.random.key(0), 8)
    assert batch["obs"].shape == (8, 3)
    # sampled indices must come from the filled region
    assert float(batch["obs"].max()) <= 11.0


def test_ring_wraparound():
    buf = replay.init(4, 1, 1)
    for i in range(6):
        buf = replay.add(buf, jnp.full((1, 1), float(i)), jnp.zeros((1, 1)),
                         jnp.zeros((1,)), jnp.zeros((1, 1)),
                         jnp.zeros((1,), jnp.bool_))
    assert int(buf.size) == 4
    assert int(buf.ptr) == 2
    vals = sorted(np.asarray(buf.obs).ravel().tolist())
    assert vals == [2.0, 3.0, 4.0, 5.0]  # oldest overwritten


def test_single_add_crossing_capacity_wraps():
    """One `add` whose batch straddles the capacity boundary: slots wrap
    modulo cap, ptr lands past the wrap, size saturates at cap."""
    cap = 8
    buf = replay.init(cap, 1, 1)
    fill = jnp.arange(6, dtype=jnp.float32)[:, None]  # ptr -> 6
    buf = replay.add(buf, fill, jnp.zeros((6, 1)), jnp.zeros((6,)),
                     jnp.zeros((6, 1)), jnp.zeros((6,), jnp.bool_))
    cross = jnp.arange(100.0, 105.0)[:, None]          # slots 6,7,0,1,2
    buf = replay.add(buf, cross, jnp.ones((5, 1)), jnp.ones((5,)),
                     cross + 1, jnp.ones((5,), jnp.bool_))
    assert int(buf.ptr) == (6 + 5) % cap == 3
    assert int(buf.size) == cap
    obs = np.asarray(buf.obs).ravel()
    np.testing.assert_array_equal(obs[[6, 7, 0, 1, 2]],
                                  [100.0, 101.0, 102.0, 103.0, 104.0])
    np.testing.assert_array_equal(obs[[3, 4, 5]], [3.0, 4.0, 5.0])
    # every field wrapped in lockstep with obs
    np.testing.assert_array_equal(np.asarray(buf.next_obs).ravel()[[6, 0]],
                                  [101.0, 103.0])
    assert bool(np.asarray(buf.done)[[6, 7, 0, 1, 2]].all())
    assert not bool(np.asarray(buf.done)[[3, 4, 5]].any())


def test_ptr_size_invariants_over_many_adds():
    cap = 8
    buf = replay.init(cap, 1, 1)
    written = 0
    for b in (3, 5, 7, 2, 8, 1):
        batch = jnp.ones((b, 1))
        buf = replay.add(buf, batch, batch, jnp.ones((b,)), batch,
                         jnp.zeros((b,), jnp.bool_))
        written += b
        assert int(buf.ptr) == written % cap
        assert int(buf.size) == min(written, cap)


def test_sample_never_returns_uninitialized_slots():
    """Partially-filled buffer: sampling must only draw from [0, size) —
    uninitialized slots (zeros here) may never surface."""
    buf = replay.init(64, 1, 1)
    filled = jnp.full((3, 1), 7.0)
    buf = replay.add(buf, filled, filled, jnp.full((3,), 7.0), filled,
                     jnp.ones((3,), jnp.bool_))
    for seed in range(20):
        batch = replay.sample(buf, jax.random.key(seed), 32)
        assert bool((np.asarray(batch["obs"]) == 7.0).all()), \
            f"seed {seed} sampled an unwritten slot"
        assert bool(np.asarray(batch["done"]).all())


def test_sample_from_empty_buffer_is_safe():
    """size=0 guard: sampling an empty buffer must not index garbage
    (clamped to slot 0) — callers gate on warmup, but the op stays total."""
    buf = replay.init(16, 2, 1)
    batch = replay.sample(buf, jax.random.key(0), 4)
    assert batch["obs"].shape == (4, 2)
    assert bool((np.asarray(batch["obs"]) == 0.0).all())


def test_overflow_batch_keeps_newest_transitions():
    """One `add` with B > capacity: `(ptr + arange(B)) % cap` holds
    duplicate indices, and `.at[idx].set` leaves the winner among duplicate
    writes UNSPECIFIED — the fix drops the doomed leading rows before the
    scatter so the newest `cap` transitions deterministically win, with
    ptr/size accounted as if all B were written then wrapped."""
    cap = 4
    buf = replay.init(cap, 1, 1)
    # pre-fill two slots so the overflow also exercises a nonzero ptr
    pre = jnp.full((2, 1), -1.0)
    buf = replay.add(buf, pre, pre, jnp.zeros((2,)), pre,
                     jnp.zeros((2,), jnp.bool_))
    big = jnp.arange(10.0, 16.0)[:, None]          # 6 rows into cap=4
    buf = replay.add(buf, big, big + 100, jnp.arange(6.0), big + 200,
                     jnp.ones((6,), jnp.bool_))
    assert int(buf.size) == cap
    assert int(buf.ptr) == (2 + 6) % cap == 0
    # the newest 4 rows (12..15) must occupy slots (ptr+2+arange(4))%4 =
    # [0, 1, 2, 3] shifted by the dropped rows: start = 2 + (6-4) = 4 -> 0
    obs = np.asarray(buf.obs).ravel()
    np.testing.assert_array_equal(obs, [12.0, 13.0, 14.0, 15.0])
    # all fields wrap in lockstep
    np.testing.assert_array_equal(np.asarray(buf.action).ravel(),
                                  [112.0, 113.0, 114.0, 115.0])
    np.testing.assert_array_equal(np.asarray(buf.reward),
                                  [2.0, 3.0, 4.0, 5.0])
    np.testing.assert_array_equal(np.asarray(buf.next_obs).ravel(),
                                  [212.0, 213.0, 214.0, 215.0])
    assert bool(np.asarray(buf.done).all())


def test_overflow_batch_exact_multiple_of_capacity():
    """B == 2*cap: the last cap rows land exactly where ptr arithmetic
    says, and a jitted add agrees with the eager one."""
    cap = 3
    buf = replay.init(cap, 1, 1)
    big = jnp.arange(6.0)[:, None]
    add_jit = jax.jit(replay.add)
    buf = add_jit(buf, big, big, jnp.arange(6.0), big,
                  jnp.zeros((6,), jnp.bool_))
    assert int(buf.ptr) == 0 and int(buf.size) == cap
    np.testing.assert_array_equal(np.asarray(buf.obs).ravel(),
                                  [3.0, 4.0, 5.0])


def test_add_batch_matches_add_bitwise():
    """`add_batch` is `add` in the dict transition layout `sample` returns
    and the scanned device loop stores through — same ring, bit for bit."""
    rng = np.random.default_rng(0)
    batch = {
        "obs": jnp.asarray(rng.standard_normal((5, 3)), jnp.float32),
        "action": jnp.asarray(rng.standard_normal((5, 2)), jnp.float32),
        "reward": jnp.asarray(rng.standard_normal((5,)), jnp.float32),
        "next_obs": jnp.asarray(rng.standard_normal((5, 3)), jnp.float32),
        "done": jnp.asarray(rng.integers(0, 2, (5,)), bool),
    }
    b1 = replay.add_batch(replay.init(8, 3, 2), batch)
    b2 = replay.add(replay.init(8, 3, 2), batch["obs"], batch["action"],
                    batch["reward"], batch["next_obs"], batch["done"])
    for f in ("obs", "action", "reward", "next_obs", "done", "ptr", "size"):
        np.testing.assert_array_equal(np.asarray(getattr(b1, f)),
                                      np.asarray(getattr(b2, f)), f)
    # round-trips under jit/scan: store what sample returns
    def body(buf, key):
        return replay.add_batch(buf, replay.sample(buf, key, 4)), None
    out, _ = jax.jit(lambda b, ks: jax.lax.scan(body, b, ks))(
        b1, jax.random.split(jax.random.key(1), 6))
    assert int(out.size) == 8 and int(out.ptr) == (5 + 6 * 4) % 8


@pytest.mark.parametrize("b", [1, 4, 7, 10])
def test_add_matches_a_plain_ring(b):
    """Adds of B rows, jitted as the loops run them, against a numpy ring
    that stores one row at a time, bit for bit after every add.  Capacity 7
    is prime, so 4-row adds start at every slot; B = 1 (the slice store)
    writes every slot from 0 to cap-1 twice, overwriting a full ring; B = 7
    and 10 (= cap + 3) take the scatter path."""
    cap, obs_dim, act_dim = 7, 3, 2
    rng = np.random.default_rng(b)
    add = jax.jit(replay.add_batch)
    buf = replay.init(cap, obs_dim, act_dim)
    ring = {"obs": np.zeros((cap, obs_dim), np.float32),
            "action": np.zeros((cap, act_dim), np.float32),
            "reward": np.zeros(cap, np.float32),
            "next_obs": np.zeros((cap, obs_dim), np.float32),
            "done": np.zeros(cap, bool)}
    ptr = size = 0
    for _ in range(2 * cap // b + 2):
        rows = {"obs": rng.standard_normal((b, obs_dim)).astype(np.float32),
                "action": rng.standard_normal((b, act_dim)).astype(np.float32),
                "reward": rng.standard_normal(b).astype(np.float32),
                "next_obs": rng.standard_normal((b, obs_dim)).astype(np.float32),
                "done": rng.integers(0, 2, b).astype(bool)}
        buf = add(buf, {k: jnp.asarray(v) for k, v in rows.items()})
        for i in range(b):
            for k in ring:
                ring[k][ptr] = rows[k][i]
            ptr, size = (ptr + 1) % cap, min(size + 1, cap)
        for k in ring:
            np.testing.assert_array_equal(np.asarray(getattr(buf, k)), ring[k], k)
        assert (int(buf.ptr), int(buf.size)) == (ptr, size)
