"""Plain float32 reference of the DDPG job in `bench/configs/ddpg_*.json`.

Written from the configuration alone; it imports nothing of the program.
It holds:

  * the networks: per layer a QAT site (Q15.16 projection before the QAT
    delay, 16-bit affine codes after it), the dense layer, the activation.
    Every value a site gives enters the contraction whole, as the
    configuration's `precision` states: a 16-bit code keeps its 16 bits;
  * the update: TD target from the target nets, critic MSE, Adam on Q15.16
    gradients with Q15.16 weights, actor loss through the updated critic,
    Polyak soft update of both targets;
  * the surrogate chain environment with auto-reset, Gaussian exploration
    and the ring replay, so that the reference can follow a training
    window's first steps from the same seeded state and keys.

`precision` selects the contraction: "highest" is the configuration's;
"high", three bfloat16 passes, is the control, spelled out in bfloat16
limbs so that it reads the same on a CPU and a TPU.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

Array = jax.Array
F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
Q1516 = 2.0 ** 16
RAW_MIN, RAW_MAX = -(2.0 ** 31), 2.0 ** 31 - 1


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(F32)


def _dot_fwd_value(a, b, precision: str):
    d = partial(jnp.dot, precision=HIGHEST, preferred_element_type=F32)
    if precision == "highest":
        return d(a, b)
    a_hi, b_hi = _bf16(a), _bf16(b)
    if precision == "high":
        a_lo, b_lo = _bf16(a - a_hi), _bf16(b - b_hi)
        return d(a_hi, b_hi) + (d(a_hi, b_lo) + d(a_lo, b_hi))
    raise ValueError(f"unknown precision {precision!r}")


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def dot(a, b, precision: str):
    """a @ b at `precision`, forward and backward alike."""
    return _dot_fwd_value(a, b, precision)


def _dot_fwd(a, b, precision):
    return _dot_fwd_value(a, b, precision), (a, b)


def _dot_bwd(precision, res, g):
    a, b = res
    return _dot_fwd_value(g, b.T, precision), _dot_fwd_value(a.T, g, precision)


dot.defvjp(_dot_fwd, _dot_bwd)


def q1516(x):
    """Round onto the Q15.16 lattice (saturating)."""
    return jnp.round(jnp.clip(x * Q1516, RAW_MIN, RAW_MAX)) / Q1516


def affine_params(a_min, a_max, bits: int):
    """Algorithm 1's 16-bit affine grid over a captured range: it always
    holds 0, has 2^bits - 1 intervals, and a zero point z."""
    a_min = jnp.minimum(a_min, 0.0)
    a_max = jnp.maximum(a_max, 0.0)
    span = jnp.abs(a_min) + jnp.abs(a_max)
    delta = jnp.where(span > 0, span / (2.0 ** bits - 1.0), 1.0).astype(F32)
    z = jnp.round(-a_min / delta)
    return delta, z


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def affine_site(x, delta, z, bits: int):
    """16-bit affine fake quantization; straight-through inside the grid,
    zero gradient where the code clips."""
    q = jnp.clip(jnp.round(x / delta) + z, 0.0, 2.0 ** bits - 1.0)
    return (q - z) * delta


def _affine_fwd(x, delta, z, bits):
    return affine_site(x, delta, z, bits), (x, delta, z)


def _affine_bwd(bits, res, g):
    x, delta, z = res
    lo, hi = -z * delta, (2.0 ** bits - 1.0 - z) * delta
    inside = jnp.logical_and(x >= lo, x <= hi)
    return jnp.where(inside, g, 0.0), jnp.zeros_like(delta), jnp.zeros_like(z)


affine_site.defvjp(_affine_fwd, _affine_bwd)


@jax.custom_vjp
def q1516_site(x):
    """Q15.16 projection of an activation, straight-through."""
    return q1516(x)


q1516_site.defvjp(lambda x: (q1516(x), None), lambda _, g: (g,))


_ACTS = {"relu": jax.nn.relu, "tanh": jnp.tanh, "none": lambda x: x}


@dataclasses.dataclass(frozen=True)
class Net:
    """Static description of one network's QAT datapath."""

    activations: tuple[str, ...]
    quantized: bool          # past the QAT delay
    bits: int = 16
    precision: str = "highest"


def mlp(layers: list[dict], x: Array, net: Net, ranges=None) -> Array:
    """layers: [{"w": (in, out), "b": (out,)}]; ranges: per-site (a_min,
    a_max) pairs, used in the quantized phase."""
    for i, actn in enumerate(net.activations):
        if net.quantized:
            delta, z = affine_params(ranges[i][0], ranges[i][1], net.bits)
            x = affine_site(x, delta, z, net.bits)
        else:
            x = q1516_site(x)
        x = _ACTS[actn](dot(x, layers[i]["w"], net.precision) + layers[i]["b"])
    return x


def site_extrema(layers: list[dict], x: Array, net: Net) -> list[tuple[Array, Array]]:
    """(min, max) of every site input of a monitor-phase forward pass: how
    the captured ranges of Algorithm 1 arise."""
    out = []
    for i, actn in enumerate(net.activations):
        out.append((jnp.min(x), jnp.max(x)))
        x = q1516_site(x)
        x = _ACTS[actn](dot(x, layers[i]["w"], net.precision) + layers[i]["b"])
    return out


# --------------------------------------------------------------------------- #
# weights
# --------------------------------------------------------------------------- #

def init_layers(key, sizes: list[int], final_bound: float | None) -> list[dict]:
    """DDPG's init, uniform(+-1/sqrt(fan_in)); the output layer takes
    `final_bound` when given.  Projected onto Q15.16 (the weight memory)."""
    keys = jax.random.split(key, 2 * (len(sizes) - 1))
    out = []
    for i in range(len(sizes) - 1):
        last = i == len(sizes) - 2
        bound = final_bound if (last and final_bound is not None) else sizes[i] ** -0.5
        w = jax.random.uniform(keys[2 * i], (sizes[i], sizes[i + 1]), F32, -bound, bound)
        b = jax.random.uniform(keys[2 * i + 1], (sizes[i + 1],), F32, -bound, bound)
        out.append({"w": q1516(w), "b": q1516(b)})
    return out


def actor_sizes(cfg) -> list[int]:
    return [cfg["obs_dim"], *cfg["hidden"], cfg["act_dim"]]


def critic_sizes(cfg) -> list[int]:
    return [cfg["obs_dim"] + cfg["act_dim"], *cfg["hidden"], 1]


# --------------------------------------------------------------------------- #
# the update
# --------------------------------------------------------------------------- #

def adam(p, g, m, v, t, lr, cfg):
    """Adam on a Q15.16 gradient, the parameter stored back on Q15.16."""
    b1, b2, eps = cfg["adam_b1"], cfg["adam_b2"], cfg["adam_eps"]
    g = q1516(g)
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    tf = t.astype(F32)
    mhat = m / (1.0 - b1 ** tf)
    vhat = v / (1.0 - b2 ** tf)
    return q1516(p - lr * mhat / (jnp.sqrt(vhat) + eps)), m, v


def update(agent: dict, batch: dict, cfg: dict, actor_net: Net, critic_net: Net,
           ranges: dict, half_batch: bool = False) -> dict:
    """One DDPG update.  agent: {"actor", "critic", "actor_t", "critic_t"}
    layer lists, {"actor_m", "actor_v", "critic_m", "critic_v"} moment
    lists, "t" the optimizer steps taken.  ranges: {"actor": [...],
    "critic": [...]} per-site (a_min, a_max).  `half_batch` is a planted
    fault: the losses are the mean over the first half of the rows."""
    ra, rc = ranges.get("actor"), ranges.get("critic")
    if half_batch:
        batch = jax.tree.map(lambda x: x[: x.shape[0] // 2], batch)

    def critic_q(c, obs, a):
        return mlp(c, jnp.concatenate([obs, a], -1), critic_net, rc)[:, 0]

    obs, act, rew = batch["obs"], batch["action"], batch["reward"]
    nobs, done = batch["next_obs"], batch["done"].astype(F32)
    next_a = mlp(agent["actor_t"], nobs, actor_net, ra)
    q_next = critic_q(agent["critic_t"], nobs, next_a)
    y = jax.lax.stop_gradient(rew + cfg["gamma"] * (1.0 - done) * q_next)
    closs, cg = jax.value_and_grad(
        lambda c: jnp.mean(jnp.square(critic_q(c, obs, act) - y)))(agent["critic"])
    t = agent["t"] + 1
    critic, cm, cv = _adam_tree(agent["critic"], cg, agent["critic_m"], agent["critic_v"], t,
                                cfg["critic_lr"], cfg)
    aloss, ag = jax.value_and_grad(
        lambda a: -jnp.mean(critic_q(critic, obs, mlp(a, obs, actor_net, ra))))(agent["actor"])
    actor, am, av = _adam_tree(agent["actor"], ag, agent["actor_m"], agent["actor_v"], t,
                               cfg["actor_lr"], cfg)
    tau = cfg["tau"]
    soft = lambda tgt, p: jax.tree.map(lambda a, b: (1.0 - tau) * a + tau * b, tgt, p)
    return dict(actor=actor, critic=critic, actor_t=soft(agent["actor_t"], actor),
                critic_t=soft(agent["critic_t"], critic), actor_m=am, actor_v=av,
                critic_m=cm, critic_v=cv, t=t, critic_loss=closs, actor_loss=aloss)


def _adam_tree(params, grads, ms, vs, t, lr, cfg):
    out = jax.tree.map(lambda p, g, m, v: adam(p, g, m, v, t, lr, cfg), params, grads, ms, vs)
    pick = lambda i: jax.tree.map(lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)


# --------------------------------------------------------------------------- #
# the environment, exploration and replay of a training window
# --------------------------------------------------------------------------- #

def env_init(key, cfg):
    """A fresh episode: (q, qd, t, key) and its observation."""
    dyn = cfg["env_dynamics"]
    n = dyn["n_joints"] + dyn["n_aux"]
    kq, kd, knext = jax.random.split(key, 3)
    s = dict(q=0.1 * jax.random.normal(kq, (n,)), qd=0.1 * jax.random.normal(kd, (n,)),
             t=jnp.zeros((), jnp.int32), key=knext)
    return s, env_obs(s, cfg)


def env_obs(s, cfg):
    na = cfg["env_dynamics"]["n_aux"]
    q, qd = s["q"], s["qd"]
    return jnp.concatenate([q[1:na], q[na:], qd[:na], qd[na:]]).astype(F32)


def env_step(s, u, cfg):
    """The surrogate chain: damped joints driven by torque, thrust from
    coordinated paddling, damped velocity, height and pitch."""
    dyn = cfg["env_dynamics"]
    na, nj, dt = dyn["n_aux"], dyn["n_joints"], dyn["dt"]
    u = jnp.clip(u, -1.0, 1.0)
    aux, th = s["q"][:na], s["q"][na:]
    auxd, thd = s["qd"][:na], s["qd"][na:]
    thdd = dyn["torque_gain"] * u - 2.0 * thd - 4.0 * th
    thd_n = thd + dt * thdd
    th_n = th + dt * thd_n
    signs = jnp.where(jnp.arange(nj) % 2 == 0, 1.0, -1.0)
    thrust = jnp.sum(signs * jnp.sin(th) * thd)
    v = aux[0]
    v_n = v + dt * (thrust - 0.5 * v)
    h, hd = aux[1], auxd[1]
    hd_n = hd + dt * (-4.0 * h - 1.0 * hd + 0.1 * jnp.sum(jnp.abs(thd)) - 0.2)
    h_n = h + dt * hd_n
    p, pd = aux[2], auxd[2]
    pd_n = pd + dt * (-2.0 * p - 1.0 * pd + 0.05 * jnp.sum(u * signs))
    p_n = p + dt * pd_n
    q_n = jnp.concatenate([jnp.stack([v_n, h_n, p_n]), th_n])
    qd_n = jnp.concatenate([jnp.stack([thrust - 0.5 * v, hd_n, pd_n]), thd_n])
    t_n = s["t"] + 1
    ns = dict(q=q_n, qd=qd_n, t=t_n, key=s["key"])
    reward = (v_n - dyn["ctrl_cost"] * jnp.sum(jnp.square(u))).astype(F32)
    fallen = jnp.logical_and(cfg["terminate_on_fall"], h_n < dyn["fall_height"])
    done = jnp.logical_or(t_n >= cfg["episode_length"], fallen)
    return ns, env_obs(ns, cfg), reward, done


def env_step_auto(s, u, cfg):
    """Step, and restart the episode where it ended; reward and done are the
    step's own, state and observation those after the restart."""
    ns, obs, reward, done = env_step(s, u, cfg)
    key_next, key_reset = jax.random.split(ns["key"])
    rs, robs = env_init(key_reset, cfg)
    ns = dict(ns, key=key_next)
    out = jax.tree.map(lambda a, b: jnp.where(done, b, a), ns, rs)
    return out, jnp.where(done, robs, obs), reward, done


def window_keys(key):
    """The per-timestep key split of the training loop."""
    key, k_noise, k_sample = jax.random.split(key, 3)
    return key, k_noise, k_sample


def act(actor, obs, eps, net: Net, ranges):
    return jnp.clip(mlp(actor, obs, net, ranges) + eps, -1.0, 1.0)


def noise(k_noise, n, cfg):
    return cfg["exploration_sigma"] * jax.random.normal(k_noise, (n, cfg["act_dim"]))


def make_rollout(cfg: dict, actor_net: Net, steps: int):
    """A jitted run of `steps` timesteps of act -> explore -> env step with
    the actor held fixed (the replay is still below its warm-up).  It
    returns the final carry and the rows the timesteps stored."""
    step = jax.vmap(partial(env_step_auto, cfg=cfg))

    @jax.jit
    def run(actor, env_state, obs, key, ranges):
        n = obs.shape[0]

        def body(carry, _):
            env_state, obs, key = carry
            key, k_noise, _ = window_keys(key)
            a = act(actor, obs, noise(k_noise, n, cfg), actor_net, ranges)
            env_state, nobs, r, d = step(env_state, a)
            return (env_state, nobs, key), dict(obs=obs, action=a, reward=r, next_obs=nobs, done=d)

        (env_state, obs, key), rows = jax.lax.scan(body, (env_state, obs, key), None, length=steps)
        return env_state, obs, key, jax.tree.map(lambda x: x.reshape(-1, *x.shape[2:]), rows)

    return run
