"""FIXAR's end-to-end DRL loop (operation sequence of Fig. 3).

Two execution modes:

  * ``host``  — paper-faithful: the environment steps outside the jitted
    region (the paper's CPU-side MuJoCo), actions/batches cross an explicit
    boundary each timestep, and we time the three Fig.-9 segments:
    env time / transfer (dispatch) time / accelerator compute time.

  * ``device`` — TPU-idiomatic (beyond-paper): a vmapped env fleet, the
    replay buffer, exploration noise, and the DDPG update all live in one
    jitted+scanned program — ``train_device`` runs an entire eval window
    (act → explore → env-step → store → update × window) as a SINGLE
    ``lax.scan`` launch with zero host round-trips.

Both drivers, ``train_host`` and ``train_device``, share the same DDPG
update (the one ``DDPGConfig.backend`` names), QAT state, replay
semantics, and the ``TrainConfig`` knobs.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.obs.trace import NULL_TRACER
from repro.rl import ddpg, replay
from repro.rl.envs.base import EnvState, env_init, init_fleet, step_fleet
from repro.rl.noise import NoiseProcess, NoiseState

Array = jax.Array

# The phases of one timestep, in order.  Each is a `jax.named_scope` around
# its region of the scanned window (and of evaluation's act and env step),
# so it reaches the compiled program as a component of every instruction's
# `op_name`; `obs.phases.op_phases` maps a compiled module's instructions
# onto these names.  `train_host` names its host spans with the same words.
PHASES = ("act", "env", "replay_add", "replay_sample", "update")
ACT, ENV, REPLAY_ADD, REPLAY_SAMPLE, UPDATE = PHASES


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """One config for both training drivers (`train_host` /
    `train_device`): a single frozen — hashable, therefore jit-static —
    dataclass instead of per-driver kwarg sprawl."""

    total_steps: int = 10_000
    warmup_steps: int = 1_000          # env steps before updates start
    replay_capacity: int = 100_000
    eval_every: int = 5_000            # paper: evaluate every 5000 timesteps
    eval_episodes: int = 10            # paper: 10 random starts
    n_envs: int = 1                    # device mode farms a vmapped fleet
    seed: int = 0
    noise_kind: str = "gaussian"       # rl/noise process: gaussian|ou|none
    noise_sigma: Optional[float] = None  # None -> dcfg.exploration_sigma


def _noise_proc(cfg: TrainConfig, dcfg: ddpg.DDPGConfig) -> NoiseProcess:
    sigma = dcfg.exploration_sigma if cfg.noise_sigma is None else cfg.noise_sigma
    return NoiseProcess(kind=cfg.noise_kind, sigma=sigma)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    agent: ddpg.DDPGState | ddpg.PaddedDDPGState  # the latter inside a window (ddpg.to_carry)
    env_state: EnvState      # fleet-batched (leading n_envs axis)
    obs: Array               # (n_envs, obs_dim)
    buf: replay.ReplayBuffer
    noise: NoiseState        # (n_envs, act_dim) exploration carry
    key: Array


def init_train_state(env, cfg: TrainConfig, dcfg: ddpg.DDPGConfig) -> TrainState:
    key = jax.random.key(cfg.seed)
    k_agent, k_env, k_loop = jax.random.split(key, 3)
    agent = ddpg.init(k_agent, env.spec, dcfg)
    n = max(cfg.n_envs, 1)
    env_state, obs = init_fleet(env, k_env, n)
    buf = replay.init(cfg.replay_capacity, env.spec.obs_dim, env.spec.act_dim)
    nz = _noise_proc(cfg, dcfg).init((n, env.spec.act_dim))
    return TrainState(agent=agent, env_state=env_state, obs=obs, buf=buf, noise=nz, key=k_loop)


# --------------------------------------------------------------------- #
# The shared rollout core: batched act (+ optional exploration) and the
# vmapped env transition.  `_eval_episodes`, the scanned training window,
# and `train_host`'s per-step section all go through these two helpers —
# no near-copies of the act→step chain.
# --------------------------------------------------------------------- #

def _act_explore(
    agent: ddpg.DDPGState | ddpg.PaddedDDPGState,
    obs: Array,
    nz: NoiseState,
    k_noise: Array,
    *,
    proc: NoiseProcess,
    dcfg: ddpg.DDPGConfig,
) -> tuple[NoiseState, Array]:
    """Actor forward + exploration noise  [FPGA FP + PRNG of Fig. 2]."""
    nz, eps = proc.sample(nz, k_noise)
    return nz, ddpg.act(agent, obs, cfg=dcfg, noise=eps)


def _policy_env_step(
    agent: ddpg.DDPGState | ddpg.PaddedDDPGState,
    env_state: EnvState,
    obs: Array,
    *,
    env,
    dcfg: ddpg.DDPGConfig,
    autoreset: bool = True,
) -> tuple[EnvState, Array, Array, Array, Array]:
    """One greedy act → vmapped env-step over a fleet; auto-reset keeps
    done lanes in lockstep (training), `autoreset=False` leaves terminal
    states in place (evaluation stops counting via its alive mask)."""
    with jax.named_scope(ACT):
        action = ddpg.act(agent, obs, cfg=dcfg)
    with jax.named_scope(ENV):
        env_state, next_obs, reward, done = step_fleet(env, env_state, action, autoreset=autoreset)
    return env_state, next_obs, reward, done, action


def _one_timestep(
    ts: TrainState, env, cfg: TrainConfig, dcfg: ddpg.DDPGConfig
) -> tuple[TrainState, dict[str, Array]]:
    key, k_noise, k_sample = jax.random.split(ts.key, 3)

    # 1. actor forward (inference) + exploration noise  [FPGA FP + PRNG]
    with jax.named_scope(ACT):
        nz, action = _act_explore(
            ts.agent, ts.obs, ts.noise, k_noise, proc=_noise_proc(cfg, dcfg), dcfg=dcfg
        )

    # 2. environment transition (vmapped fleet)          [host CPU in paper]
    with jax.named_scope(ENV):
        env_state, next_obs, reward, done = step_fleet(env, ts.env_state, action)

    # 3. store the fleet's transitions                   [host replay memory]
    with jax.named_scope(REPLAY_ADD):
        buf = replay.add_batch(
            ts.buf,
            {"obs": ts.obs, "action": action, "reward": reward, "next_obs": next_obs, "done": done},
        )

    # 4. sample batch + 5. critic/actor BP+WU            [FPGA training]
    with jax.named_scope(REPLAY_SAMPLE):
        batch = replay.sample(buf, k_sample, dcfg.batch_size)

    def run_update(agent):
        new_agent, m = ddpg.update(agent, batch, dcfg)
        return new_agent, m

    def skip_update(agent):
        zero = {
            "critic_loss": jnp.float32(0), "actor_loss": jnp.float32(0), "q_mean": jnp.float32(0)
        }
        return agent, zero

    with jax.named_scope(UPDATE):
        do_update = buf.size >= cfg.warmup_steps
        agent, metrics = jax.lax.cond(do_update, run_update, skip_update, ts.agent)
    metrics["reward"] = jnp.mean(reward)
    metrics["did_update"] = do_update.astype(jnp.int32)
    ts = TrainState(
        agent=agent, env_state=env_state, obs=next_obs, buf=buf, noise=nz, key=key
    )
    return ts, metrics


@partial(jax.jit, static_argnames=("env", "cfg", "dcfg", "window"), donate_argnums=(0,))
def _train_window(
    ts: TrainState, *, env, cfg: TrainConfig, dcfg: ddpg.DDPGConfig, window: int
) -> tuple[TrainState, dict[str, Array]]:
    """`window` full FIXAR timesteps — act → explore → env-step → store →
    update — as ONE `lax.scan` inside ONE jitted launch.  Module-level jit
    with `env`/`cfg`/`dcfg`/`window` as static keys: repeated windows (and
    every driver sharing this helper) hit the cache instead of re-tracing
    the scanned body — the retrace regression is pinned in
    tests/test_loop.py.

    The scan carries the agent in the form `ddpg.to_carry` gives for
    `dcfg.backend` (the fused step's padded layout: no timestep pads or
    slices the eight parameter sets around the launches)."""
    ts = dataclasses.replace(ts, agent=ddpg.to_carry(ts.agent, dcfg))

    def body(carry, _):
        carry, m = _one_timestep(carry, env, cfg, dcfg)
        return carry, (m["reward"], m["did_update"])

    ts, (rewards, updates) = jax.lax.scan(body, ts, None, length=window)
    ts = dataclasses.replace(ts, agent=ddpg.from_carry(ts.agent))
    return ts, {"reward": jnp.mean(rewards), "updates": jnp.sum(updates)}


def train_device(
    env,
    cfg: Optional[TrainConfig] = None,
    dcfg: Optional[ddpg.DDPGConfig] = None,
    *,
    eval_fn: Optional[Callable] = None,
) -> tuple[TrainState, dict[str, Any]]:
    """Fully device-resident training: each eval window (`cfg.eval_every`
    timesteps x `cfg.n_envs` fleet lanes) runs as a single jitted
    `lax.scan` launch — the host only reads back the window's scalar
    metrics and runs the (also single-launch) evaluation.  Updates run
    through whatever backend `dcfg.backend` names.

    History per window: `step`, `eval_reward`, `train_reward` (window mean
    fleet reward), `ips` (env-steps/s = window x n_envs / wall), and
    `updates_per_s` (post-warmup updates / wall).
    """
    cfg = TrainConfig() if cfg is None else cfg
    dcfg = ddpg.DDPGConfig() if dcfg is None else dcfg
    ts = init_train_state(env, cfg, dcfg)
    evaluator = evaluate if eval_fn is None else eval_fn
    history = {"step": [], "eval_reward": [], "train_reward": [], "ips": [], "updates_per_s": []}
    steps_done = 0
    while steps_done < cfg.total_steps:
        window = min(cfg.eval_every, cfg.total_steps - steps_done)
        t0 = time.perf_counter()
        ts, stats = _train_window(ts, env=env, cfg=cfg, dcfg=dcfg, window=window)
        jax.block_until_ready(stats["reward"])
        dt = time.perf_counter() - t0
        steps_done += window
        k_eval = jax.random.fold_in(jax.random.key(cfg.seed + 7), steps_done)
        ev = evaluator(env, ts.agent, dcfg, k_eval, cfg.eval_episodes)
        history["step"].append(steps_done)
        history["eval_reward"].append(float(ev))
        history["train_reward"].append(float(stats["reward"]))
        history["ips"].append(window * max(cfg.n_envs, 1) / dt)
        history["updates_per_s"].append(int(stats["updates"]) / dt)
    return ts, history


def train_host(
    env, cfg: TrainConfig, dcfg: ddpg.DDPGConfig, *, learner=None, tracer=None, observability=None
) -> tuple[TrainState, dict[str, Any]]:
    """Paper-faithful host loop with the Fig.-9 timing breakdown.

    Each timestep: host env step (CPU), device_put of the sampled batch
    (the PCIe import), then the jitted inference+update (the accelerator).
    Shares `TrainConfig` (and the act/explore/env-transition helpers) with
    `train_device`; `n_envs > 1` steps a host-driven fleet.

    `learner` (optional) is a `train/learner.LearnerEngine` (or anything
    with its `load_state`/`run_update`/`state` surface): when given, the
    freshly initialized agent is installed into the engine and every
    update streams through `learner.run_update(batch)` — bucket padding
    and learner metrics included — instead of the loop's own jitted
    `ddpg.update`.  The engine trains through the backend of the config it
    was built with; `dcfg.backend` drives acting.

    `tracer` (optional) is an `obs.Tracer`: when enabled, every timestep
    emits its phases as spans named from `PHASES` (`act` / `env` /
    `replay_add` / `replay_sample` / `update`, category `loop`) — layered
    over a learner's own engine spans, this is the full host-loop picture
    in one Perfetto timeline, and under a `jax.profiler` capture the same
    names sit on the device trace's clock.

    `observability` (optional) is an `obs.Observability` bundle: its
    tracer is used when `tracer` isn't given, its HTTP endpoint
    (`serve_http=port`) is started so the loop's host serves /metrics +
    /healthz while training, and the tracer is flushed on exit — normal
    or aborted — so the trace always lands on disk.
    """
    if observability is not None:
        if tracer is None:
            tracer = observability.tracer
        observability.ensure_server()
    ts = init_train_state(env, cfg, dcfg)
    proc = _noise_proc(cfg, dcfg)
    act_jit = jax.jit(partial(_act_explore, proc=proc, dcfg=dcfg))
    upd_jit = jax.jit(partial(ddpg.update, cfg=dcfg))
    sample_jit = jax.jit(partial(replay.sample, batch=dcfg.batch_size))
    add_jit = jax.jit(replay.add_batch)
    if learner is not None:
        learner.load_state(ts.agent)

    tracer = NULL_TRACER if tracer is None else tracer
    times = {"env": 0.0, "runtime": 0.0, "accelerator": 0.0}
    key = ts.key
    agent, env_state, obs, buf, nz = (ts.agent, ts.env_state, ts.obs, ts.buf, ts.noise)
    try:
        for step in range(cfg.total_steps):
            key, k_noise, k_sample = jax.random.split(key, 3)

            t0 = time.perf_counter()
            with tracer.span(ACT, cat="loop", step=step):
                nz, action = act_jit(agent, obs, nz, k_noise)
                jax.block_until_ready(action)
            t1 = time.perf_counter()

            # the env fleet steps OUTSIDE the jitted region (eager vmap):
            # the paper's host-side simulator boundary
            with tracer.span(ENV, cat="loop", step=step):
                env_state, next_obs, reward, done = step_fleet(env, env_state, action)
                jax.block_until_ready(next_obs)
            t2 = time.perf_counter()

            # replay add + batch sample + "PCIe import" (device transfer)
            with tracer.span(REPLAY_ADD, cat="loop", step=step):
                buf = add_jit(
                    buf,
                    {
                        "obs": obs,
                        "action": action,
                        "reward": reward,
                        "next_obs": next_obs,
                        "done": done,
                    },
                )
                jax.block_until_ready(buf.ptr)
            with tracer.span(REPLAY_SAMPLE, cat="loop", step=step):
                batch = sample_jit(buf, k_sample)
                if learner is None:
                    batch = jax.device_put(batch)
                else:
                    # the learner's queue holds HOST arrays (its "PCIe import"
                    # happens inside run_update and is billed to the
                    # accelerator segment there) — pulling to host here,
                    # instead of a device_put the engine would immediately
                    # undo, keeps the timing breakdown honest and skips a
                    # wasted round trip
                    batch = jax.device_get(batch)
                jax.block_until_ready(batch)
            t3 = time.perf_counter()

            if int(buf.size) >= cfg.warmup_steps:
                with tracer.span(UPDATE, cat="loop", step=step):
                    if learner is not None:
                        learner.run_update(batch)    # blocks until applied
                        agent = learner.state
                    else:
                        agent, _ = upd_jit(agent, batch)
                        jax.block_until_ready(agent.step)
            t4 = time.perf_counter()

            times["accelerator"] += (t1 - t0) + (t4 - t3)
            times["env"] += t2 - t1
            times["runtime"] += t3 - t2
            obs = next_obs
    finally:
        if observability is not None:
            observability.flush()

    ts = TrainState(agent=agent, env_state=env_state, obs=obs, buf=buf, noise=nz, key=key)
    return ts, {"times": times, "total_steps": cfg.total_steps}


@partial(jax.jit, static_argnames=("env", "dcfg"))
def _eval_episodes(agent: ddpg.DDPGState, keys: Array, *, env, dcfg: ddpg.DDPGConfig) -> Array:
    """Module-level jitted eval body — hoisted out of `evaluate` so repeat
    eval calls hit the jit cache instead of re-tracing the full episode
    scan (a closure-defined `@jax.jit` function is a fresh function object,
    and therefore a fresh trace, on every call).  `env` and `dcfg` are
    frozen dataclasses, hence hashable static keys; `agent` and `keys` are
    traced, so evolving params never retrace.

    The episodes run as a FLEET: vmapped `init` over the episode keys, then
    one scan of the shared `_policy_env_step` rollout core (no auto-reset —
    a finished episode parks while `alive` masks its rewards out), so this
    is the same act→step program the scanned training window runs, minus
    exploration/store/update."""
    env_state, obs = jax.vmap(partial(env_init, env))(keys)
    n = keys.shape[0]

    def body(carry, _):
        env_state, obs, total, alive = carry
        env_state, obs, r, done, _ = _policy_env_step(
            agent, env_state, obs, env=env, dcfg=dcfg, autoreset=False
        )
        total = total + r * alive
        alive = alive * (1.0 - done.astype(jnp.float32))
        return (env_state, obs, total, alive), None

    (_, _, total, _), _ = jax.lax.scan(
        body,
        (env_state, obs, jnp.zeros(n, jnp.float32), jnp.ones(n, jnp.float32)),
        None,
        length=env.spec.episode_length,
    )
    return jnp.mean(total)


def evaluate(
    env, agent: ddpg.DDPGState, dcfg: ddpg.DDPGConfig, key: Array, n_episodes: int = 10
) -> Array:
    """Paper protocol: average cumulative reward over `n_episodes` random
    starts, accumulating until the agent falls (done) or the episode ends."""
    keys = jax.random.split(key, n_episodes)
    return _eval_episodes(agent, keys, env=env, dcfg=dcfg)
