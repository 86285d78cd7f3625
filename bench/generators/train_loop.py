"""Training cells: the device-resident DDPG loop of `repro.rl.loop`.

The traffic file gives the fleet size `n_envs`, the scanned `window` in
timesteps (one update per timestep), the QAT `phase` the window lies in,
and the number of updates that `correct` follows.  A run:

  1. makes the start of a run from the seed, in one jitted call: the
     weights, a replay filled to all but the first window's rows, the
     fleet's episodes, the loop's key and, past the QAT delay, the ranges
     the monitor phase captured.  The program gets them in its own types;
  2. set-up: drives that state through one window, the program's own
     scanned call, whose last `check_updates` timesteps are the run's first
     updates (the replay reaches its warm-up there), and one evaluation;
     both compile here, or load from the compile cache;
  3. measures whole windows, each followed by the paper's evaluation, the
     way `loop.train_device` runs them, until `--seconds` have passed:
     train IPS is the updates times the batch over the whole span;
  4. frees the program's state and has the reference follow the first
     window from the same state and keys: the actions the window stored,
     and the parameters, targets and moments after the first updates.
"""
from __future__ import annotations

import gc
import time
from functools import partial

import numpy as np

from bench import harness
from bench import trace as tr
from bench.harness import Outcome, Reading, say


def _shapes(cfg, traffic):
    n, w = traffic["n_envs"], traffic["window"]
    cap = cfg["replay_capacity"]
    s0 = cap - n * w
    if s0 <= 0:
        raise ValueError(f"a window of {n} x {w} rows overfills the replay of {cap}")
    return n, w, cap, s0


def make_start(cfg: dict, traffic: dict):
    """A jitted function of the seed's key that makes the start of a run
    (see the module docstring)."""
    import jax
    import jax.numpy as jnp

    from bench.reference import ddpg as ref

    n, _, cap, _ = _shapes(cfg, traffic)
    od, ad = cfg["obs_dim"], cfg["act_dim"]
    quantized = traffic["phase"] == "quantized"

    @jax.jit
    def start(key):
        ka, kc, kr, ke, kl = jax.random.split(key, 5)
        actor = ref.init_layers(ka, ref.actor_sizes(cfg), 3e-3)
        critic = ref.init_layers(kc, ref.critic_sizes(cfg), 3e-3)
        k = jax.random.split(kr, 5)
        replay = dict(
            obs=jax.random.normal(k[0], (cap, od)),
            action=jax.random.uniform(k[1], (cap, ad), minval=-1.0, maxval=1.0),
            reward=jax.random.normal(k[2], (cap,)),
            next_obs=jax.random.normal(k[3], (cap, od)),
            done=jax.random.uniform(k[4], (cap,)) < 1.0 / cfg["episode_length"])
        env_state, obs = jax.vmap(partial(ref.env_init, cfg=cfg))(jax.random.split(ke, n))
        out = dict(actor=actor, critic=critic, replay=replay, env_state=env_state, obs=obs,
                   key=kl)
        if quantized:
            cal = slice(0, min(cap, 16384))
            mon = ref.Net(tuple(cfg["actor_activations"]), quantized=False)
            cmon = ref.Net(tuple(cfg["critic_activations"]), quantized=False)
            out["ranges"] = dict(
                actor=ref.site_extrema(actor, replay["obs"][cal], mon),
                critic=ref.site_extrema(critic, jnp.concatenate(
                    [replay["obs"][cal], replay["action"][cal]], -1), cmon))
        return out

    return start


def program_state(start: dict, cfg: dict, traffic: dict):
    """The start of the run in the program's own types."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.core.qat import QATState
    from repro.core.ranges import RangeStat
    from repro.optim import adam
    from repro.rl import ddpg, loop, replay
    from repro.rl.envs.base import EnvState
    from repro.rl.noise import NoiseState

    n, _, cap, s0 = _shapes(cfg, traffic)
    params = lambda layers: {f"l{i}": {"w": l["w"], "b": l["b"]} for i, l in enumerate(layers)}
    actor, critic = params(start["actor"]), params(start["critic"])
    qat = QATState.init(delay=cfg["qat_delay"], sites=ddpg.ACTOR_SITES + ddpg.CRITIC_SITES,
                        n_bits=cfg["qat_bits"])
    if traffic["phase"] == "quantized":
        ranges = {}
        for net, sites in (("actor", ddpg.ACTOR_SITES), ("critic", ddpg.CRITIC_SITES)):
            for site, (mn, mx) in zip(sites, start["ranges"][net]):
                ranges[site] = RangeStat(a_min=mn, a_max=mx, count=jnp.array(1, jnp.int32))
        qat = dataclasses.replace(qat, ranges=ranges,
                                  step=jnp.array(cfg["qat_delay"], jnp.int32))
    agent = ddpg.DDPGState(
        actor=actor, critic=critic, actor_target=jax.tree.map(jnp.copy, actor),
        critic_target=jax.tree.map(jnp.copy, critic), actor_opt=adam.init(actor),
        critic_opt=adam.init(critic), qat=qat, step=jnp.zeros((), jnp.int32))
    rp = start["replay"]
    buf = replay.ReplayBuffer(obs=rp["obs"], action=rp["action"], reward=rp["reward"],
                              next_obs=rp["next_obs"], done=rp["done"],
                              ptr=jnp.array(s0, jnp.int32), size=jnp.array(s0, jnp.int32))
    es = start["env_state"]
    env_state = EnvState(q=es["q"], qd=es["qd"], t=es["t"], key=es["key"])
    return loop.TrainState(agent=agent, env_state=env_state, obs=start["obs"], buf=buf,
                           noise=NoiseState(x=jnp.zeros((n, cfg["act_dim"]), jnp.float32)),
                           key=start["key"])


def _keep(start: dict) -> dict:
    """Copies of what the reference starts from: the window consumes
    (donates) the arrays the program was given."""
    import jax
    import jax.numpy as jnp

    return jax.tree.map(jnp.copy, dict(
        actor=start["actor"], critic=start["critic"], env_state=start["env_state"],
        obs=start["obs"], key=start["key"], ranges=start.get("ranges")))


def program_configs(cfg: dict, traffic: dict):
    """The program's static configs.  They hold nothing of the seed, so
    every seed runs the same compiled programs."""
    from repro.rl import ddpg, envs, loop

    n, w, cap, _ = _shapes(cfg, traffic)
    dcfg = ddpg.DDPGConfig(
        gamma=cfg["gamma"], tau=cfg["tau"], actor_lr=cfg["actor_lr"], critic_lr=cfg["critic_lr"],
        batch_size=cfg["batch_size"], qat_delay=cfg["qat_delay"], qat_bits=cfg["qat_bits"],
        backend="pallas_fused_step", exploration_sigma=cfg["exploration_sigma"])
    # the replay reaches its warm-up on the first window's last check_updates timesteps
    warmup = cap - (traffic["check_updates"] - 1) * n
    tcfg = loop.TrainConfig(total_steps=w, warmup_steps=warmup, replay_capacity=cap,
                            eval_every=w, eval_episodes=cfg["eval_episodes"], n_envs=n, seed=0)
    env = envs.make(cfg["env"])
    return env, dcfg, tcfg


def snapshot(ts, s0: int, rows: int):
    """Copies of what the check compares, taken before the next window
    consumes the state."""
    import jax
    import jax.numpy as jnp

    a = ts.agent
    pick = lambda p: [dict(w=jnp.copy(p[f"l{i}"]["w"]), b=jnp.copy(p[f"l{i}"]["b"]))
                      for i in range(len(p))]
    return jax.device_get(dict(
        actor=pick(a.actor), critic=pick(a.critic), actor_t=pick(a.actor_target),
        critic_t=pick(a.critic_target), actor_m=pick(a.actor_opt.mu),
        critic_m=pick(a.critic_opt.mu), updates=a.step,
        rows_obs=ts.buf.obs[s0:s0 + rows], rows_action=ts.buf.action[s0:s0 + rows]))


def run(cell, *, seed: int, seconds: float, trace: bool, t_start: float, devices,
        compiles) -> Outcome:
    import jax

    from repro.rl import loop

    cfg, traffic = cell.config, cell.traffic
    n, w, cap, s0 = _shapes(cfg, traffic)
    key = jax.random.key(seed)
    start = make_start(cfg, traffic)(key)
    ts = program_state(start, cfg, traffic)
    keep = _keep(start)
    del start
    env, dcfg, tcfg = program_configs(cfg, traffic)
    k_eval = jax.random.fold_in(key, 7)

    # set-up: the first window, whose last timesteps are the first updates
    ts, stats = loop._train_window(ts, env=env, cfg=tcfg, dcfg=dcfg, window=w)
    first_updates = int(stats["updates"])
    snap = snapshot(ts, s0, n * w)
    float(loop.evaluate(env, ts.agent, dcfg, jax.random.fold_in(k_eval, 0), cfg["eval_episodes"]))
    setup_s = time.perf_counter() - t_start
    compiles_before = compiles.count

    # the measured (or traced) span: whole windows, each with its evaluation
    log_dir = None
    if trace:
        log_dir = harness.start_trace(cell.name, seed)
    windows = updates = 0
    with jax.profiler.TraceAnnotation("bench.traced"):
        t0 = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("bench.window"):
                ts, stats = loop._train_window(ts, env=env, cfg=tcfg, dcfg=dcfg, window=w)
            with jax.profiler.TraceAnnotation("bench.readback"):
                updates += int(stats["updates"])
            windows += 1
            with jax.profiler.TraceAnnotation("bench.eval"):
                float(loop.evaluate(env, ts.agent, dcfg, jax.random.fold_in(k_eval, windows),
                                    cfg["eval_episodes"]))
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    in_window = compiles.count - compiles_before
    mem = harness.memory_peak(devices)
    del ts, stats
    gc.collect()

    batch = cfg["batch_size"]
    notes = dict(compiles_in_window=in_window, windows=windows, updates=updates,
                 span_s=elapsed, first_window_updates=first_updates)
    checks, numbers = check(cfg, traffic, cell.limits, keep, snap, key)
    reading = None
    if trace:
        reading = Reading(trace=tr.load_and_remove(log_dir), counters=dict(updates=updates),
                          work=harness.work_module(cfg), config=cfg, traffic=traffic,
                          peaks=harness.device_kind_peaks(devices[0].device_kind),
                          measured=dict(updates=updates, timesteps=windows * w),
                          chips=len(devices))
    return Outcome(metrics=dict(setup_s=setup_s, train_ips=updates * batch / elapsed),
                   attempted=windows, failed=0, checks=checks, memory_peak_bytes=mem,
                   reading=reading, notes=notes, numbers=numbers)


# --------------------------------------------------------------------------- #
# the comparison with the reference
# --------------------------------------------------------------------------- #

def reference_window(cfg: dict, traffic: dict, keep: dict, key, precision: str,
                     half_batch: bool = False) -> dict:
    """The reference's own run of the first window from the same start:
    the stored rows, the actor that acted at each of the last timesteps,
    and the agent after the first updates."""
    import jax
    import jax.numpy as jnp

    from bench.reference import ddpg as ref

    n, w, cap, s0 = _shapes(cfg, traffic)
    k_upd = traffic["check_updates"]
    quant = traffic["phase"] == "quantized"
    anet = ref.Net(tuple(cfg["actor_activations"]), quant, cfg["qat_bits"], precision)
    cnet = ref.Net(tuple(cfg["critic_activations"]), quant, cfg["qat_bits"], precision)
    ranges = keep["ranges"] or {}
    replay = make_start(cfg, traffic)(key)["replay"]   # the same rows the program was given
    env_state, obs, lkey, rows = ref.make_rollout(cfg, anet, w - k_upd)(
        keep["actor"], keep["env_state"], keep["obs"], keep["key"], ranges.get("actor"))
    zeros = lambda layers: jax.tree.map(jnp.zeros_like, layers)
    agent = dict(actor=keep["actor"], critic=keep["critic"], actor_t=keep["actor"],
                 critic_t=keep["critic"], actor_m=zeros(keep["actor"]),
                 actor_v=zeros(keep["actor"]), critic_m=zeros(keep["critic"]),
                 critic_v=zeros(keep["critic"]), t=jnp.zeros((), jnp.int32))
    step_env = jax.jit(jax.vmap(partial(ref.env_step_auto, cfg=cfg)))
    upd = jax.jit(partial(ref.update, cfg=cfg, actor_net=anet, critic_net=cnet,
                          half_batch=half_batch))
    act = jax.jit(partial(ref.act, net=anet))
    fresh = {k: [v] for k, v in rows.items()}
    actors = []
    for j in range(w - k_upd, w):
        lkey, k_noise, k_sample = ref.window_keys(lkey)
        actors.append(agent["actor"])
        a = act(agent["actor"], obs, ref.noise(k_noise, n, cfg), ranges=ranges.get("actor"))
        env_state, nobs, r, d = step_env(env_state, a)
        for k, v in dict(obs=obs, action=a, reward=r, next_obs=nobs, done=d).items():
            fresh[k].append(v)
        obs = nobs
        size = min(s0 + (j + 1) * n, cap)
        idx = jax.random.randint(k_sample, (cfg["batch_size"],), 0,
                                 jnp.maximum(jnp.array(size, jnp.int32), 1))
        stored = {k: jnp.concatenate(v) for k, v in fresh.items()}
        old = idx < s0
        batch = {k: jnp.where(old.reshape(-1, *([1] * (stored[k].ndim - 1))),
                              replay[k][jnp.minimum(idx, s0 - 1)],
                              stored[k][jnp.clip(idx - s0, 0, stored[k].shape[0] - 1)])
                 for k in stored}
        agent = upd(agent, batch, ranges=ranges)
    stored = {k: jnp.concatenate(v) for k, v in fresh.items()}
    return dict(rows_obs=stored["obs"], rows_action=stored["action"], agent=agent,
                actors=actors, anet=anet, ranges=ranges)


def _noise_all(cfg, traffic, key0):
    """The exploration noise of every timestep of the first window."""
    import jax

    from bench.reference import ddpg as ref

    n, w, _, _ = _shapes(cfg, traffic)

    @jax.jit
    def run(key):
        def body(k, _):
            k, k_noise, _ = ref.window_keys(k)
            return k, ref.noise(k_noise, n, cfg)
        return jax.lax.scan(body, key, None, length=w)[1].reshape(w * n, -1)

    return run(key0)


def leaf_gaps(cand: list, refl: list, base: list | None,
              keep_mask: list) -> tuple[float, float, int]:
    """Each leaf's gap between the candidate's norm and the reference's,
    over the larger of that leaf's reference norm and the median leaf's;
    returns the worst leaf's, the median leaf's and the worst leaf's index.
    `base`, when given, is subtracted first (a change)."""
    norms_c, norms_r = [], []
    for i, (c, r) in enumerate(zip(cand, refl)):
        b = 0.0 if base is None else np.asarray(base[i], np.float64)
        norms_c.append(float(np.linalg.norm(np.asarray(c, np.float64) - b)))
        norms_r.append(float(np.linalg.norm(np.asarray(r, np.float64) - b)))
    med = float(np.median(norms_r))
    gaps = [abs(c - r) / max(r, med) if k else -1.0
            for c, r, k in zip(norms_c, norms_r, keep_mask)]
    kept = [g for g in gaps if g >= 0.0]
    if not kept:
        return float("nan"), float("nan"), -1
    return max(kept), float(np.median(kept)), int(np.argmax(gaps))


def _leaves(layers: list) -> list:
    out = []
    for layer in layers:
        out += [layer["w"], layer["b"]]
    return out


def compare(cfg: dict, traffic: dict, cand: dict, refw: dict, keep: dict, eps_all) -> dict:
    """The numbers `correct` compares, for a candidate (the program's
    snapshot, or the control) against the reference's run."""
    import jax
    import jax.numpy as jnp

    from bench.reference import ddpg as ref

    n, w, _, _ = _shapes(cfg, traffic)
    k_upd = traffic["check_updates"]
    # the actions the window stored, against the reference's actor on the
    # same observations with the same noise
    obs = jnp.asarray(cand["rows_obs"])
    act = jax.jit(partial(ref.act, net=refw["anet"]))
    split = (w - k_upd + 1) * n     # rows acted on by the starting actor
    want = [act(keep["actor"], obs[:split], eps_all[:split], ranges=refw["ranges"].get("actor"))]
    for i in range(1, k_upd):
        sl = slice((w - k_upd + i) * n, (w - k_upd + i + 1) * n)
        want.append(act(refw["actors"][i], obs[sl], eps_all[sl],
                        ranges=refw["ranges"].get("actor")))
    gap = jnp.abs(jnp.concatenate(want) - jnp.asarray(cand["rows_action"]))

    ra = jax.device_get(refw["agent"])
    out = dict(act_gap=float(jnp.max(gap)), act_mean_gap=float(jnp.mean(gap)))
    grad_norms = [float(np.linalg.norm(x))
                  for x in _leaves(ra["actor_m"]) + _leaves(ra["critic_m"])]
    med = float(np.median(grad_norms))
    keep_mask = [g >= 1e-3 * med for g in grad_norms]
    base = _leaves(keep["actor"]) + _leaves(keep["critic"])
    both = lambda a, c: _leaves(a) + _leaves(c)
    names = both(*[[dict(w=f"{net}.l{i}.w", b=f"{net}.l{i}.b") for i in range(len(keep[net]))]
                   for net in ("actor", "critic")])
    worst_leaf = {}
    for name, key, b in (("change", "", base), ("target", "_t", base), ("moment", "_m", None)):
        worst, median, at = leaf_gaps(both(cand["actor" + key], cand["critic" + key]),
                                      both(ra["actor" + key], ra["critic" + key]), b, keep_mask)
        out[f"{name}_gap"], out[f"{name}_median_gap"] = worst, median
        worst_leaf[name] = names[at] if at >= 0 else None
    say(f"bench: worst leaf {worst_leaf}")
    return out


def candidate_of(refw: dict) -> dict:
    """A reference run in the program's place (the control)."""
    import jax

    a = jax.device_get(refw["agent"])
    return dict(actor=a["actor"], critic=a["critic"], actor_t=a["actor_t"],
                critic_t=a["critic_t"], actor_m=a["actor_m"], critic_m=a["critic_m"],
                rows_obs=refw["rows_obs"], rows_action=refw["rows_action"])


def check(cfg, traffic, limits, keep, snap, key) -> tuple[list, dict]:
    refw = reference_window(cfg, traffic, keep, key, "highest")
    eps_all = _noise_all(cfg, traffic, keep["key"])
    nums = compare(cfg, traffic, snap, refw, keep, eps_all)
    # the window must have run exactly the first updates: an exact count
    nums["update_count_gap"] = float(abs(traffic["check_updates"] - int(snap["updates"])))
    say(f"bench: check numbers {nums}")
    return [(k, nums[k], limits[k]) for k in limits], nums


def readings(cell, seed: int, variant: str) -> dict:
    """The numbers of the reference put in the program's place, from the
    same start: `high` (three bfloat16 passes) is the control,
    `half_batch` the planted fault.  For `bench/control.py`."""
    import jax

    cfg, traffic = cell.config, cell.traffic
    key = jax.random.key(seed)
    keep = _keep(make_start(cfg, traffic)(key))
    refw = reference_window(cfg, traffic, keep, key, "highest")
    cand = reference_window(cfg, traffic, keep, key,
                            "highest" if variant == "half_batch" else variant,
                            half_batch=variant == "half_batch")
    return compare(cfg, traffic, candidate_of(cand), refw, keep,
                   _noise_all(cfg, traffic, keep["key"]))
