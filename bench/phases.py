"""Device time of each named phase of the scanned training window.

The program names the phases of a timestep with `jax.named_scope`
(`repro.rl.loop.PHASES`: act, env, replay_add, replay_sample, update), and
names the update's two launches `%fxp_mlp_train_step_critic.N` and
`%fxp_mlp_train_step_actor.N`.  A scope reaches the device trace only
through the compiled program: `repro.obs.phases.op_phases` maps each
instruction of the compiled window to its phase, and a trace names each
operation by its instruction.

So the readers compile the window again, once per process and after the
traced span: the run's state is rebuilt as shapes from the generator's own
functions, and the compile is a hit in the harness's compile cache.  They
then sum the device time of the window program's operations per phase and
per launch.  A traced operation with no instruction of its name in the
compiled text means the text is not the program that ran: then, and for a
program that names no phases, every reader gives no number.
"""
from __future__ import annotations

import functools
import json
import time
import traceback
from typing import Optional

from bench import trace as tr
from bench.harness import say

UNSCOPED = "unscoped"
LAUNCHES = {"critic": ["%fxp_mlp_train_step_critic*"], "actor": ["%fxp_mlp_train_step_actor*"]}


def window_text(config: dict, traffic: dict) -> Optional[str]:
    """The compiled text of the window a cell runs, or None where the
    program names no phases or the window does not compile again."""
    try:
        return _window_text(json.dumps(config, sort_keys=True),
                            json.dumps(traffic, sort_keys=True))
    except Exception:  # a reader gives no number rather than end the run
        say(f"bench: the window did not compile again for its phases:\n{traceback.format_exc()}")
        return None


@functools.lru_cache(maxsize=None)
def _window_text(config_json: str, traffic_json: str) -> Optional[str]:
    from repro.rl import loop

    if not hasattr(loop, "PHASES"):
        return None
    import jax

    from bench.generators import train_loop as gen

    cfg, traffic = json.loads(config_json), json.loads(traffic_json)
    t0 = time.perf_counter()
    start = jax.eval_shape(gen.make_start(cfg, traffic), jax.random.key(0))
    ts = jax.eval_shape(functools.partial(gen.program_state, cfg=cfg, traffic=traffic), start)
    # shapes alone: the run's arrays were never committed to a device, and a
    # sharding would select another compiled program
    ts = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, weak_type=a.weak_type), ts)
    env, dcfg, tcfg = gen.program_configs(cfg, traffic)
    text = loop._train_window.lower(ts, env=env, cfg=tcfg, dcfg=dcfg,
                                    window=traffic["window"]).compile().as_text()
    say(f"bench: phases_compile_s = {time.perf_counter() - t0}")
    return text


@functools.lru_cache(maxsize=None)
def _op_phases(text: str) -> dict:
    from repro.obs.phases import op_phases
    from repro.rl import loop

    return op_phases(text, loop.PHASES)


_last: tuple = (None, None)     # (trace, its sums): the seven readers share one pass


def phase_ns(r) -> Optional[dict]:
    """{phase, `UNSCOPED`, "critic", "actor": summed device time in ns} of
    the window program's operations in the traced span; None where the
    phases cannot be read."""
    global _last
    if _last[0] is not r.trace:
        _last = (r.trace, _sum(r))
    return _last[1]


def _sum(r) -> Optional[dict]:
    win = tr.ops_within(r.trace, tr.module_patterns("train_window"))
    text = window_text(r.config, r.traffic) if win.ops else None
    if text is None:
        return None
    phases = _op_phases(text)
    out: dict = {}
    loose: dict = {}
    lo, hi = win.window
    for name, s, e in win.ops:
        inst = name.split(" ", 1)[0].lstrip("%")      # `%copy.70 f32[...]` -> `copy.70`
        phase = phases.get(inst)
        if phase is None:
            say(f"bench: traced operation {inst!r} is not in the compiled window;"
                " no phase is read")
            return None
        if min(e, hi) > max(s, lo):
            out[phase] = out.get(phase, 0) + min(e, hi) - max(s, lo)
            if phase == UNSCOPED:
                loose[inst] = loose.get(inst, 0) + min(e, hi) - max(s, lo)
    out = {k: v / win.n_devices for k, v in out.items()}
    for launch, pats in LAUNCHES.items():
        out[launch] = tr.op_time_ns(win, pats)
    _note(r, out, loose, tr.busy_ns(win))
    return out


def per_timestep_us(r, phase: str) -> Optional[float]:
    ns = phase_ns(r)
    if ns is None or r.measured["timesteps"] <= 0:
        return None
    return ns.get(phase, 0) / 1e3 / r.measured["timesteps"]


def per_update_us(r, key: str) -> Optional[float]:
    ns = phase_ns(r)
    if ns is None or r.measured["updates"] <= 0:
        return None
    return ns.get(key, 0) / 1e3 / r.measured["updates"]


def _note(r, ns: dict, loose: dict, busy: float) -> None:
    """What no metric reports: the unscoped time and its longest
    instructions, and how phases and unscoped time add up against the
    window program's busy time."""
    steps = max(r.measured["timesteps"], 1)
    parts = sum(v for k, v in ns.items() if k not in LAUNCHES)
    per_step = {k: v / 1e3 / steps for k, v in ns.items() if k not in LAUNCHES}
    top = sorted(loose.items(), key=lambda kv: -kv[1])[:5]
    say(f"bench: phase_us_per_timestep = {per_step}")
    say(f"bench: unscoped_top_us_per_timestep = {[[k, v / 1e3 / steps] for k, v in top]}")
    say(f"bench: unscoped_share = {ns.get(UNSCOPED, 0) / busy if busy else None}")
    say(f"bench: phases_over_window_busy = {parts / busy if busy else None}")
