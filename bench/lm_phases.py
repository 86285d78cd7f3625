"""Device time of each named phase of the LM train step.

The program names the phases of its train step with `jax.named_scope`
(`repro.train.step.PHASES`: embed, mamba_proj, ssd, attn, ffn, head,
optimizer).  A scope reaches the device trace only through the compiled
program: `repro.obs.phases.op_phases` maps each instruction of the compiled
step to its phase, and a trace names each operation by its instruction.

So the readers compile the step again, once per process and after the
traced span: the run's state and batch are rebuilt as shapes from the
generator's own functions, and the compile is a hit in the harness's compile
cache.  They then sum the device time of the step program's operations per
phase.  A traced operation with no instruction of its name in the compiled
text means the text is not the program that ran: then, and for a program
that names no phases, every reader gives no number.
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
STEP_MODULE = ["jit_train_step*"]


def step_text(config: dict, traffic: dict) -> Optional[str]:
    """The compiled text of the step a cell runs, or None where the
    program names no phases or the step does not compile again."""
    try:
        return _step_text(json.dumps(config, sort_keys=True), json.dumps(traffic, sort_keys=True))
    except Exception:  # a reader gives no number rather than end the run
        say(f"bench: the step did not compile again for its phases:\n{traceback.format_exc()}")
        return None


@functools.lru_cache(maxsize=None)
def _step_text(config_json: str, traffic_json: str) -> Optional[str]:
    from repro.train import step as train_step

    if not hasattr(train_step, "PHASES"):
        return None
    import jax
    import jax.numpy as jnp

    from bench.generators import lm_train as gen

    cfg, traffic = json.loads(config_json), json.loads(traffic_json)
    t0 = time.perf_counter()
    state = jax.eval_shape(gen.make_start(cfg), jax.random.key(0))
    batch = jax.eval_shape(gen.batch_program(cfg, traffic, 0), jnp.int32(0))[0]
    # shapes alone: a sharding would select another compiled program
    bare = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, weak_type=a.weak_type), t)
    text = gen.step_program(cfg).lower(bare(state), bare(batch)).compile().as_text()
    say(f"bench: phases_compile_s = {time.perf_counter() - t0}")
    return text


@functools.lru_cache(maxsize=None)
def _op_phases(text: str) -> dict:
    from repro.obs.phases import op_phases
    from repro.train.step import PHASES

    return op_phases(text, PHASES)


_last: tuple = (None, None)     # (trace, its sums): the readers share one pass


def phase_ns(r) -> Optional[dict]:
    """{phase or `UNSCOPED`: summed device time in ns} of the step
    program's operations in the traced span; None where the phases cannot
    be read."""
    global _last
    if _last[0] is not r.trace:
        _last = (r.trace, _sum(r))
    return _last[1]


def _sum(r) -> Optional[dict]:
    win = tr.ops_within(r.trace, STEP_MODULE)
    text = step_text(r.config, r.traffic) if win.ops else None
    if text is None:
        return None
    phases = _op_phases(text)
    out: dict = {}
    loose: dict = {}
    lo, hi = win.window
    for name, s, e in win.ops:
        inst = name.split(" ", 1)[0].lstrip("%")
        phase = phases.get(inst)
        if phase is None:
            say(f"bench: traced operation {inst!r} is not in the compiled step; no phase is read")
            return None
        if min(e, hi) > max(s, lo):
            out[phase] = out.get(phase, 0) + min(e, hi) - max(s, lo)
            if phase == UNSCOPED:
                loose[inst] = loose.get(inst, 0) + min(e, hi) - max(s, lo)
    out = {k: v / win.n_devices for k, v in out.items()}
    _note(r, out, loose, tr.busy_ns(win))
    return out


def per_step_us(r, phase: str) -> Optional[float]:
    ns = phase_ns(r)
    if ns is None or r.measured["steps"] <= 0:
        return None
    return ns.get(phase, 0) / 1e3 / r.measured["steps"]


def _note(r, ns: dict, loose: dict, busy: float) -> None:
    """What no metric reports: each phase's time a step, the unscoped time
    and its longest instructions, and how the phases add up against the
    step program's busy time."""
    steps = max(r.measured["steps"], 1)
    top = sorted(loose.items(), key=lambda kv: -kv[1])[:5]
    say(f"bench: lm_phase_us_per_step = { {k: v / 1e3 / steps for k, v in ns.items()} }")
    say(f"bench: lm_unscoped_top_us_per_step = {[[k, v / 1e3 / steps] for k, v in top]}")
    say(f"bench: lm_unscoped_share = {ns.get(UNSCOPED, 0) / busy if busy else None}")
    say(f"bench: lm_phases_over_step_busy = {sum(ns.values()) / busy if busy else None}")
