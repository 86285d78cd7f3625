"""The training window's named phases on the compiled program.

One ahead-of-time compile of `loop._train_window` for a described TPU v5e
(no chip attached), at the benchmark cell's shapes: halfcheetah, one env,
a 10^6-row replay, batch 128, 1,000-step windows, the two-launch fused
update.  `obs.phases.op_phases` then maps every instruction to one of
`loop.PHASES`; the tests pin what a device trace of the loop is summed by.
The topology is described inside a fixture, as in
`tests/kernels/test_tpu_compile.py`.
"""
import os
import re
from functools import partial

import jax
import pytest
from jax.sharding import SingleDeviceSharding

from repro.obs.phases import UNSCOPED, op_phases
from repro.rl import ddpg, envs, loop

CAPACITY, BATCH, WINDOW = 1_000_000, 128, 1000
OBS_DIM, ACT_DIM = 17, 6
UPDATE_ROW = -(-max(ddpg.HIDDEN) // 128) * 128  # the widest layer's row, in whole lanes

_INSTRUCTION = re.compile(r"\s*(?:ROOT\s+)?%([\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\(")
_COMPUTATION = re.compile(r"(?:ENTRY )?%([\w.\-]+) ")
_ARRAY = re.compile(r"(\w+)\[([\d,]*)\]")
# instructions that name or move no data of their own
_NO_WORK = {"parameter", "get-tuple-element", "tuple", "constant", "bitcast", "while",
            "conditional"}


@pytest.fixture(scope="module")
def window_text():
    """The compiled window's text, with the kernels compiled for the chip
    (interpret mode off while tracing) and no compile cache involved."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    from repro.kernels.fxp_mlp import ops

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    one_chip = SingleDeviceSharding(topo.devices[0])
    env = envs.make("halfcheetah")
    dcfg = ddpg.DDPGConfig(batch_size=BATCH, backend="pallas_fused_step")
    cfg = loop.TrainConfig(total_steps=WINDOW, warmup_steps=CAPACITY // 2,
                           replay_capacity=CAPACITY, eval_every=WINDOW, n_envs=1)
    ts = jax.eval_shape(partial(loop.init_train_state, env, cfg, dcfg))
    ts = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), ts)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(ops, "interpret_mode", lambda: False)
            jax.clear_caches()
            lowered = loop._train_window.lower(ts, env=env, cfg=cfg, dcfg=dcfg, window=WINDOW)
            return lowered.compile().as_text()
    finally:
        jax.clear_caches()
        jax.config.update("jax_enable_compilation_cache", prev)


def _instructions(text: str) -> dict:
    """{name: (computation, opcode, shape, line)} for every instruction."""
    out, comp = {}, None
    for line in text.splitlines():
        if line and not line.startswith(" "):
            m = _COMPUTATION.match(line)
            comp = m.group(1) if m else comp
            continue
        m = _INSTRUCTION.match(line)
        if m:
            out[m.group(1)] = (comp, m.group(3), m.group(2), line)
    return out


def _scan_body(text: str) -> dict:
    """The instructions that run once per timestep: the while loop's body
    and the branches of the conditionals in it (fused computations run as
    their fusion, and are left out)."""
    ins = _instructions(text)
    (body,) = re.findall(r"body=%([\w.\-]+)", text)
    comps = {body}
    for _, (comp, op, _, line) in ins.items():
        if comp == body and op == "conditional":
            comps |= set(re.findall(r"%([\w.\-]+)", line.split("branch_computations=")[1]))
    return {n: v for n, v in ins.items() if v[0] in comps}


def _arrays(shape: str) -> list:
    """[(dtype, element count)] of each array in an instruction's shape."""
    out = []
    for dtype, dims in _ARRAY.findall(shape):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        out.append((dtype, n))
    return out


def test_every_phase_is_named_in_the_metadata(window_text):
    names = set(re.findall(r'op_name="([^"]*)"', window_text))
    for phase in loop.PHASES:
        assert any(f"/{phase}/" in n for n in names), phase


def test_replay_relayouts_belong_to_sampling(window_text):
    """Sampling reads the ring where it lies.  A scatter store would pin the
    ring row-major, and XLA would relay each 10^6-row field out for the
    batch gather under `replay_sample` every step; the one-row slice store
    keeps the gather's layout.  So what `replay_sample` holds of a field is
    the carry itself, and no 10^6-row array is copied anywhere in the
    window, in the scan or around it.  (`tests/kernels/test_tpu_compile.py`
    guards the store and sample alone, in a few seconds.)"""
    ins = _instructions(window_text)
    phases = op_phases(window_text, loop.PHASES)
    held = {op for n, (_, op, shape, _) in ins.items()
            if phases[n] == loop.REPLAY_SAMPLE and f"[{CAPACITY}," in shape}
    assert held == {"parameter", "get-tuple-element"}
    copies = [n for n, (_, op, shape, _) in ins.items()
              if op in ("copy", "copy-start", "copy-done") and f"[{CAPACITY}," in shape]
    assert copies == []


def test_update_launches_are_named_critic_and_actor(window_text):
    phases = op_phases(window_text, loop.PHASES)
    kernels = sorted(n for n, (*_, line) in _instructions(window_text).items()
                     if 'custom_call_target="tpu_custom_call"' in line)
    launches = [n for n in kernels if n.startswith("fxp_mlp_train_step")]
    assert [re.sub(r"\.\d+$", "", n) for n in launches] == [
        "fxp_mlp_train_step_actor", "fxp_mlp_train_step_critic"]
    assert {phases[n] for n in launches} == {loop.UPDATE}
    # the act kernel keeps its name, under act
    (act,) = [n for n in kernels if n not in launches]
    assert re.fullmatch(r"fxp_mlp_train\.\d+", act) and phases[act] == loop.ACT


def test_unscoped_ops_are_scalar_glue(window_text):
    """What no phase claims in a timestep writes at most one fleet row an
    instruction: scalars, the loop key's threefry split (u32), one fusion
    XLA built across env's auto-reset select and replay's one-row read
    (f32[1, 17]), the scan's per-step writes into its stacked (window,)
    outputs, and the compiler's async move of one row of an update output
    (f32[1, 512]) between memory spaces on its way out of the update's
    branch."""
    phases = op_phases(window_text, loop.PHASES)
    loose = {n: v for n, v in _scan_body(window_text).items()
             if phases[n] == UNSCOPED and v[1] not in _NO_WORK}
    assert loose
    ins = _instructions(window_text)

    def source(name):
        """What an async copy moves: its `copy-start`'s operand."""
        operand = re.search(r"copy-(?:start|done)\(%([\w.\-]+)\)", ins[name][3]).group(1)
        return source(operand) if operand.startswith("copy-start") else operand

    for name, (_, op, shape, line) in loose.items():
        sizes = [n for _, n in _arrays(shape)]
        stacked = "while/body/dynamic_update_slice" in line and sizes == [WINDOW]
        moved = (op in ("copy-start", "copy-done") and phases[source(name)] == loop.UPDATE
                 and max(sizes) <= UPDATE_ROW)
        assert stacked or moved or max(sizes) <= OBS_DIM, (name, op, shape)


def test_train_host_spans_use_the_phase_names():
    from repro.obs import Tracer

    env = envs.make("pendulum")
    dcfg = ddpg.DDPGConfig(qat_enabled=False, batch_size=8)
    cfg = loop.TrainConfig(total_steps=4, warmup_steps=2, replay_capacity=32,
                           eval_every=10 ** 6)
    tracer = Tracer()
    loop.train_host(env, cfg, dcfg, tracer=tracer)
    evs = tracer.events()
    assert {e["cat"] for e in evs} == {"loop"}
    by_step = {}
    for e in evs:
        by_step.setdefault(e["args"]["step"], []).append(e["name"])
    # updates start once two rows are stored: from the second step on
    assert by_step[0] == list(loop.PHASES[:-1])
    assert all(by_step[s] == list(loop.PHASES) for s in (1, 2, 3))


def test_evaluation_is_labelled_act_and_env():
    env = envs.make("pendulum")
    dcfg = ddpg.DDPGConfig(qat_enabled=False)
    agent = jax.eval_shape(partial(ddpg.init, jax.random.key(0), env.spec, dcfg))
    keys = jax.eval_shape(lambda: jax.random.split(jax.random.key(0), 2))
    text = loop._eval_episodes.lower(agent, keys, env=env, dcfg=dcfg).compile().as_text()
    assert set(op_phases(text, loop.PHASES).values()) >= {loop.ACT, loop.ENV}


def _f32_dims(shape: str):
    """The dimensions of an instruction's single float32 result, or None."""
    arrays = _ARRAY.findall(shape)
    if len(arrays) != 1 or arrays[0][0] != "f32":
        return None
    return tuple(int(d) for d in filter(None, arrays[0][1].split(",")))


def _leaf_shapes() -> tuple[set, set]:
    """(unpadded, padded) shapes of the agent's parameter leaves: each
    weight and bias as `ddpg` holds it, and as the fused kernels take it
    (lanes rounded up to 128; a bias as (1, N) and as the (N,) it is
    padded from)."""
    up = lambda n: -(-n // 128) * 128
    unpadded, padded = set(), set()
    for dims in ((OBS_DIM, *ddpg.HIDDEN, ACT_DIM), (OBS_DIM + ACT_DIM, *ddpg.HIDDEN, 1)):
        for k, n in zip(dims[:-1], dims[1:]):
            unpadded |= {(k, n), (n,)}
            padded |= {(up(k), up(n)), (1, up(n)), (up(n),)}
    return unpadded, padded


def test_update_and_act_neither_pad_nor_unpad_the_agent(window_text):
    """The window carries the agent in the fused kernels' layout: no
    timestep pads a parameter leaf into it or slices one back out, in the
    update's branch or in acting.  (Padding all eight parameter sets
    around every update shows here as 40 pads and 32 slices, and acting
    on unpadded weights as 5 more pads.)"""
    phases = op_phases(window_text, loop.PHASES)
    unpadded, padded = _leaf_shapes()
    found = [(n, op, shape) for n, (_, op, shape, _) in _scan_body(window_text).items()
             if phases[n] in (loop.UPDATE, loop.ACT)
             and ((op == "pad" and _f32_dims(shape) in padded)
                  or (op == "slice" and _f32_dims(shape) in unpadded))]
    assert found == []


def test_working_instructions_a_timestep(window_text):
    """The instructions that run once a timestep and do work (fused
    computations count once, as their fusion; parameters, tuples,
    constants, bitcasts and the loop's own control are left out): 413 at
    the benchmark cell's shapes, of which the update's branch holds 220
    (both launches, the batch's pads, the QAT and Adam scalars) and acting
    75.  Padding and unpadding the agent around every update instead
    compiles to 536 (update 334, act 82)."""
    phases = op_phases(window_text, loop.PHASES)
    working = [n for n, (_, op, _, _) in _scan_body(window_text).items() if op not in _NO_WORK]
    assert len(working) <= 450, len(working)
    per_phase = {p: sum(phases[n] == p for n in working) for p in (loop.UPDATE, loop.ACT)}
    assert per_phase[loop.UPDATE] <= 240 and per_phase[loop.ACT] <= 78, per_phase
