"""Compile rehearsal: the main-path Pallas kernels at the paper's widths,
compiled ahead of time for a described TPU v5e (no chip attached).

Interpret mode cannot see what Mosaic refuses (scalar stores into VMEM,
boolean selects, block shapes off the (8, 128) tiling); the chip's compiler
can, and it is installed here.  Each test compiles with `interpret=False`
and counts the Pallas kernels (`tpu_custom_call`) in the compiled program;
one more compiles the replay's store-then-sample scan and counts its
relayout copies.
The topology is described inside a fixture, never at import: only one
process may load the TPU library, and the test workers import every file.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.kernels.fxp_matmul.ops import fxp_dense
from repro.kernels._compat import round_up
from repro.kernels.fxp_mlp.ops import (fxp_mlp_forward, fxp_mlp_train,
                                       fxp_mlp_train_step_padded)
from repro.kernels.quantize.ops import monitor_quant
from repro.optim import adam
from repro.rl import ddpg, replay

OBS, ACT = 17, 6                                # halfcheetah
ACTOR = (OBS, *ddpg.HIDDEN, ACT)                # 17-400-300-6
CRITIC = (OBS + ACT, *ddpg.HIDDEN, 1)           # 23-400-300-1


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _net(sharding, dims):
    ws = tuple(_sds(sharding, (k, n)) for k, n in zip(dims[:-1], dims[1:]))
    bs = tuple(_sds(sharding, (n,)) for n in dims[1:])
    return ws, bs


def _padded_net(sharding, dims):
    """One net's interleaved [w0, b0, w1, b1, ...] in the kernels' layout."""
    p = [round_up(d, 128) for d in dims]
    return [_sds(sharding, shape) for k, n in zip(p[:-1], p[1:])
            for shape in ((k, n), (1, n))]


def _kernels(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("batch", [128, 512])
def test_fused_forward_compiles(one_chip, batch):
    ws, bs = _net(one_chip, ACTOR)
    fn = jax.jit(functools.partial(fxp_mlp_forward, activations=ddpg.ACTOR_ACTS,
                                   interpret=False))
    compiled = fn.lower(_sds(one_chip, (batch, OBS)), ws, bs,
                        _sds(one_chip, (3,)), _sds(one_chip, (3,)),
                        quant_phase=_sds(one_chip, (), jnp.bool_)).compile()
    assert _kernels(compiled) == 1


@pytest.mark.parametrize("batch", [128, 512])
def test_fused_train_step_compiles(one_chip, batch):
    s = functools.partial(_sds, one_chip)
    actor, critic = _padded_net(one_chip, ACTOR), _padded_net(one_chip, CRITIC)
    consts = adam.StepConstants(*(s(()) for _ in adam.StepConstants._fields))
    fn = jax.jit(functools.partial(
        fxp_mlp_train_step_padded, actor_acts=ddpg.ACTOR_ACTS,
        critic_acts=ddpg.CRITIC_ACTS, actor_dims=ACTOR, critic_dims=CRITIC,
        gamma=0.99, tau=0.005, interpret=False))
    compiled = fn.lower(
        s((batch, OBS)), s((batch, ACT)), s((batch,)), s((batch,)), s((batch, OBS)),
        s((batch,)), actor, critic, actor, critic, actor, actor, critic, critic,
        s((6,)), s((6,)), consts, consts, s((), jnp.bool_)).compile()
    assert _kernels(compiled) == 2


def test_custom_vjp_pair_compiles(one_chip):
    ws, bs = _net(one_chip, ACTOR)

    def loss(x, ws, bs, deltas, zs, phase):
        y, _, _ = fxp_mlp_train(x, ws, bs, deltas, zs, activations=ddpg.ACTOR_ACTS,
                                quant_phase=phase, interpret=False)
        return jnp.sum(y)

    fn = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    compiled = fn.lower(_sds(one_chip, (128, OBS)), ws, bs, _sds(one_chip, (3,)),
                        _sds(one_chip, (3,)), _sds(one_chip, (), jnp.bool_)).compile()
    assert _kernels(compiled) == 2      # the fused forward and the fused backward


def test_dense_layer_compiles(one_chip):
    fn = jax.jit(functools.partial(fxp_dense, activation="relu", interpret=False))
    compiled = fn.lower(_sds(one_chip, (7, OBS)), _sds(one_chip, (OBS, 400)),
                        _sds(one_chip, (400,))).compile()
    assert _kernels(compiled) == 1


def test_single_row_replay_keeps_its_layout(one_chip):
    """A one-env step stores one row into a 10^6-row ring and samples a
    128-row batch that only the update's branch reads.  The batch gather
    wants each field column-major; a scatter store pins the scan's carry
    row-major, and XLA then copies every field into the gather's layout
    each step (512 MB each, lanes padded to 128).  The slice store leaves
    the carry in the gather's layout: no 10^6-row array is copied."""
    cap = 1_000_000
    buf = jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype),
                       jax.eval_shape(functools.partial(replay.init, cap, OBS, ACT)))

    def step(carry, row):
        buf, key = carry
        key, k_sample = jax.random.split(key)
        buf = replay.add_batch(buf, row)
        batch = replay.sample(buf, k_sample, 128)
        loss = jax.lax.cond(buf.size >= 2,
                            lambda b: sum(jnp.sum(v.astype(jnp.float32)) for v in b.values()),
                            lambda b: jnp.float32(0), batch)
        return (buf, key), loss

    rows = {"obs": _sds(one_chip, (8, 1, OBS)), "action": _sds(one_chip, (8, 1, ACT)),
            "reward": _sds(one_chip, (8, 1)), "next_obs": _sds(one_chip, (8, 1, OBS)),
            "done": _sds(one_chip, (8, 1), jnp.bool_)}
    key = jax.eval_shape(lambda: jax.random.key(0))
    fn = jax.jit(lambda buf, key, rows: jax.lax.scan(step, (buf, key), rows), donate_argnums=0)
    text = fn.lower(buf, _sds(one_chip, key.shape, key.dtype), rows).compile().as_text()
    copies = re.findall(rf"= \S*\[{cap},[^\n]* copy(?:-start)?\(", text)
    assert copies == []


def test_monitor_quant_compiles(one_chip):
    fn = jax.jit(functools.partial(monitor_quant, interpret=False))
    compiled = fn.lower(_sds(one_chip, (128, 400)), _sds(one_chip, ()), _sds(one_chip, ()),
                        _sds(one_chip, (), jnp.bool_)).compile()
    assert _kernels(compiled) == 1


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Kernels in code that picks interpret mode from the (CPU) backend at
    trace time compile for the chip instead.  Traces made on either side
    of the switch are dropped, so no other test in this worker reuses one."""
    from repro.kernels.fxp_mlp import ops

    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_sharded_serve_act_compiles(topo, compiled_kernels):
    """The policy engine's act over a 4-chip `data` mesh: the compiler
    cannot partition a Mosaic kernel, so the engine wraps it in shard_map —
    one kernel per chip on its 128 of the 512 rows."""
    from repro.rl.envs.base import EnvSpec
    from repro.serve.policy import PolicyEngine

    mesh = Mesh(np.array(topo.devices), ("data",))
    state = ddpg.init(jax.random.key(0), EnvSpec("hc", obs_dim=OBS, act_dim=ACT),
                      ddpg.DDPGConfig())
    engine = PolicyEngine.from_ddpg(state, mesh=mesh, force_mode="fused")
    replicated = NamedSharding(mesh, P())
    as_sds = lambda t: jax.tree.map(lambda a: _sds(replicated, a.shape, a.dtype), t)
    x = _sds(NamedSharding(mesh, P("data")), (512, OBS))
    compiled = engine._sharded_fns["fused"].lower(
        as_sds(engine.actor), x, as_sds(engine.frozen)).compile()
    assert _kernels(compiled) == 1
    # the kernel's output rows: the 512-row batch split four ways
    rows = re.findall(r'= \(f32\[(\d+),\d+\][^\n]*custom_call_target="tpu_custom_call"',
                      compiled.as_text())
    assert rows == ["128"]
    assert compiled.output_shardings.spec == P("data")
