"""Fig. 10 — accelerator-only throughput + energy efficiency.

Accelerator-only IPS: time only the jitted inference+update work (no env,
no host transfer).  Energy: no power rail to read on CPU, so the IPS/W
column is MODELED from the roofline terms of the DDPG step on the TPU
target (bounded by max(compute, memory) term × chip TDP) — clearly labeled
as modeled; the measured CPU IPS column is real wall-time.

Paper reference points: 53,826.8 IPS and 2,638.0 IPS/W on the U50.
"""
import pathlib
import sys

_REPO = pathlib.Path(__file__).resolve().parents[1]
if str(_REPO) not in sys.path:
    sys.path.insert(0, str(_REPO))

import argparse
import json

import jax
import jax.numpy as jnp

from benchmarks.common import RESULTS, emit, time_fn

from repro.rl import ddpg, replay
from repro.rl.envs.locomotion import make

BATCHES = (64, 128, 256, 512)

# TPU v5e modeling constants (per task spec + public TDP)
PEAK_FLOPS = 197e12
HBM_BW = 819e9
CHIP_W = 170.0  # v5e max TDP (modeled upper bound on power)


def ddpg_step_flops(obs_dim: int, act_dim: int, batch: int) -> float:
    """Analytic MACs of one DDPG timestep (fwd+bwd of actor+critic on the
    batch + actor inference), 2 flops per MAC."""
    a = obs_dim * 400 + 400 * 300 + 300 * act_dim
    c = (obs_dim + act_dim) * 400 + 400 * 300 + 300
    infer = 2 * a                       # single-state actor forward
    train = 3 * 2 * (a + c) * batch     # fwd+bwd ~3x fwd for both nets
    target = 2 * (a + c) * batch        # target-net forwards
    return 2.0 * (infer + train + target)


def run(env_name: str, iters: int) -> dict:
    env = make(env_name)
    out = {}
    for bs in BATCHES:
        dcfg = ddpg.DDPGConfig(batch_size=bs, qat_delay=10)
        agent = ddpg.init(jax.random.key(0), env.spec, dcfg)
        buf = replay.init(4096, env.spec.obs_dim, env.spec.act_dim)
        obs = jax.random.normal(jax.random.key(1), (1, env.spec.obs_dim))
        buf = replay.add(buf, jnp.repeat(obs, 1024, 0),
                         jnp.zeros((1024, env.spec.act_dim)),
                         jnp.zeros((1024,)),
                         jnp.repeat(obs, 1024, 0),
                         jnp.zeros((1024,), jnp.bool_))
        batch = replay.sample(buf, jax.random.key(2), bs)

        @jax.jit
        def accel_work(agent, obs, batch):
            act = ddpg.act(agent, obs, cfg=dcfg)
            agent2, _ = ddpg.update(agent, batch, dcfg)
            return act, agent2

        us = time_fn(lambda: accel_work(agent, obs, batch), iters=iters)
        ips_cpu = 1e6 / us
        flops = ddpg_step_flops(env.spec.obs_dim, env.spec.act_dim, bs)
        # modeled TPU step time: max(compute, memory) roofline term; the
        # DDPG model (514KB) lives in VMEM so memory term ~ activations only
        t_tpu = max(flops / PEAK_FLOPS, 64e-6)  # dispatch floor 64us
        ips_tpu = 1.0 / t_tpu
        ipw_tpu = ips_tpu / CHIP_W
        out[bs] = {"ips_cpu_measured": ips_cpu,
                   "ips_tpu_modeled": ips_tpu,
                   "ips_per_w_tpu_modeled": ipw_tpu}
        emit(f"fig10/{env_name}/batch{bs}", us,
             f"ips_cpu={ips_cpu:.1f};ips_tpu_model={ips_tpu:.0f};"
             f"ipw_model={ipw_tpu:.1f}")
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--env", default="halfcheetah")
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args(argv)
    out = run(args.env, args.iters)
    RESULTS.mkdir(parents=True, exist_ok=True)
    (RESULTS / f"fig10_{args.env}.json").write_text(json.dumps(out, indent=2))


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
