"""Quickstart: FIXAR fixed-point QAT training of DDPG on a continuous-control
task — the paper's platform in ~40 lines.

    PYTHONPATH=src python examples/quickstart.py
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))


from repro.rl import ddpg, loop
from repro.rl.envs.locomotion import make


def main():
    env = make("pendulum")
    total_steps = 6_000

    # FIXAR Algorithm 1: fxp32 everywhere; activations drop to 16-bit affine
    # after the quantization delay (40% of training, as in the paper's runs).
    dcfg = ddpg.DDPGConfig(
        batch_size=64,
        actor_lr=3e-4, critic_lr=1e-3,
        qat_enabled=True, fxp_weights=True,
        qat_delay=int(0.4 * total_steps),
        qat_bits=16,
    )
    cfg = loop.TrainConfig(total_steps=total_steps, warmup_steps=500,
                           eval_every=2_000, replay_capacity=20_000,
                           eval_episodes=4, seed=0)

    print(f"training DDPG on {env.spec.name} "
          f"(obs={env.spec.obs_dim}, act={env.spec.act_dim}), "
          f"quantization delay={dcfg.qat_delay} steps")
    ts, hist = loop.train_device(env, cfg, dcfg)
    for s, r, ips in zip(hist["step"], hist["eval_reward"], hist["ips"]):
        phase = "fxp16-activations" if s >= dcfg.qat_delay else "fxp32"
        print(f"  step {s:6d}  eval_reward {r:8.1f}  ips {ips:7.1f}  [{phase}]")
    print("done — captured activation ranges:",
          {k: (round(float(v.a_min), 2), round(float(v.a_max), 2))
           for k, v in ts.agent.qat.ranges.items()})


if __name__ == "__main__":
    main()
