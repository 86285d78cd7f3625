"""Stream batched DDPG updates through the learner engine.

Simulates the training workload FIXAR's headline number comes from (many
update batches driven through the fused kernels with intra-batch
parallelism): producer threads submit replay batches and trajectory
chunks; the update batcher coalesces them into padded buckets; every
micro-batch trains through the backend the DDPG config names (jnp
autodiff here; `backend="pallas_fused_step"` runs the 2-launch
whole-update kernel) and applies sequentially to one training state.

    PYTHONPATH=src python examples/train_learner.py
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import threading
import time

import jax
import numpy as np

from repro.rl import ddpg
from repro.serve.policy import BatcherConfig
from repro.rl.envs.locomotion import make
from repro.train.learner import LearnerEngine


def replay_batch(rng, n, obs_dim, act_dim):
    return {
        "obs": rng.standard_normal((n, obs_dim)).astype(np.float32),
        "action": rng.uniform(-1, 1, (n, act_dim)).astype(np.float32),
        "reward": rng.standard_normal((n,)).astype(np.float32),
        "next_obs": rng.standard_normal((n, obs_dim)).astype(np.float32),
        "done": np.zeros((n,), bool),
    }


def main():
    env = make("halfcheetah")
    cfg = ddpg.DDPGConfig(qat_delay=0)  # quantized phase from step 0
    state = ddpg.init(jax.random.key(0), env.spec, cfg)
    engine = LearnerEngine.from_ddpg(
        state, cfg, batcher=BatcherConfig(buckets=(8, 32, 128), max_wait_ms=2.0))
    # warm the buckets the producers actually hit
    n = engine.warmup(buckets=(8, 32), padded=True)
    print(f"learner up: backend={cfg.backend}, warmed {n} executables")

    rng = np.random.default_rng(0)
    engine.start()
    t0 = time.perf_counter()

    def producer(k):
        prng = np.random.default_rng(k)
        futs = [engine.submit(replay_batch(
                    prng, int(prng.integers(4, 32)),
                    env.spec.obs_dim, env.spec.act_dim))
                for _ in range(8)]
        for f in futs:
            m = f.result(timeout=600.0)
            assert "critic_loss" in m

    threads = [threading.Thread(target=producer, args=(k,))
               for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # one whole-trajectory chunk, larger than the top bucket (auto-split)
    traj = replay_batch(rng, 300, env.spec.obs_dim, env.spec.act_dim)
    m = engine.submit(traj).result(timeout=600.0)
    engine.stop()
    dt = time.perf_counter() - t0

    s = engine.stats()
    print(f"{s['requests']} requests -> {s['updates']} updates "
          f"({s['transitions']} transitions) in {dt:.2f}s: "
          f"{s['train_ips_wall']:.0f} wall train-IPS, "
          f"{s['train_ips_device']:.0f} device train-IPS")
    print(f"latency p50 {s['p50_ms']:.2f} ms, p99 {s['p99_ms']:.2f} ms; "
          f"occupancy {s['batch_occupancy']:.2f}; "
          f"modes {s['mode_histogram']}; trajectory chunks={m['chunks']}")
    print(f"state advanced to step {int(engine.state.step)}")


if __name__ == "__main__":
    main()
