"""End-to-end behaviour tests for the FIXAR platform."""
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.data.synthetic import DataConfig, DataIterator
from repro.models.config import ShapeConfig
from repro.optim import adam
from repro.rl import ddpg, loop
from repro.rl.envs.locomotion import make
from repro.train.step import init_state, make_train_step

REPO = pathlib.Path(__file__).resolve().parents[1]


def test_fixar_timestep_sequence():
    """One full FIXAR timestep (Fig. 3): inference -> env -> replay ->
    critic update -> actor update, fused; state stays finite."""
    env = make("hopper")
    dcfg = ddpg.DDPGConfig(batch_size=32, qat_delay=5)
    cfg = loop.TrainConfig(total_steps=40, warmup_steps=8,
                           replay_capacity=512, eval_every=10 ** 9)
    ts, _ = loop.train_device(env, cfg, dcfg,
                              eval_fn=lambda *a: jnp.float32(0.0))
    assert int(ts.agent.step) > 0
    assert all(bool(jnp.all(jnp.isfinite(x)))
               for x in jax.tree.leaves(ts.agent.actor))


def test_host_mode_produces_breakdown():
    """Fig.-9 style env/runtime/accelerator time split."""
    env = make("pendulum")
    dcfg = ddpg.DDPGConfig(batch_size=16)
    cfg = loop.TrainConfig(total_steps=30, warmup_steps=10,
                           replay_capacity=256, eval_every=10 ** 9)
    _, report = loop.train_host(env, cfg, dcfg)
    t = report["times"]
    assert set(t) == {"env", "runtime", "accelerator"}
    assert all(v > 0 for v in t.values())


def test_lm_loss_decreases_on_synthetic_stream():
    """Train demo-smoke on fresh synthetic batches: loss goes down (the
    stream has learnable n-gram structure, see data/synthetic.py)."""
    cfg = registry.get_smoke("demo_100m")
    shape = ShapeConfig("t", "train", 64, 8)
    state = init_state(jax.random.key(0), cfg)
    step = jax.jit(make_train_step(cfg, adam.AdamConfig(lr=3e-3,
                                                        grad_clip_norm=1.0)))
    it = DataIterator(DataConfig(seed=0), cfg, shape)
    losses = []
    for _ in range(30):
        state, m = step(state, next(it))
        losses.append(float(m["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.2


def test_train_driver_cli_resume(tmp_path):
    """The launch driver trains, checkpoints, and resumes deterministically."""
    from repro.launch.train import main
    ckpt_dir = str(tmp_path / "ck")
    main(["--arch", "demo_100m", "--smoke", "--steps", "12", "--batch", "2",
          "--seq", "32", "--ckpt-dir", ckpt_dir, "--ckpt-every", "6",
          "--log-every", "6"])
    main(["--arch", "demo_100m", "--smoke", "--steps", "18", "--batch", "2",
          "--seq", "32", "--ckpt-dir", ckpt_dir, "--resume",
          "--log-every", "6"])
    from repro.checkpoint import ckpt as C
    assert C.latest_step(ckpt_dir) == 18


def test_generate_shapes():
    from repro.serve.engine import generate
    cfg = registry.get_smoke("qwen2_0_5b")
    from repro.models import transformer as T
    params = T.init_params(jax.random.key(0), cfg)
    prompt = jax.random.randint(jax.random.key(1), (2, 5), 0, cfg.vocab_size)
    out = generate(params, cfg, prompt, max_new=4)
    assert out.shape == (2, 9)
    assert int(out.max()) < cfg.vocab_size
