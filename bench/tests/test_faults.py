"""A whole run with the timed path broken underneath reads `correct`
false: once for each fault a cell can have.  The program's kernels run in
the interpreter here, so the cells are cut to small widths."""
import jax
import numpy as np
import pytest

from bench.tests._cells import cell, run_cell

TRAIN_CUT = dict(config=dict(hidden=[64, 48], replay_capacity=2048, eval_episodes=2,
                             episode_length=50),
                 traffic=dict(window=24))


@pytest.fixture
def fresh_jit():
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_sound_training_run_is_correct(fresh_jit):
    res = run_cell(cell("train_halfcheetah_monitor", **TRAIN_CUT), seed=5)
    assert res["correct"], res["check"]


def _zero_metrics():
    z = jax.numpy.float32(0)
    return {"critic_loss": z, "actor_loss": z, "q_mean": z}


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "action_altered"])
def test_training_fault_is_caught(fault, fresh_jit, monkeypatch):
    from repro.rl import ddpg

    update, act = ddpg.update, ddpg.act
    if fault == "state_unchanged":
        monkeypatch.setattr(ddpg, "update", lambda s, b, cfg: (s, _zero_metrics()))
    elif fault == "half_batch":
        monkeypatch.setattr(ddpg, "update", lambda s, b, cfg: update(
            s, jax.tree.map(lambda x: x[: x.shape[0] // 2], b), cfg))
    else:
        monkeypatch.setattr(ddpg, "act", lambda *a, **k: act(*a, **k).at[..., 0].add(1e-2))
    res = run_cell(cell("train_halfcheetah_monitor", **TRAIN_CUT), seed=5)
    assert not res["correct"], res["check"]


def test_serving_answer_altered_is_caught(fresh_jit, monkeypatch):
    from repro.serve.policy import PolicyEngine

    run_batch = PolicyEngine.run_batch
    altered_once = []

    def altered(self, obs):
        y = run_batch(self, obs)
        if self._thread is not None and not altered_once:   # serving, in the window
            altered_once.append(True)
            y = np.array(y)
            y[0, 0] += 1e-2
        return y

    c = cell("serve_hopper_open_monitor", config=dict(hidden=[32, 32]),
             traffic=dict(rate_per_s=100.0))
    monkeypatch.setattr(PolicyEngine, "run_batch", altered)
    res = run_cell(c, seed=9, seconds=1.0)
    assert not res["correct"], res["check"]
