"""Granite 4.0-H (Mamba-2 + NoPE attention hybrid) on the normal LM
training path: the registry's smoke config through `make_train_step` with
QAT monitor sites in every layer, the step's named phases, and
`launch/train.py`."""
import dataclasses
import json
import pathlib
import sys

import jax
import jax.numpy as jnp

REPO = pathlib.Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:   # bench/ is imported as a package from the root
    sys.path.insert(0, str(REPO))

from bench.generators import lm_train as gen  # noqa: E402
from repro.configs import registry  # noqa: E402
from repro.data.synthetic import DataConfig, make_batch  # noqa: E402
from repro.models.config import MAMBA2, ShapeConfig  # noqa: E402
from repro.train.step import PHASES, init_state, make_train_step  # noqa: E402


def _opt():
    return gen.opt_config(json.loads((REPO / "bench/configs/granite_4_0_h_micro.json").read_text()))


def _packed_batch(mcfg, seq=32, rows=2, seed=5):
    dc = DataConfig(seed=seed, packed=True, doc_len_median=12, doc_len_sigma=1.0,
                    doc_len_min=2, doc_len_max=seq)
    return make_batch(dc, mcfg, ShapeConfig("t", "train", seq, rows), 0)



def test_train_step_runs_qat_sites_in_every_layer():
    cfg = dataclasses.replace(registry.get_smoke("granite_4_0_h_micro"), qat=True,
                              qat_delay=5)
    state = init_state(jax.random.key(0), cfg)
    step = jax.jit(make_train_step(cfg, _opt()))
    batch = _packed_batch(cfg)
    state, metrics = step(state, batch)
    assert bool(jnp.isfinite(metrics["loss"])) and int(metrics["quant_phase"]) == 0
    for slot, bt in zip(state.ranges["scan"], cfg.block_pattern):
        sites = ("mamba_in", "mamba_out_in") if bt == MAMBA2 else ("attn_in", "attn_o_in")
        for site in sites + ("mlp_in", "mlp_down_in"):
            assert int(slot[site].count[0]) == 1, (bt, site)
            assert float(slot[site].a_max[0]) > float(slot[site].a_min[0])


def test_every_phase_names_part_of_the_compiled_step():
    from repro.obs.phases import op_phases

    cfg = registry.get_smoke("granite_4_0_h_micro")
    state = jax.eval_shape(lambda k: init_state(k, cfg), jax.random.key(0))
    batch = jax.eval_shape(lambda: _packed_batch(cfg))
    step = jax.jit(make_train_step(cfg, _opt(), ce_chunk=16,
                                   attn_chunk=16))
    phases = op_phases(step.lower(state, batch).compile().as_text(), PHASES)
    assert set(PHASES) <= set(phases.values())


def test_launch_train_runs_the_smoke_config(capsys):
    from repro.launch.train import main

    main(["--arch", "granite-4.0-h-micro", "--smoke", "--qat", "--qat-delay", "2",
          "--steps", "3", "--batch", "1", "--seq", "32", "--log-every", "1"])
    out = capsys.readouterr().out
    assert "arch=granite-h-smoke" in out and out.rstrip().endswith("done")
