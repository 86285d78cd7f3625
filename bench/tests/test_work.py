"""The work counts against hand figures for the paper's HalfCheetah job."""
import json
import os

import pytest

from bench.work import ddpg as work

CFG = os.path.join(os.path.dirname(__file__), "..", "configs")


def cfg(name):
    with open(os.path.join(CFG, name + ".json")) as f:
        return json.load(f)


def test_update_flops_per_sample_halfcheetah():
    # actor 17*400 + 400*300 + 300*6 = 128,600 multiply-adds a row, critic
    # 23*400 + 400*300 + 300*1 = 129,500; 4 actor and 6 critic passes
    c = cfg("ddpg_halfcheetah")
    assert work.macs(c, "actor") == 128_600
    assert work.macs(c, "critic") == 129_500
    assert work.update_flops_per_sample(c) == 2 * (4 * 128_600 + 6 * 129_500)
    assert work.update_flops_per_sample(c) == pytest.approx(2.6e6, rel=0.01)


def test_update_bytes_halfcheetah():
    # 259,507 parameters; 8 values each read or written, the target actor
    # and the updated critic read once more, 128 rows of 42 values
    c = cfg("ddpg_halfcheetah")
    assert work.params(c, "actor") + work.params(c, "critic") == 259_507
    assert work.update_bytes(c) == 4 * (9 * 259_507 + 128 * 42)
    assert work.update_bytes(c) == pytest.approx(9.3e6, rel=0.01)


def test_roofline_bound_is_bytes_at_these_widths():
    c = cfg("ddpg_halfcheetah")
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = work.least_time_s(work.update_flops(c), work.update_bytes(c), peaks)
    assert bound == "bytes"
    assert t == pytest.approx(work.update_bytes(c) / 819e9)


def test_act_counts_hopper():
    c = cfg("ddpg_hopper")
    assert work.macs(c, "actor") == 11 * 400 + 400 * 300 + 300 * 3
    assert work.act_flops(c, 10) == 20 * work.macs(c, "actor")
    assert work.act_bytes(c, 0) == 4 * work.params(c, "actor")
