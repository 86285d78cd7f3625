"""The per-phase readers on a synthetic trace of two timesteps and the
compiled text it was made against (`data/train_window_phases.json`): the
seven metrics and the unscoped time partition the window program's busy
time, one traced operation missing from the text silences every reader,
and the older readers still find the renamed update launches."""
import importlib
import json
import os

import pytest

from bench import harness, phases
from bench import trace as tr

DATA = os.path.join(os.path.dirname(__file__), "data", "train_window_phases.json")
CELL = "train_halfcheetah_monitor"
PER_TIMESTEP = ["train_act_us", "train_env_us", "train_replay_add_us", "train_replay_sample_us"]
PER_UPDATE = ["train_update_us", "train_critic_step_us", "train_actor_step_us"]


def _data() -> dict:
    with open(DATA) as f:
        return json.load(f)


@pytest.fixture
def reading(monkeypatch):
    d = _data()
    monkeypatch.setattr(phases, "window_text", lambda config, traffic: d["hlo"])
    cell = harness.load_cell(CELL)
    return harness.Reading(
        trace=tr.Trace.from_json(d["trace"]), counters={},
        work=harness.work_module(cell.config), config=cell.config, traffic=cell.traffic,
        peaks=harness.device_kind_peaks("TPU v5 lite"), measured=d["measured"], chips=1)


def _read(name, r):
    return importlib.import_module(f"bench.metrics.{name}").read(r)


def test_the_new_metrics_are_the_cells_per_layer_metrics():
    names = {m["name"] for m in harness.load_cell(CELL).per_layer}
    assert set(PER_TIMESTEP + PER_UPDATE) <= names


def test_phases_and_unscoped_partition_the_window_busy_time(reading):
    steps, updates = reading.measured["timesteps"], reading.measured["updates"]
    per_step = {n: _read(n, reading) for n in PER_TIMESTEP}
    per_update = {n: _read(n, reading) for n in PER_UPDATE}
    assert all(v is not None and v > 0 for v in {**per_step, **per_update}.values())
    unscoped_ns = phases.phase_ns(reading)[phases.UNSCOPED]
    win = tr.ops_within(reading.trace, tr.module_patterns("train_window"))
    total_us = (sum(per_step.values()) * steps + per_update["train_update_us"] * updates
                + unscoped_ns / 1e3)
    assert total_us == pytest.approx(tr.busy_ns(win) / 1e3, rel=1e-12)
    # the launches lie inside the update phase
    launches = per_update["train_critic_step_us"] + per_update["train_actor_step_us"]
    assert launches < per_update["train_update_us"]


def test_each_metric_reads_its_scope(reading):
    # the synthetic timestep's durations, in us: the replay's relayout is
    # billed to sampling through the gather that reads it
    assert _read("train_act_us", reading) == pytest.approx(5 + 2)
    assert _read("train_env_us", reading) == pytest.approx(11)
    assert _read("train_replay_add_us", reading) == pytest.approx(6)
    assert _read("train_replay_sample_us", reading) == pytest.approx(3 + 2000 + 4)
    assert _read("train_update_us", reading) == pytest.approx(0.5 + 13 + 26 + 23)
    assert _read("train_critic_step_us", reading) == pytest.approx(26)
    assert _read("train_actor_step_us", reading) == pytest.approx(23)


def test_an_operation_missing_from_the_text_silences_every_reader(reading):
    reading.trace.ops.insert(3, ("%fusion.999 f32[1,17]", 710_200, 710_300))
    assert [_read(n, reading) for n in PER_TIMESTEP + PER_UPDATE] == [None] * 7


def test_a_program_without_phases_gives_no_number(reading, monkeypatch):
    monkeypatch.setattr(phases, "window_text", lambda config, traffic: None)
    assert [_read(n, reading) for n in PER_TIMESTEP + PER_UPDATE] == [None] * 7


def test_older_readers_find_the_renamed_launches(reading):
    win = tr.ops_within(reading.trace, tr.module_patterns("train_window"))
    launches_ns = 2 * (26_000 + 23_000)
    assert tr.op_time_ns(win, tr.kernel_patterns("update_step")) == launches_ns
    steps = reading.measured["timesteps"]
    assert _read("train_rollout_us", reading) == pytest.approx(
        (tr.busy_ns(win) - launches_ns) / 1e3 / steps)
    w, cfg = reading.work, reading.config
    least, _ = w.least_time_s(w.update_flops(cfg), w.update_bytes(cfg), reading.peaks)
    assert _read("train_step_roofline", reading) == pytest.approx(
        100.0 * least * reading.measured["updates"] / (launches_ns / 1e9))
