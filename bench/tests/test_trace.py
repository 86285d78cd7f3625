"""The trace reduction: busy union, idle share, kernel time by name
pattern, operations inside modules, and idle gaps labelled by the host's
bench.* spans."""
import json
import os

import pytest

from bench import trace as tr

DATA = os.path.join(os.path.dirname(__file__), "data")


def small() -> tr.Trace:
    # window 0..100; two overlapping ops, one kernel, a gap at 40..60
    # while the host evaluates, and the tail 90..100 under the readback
    return tr.Trace(
        ops=[("fusion.1", 0, 20), ("fusion.2", 10, 30),
             ("%fxp_mlp_train_step.4 = (f32[128,512]{1,0}) custom-call(f32[128,128]{1,0} %x)",
              30, 40),
             ("%fxp_mlp_train_step.5 = (f32[128,512]{1,0}) custom-call(f32[128,128]{1,0} %y)",
              60, 80),
             ("copy.3", 85, 90)],
        modules=[("jit__train_window", 0, 40), ("jit__eval_episodes", 60, 90)],
        host=[("bench.window", 0, 40), ("bench.eval", 40, 90), ("bench.readback", 90, 100)],
        window=(0, 100))


def test_busy_is_the_union_of_operations():
    t = small()
    assert tr.busy_ns(t) == 40 + 20 + 5
    assert tr.idle_share(t) == pytest.approx(0.35)


def test_kernel_time_by_name_pattern():
    t = small()
    assert tr.op_time_ns(t, ["%fxp_mlp_train_step.4 *"]) == 10
    assert tr.op_time_ns(t, tr.kernel_patterns("update_step")) == 30
    assert tr.op_count(t, ["fusion.*"]) == 2


def test_operations_within_modules():
    t = tr.ops_within(small(), tr.module_patterns("train_window"))
    assert [tr.short_name(o[0]) for o in t.ops] == ["fusion.1", "fusion.2",
                                                    "%fxp_mlp_train_step.4 f32[128,512]"]
    assert tr.busy_ns(t) == 40


def test_idle_gaps_are_labelled_by_the_host():
    t = small()
    assert tr.idle_gaps(t) == [(40, 60), (80, 85), (90, 100)]
    assert tr.idle_by_host(t) == [["eval", 25e-9], ["readback", 10e-9]]


def test_top_operations():
    top = tr.top_ops(small(), 2)
    assert top[0][0] in ("fusion.1", "fusion.2", "_ddpg_actor_step_kernel")
    assert top[0][1] == 20e-9 and len(top) == 2


def test_events_are_clipped_to_the_window():
    t = small()
    t.window = (15, 70)
    # fusion.1 15..20, fusion.2 15..30, critic 30..40, actor 60..70
    assert tr.busy_ns(t) == 25 + 10
    assert tr.op_time_ns(t, ["%fxp_mlp_train_step.5 *"]) == 10


def test_round_trip_through_json():
    t = small()
    assert tr.Trace.from_json(json.loads(json.dumps(t.to_json()))) == t


def recorded() -> tr.Trace:
    with open(os.path.join(DATA, "train_halfcheetah_trace.json")) as f:
        return tr.Trace.from_json(json.load(f))


def test_recorded_trace_busy_and_idle():
    # seven timesteps of train_halfcheetah's window, recorded on a v5e:
    # the device is busy nearly throughout one scanned launch
    t = recorded()
    assert tr.window_ns(t) == pytest.approx(18.47e6, rel=1e-3)
    assert tr.busy_ns(t) <= tr.window_ns(t)
    assert tr.idle_share(t) == pytest.approx(0.02, abs=0.005)


def test_recorded_trace_update_kernels_by_name():
    # two fused update launches per timestep, about 18 us each
    t = recorded()
    assert tr.op_count(t, tr.kernel_patterns("update_step")) == 14
    per_launch = tr.op_time_ns(t, tr.kernel_patterns("update_step")) / 14
    assert per_launch == pytest.approx(17.7e3, rel=0.05)


def test_recorded_trace_top_ops_are_the_replay_relayouts():
    top = tr.top_ops(recorded(), 3)
    assert sorted(n for n, _ in top) == ["%copy.70 f32[1000000,17]", "%copy.71 f32[1000000,6]",
                                         "%copy.72 f32[1000000,17]"]


def test_recorded_trace_idle_is_billed_to_the_readback():
    # the host launched the window and waits in the readback while it runs
    gaps = tr.idle_by_host(recorded())
    assert gaps[0][0] == "readback"
    assert gaps[0][1] == pytest.approx(tr.window_ns(recorded()) * 0.02 / 1e9, rel=0.25)
    t = tr.ops_within(recorded(), tr.module_patterns("train_window"))
    assert len(t.ops) == len(recorded().ops)
