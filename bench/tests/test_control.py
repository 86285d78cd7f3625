"""The control fails: the reference computed at the cell's control
precision (the traffic file's `control`: `high`, three bfloat16 passes;
spelled out in limbs, so the same on any backend) and put
in the program's place breaks at least one of each cell's limits.  The chip
readings behind the limits are in PERF.md."""
from bench.generators import train_loop
from bench.tests._cells import cell

CELL = "train_halfcheetah_monitor"
SMALL_TRAIN = dict(config=dict(replay_capacity=4096), traffic=dict(window=256))


def _fails(nums: dict, limits: dict) -> list:
    return [k for k, lim in limits.items() if k in nums and nums[k] > lim]


def test_training_control_fails():
    c = cell(CELL, **SMALL_TRAIN)
    for seed in (3, 2 ** 31 + 5):
        nums = train_loop.readings(c, seed, c.traffic["control"])
        assert _fails(nums, c.limits), (nums, c.limits)


def test_training_half_batch_fault_fails():
    c = cell(CELL, **SMALL_TRAIN)
    nums = train_loop.readings(c, 11, "half_batch")
    assert _fails(nums, c.limits), (nums, c.limits)

