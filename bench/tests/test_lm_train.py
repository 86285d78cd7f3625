"""The LM training cell on the CPU: the generator end to end at a cut of
`train_granite_micro_packed8k` (every width divided, the same program
path), the control and the planted fault against limits of a float32 cut
and against the cell's own SSD limit, the packer's check, and the
per-phase readers on a synthetic trace of two steps and the
compiled text it was made against (`data/lm_step_phases.json`)."""
import importlib
import json
import os

import numpy as np
import pytest

from bench import harness, lm_phases
from bench import trace as tr
from bench.generators import lm_train
from bench.tests._cells import cell, run_cell

CELL = "train_granite_micro_packed8k"
DATA = os.path.join(os.path.dirname(__file__), "data", "lm_step_phases.json")
PHASE_METRICS = {"lm_ssd_us": "ssd", "lm_mamba_proj_us": "mamba_proj", "lm_attn_us": "attn",
                 "lm_optimizer_us": "optimizer"}
SMALL = dict(
    config=dict(hidden_size=64, shared_intermediate_size=128, intermediate_size=128,
                num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=8, mamba_d_head=16,
                mamba_d_state=16, mamba_chunk_size=16, vocab_size=512,
                program=dict(dtype="bfloat16", remat="block", attn_chunk=32, ce_chunk=64)),
    traffic=dict(seq_len=256, documents=dict(median=48, sigma=1.2, min=4, max=256)))


def test_the_generator_runs_the_cell_at_a_cpu_cut():
    res = run_cell(cell(CELL, **SMALL), seed=2 ** 31 + 9, seconds=0.2)
    assert res["correct"], res["check"]
    assert set(res["metrics"]) == {"setup_s", "train_ips"}
    assert res["metrics"]["train_ips"]["unit"] == "samples/s"
    assert res["check"]["step_gap"]["value"] == 0.0


def _fails(nums: dict, limits: dict) -> list:
    return [k for k, lim in limits.items() if k in nums and nums[k] > lim]


# At the cell's bfloat16 precision the step's moments do not see the control
# (the recurrence's state in bfloat16): the program's bfloat16 matmuls move
# them further, on the chip as at a cut (PERF.md §4); `ssd_gap` sees it
# (below).  In float32 the program's rounding is about 5e-7 of the worst
# leaf's moment: there both variants read far above it.  Limits of this float32 cut, set between
# its sound readings (moment_gap 5.3e-7, moment_median_gap 3.7e-7) and the
# control's (3.9e-4, 6.2e-5); the fault reads 0.031 and 4.8e-3.
F32_CUT = dict(config=dict(SMALL["config"], program=dict(SMALL["config"]["program"],
                                                         dtype="float32")),
               traffic=SMALL["traffic"])
F32_LIMITS = {"moment_gap": 1.5e-5, "moment_median_gap": 5e-6, "step_gap": 0}


def test_control_and_fault_break_a_limit_where_the_program_is_float32():
    c = cell(CELL, **F32_CUT)
    c.limits = dict(F32_LIMITS)
    seed = 2 ** 31 + 11
    assert run_cell(c, seed, seconds=0.1)["correct"]
    for variant in lm_train.VARIANTS:
        nums = lm_train.readings(c, seed, variant)
        assert _fails(nums, c.limits), (variant, nums)


# The cell's SSD widths (64 heads of 64, d_state 128, chunk 256) and its
# documents over 2,048 tokens, one Mamba layer: the SSD's own number sees a
# scan a step below float32 that the step's moments do not (PERF.md §4).
SSD_CUT = dict(config=dict(hidden_size=256, shared_intermediate_size=128,
                           intermediate_size=128, num_attention_heads=4, num_key_value_heads=2,
                           vocab_size=512, num_hidden_layers=1, layer_types=["mamba"]),
               traffic=dict(seq_len=2048))


@pytest.mark.parametrize("variant", ("program",) + lm_train.VARIANTS)
def test_the_ssd_gap_passes_the_program_and_fails_control_and_fault(variant):
    import jax

    c = cell(CELL, **SSD_CUT)
    seed = 2 ** 31 + 13
    gap = lm_train.ssd_gap(c.config, c.traffic, jax.random.key(seed), seed, variant)
    assert (gap <= c.limits["ssd_gap"]) == (variant == "program"), gap


def _packed(seed=2 ** 31 + 17):
    import jax

    c = cell(CELL, **SMALL)
    b, _ = lm_train.batch_program(c.config, c.traffic, seed)(jax.numpy.int32(0))
    return {k: np.array(v) for k, v in jax.device_get(b).items()}, c.traffic["documents"]


def test_the_packer_keeps_its_contract():
    b, docs = _packed()
    assert b["segment_ids"][0, -1] > 2      # several documents in the row
    assert lm_train.packing_faults(b, docs) == 0


@pytest.mark.parametrize("fault", ("label_across_boundary", "boundary_moved", "id_skipped",
                                   "wrong_target"))
def test_the_packing_check_sees_a_broken_batch(fault):
    b, docs = _packed()
    seg, lab = b["segment_ids"], b["labels"]
    t = int(np.flatnonzero(np.diff(seg[0]))[0])         # last token of the first document
    if fault == "label_across_boundary":
        lab[0, t] = b["tokens"][0, t + 1]
    elif fault == "boundary_moved":
        seg[0, t + 1] = seg[0, t]
    elif fault == "id_skipped":
        seg[0, t + 1:] += 1
    else:
        lab[0, 0] = (lab[0, 0] + 1) % 512
    assert lm_train.packing_faults(b, docs) > 0


def test_an_unknown_variant_is_refused():
    with pytest.raises(ValueError, match="unknown variant"):
        lm_train._variant(cell(CELL).config, "half_batch")


# --------------------------------------------------------------------------- #
# the readers on a synthetic trace
# --------------------------------------------------------------------------- #

def _data() -> dict:
    with open(DATA) as f:
        return json.load(f)


@pytest.fixture
def reading(monkeypatch):
    d = _data()
    monkeypatch.setattr(lm_phases, "step_text", lambda config, traffic: d["hlo"])
    c = harness.load_cell(CELL)
    return harness.Reading(
        trace=tr.Trace.from_json(d["trace"]), counters={}, work=harness.work_module(c.config),
        config=c.config, traffic=c.traffic, peaks=harness.device_kind_peaks("TPU v5 lite"),
        measured=d["measured"], chips=1)


def _read(name, r):
    return importlib.import_module(f"bench.metrics.{name}").read(r)


def test_the_new_metrics_are_the_cells_per_layer_metrics():
    names = {m["name"] for m in harness.load_cell(CELL).per_layer}
    assert set(PHASE_METRICS) | {"lm_train_mfu", "lm_ssd_roofline", "train_device_idle"} == names


def test_each_phase_metric_reads_its_scope(reading):
    # the synthetic step's durations in us, the same in both steps
    assert _read("lm_ssd_us", reading) == pytest.approx(15 + 9)
    assert _read("lm_mamba_proj_us", reading) == pytest.approx(20)
    assert _read("lm_attn_us", reading) == pytest.approx(4)
    assert _read("lm_optimizer_us", reading) == pytest.approx(6)


def test_phases_and_unscoped_partition_the_step_busy_time(reading):
    ns = lm_phases.phase_ns(reading)
    assert set(ns) == {"embed", "mamba_proj", "ssd", "attn", "ffn", "head", "optimizer",
                       lm_phases.UNSCOPED}
    assert ns[lm_phases.UNSCOPED] == pytest.approx(2 * (500 + 200))
    step = tr.ops_within(reading.trace, lm_phases.STEP_MODULE)
    assert sum(ns.values()) == pytest.approx(tr.busy_ns(step))
    # the batch program between the steps is no phase's
    assert tr.busy_ns(reading.trace) - tr.busy_ns(step) == pytest.approx(2 * 100)


def test_an_operation_missing_from_the_text_silences_every_phase_reader(reading):
    reading.trace.ops.insert(3, ("%fusion.999 f32[8,8]", 1100, 1200))
    names = list(PHASE_METRICS) + ["lm_ssd_roofline"]
    assert [_read(n, reading) for n in names] == [None] * len(names)


def test_a_program_without_phases_gives_no_number(reading, monkeypatch):
    monkeypatch.setattr(lm_phases, "step_text", lambda config, traffic: None)
    assert _read("lm_ssd_us", reading) is None and _read("lm_ssd_roofline", reading) is None


def test_the_roofline_and_the_mfu_read_the_work_counts(reading):
    w, cfg, peaks = reading.work, reading.config, reading.peaks
    tokens = reading.traffic["seq_len"]
    least, _ = w.least_time_s(w.ssd_step_flops(cfg, tokens), w.ssd_step_bytes(cfg, tokens), peaks)
    assert _read("lm_ssd_roofline", reading) == pytest.approx(100 * least / 24e-6)
    flops = w.step_flops(cfg, tokens, 1000.0)
    window_s = tr.window_ns(reading.trace) / 1e9
    assert _read("lm_train_mfu", reading) == pytest.approx(
        100 * flops * 2 / window_s / peaks["bf16_flops_per_s"])


def test_the_work_counts_match_the_published_reckoning():
    cfg = harness.load_cell(CELL).config
    from bench.work import granite_hybrid as w

    # 6·N over the period's matmul weights and the head slice: 4.79 GFLOP a token
    assert 6 * w.matmul_params(cfg) / 1e9 == pytest.approx(4.79, abs=0.01)
    assert w.matmul_params(cfg) == 797_850_560 - 2048 * 21 - 9 * (4352 * 5 + 3 * 64 + 4096)
    # the SSD at chunk 256: 0.11 GFLOP a token over nine layers, forward and backward
    assert w.ssd_step_flops(cfg, 1) / 1e9 == pytest.approx(0.115, abs=0.005)
