"""Bench-artifact schema gate (benchmarks/schema.py).

The checked-in BENCH_*.json artifacts and anything `benchmarks/run.py
--smoke` emits must validate, and representative drift (missing section,
renamed key, wrong type, single-batch IPS map) must FAIL — that is the whole
point of the CI schema job: format drift breaks the build instead of
silently downgrading `CostModel.from_bench` to defaults.
"""
import copy
import json
import pathlib
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:   # benchmarks/ is a namespace package
    sys.path.insert(0, str(REPO))

from benchmarks import schema as bench_schema  # noqa: E402


def _load(name):
    return json.loads((REPO / name).read_text())


@pytest.mark.parametrize("name", ["BENCH_fused_mlp.json",
                                  "BENCH_serve_policy.json",
                                  "BENCH_learner.json",
                                  "BENCH_device_loop.json",
                                  "BENCH_serve_lm.json"])
def test_checked_in_artifacts_validate(name):
    path = REPO / name
    assert path.exists(), f"{name} missing at repo root"
    assert bench_schema.validate_file(path) in bench_schema.SCHEMAS_BY_TAG


def test_unknown_schema_tag_rejected():
    with pytest.raises(bench_schema.SchemaError, match="unknown"):
        bench_schema.validate_report({"schema": "fixar/nope/v9"})


def test_fused_mlp_drift_fails():
    good = _load("BENCH_fused_mlp.json")
    bench_schema.validate_report(good)

    for mutate in (
        lambda d: d.pop("train"),                       # section dropped
        lambda d: d.pop("actor_ips_by_batch"),          # calib input dropped
        lambda d: d["train"].pop("updates_per_s"),      # key renamed away
        lambda d: d["train"].pop("ips_by_batch"),       # train fit input
        lambda d: d["train"]["ips_by_batch"].update(
            pallas={"128": 1.0}),                       # one batch only
        lambda d: d["train"].pop("launches_per_update"),    # v4 launch table
        lambda d: d["train"]["launches_per_update"].pop(
            "pallas_fused_step"),                       # fused-step column
        lambda d: d["train"]["updates_per_s"].pop(
            "pallas_fused_step"),                       # fused-step column
        lambda d: d["train"]["ips_by_batch"].pop(
            "pallas_fused_step"),                       # fused-step column
        lambda d: d["train"].update(speedup_vs_jnp=1.13),   # v3 scalar form
        lambda d: d["config"].update(net="17-400-300-6"),   # type drift
        lambda d: d["actor_ips_by_batch"].update(
            jnp={"256": 1.0}),                          # one batch only
        lambda d: d.update(schema="fixar/fused_mlp_bench/v3"),  # old tag
    ):
        bad = copy.deepcopy(good)
        mutate(bad)
        with pytest.raises(bench_schema.SchemaError):
            bench_schema.validate_report(
                bad, bench_schema.FUSED_MLP_SCHEMA
                if bad.get("schema") != "fixar/fused_mlp_bench/v4"
                else None)


def test_serve_policy_drift_fails():
    good = _load("BENCH_serve_policy.json")
    bench_schema.validate_report(good)
    for mutate in (
        lambda d: d.pop("dispatch"),
        lambda d: d["modes"].pop("fused"),
        lambda d: d["modes"]["jnp"].pop("ips_big"),
        lambda d: d["adaptive"].pop("mode_histogram"),
        lambda d: d["adaptive"]["mode_histogram"].pop("act"),  # flat again
        lambda d: d["adaptive"].pop("dispatch_audit"),  # v3 audit section
        lambda d: d["adaptive"].pop("qat_telemetry"),
        lambda d: d["adaptive"]["dispatch_audit"].pop("drift_factor"),
        lambda d: d["adaptive"]["dispatch_audit"].pop("table"),
    ):
        bad = copy.deepcopy(good)
        mutate(bad)
        with pytest.raises(bench_schema.SchemaError):
            bench_schema.validate_report(bad)


def test_learner_drift_fails():
    """The learner artifact's contract: per-backend training throughput and
    the phase-keyed mode histogram."""
    good = _load("BENCH_learner.json")
    bench_schema.validate_report(good)
    for mutate in (
        lambda d: d.pop("modes"),
        lambda d: d["modes"].pop("fused"),
        lambda d: d["modes"]["jnp"].pop("train_ips"),
        lambda d: d["adaptive"].pop("train_ips_wall"),
        lambda d: d["adaptive"]["mode_histogram"].pop("train"),
        lambda d: d["adaptive"].pop("qat_telemetry"),
        lambda d: d["config"].update(buckets=[8, 32]),  # < 3 buckets
    ):
        bad = copy.deepcopy(good)
        mutate(bad)
        with pytest.raises(bench_schema.SchemaError):
            bench_schema.validate_report(bad)


def test_device_loop_drift_fails():
    """The loop artifact's contract: an `n_envs` scaling curve with at
    least two fleet widths, the host-vs-device updates/s comparison, and
    the single-launch trace count."""
    good = _load("BENCH_device_loop.json")
    bench_schema.validate_report(good)
    first = next(iter(good["scaling"]))
    for mutate in (
        lambda d: d.pop("scaling"),
        lambda d: d.pop("host_vs_device"),
        lambda d: d.pop("launches"),
        lambda d: d["scaling"].clear()
        or d["scaling"].update({first: good["scaling"][first]}),  # one point
        lambda d: d["scaling"][first].pop("env_steps_per_s"),
        lambda d: d["scaling"][first].pop("updates_per_s"),
        lambda d: d["host_vs_device"].pop("speedup"),
        lambda d: d["host_vs_device"].pop("host_updates_per_s"),
        lambda d: d["launches"].pop("windows_traced_per_config"),
        lambda d: d["config"].update(n_envs=[1]),          # no curve
        lambda d: d["config"].update(n_envs="1,16,1024"),  # type drift
        lambda d: d.update(schema="fixar/device_loop_bench/v0"),  # old tag
    ):
        bad = copy.deepcopy(good)
        mutate(bad)
        with pytest.raises(bench_schema.SchemaError):
            bench_schema.validate_report(
                bad, bench_schema.DEVICE_LOOP_SCHEMA
                if bad.get("schema") != "fixar/device_loop_bench/v1"
                else None)


def test_serve_lm_drift_fails():
    """The LM-serving artifact's contract: serving-style metrics (tokens/s,
    TTFT percentiles, decode-batch occupancy), the sequential baseline it is
    normalized against, and a ≥2-length prompt mix."""
    good = _load("BENCH_serve_lm.json")
    bench_schema.validate_report(good)
    for mutate in (
        lambda d: d.pop("engine"),
        lambda d: d.pop("sequential"),
        lambda d: d.pop("speedup_vs_sequential"),
        lambda d: d["engine"].pop("ttft_p50_ms"),
        lambda d: d["engine"].pop("decode_occupancy"),
        lambda d: d["engine"].pop("decode_steps"),
        lambda d: d["engine"]["mode_histogram"].pop("lm"),  # phase axis
        lambda d: d["sequential"].pop("tokens_per_s_wall"),
        lambda d: d["config"].update(prompt_lens=[5]),      # no length mix
        lambda d: d["config"].update(lanes="4"),            # type drift
    ):
        bad = copy.deepcopy(good)
        mutate(bad)
        with pytest.raises(bench_schema.SchemaError):
            bench_schema.validate_report(bad)


def test_fallback_validator_agrees_with_jsonschema():
    """The stdlib-only fallback must accept what jsonschema accepts and
    reject the same representative drift, so bare CI images get the same
    gate."""
    good = _load("BENCH_fused_mlp.json")
    bench_schema._fallback_validate(good, bench_schema.FUSED_MLP_SCHEMA)
    bad = copy.deepcopy(good)
    del bad["train"]["speedup_vs_jnp"]
    with pytest.raises(bench_schema.SchemaError):
        bench_schema._fallback_validate(bad, bench_schema.FUSED_MLP_SCHEMA)
    bad2 = copy.deepcopy(good)
    bad2["actor_ips"]["jnp"] = "fast"
    with pytest.raises(bench_schema.SchemaError):
        bench_schema._fallback_validate(bad2, bench_schema.FUSED_MLP_SCHEMA)


def test_cli_reports_ok_and_fail(tmp_path, capsys):
    good = REPO / "BENCH_fused_mlp.json"
    assert bench_schema.main(["--check", str(good)]) == 0
    bad = tmp_path / "BENCH_fused_mlp.json"
    data = _load("BENCH_fused_mlp.json")
    del data["phases"]
    bad.write_text(json.dumps(data))
    assert bench_schema.main(["--check", str(bad)]) == 1
    truncated = tmp_path / "trunc.json"
    truncated.write_text('{"schema": "fixar/fused')
    assert bench_schema.main([str(truncated)]) == 1
