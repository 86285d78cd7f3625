"""JSON schemas for the tracked benchmark artifacts.

`BENCH_fused_mlp.json`, `BENCH_serve_policy.json`, `BENCH_learner.json`,
`BENCH_device_loop.json`, and `BENCH_serve_lm.json` are consumed
programmatically — `CostModel.from_bench` calibrates the serving
dispatcher from the kernel bench,
and the CI bench job diffs the serving/training/loop/LM numbers across PRs
— so format drift must fail the build instead of silently degrading the
cost model to its defaults.  This module is the single source of truth for
all five shapes:

    python -m benchmarks.schema --check BENCH_fused_mlp.json \
        BENCH_serve_policy.json BENCH_learner.json BENCH_device_loop.json

validates files against the schema matching their `schema` tag (exit code 1
on the first violation).  CI runs exactly that after `benchmarks/run.py
--smoke`; tests/test_bench_schema.py pins the checked-in artifacts and the
smoke output against the same schemas.

Validation uses `jsonschema` when available and falls back to a minimal
structural checker (required keys + type tags) on bare images, so the gate
itself has no hard dependency beyond the stdlib.
"""
from __future__ import annotations

import json
import pathlib
import sys

_NUM = {"type": "number"}
_STR = {"type": "string"}
_NUM_MAP = {"type": "object", "additionalProperties": _NUM}

# per-backend {batch_size: ips} map, at least two batch points so
# CostModel.from_bench can separate slope from intercept
_IPS_BY_BATCH = {
    "type": "object",
    "additionalProperties": {
        "type": "object",
        "additionalProperties": _NUM,
        "minProperties": 2,
    },
}

FUSED_MLP_SCHEMA = {
    "$schema": "http://json-schema.org/draft-07/schema#",
    "type": "object",
    "required": ["schema", "config", "pallas_calls_traced", "phases",
                 "actor_ips", "actor_ips_by_batch", "train"],
    "properties": {
        # v4: train section gains the whole-update fused-step backend — a
        # pallas_fused_step column in updates_per_s / ips_by_batch, a
        # launches_per_update table, and speedup_vs_jnp becomes a
        # per-backend map ({"pallas": x, "pallas_fused_step": y})
        "schema": {"const": "fixar/fused_mlp_bench/v4"},
        "config": {
            "type": "object",
            "required": ["batch", "batches", "net", "backend"],
            "properties": {
                "batch": {"type": "integer"},
                "batches": {"type": "array", "items": {"type": "integer"},
                            "minItems": 2},
                "net": {"type": "array", "items": {"type": "integer"},
                        "minItems": 2},
                "backend": _STR,
                "smoke": {"type": "boolean"},
            },
        },
        "pallas_calls_traced": {
            "type": "object",
            "required": ["fused", "perlayer", "perlayer_executed"],
            "additionalProperties": {"type": "integer"},
        },
        "phases": {
            "type": "object",
            "required": ["full", "half"],
            "additionalProperties": {
                "type": "object",
                "required": ["fused_us", "perlayer_us", "speedup"],
                "additionalProperties": _NUM,
            },
        },
        "actor_ips": _NUM_MAP,
        "actor_ips_by_batch": _IPS_BY_BATCH,
        "train": {
            "type": "object",
            "required": ["batch", "updates_per_s", "train_ips",
                         "ips_by_batch", "pallas_calls_traced",
                         "launches_per_update", "speedup_vs_jnp"],
            "properties": {
                "batch": {"type": "integer"},
                "batches": {"type": "array", "items": {"type": "integer"},
                            "minItems": 2},
                "updates_per_s": {
                    "type": "object",
                    "required": ["jnp", "pallas", "pallas_fused_step"],
                    "additionalProperties": _NUM,
                },
                "train_ips": _NUM_MAP,
                "ips_by_batch": {
                    "type": "object",
                    "required": ["jnp", "pallas", "pallas_fused_step"],
                    "additionalProperties": {
                        "type": "object",
                        "additionalProperties": _NUM,
                        "minProperties": 2,
                    },
                },
                "pallas_calls_traced": {
                    "type": "object",
                    "additionalProperties": {"type": "integer"},
                },
                "launches_per_update": {
                    "type": "object",
                    "required": ["jnp", "pallas", "pallas_fused_step"],
                    "additionalProperties": {"type": "integer"},
                },
                "speedup_vs_jnp": {
                    "type": "object",
                    "required": ["pallas", "pallas_fused_step"],
                    "additionalProperties": _NUM,
                },
            },
        },
    },
}

# the two observability sections every adaptive engine run now reports
# (engine.stats()["dispatch_audit"] / ["qat_telemetry"]): the audit must
# carry the drift verdict + the per-(phase, mode, bucket) table; the QAT
# telemetry is a per-site map ({} when QAT is off).  drift_factor is None
# until a batch was recorded, so only presence is required.
_DISPATCH_AUDIT = {
    "type": "object",
    "required": ["drift_factor", "stale", "threshold", "batches", "table"],
    "properties": {
        "stale": {"type": "boolean"},
        "threshold": _NUM,
        "batches": {"type": "integer"},
        "table": {"type": "object"},
    },
}

_QAT_TELEMETRY = {
    "type": "object",
    "additionalProperties": {
        "type": "object",
        "required": ["a_min", "a_max"],
    },
}

SERVE_POLICY_SCHEMA = {
    "$schema": "http://json-schema.org/draft-07/schema#",
    "type": "object",
    "required": ["schema", "config", "modes", "dispatch", "adaptive"],
    "properties": {
        # v3: adaptive grows dispatch_audit + qat_telemetry, and
        # mode_histogram is phase-keyed ({"act": {mode: n}}) to match the
        # learner's shape
        "schema": {"const": "fixar/serve_policy_bench/v3"},
        "config": {
            "type": "object",
            "required": ["net", "big_batch", "backend", "qat"],
        },
        "modes": {
            "type": "object",
            "required": ["fused", "layer", "jnp"],
            "additionalProperties": {
                "type": "object",
                "required": ["ips_big", "p50_ms", "p99_ms", "batches"],
            },
        },
        "dispatch": {
            "type": "object",
            "required": ["default", "calibrated", "calibration_source"],
            "properties": {
                "default": {"type": "object",
                            "additionalProperties": _STR},
                "calibrated": {"type": "object",
                               "additionalProperties": _STR},
                "calibration_source": _STR,
            },
        },
        "adaptive": {
            "type": "object",
            "required": ["requests", "ips_wall", "p50_ms", "p99_ms",
                         "batch_occupancy", "mode_histogram",
                         "dispatch_audit", "qat_telemetry"],
            "properties": {
                "mode_histogram": {     # per-phase: {"act": {mode: n}}
                    "type": "object",
                    "required": ["act"],
                    "additionalProperties": {
                        "type": "object",
                        "additionalProperties": {"type": "integer"},
                    },
                },
                "dispatch_audit": _DISPATCH_AUDIT,
                "qat_telemetry": _QAT_TELEMETRY,
            },
        },
    },
}

# the learner bench: the training-throughput twin of the serving artifact
# (updates/sec, train IPS, latency percentiles per training backend, and
# the streaming engine's mode histogram keyed by phase)
LEARNER_SCHEMA = {
    "$schema": "http://json-schema.org/draft-07/schema#",
    "type": "object",
    "required": ["schema", "config", "modes", "adaptive"],
    "properties": {
        # v2: adaptive grows dispatch_audit + qat_telemetry (engine stats
        # sections; the mode histogram was already phase-keyed)
        "schema": {"const": "fixar/learner_bench/v2"},
        "config": {
            "type": "object",
            "required": ["net", "buckets", "big_batch", "backend", "qat"],
            "properties": {
                "net": {"type": "array", "items": {"type": "integer"},
                        "minItems": 2},
                "buckets": {"type": "array", "items": {"type": "integer"},
                            "minItems": 3},
                "big_batch": {"type": "integer"},
                "backend": _STR,
                "qat": _STR,
                "smoke": {"type": "boolean"},
            },
        },
        "modes": {
            "type": "object",
            "required": ["fused", "jnp"],
            "additionalProperties": {
                "type": "object",
                "required": ["updates_per_s", "train_ips", "p50_ms",
                             "p99_ms", "updates"],
            },
        },
        "adaptive": {
            "type": "object",
            "required": ["requests", "updates", "transitions",
                         "updates_per_s_wall", "train_ips_wall", "p50_ms",
                         "p99_ms", "batch_occupancy", "mode_histogram",
                         "qat_telemetry"],
            "properties": {
                "mode_histogram": {       # per-phase: {"train": {mode: n}}
                    "type": "object",
                    "required": ["train"],
                    "additionalProperties": {
                        "type": "object",
                        "additionalProperties": {"type": "integer"},
                    },
                },
                "dispatch_audit": _DISPATCH_AUDIT,
                "qat_telemetry": _QAT_TELEMETRY,
            },
        },
    },
}

# the device-resident loop bench: env-steps/s + updates/s vs fleet width
# (`n_envs` scaling of the single-launch scanned window) and the wall
# updates/s comparison against the paper-faithful host loop
DEVICE_LOOP_SCHEMA = {
    "$schema": "http://json-schema.org/draft-07/schema#",
    "type": "object",
    "required": ["schema", "config", "scaling", "host_vs_device", "launches"],
    "properties": {
        "schema": {"const": "fixar/device_loop_bench/v1"},
        "config": {
            "type": "object",
            "required": ["env", "net", "batch", "window", "n_envs",
                         "backend"],
            "properties": {
                "env": _STR,
                "net": {"type": "array", "items": {"type": "integer"},
                        "minItems": 2},
                "batch": {"type": "integer"},
                "window": {"type": "integer"},
                # at least two fleet widths, or there is no scaling curve
                "n_envs": {"type": "array", "items": {"type": "integer"},
                           "minItems": 2},
                "backend": _STR,
                "smoke": {"type": "boolean"},
            },
        },
        "scaling": {     # {str(n_envs): {env_steps_per_s, updates_per_s, ..}}
            "type": "object",
            "minProperties": 2,
            "additionalProperties": {
                "type": "object",
                "required": ["env_steps_per_s", "updates_per_s", "wall_s"],
                "additionalProperties": _NUM,
            },
        },
        "host_vs_device": {
            "type": "object",
            "required": ["host_updates_per_s", "device_updates_per_s",
                         "speedup", "host_steps"],
            "additionalProperties": _NUM,
        },
        "launches": {    # the single-launch-per-window claim, as data
            "type": "object",
            "required": ["windows_traced_per_config"],
            "additionalProperties": {"type": "integer"},
        },
    },
}

# the continuously-batched LM serving bench: tokens/s, time-to-first-token
# percentiles, decode-batch occupancy for the lane scheduler, against a
# single-lane sequential baseline on the same compiled functions
SERVE_LM_SCHEMA = {
    "$schema": "http://json-schema.org/draft-07/schema#",
    "type": "object",
    "required": ["schema", "config", "engine", "sequential",
                 "speedup_vs_sequential"],
    "properties": {
        "schema": {"const": "fixar/serve_lm_bench/v1"},
        "config": {
            "type": "object",
            "required": ["arch", "lanes", "max_seq", "max_new", "requests",
                         "prompt_lens"],
            "properties": {
                "arch": _STR,
                "lanes": {"type": "integer"},
                "max_seq": {"type": "integer"},
                "max_new": {"type": "integer"},
                "requests": {"type": "integer"},
                "prompt_lens": {"type": "array",
                                "items": {"type": "integer"}, "minItems": 2},
                "smoke": {"type": "boolean"},
            },
        },
        "engine": {
            "type": "object",
            "required": ["requests", "tokens", "decode_steps",
                         "tokens_per_s_wall", "ttft_p50_ms", "ttft_p99_ms",
                         "p50_ms", "p99_ms", "decode_occupancy", "lanes",
                         "mode_histogram"],
            "properties": {
                "requests": {"type": "integer"},
                "tokens": {"type": "integer"},
                "decode_steps": {"type": "integer"},
                "lanes": {"type": "integer"},
                "mode_histogram": {    # per-phase: {"lm": {mode: n}}
                    "type": "object",
                    "required": ["lm"],
                    "additionalProperties": {
                        "type": "object",
                        "additionalProperties": {"type": "integer"},
                    },
                },
            },
        },
        "sequential": {
            "type": "object",
            "required": ["tokens", "tokens_per_s_wall"],
            "additionalProperties": _NUM,
        },
        "speedup_vs_sequential": _NUM,
    },
}

SCHEMAS_BY_TAG = {
    "fixar/fused_mlp_bench/v4": FUSED_MLP_SCHEMA,
    "fixar/serve_policy_bench/v3": SERVE_POLICY_SCHEMA,
    "fixar/learner_bench/v2": LEARNER_SCHEMA,
    "fixar/device_loop_bench/v1": DEVICE_LOOP_SCHEMA,
    "fixar/serve_lm_bench/v1": SERVE_LM_SCHEMA,
}


class SchemaError(ValueError):
    """A bench artifact does not match its declared schema."""


def _fallback_validate(data, schema, path="$"):
    """Tiny structural subset of JSON Schema: type / const / required /
    properties / additionalProperties / items / minItems / minProperties —
    exactly what the schemas above use."""
    types = {"object": dict, "array": list, "string": str,
             "integer": int, "boolean": bool, "number": (int, float)}
    t = schema.get("type")
    if t is not None:
        py = types[t]
        ok = isinstance(data, py)
        if t in ("integer", "number") and isinstance(data, bool):
            ok = False
        if not ok:
            raise SchemaError(f"{path}: expected {t}, got "
                              f"{type(data).__name__}")
    if "const" in schema and data != schema["const"]:
        raise SchemaError(f"{path}: expected {schema['const']!r}, "
                          f"got {data!r}")
    if isinstance(data, dict):
        for key in schema.get("required", ()):
            if key not in data:
                raise SchemaError(f"{path}: missing required key {key!r}")
        if len(data) < schema.get("minProperties", 0):
            raise SchemaError(f"{path}: needs >= "
                              f"{schema['minProperties']} entries")
        props = schema.get("properties", {})
        extra = schema.get("additionalProperties")
        for key, val in data.items():
            if key in props:
                _fallback_validate(val, props[key], f"{path}.{key}")
            elif isinstance(extra, dict):
                _fallback_validate(val, extra, f"{path}.{key}")
    if isinstance(data, list):
        if len(data) < schema.get("minItems", 0):
            raise SchemaError(f"{path}: needs >= {schema['minItems']} items")
        item_schema = schema.get("items")
        if isinstance(item_schema, dict):
            for i, val in enumerate(data):
                _fallback_validate(val, item_schema, f"{path}[{i}]")


def validate_report(data: dict, schema: dict | None = None) -> None:
    """Validate a loaded bench report; raises SchemaError on mismatch."""
    if schema is None:
        tag = data.get("schema") if isinstance(data, dict) else None
        schema = SCHEMAS_BY_TAG.get(tag)
        if schema is None:
            raise SchemaError(
                f"unknown bench schema tag {tag!r}; known: "
                f"{sorted(SCHEMAS_BY_TAG)}")
    try:
        import jsonschema
    except ImportError:
        _fallback_validate(data, schema)
        return
    try:
        jsonschema.validate(data, schema)
    except jsonschema.ValidationError as err:
        raise SchemaError(str(err)) from err


def validate_file(path) -> str:
    """Validate one artifact; returns its schema tag."""
    data = json.loads(pathlib.Path(path).read_text())
    validate_report(data)
    return data["schema"]


# Chrome trace-event JSONL (what `obs.Tracer.write`/`flush` emit and the
# benches drop next to their JSON artifacts): every line one JSON object,
# only complete ("X") and instant ("i") phases — a by-construction
# guarantee that no span is left unclosed — with the keys Perfetto needs.
_TRACE_PHASES = {"X", "i"}
_TRACE_REQUIRED = ("name", "ph", "ts", "pid", "tid")


def validate_trace_event(ev: dict, where: str = "event") -> None:
    """One trace event: required keys, known phase, sane timestamps."""
    if not isinstance(ev, dict):
        raise SchemaError(f"{where}: expected object, got "
                          f"{type(ev).__name__}")
    for key in _TRACE_REQUIRED:
        if key not in ev:
            raise SchemaError(f"{where}: missing required key {key!r}")
    if ev["ph"] not in _TRACE_PHASES:
        raise SchemaError(f"{where}: phase {ev['ph']!r} not in "
                          f"{sorted(_TRACE_PHASES)} — an unclosed or "
                          f"async span leaked into the trace")
    if not isinstance(ev["ts"], (int, float)) or isinstance(ev["ts"], bool):
        raise SchemaError(f"{where}: ts must be a number")
    if ev["ph"] == "X":
        dur = ev.get("dur")
        if not isinstance(dur, (int, float)) or isinstance(dur, bool):
            raise SchemaError(f"{where}: complete event needs numeric dur")
        if dur < 0:
            raise SchemaError(f"{where}: negative duration {dur}")


def validate_trace_file(path, min_events: int = 1) -> int:
    """Validate a trace JSONL file; returns the event count.  Fails on
    unparsable lines, unknown phases, missing keys, negative durations,
    or fewer than `min_events` events (an empty trace from an
    instrumented run means the tracer was silently disabled)."""
    n = 0
    with open(path) as fh:
        for i, line in enumerate(fh, 1):
            line = line.strip()
            if not line:
                continue
            try:
                ev = json.loads(line)
            except json.JSONDecodeError as err:
                raise SchemaError(f"line {i}: not valid JSON: {err}") \
                    from err
            validate_trace_event(ev, f"line {i}")
            n += 1
    if n < min_events:
        raise SchemaError(f"only {n} events (< {min_events}); the traced "
                          f"run produced no spans")
    return n


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    trace_mode = False
    if argv[:1] == ["--check-trace"]:
        trace_mode, argv = True, argv[1:]
    elif argv[:1] == ["--check"]:
        argv = argv[1:]
    if not argv:
        print("usage: python -m benchmarks.schema --check FILE [FILE...]\n"
              "       python -m benchmarks.schema --check-trace "
              "TRACE.jsonl [TRACE.jsonl...]", file=sys.stderr)
        return 2
    for path in argv:
        try:
            if trace_mode:
                n = validate_trace_file(path)
                print(f"ok {path} ({n} trace events)")
            else:
                tag = validate_file(path)
                print(f"ok {path} ({tag})")
        except (OSError, json.JSONDecodeError, SchemaError) as err:
            print(f"FAIL {path}: {err}", file=sys.stderr)
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
