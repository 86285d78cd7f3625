"""Cells cut to a size a CPU test run holds: the published widths where
only the reference runs, smaller ones where the program's kernels run in
the interpreter.  A cell of `BENCHMARK.json` keeps its limits file."""
import copy

from bench import harness

# the serving mix on the Hopper configuration, which BENCHMARK.json does not
# run yet: the harness's serving path is still checked on the CPU
PREPARED = {
    "serve_hopper_open_monitor": dict(
        config="ddpg_hopper",
        traffic={"generator": "serve_open", "arrival": "poisson", "rate_per_s": 10000,
                 "phase": "monitor", "control": "high"},
        limits={"max_gap": 1e-4, "mean_gap": 1e-6, "missing": 0}),
}


def _prepared(name: str) -> harness.Cell:
    p = PREPARED[name]
    return harness.Cell(name=name, chips=1,
                        config=harness._load_json("configs", p["config"] + ".json"),
                        traffic=dict(p["traffic"]), limits=dict(p["limits"]),
                        end_to_end=[], per_layer=[])


def cell(name: str, **cut) -> harness.Cell:
    c = copy.deepcopy(_prepared(name) if name in PREPARED else harness.load_cell(name))
    c.config.update(cut.pop("config", {}))
    c.traffic.update(cut.pop("traffic", {}))
    assert not cut, cut
    return c


def run_cell(c: harness.Cell, seed: int, seconds: float = 0.5):
    """Drive a whole run without the harness's look for a chip; returns the
    result line as a dict."""
    import contextlib
    import io
    import json

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = harness.run(["--workload", c.name, "--seed", str(seed), "--seconds", str(seconds),
                          "--trace", "0"], require_chip=False, cell=c)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])
