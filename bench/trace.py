"""From a profiler trace to the numbers the per-layer readers use.

A traced run records the device with `jax.profiler` and marks what the
host does with `jax.profiler.TraceAnnotation` spans named `bench.<what>`.
`load` reduces the `.xplane.pb` to a `Trace`: the device operations (one
interval each, on the device's own line of executed operations), the
device modules (one interval per executed program) and the host's
`bench.*` spans, all on the profiler's one clock.  Everything after that
is plain interval arithmetic, kept here so that every reader computes a
number the same way and `bench/tests` can check it on a small recorded
trace.

The name patterns that find kernels, programs and container operations
are in `bench/kernels.json`.
"""
from __future__ import annotations

import bisect
import dataclasses
import fnmatch
import glob
import json
import os
import sys
from typing import Iterable, Optional

# the line of a device plane that holds one event per executed operation
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
KERNELS = os.path.join(os.path.dirname(__file__), "kernels.json")


@dataclasses.dataclass
class Trace:
    """Intervals in nanoseconds on the profiler's clock."""

    ops: list[tuple[str, int, int]]          # device operations (name, start, end)
    modules: list[tuple[str, int, int]]      # executed device programs
    host: list[tuple[str, int, int]]         # the harness's bench.* spans
    window: tuple[int, int]                  # the traced span
    n_devices: int = 1

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @staticmethod
    def from_json(d: dict) -> "Trace":
        return Trace(ops=[tuple(x) for x in d["ops"]], modules=[tuple(x) for x in d["modules"]],
                     host=[tuple(x) for x in d["host"]], window=tuple(d["window"]),
                     n_devices=d.get("n_devices", 1))


def load_and_remove(log_dir: str) -> Trace:
    """`load`, then delete the profiler's files: a run keeps only the
    reduction."""
    import shutil

    try:
        return load(log_dir)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)


def load(log_dir: str, window_span: str = "bench.traced") -> Trace:
    """Read the newest `.xplane.pb` under `log_dir`.  The traced window is
    the host span named `window_span`; events outside it are dropped."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    print(f"bench: trace file {os.path.getsize(paths[-1])} bytes", file=sys.stderr, flush=True)
    data = ProfileData.from_file(paths[-1])
    ops, modules, host = [], [], []
    n_devices = 0
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            n_devices += 1
            for line in plane.lines:
                dest = {OPS_LINE: ops, MODULES_LINE: modules}.get(line.name)
                if dest is None:
                    continue
                for ev in line.events:
                    dest.append((ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append((ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns)))
    spans = [h for h in host if h[0] == window_span]
    if not spans:
        raise ValueError(f"no host span {window_span!r} in the trace")
    window = (spans[0][1], spans[0][2])
    containers = _patterns("containers")
    ops = [o for o in ops if not matches(o[0], containers)]
    inside = lambda evs: sorted((e for e in evs if e[2] > window[0] and e[1] < window[1]),
                                key=lambda e: (e[1], e[2]))
    return Trace(ops=inside(ops), modules=inside(modules),
                 host=inside(h for h in host if h[0] != window_span), window=window,
                 n_devices=max(n_devices, 1))


# --------------------------------------------------------------------------- #
# interval arithmetic
# --------------------------------------------------------------------------- #

def clip(intervals: Iterable[tuple], window: tuple[int, int]) -> list[tuple[int, int]]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for *_, s, e in intervals if min(e, hi) > max(s, lo)]


def union(intervals: Iterable[tuple[int, int]]) -> list[tuple[int, int]]:
    """Merge overlapping (start, end) intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Iterable[tuple[int, int]]) -> int:
    return sum(e - s for s, e in intervals)


def busy_ns(tr: Trace, names: Optional[list[str]] = None) -> float:
    """Device busy time in the window: the union of operation intervals,
    averaged over the devices traced.  `names` keeps only the operations
    that match one of the patterns."""
    ops = tr.ops if names is None else [o for o in tr.ops if matches(o[0], names)]
    return total(union(clip(ops, tr.window))) / tr.n_devices


def window_ns(tr: Trace) -> int:
    return tr.window[1] - tr.window[0]


def idle_share(tr: Trace) -> float:
    """1 - busy / window."""
    return 1.0 - busy_ns(tr) / window_ns(tr)


def matches(name: str, patterns: list[str]) -> bool:
    return any(fnmatch.fnmatchcase(name, p) for p in patterns)


def _patterns(*keys: str) -> list[str]:
    with open(KERNELS) as f:
        d = json.load(f)
    for k in keys:
        d = d[k]
    return d


def kernel_patterns(kernel: str) -> list[str]:
    return _patterns("kernels", kernel)


def module_patterns(module: str) -> list[str]:
    return _patterns("modules", module)


def short_name(name: str) -> str:
    """An operation's HLO name and output shape, without its operands:
    `%copy.70 f32[1000000,17]`."""
    head, _, rest = name.partition(" = ")
    return f"{head} {rest.split('{')[0].split(' ')[0].lstrip('(')}".strip() if rest else head


def op_time_ns(tr: Trace, patterns: list[str]) -> float:
    """Summed device time of the operations whose name matches, averaged
    over the devices traced."""
    return sum(min(e, tr.window[1]) - max(s, tr.window[0])
               for n, s, e in tr.ops if matches(n, patterns)
               and min(e, tr.window[1]) > max(s, tr.window[0])) / tr.n_devices


def op_count(tr: Trace, patterns: list[str]) -> int:
    return sum(1 for n, *_ in tr.ops if matches(n, patterns))


def ops_within(tr: Trace, module_patterns: list[str]) -> Trace:
    """The same trace, its operations cut to those that run inside a
    module whose name matches."""
    mods = union(clip([m for m in tr.modules if matches(m[0], module_patterns)], tr.window))
    starts = [ms for ms, _ in mods]
    keep = []
    for n, s, e in tr.ops:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s >= mods[i][0] and e <= mods[i][1]:
            keep.append((n, s, e))
    return dataclasses.replace(tr, ops=keep)


def top_ops(tr: Trace, k: int = 10) -> list[list]:
    """The device operations that took most time: [name, seconds]."""
    acc: dict[str, int] = {}
    for n, s, e in tr.ops:
        n = short_name(n)
        acc[n] = acc.get(n, 0) + (min(e, tr.window[1]) - max(s, tr.window[0]))
    best = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[n, t / 1e9 / tr.n_devices] for n, t in best]


def idle_gaps(tr: Trace) -> list[tuple[int, int]]:
    """The intervals of the window in which no device operation ran."""
    busy = union(clip(tr.ops, tr.window))
    gaps, cur = [], tr.window[0]
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < tr.window[1]:
        gaps.append((cur, tr.window[1]))
    return gaps


def host_label(tr: Trace, t: int) -> str:
    """The innermost (shortest) bench.* host span that covers time t."""
    best = None
    for n, s, e in tr.host:
        if s <= t < e and (best is None or e - s < best[1]):
            best = (n, e - s)
    return best[0][len(HOST_PREFIX):] if best else "other"


def idle_by_host(tr: Trace, k: int = 10) -> list[list]:
    """Idle device time split by what the host was doing, longest first:
    [label, seconds].  Each gap is cut at the host spans' edges, so one
    long gap is billed to every span it covers."""
    edges = sorted({t for _, s, e in tr.host for t in (s, e)})
    acc: dict[str, int] = {}
    for gs, ge in idle_gaps(tr):
        cuts = [gs] + [t for t in edges if gs < t < ge] + [ge]
        for a, b in zip(cuts, cuts[1:]):
            lab = host_label(tr, (a + b) // 2)
            acc[lab] = acc.get(lab, 0) + (b - a)
    best = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[n, t / 1e9] for n, t in best]
