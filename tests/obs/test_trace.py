"""obs/trace: span semantics, zero-overhead disabled path, JSONL export."""
import json
import threading

from repro.obs.trace import NULL_TRACER, Tracer, _NULL_SPAN, read_jsonl


def test_disabled_tracer_is_shared_noop():
    t = Tracer(enabled=False)
    sp = t.span("x", a=1)
    assert sp is _NULL_SPAN                 # no allocation per span site
    with sp as s:
        s.set(b=2)                          # no-op, no error
    t.complete("y", 0.0, 1.0)
    t.instant("z")
    assert t.events() == []
    assert NULL_TRACER.enabled is False


def test_span_records_complete_event_with_args():
    clock = iter([0.0, 1.0, 1.5]).__next__  # t0, enter, exit
    t = Tracer(clock=clock)
    with t.span("work", cat="test", bucket=8) as sp:
        sp.set(mode="fused")
    (ev,) = t.events()
    assert ev["name"] == "work" and ev["ph"] == "X" and ev["cat"] == "test"
    assert ev["ts"] == 1e6 and ev["dur"] == 0.5e6
    assert ev["args"] == {"bucket": 8, "mode": "fused"}
    assert ev["pid"] > 0 and ev["tid"] > 0


def test_complete_and_instant_events():
    clock = iter([10.0, 99.0]).__next__     # t0, instant's now
    t = Tracer(clock=clock)
    t.complete("req", 11.0, 12.5, cat="request", n=3)
    t.instant("mark")
    ev_x, ev_i = t.events()
    assert ev_x["ts"] == 1e6 and ev_x["dur"] == 1.5e6
    assert ev_x["args"] == {"n": 3}
    assert ev_i["ph"] == "i" and ev_i["ts"] == 89e6


def test_negative_duration_clamped():
    t = Tracer()
    t.complete("backwards", 2.0, 1.0)
    (ev,) = t.events()
    assert ev["dur"] == 0.0                 # never a negative-width span


def test_max_events_drops_new_not_old():
    t = Tracer(max_events=2)
    for i in range(5):
        t.complete(f"e{i}", 0.0, 1.0)
    evs = t.events()
    assert [e["name"] for e in evs] == ["e0", "e1"]
    assert t.dropped == 3
    t.clear()
    assert t.events() == [] and t.dropped == 0
    t.complete("again", 0.0, 1.0)
    assert len(t.events()) == 1


def test_jsonl_well_formedness(tmp_path):
    """The satellite's trace-JSONL test: every line parses as one JSON
    object, every span is closed (complete events only, non-negative
    dur), and timestamps are sorted so consumers can stream."""
    t = Tracer()
    def worker(k):
        for i in range(20):
            with t.span(f"w{k}.op", idx=i):
                pass
    threads = [threading.Thread(target=worker, args=(k,)) for k in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    t.instant("done")
    path = tmp_path / "trace.jsonl"
    assert t.write(path) == str(path)

    raw_lines = path.read_text().splitlines()
    assert len(raw_lines) == 81             # 4*20 spans + 1 instant
    evs = [json.loads(line) for line in raw_lines]
    assert evs == read_jsonl(path)
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts)                 # monotone stream order
    for e in evs:
        assert e["ph"] in ("X", "i")
        assert e["ts"] >= 0
        if e["ph"] == "X":                  # every span closed: ts+dur
            assert e["dur"] >= 0
        assert {"name", "cat", "pid", "tid"} <= set(e)


def test_tracer_thread_safety_event_count():
    t = Tracer()
    n, per = 8, 500

    def worker():
        for _ in range(per):
            with t.span("op"):
                pass

    threads = [threading.Thread(target=worker) for _ in range(n)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert len(t.events()) == n * per
    assert t.dropped == 0


# --------------------------------------------------------------------- #
# path-bound tracers: flush/close/context-manager semantics
# --------------------------------------------------------------------- #

def test_flush_writes_to_configured_path(tmp_path):
    path = tmp_path / "t.jsonl"
    t = Tracer(path=str(path))
    with t.span("a"):
        pass
    assert t.flush() == str(path)
    assert [e["name"] for e in read_jsonl(path)] == ["a"]
    with t.span("b"):
        pass
    t.flush()                               # idempotent full rewrite
    assert [e["name"] for e in read_jsonl(path)] == ["a", "b"]


def test_flush_without_path_or_disabled_is_noop(tmp_path):
    assert Tracer().flush() is None         # no path configured
    t = Tracer(enabled=False, path=str(tmp_path / "x.jsonl"))
    assert t.flush() is None                # disabled: nothing to say
    assert not (tmp_path / "x.jsonl").exists()


def test_close_flushes_then_disables(tmp_path):
    path = tmp_path / "t.jsonl"
    t = Tracer(path=str(path))
    with t.span("kept"):
        pass
    t.close()
    assert [e["name"] for e in read_jsonl(path)] == ["kept"]
    assert not t.enabled
    with t.span("dropped"):                 # post-close spans are no-ops
        pass
    t.close()                               # second close: no rewrite crash
    assert [e["name"] for e in read_jsonl(path)] == ["kept"]


def test_context_manager_lands_trace_on_exception(tmp_path):
    path = tmp_path / "t.jsonl"
    try:
        with Tracer(path=str(path)) as t:
            with t.span("before-crash"):
                pass
            raise RuntimeError("aborted run")
    except RuntimeError:
        pass
    # the whole point: an aborted run still left its trace on disk
    assert [e["name"] for e in read_jsonl(path)] == ["before-crash"]


# --------------------------------------------------------------------- #
# spans on the profiler's clock
# --------------------------------------------------------------------- #

def _host_events(log_dir) -> set:
    """The names of the events on a profile's host planes."""
    import glob
    import os

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    data = ProfileData.from_file(path)
    return {ev.name for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}


def test_enabled_span_lands_on_the_profile_host_plane(tmp_path):
    import jax

    on, off = Tracer(), Tracer(enabled=False)
    with jax.profiler.trace(str(tmp_path)):
        with on.span("replay_sample.enabled", cat="loop"):
            pass
        with off.span("replay_sample.disabled", cat="loop"):
            pass
        on.complete("request.after_the_fact", 0.0, 1.0)
    names = _host_events(tmp_path)
    assert "replay_sample.enabled" in names
    assert "replay_sample.disabled" not in names
    # a span recorded after the fact stays in the JSONL export only
    assert "request.after_the_fact" not in names
    assert [e["name"] for e in on.events()] == ["replay_sample.enabled",
                                                "request.after_the_fact"]
