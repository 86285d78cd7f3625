"""The act's share of its roofline: the least time of the requests
answered (real rows; the actor's parameters read once a call) over the
device time of the operations inside the engine's act programs, whatever
mode the dispatcher chose."""
from bench import trace as tr


def read(r):
    act = tr.ops_within(r.trace, tr.module_patterns("act"))
    t_ns = tr.busy_ns(act)
    calls, rows = r.counters.get("batches", 0), r.counters.get("requests", 0)
    if t_ns <= 0 or not calls:
        return None
    cfg, w = r.config, r.work
    nbytes = calls * w.act_bytes(cfg, 0) + (w.act_bytes(cfg, rows) - w.act_bytes(cfg, 0))
    least, _bound = w.least_time_s(w.act_flops(cfg, rows), nbytes, r.peaks)
    return 100.0 * least / (t_ns / 1e9)
