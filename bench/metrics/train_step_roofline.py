"""The two fused update launches' share of their roofline: the least time
of the updates traced (operations over the bf16 peak or bytes over HBM
bandwidth, whichever is larger; bytes bound at these widths) over the
launches' summed device time."""
from bench import trace as tr


def read(r):
    pats = tr.kernel_patterns("update_step")
    t_ns = tr.op_time_ns(r.trace, pats)
    if t_ns <= 0 or r.measured["updates"] <= 0:
        return None
    cfg, w = r.config, r.work
    least, _bound = w.least_time_s(w.update_flops(cfg), w.update_bytes(cfg), r.peaks)
    return 100.0 * least * r.measured["updates"] / (t_ns / 1e9)
