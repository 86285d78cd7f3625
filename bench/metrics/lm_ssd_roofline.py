"""The SSD scans' share of their roofline: the least time of a step's SSD
work (`bench/work`: operations over the bf16 peak or bytes over HBM
bandwidth, whichever is larger) over the `ssd` phase's device time a step."""
from bench import lm_phases


def read(r):
    us = lm_phases.per_step_us(r, "ssd")
    if not us:
        return None
    tokens = r.traffic["seq_len"] * r.traffic["batch"]
    w = r.work
    least, _bound = w.least_time_s(w.ssd_step_flops(r.config, tokens),
                                   w.ssd_step_bytes(r.config, tokens), r.peaks)
    return 100.0 * least / (us / 1e6)
