"""The served act's share of the chip's bf16 peak: operations of the
requests answered (real rows only, no bucket padding) over the traced
window."""
from bench import trace as tr


def read(r):
    flops = r.work.act_flops(r.config, r.counters["requests"])
    return 100.0 * flops / (tr.window_ns(r.trace) / 1e9) / (r.chips * r.peaks["bf16_flops_per_s"])
