"""The LM train step's share of the chip's bf16 peak: model operations per
sequence (`bench/work`: 6·N over the matmul weights held, the chunked SSD
and attention over the causal pairs within the documents measured, nothing
recomputed) times sequences per second over the traced window."""
from bench import trace as tr


def read(r):
    m = r.measured
    if m["sequences"] <= 0:
        return None
    tokens = r.traffic["seq_len"]
    per_seq = r.work.step_flops(r.config, tokens, m["attn_pairs_per_sequence"])
    seqs_per_s = m["sequences"] / (tr.window_ns(r.trace) / 1e9)
    return 100.0 * per_seq * seqs_per_s / (r.chips * r.peaks["bf16_flops_per_s"])
