"""Whole training step's share of the chip's bf16 peak: model operations
per trained sample (the update's 4 actor and 6 critic passes, and the
act of the fleet's rows per update) times trained samples per second over
the traced window."""
from bench import trace as tr


def read(r):
    cfg, w = r.config, r.work
    per_sample = (w.update_flops_per_sample(cfg)
                  + w.act_flops(cfg, r.traffic["n_envs"]) / cfg["batch_size"])
    ips = r.measured["updates"] * cfg["batch_size"] / (tr.window_ns(r.trace) / 1e9)
    return 100.0 * per_sample * ips / (r.chips * r.peaks["bf16_flops_per_s"])
