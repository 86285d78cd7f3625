"""Work counts of the DDPG job from its logical shapes.

Operations count two per multiply-add of the published widths, with no
padding and nothing recomputed.  Bytes count float32 values (the Q15.16
storage), each read or written once per use, so the counts do not change
when the implementation does.
"""
from __future__ import annotations

F32 = 4


def _dims(cfg, net):
    od, ad, hid = cfg["obs_dim"], cfg["act_dim"], cfg["hidden"]
    if net == "actor":
        return [od, *hid, ad]
    return [od + ad, *hid, 1]


def macs(cfg, net: str) -> int:
    """Multiply-adds of one row through one network's forward pass."""
    d = _dims(cfg, net)
    return sum(a * b for a, b in zip(d[:-1], d[1:]))


def params(cfg, net: str) -> int:
    d = _dims(cfg, net)
    return sum(a * b + b for a, b in zip(d[:-1], d[1:]))


def update_flops_per_sample(cfg) -> int:
    """One trained sample of one update: the critic step runs the target
    actor and target critic forward, the critic forward and its backward
    (twice the forward); the actor step runs the actor forward, the
    updated critic forward, the critic's input gradient (once the forward)
    and the actor's backward.  That is 4 actor and 6 critic passes."""
    return 2 * (4 * macs(cfg, "actor") + 6 * macs(cfg, "critic"))


def update_bytes(cfg) -> int:
    """One update: each network's parameters, Adam moments and targets are
    read and written once (8 values a parameter), the critic step reads the
    target actor and the actor step the updated critic, and the batch's
    rows are read once."""
    pa, pc = params(cfg, "actor"), params(cfg, "critic")
    row = 2 * cfg["obs_dim"] + cfg["act_dim"] + 2
    return F32 * (8 * (pa + pc) + pa + pc + cfg["batch_size"] * row)


def update_flops(cfg) -> int:
    return cfg["batch_size"] * update_flops_per_sample(cfg)


def act_flops(cfg, rows: int) -> int:
    return 2 * rows * macs(cfg, "actor")


def act_bytes(cfg, rows: int) -> int:
    """The actor's parameters once, each row's observation and action."""
    return F32 * (params(cfg, "actor") + rows * (cfg["obs_dim"] + cfg["act_dim"]))


def least_time_s(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """The roofline: the larger of operations over the bf16 peak and bytes
    over the HBM bandwidth, and which of the two bounds it."""
    tc, tb = flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tb else (tb, "bytes")
