"""Device time per step of the LM train step's `mamba_proj` phase: the Mamba-2
mixers outside their SSD scan: in_proj, the conv, dt, the gated norm,
out_proj, with the block's norm and residual add."""
from bench import lm_phases


def read(r):
    return lm_phases.per_step_us(r, "mamba_proj")
