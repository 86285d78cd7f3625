"""Device time per step of the LM train step's `ssd` phase: the chunked SSD
scans of the Mamba-2 layers (forward, recomputed forward and backward)."""
from bench import lm_phases


def read(r):
    return lm_phases.per_step_us(r, "ssd")
