"""Device time per step of the LM train step's `attn` phase: the attention
layer: its norm, QKV, the document-masked softmax over query chunks, the
output projection and residual add."""
from bench import lm_phases


def read(r):
    return lm_phases.per_step_us(r, "attn")
