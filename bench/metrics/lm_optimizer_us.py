"""Device time per step of the LM train step's `optimizer` phase: the
gradients' Q15.16 lattice (with QAT), the global clip, Adam and the weights'
lattice."""
from bench import lm_phases


def read(r):
    return lm_phases.per_step_us(r, "optimizer")
