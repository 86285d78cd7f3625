"""Device time per timestep of the window's `env` phase: the vmapped
fleet's step and its auto-reset."""
from bench import phases


def read(r):
    return phases.per_timestep_us(r, "env")
