"""Device time per timestep of the window's `replay_add` phase: the
fleet's transitions written into the replay ring."""
from bench import phases


def read(r):
    return phases.per_timestep_us(r, "replay_add")
