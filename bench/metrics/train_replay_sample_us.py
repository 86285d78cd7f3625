"""Device time per timestep of the window's `replay_sample` phase: the
batch's index draw and gathers, with the relayout copies of the replay's
columns that the compiler inserts for the gathers."""
from bench import phases


def read(r):
    return phases.per_timestep_us(r, "replay_sample")
