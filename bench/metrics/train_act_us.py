"""Device time per timestep of the window's `act` phase: the actor's
forward (the fused act kernel `%fxp_mlp_train.N`) and the exploration
noise."""
from bench import phases


def read(r):
    return phases.per_timestep_us(r, "act")
