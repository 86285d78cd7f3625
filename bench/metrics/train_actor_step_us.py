"""Device time per update of the actor's fused launch,
`%fxp_mlp_train_step_actor.N`: actor forward through the updated critic,
the policy gradient, Adam and the target's soft update."""
from bench import phases


def read(r):
    return phases.per_update_us(r, "actor")
