"""Device time per update of the critic's fused launch,
`%fxp_mlp_train_step_critic.N`: critic forward, TD target, backward, Adam
and the target's soft update."""
from bench import phases


def read(r):
    return phases.per_update_us(r, "critic")
