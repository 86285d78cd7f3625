"""Device time per update of the window's `update` phase: the two fused
launches, the padding and unpadding of their leaves, and the reduction of
the site ranges."""
from bench import phases


def read(r):
    return phases.per_update_us(r, "update")
