"""Share of the traced serving span in which no operation ran on the
device."""
from bench import trace as tr


def read(r):
    return 100.0 * tr.idle_share(r.trace)
