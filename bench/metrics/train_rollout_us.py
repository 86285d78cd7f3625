"""Device busy time per timestep inside the training windows, outside the
two update launches: act, env step, replay store and sample, and the scan
body's glue."""
from bench import trace as tr


def read(r):
    win = tr.ops_within(r.trace, tr.module_patterns("train_window"))
    if not win.ops or r.measured["timesteps"] <= 0:
        return None
    step = tr.kernel_patterns("update_step")
    rest_ns = tr.busy_ns(win) - tr.op_time_ns(win, step)
    return rest_ns / 1e3 / r.measured["timesteps"]
