"""Device time of the guard's statistics, in milliseconds per training
step of the traced window: the ops under ``guard/stats_sweep`` (the entry
rounding, A, B, the gradient Gram and the B-Gram's update)."""
from perfbench import trace


def read(record):
    if record.trace is None or not record.ops or record.steps == 0:
        return None
    ns = trace.scoped_ns(record.ops, "guard/stats_sweep", record.lo, record.hi)
    if ns == 0:
        return None
    return ns * 1e-6 / record.steps
