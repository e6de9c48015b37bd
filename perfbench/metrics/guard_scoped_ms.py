"""Device time of the ops under the guard's ``guard/`` named scopes, in
milliseconds per training step of the traced window.  In the ``dp_exact``
guard only the filter and the aggregate carry a scope; its statistics
(A, the Grams, the B update) run unscoped and are not counted."""
from perfbench import trace


def read(record):
    if record.trace is None or not record.ops or record.steps == 0:
        return None
    ns = trace.scoped_ns(record.ops, "guard/", record.lo, record.hi)
    if ns == 0:
        return None
    return ns * 1e-6 / record.steps
