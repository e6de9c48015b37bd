"""Share of the traced window in which no op ran on the device, in %:
1 - (union of the device's op intervals) / (the window's length), the
window being the benchmark's own ``perfbench/window`` host span."""
from perfbench import trace


def read(record):
    if record.trace is None or not record.ops or not record.hi > record.lo:
        return None
    busy = trace.busy_ns(record.ops, record.lo, record.hi)
    return 100.0 * (1.0 - busy / (record.hi - record.lo))
