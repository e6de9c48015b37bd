"""Device time of the flat view, in milliseconds per training step of the
traced window: the ops under ``flat/ravel`` (the (W, d) gradient view and
the flat parameters) and ``flat/unravel`` (ξ back to a tree).  The
XLA-made writes of the view that carry no ``op_name`` are not counted
here but in ``unattributed_ms``."""
from perfbench import layers


def read(record):
    return layers.ms_per_step(record, "flat")
