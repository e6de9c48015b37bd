"""Device time of the optimizer, in milliseconds per training step of the
traced window: the ops under ``optim/update`` (AdamW's clip, moments and
update, and the add to the parameters; ``perfbench/layers.py``)."""
from perfbench import layers


def read(record):
    return layers.ms_per_step(record, "optim")
