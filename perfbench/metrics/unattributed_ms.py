"""Device time that no layer of the train step claims, in milliseconds per
training step of the traced window: ops with no ``op_name`` (copies and
slices that XLA makes, such as writes into the (W, d) view) and named ops
outside the step's layers (the batch synthesis, the metrics;
``perfbench/layers.py``)."""
from perfbench import layers


def read(record):
    return layers.ms_per_step(record, layers.UNATTRIBUTED)
