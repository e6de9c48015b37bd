"""Device time of the model's backward passes, in milliseconds per
training step of the traced window: the ops under the ``model/loss`` scope
whose name holds ``transpose(``, the forward that ``jax.checkpoint``
recomputes among them (``perfbench/layers.py``)."""
from perfbench import layers


def read(record):
    return layers.ms_per_step(record, "model_bwd")
