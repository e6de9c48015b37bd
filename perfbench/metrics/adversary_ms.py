"""Device time of the simulated Byzantine workers, in milliseconds per
training step of the traced window: the ops under ``adversary/attack``
(the honest mean, the V estimate's Gram, the attack, the adversary's
state), which no deployment pays (``perfbench/layers.py``)."""
from perfbench import layers


def read(record):
    return layers.ms_per_step(record, "adversary")
