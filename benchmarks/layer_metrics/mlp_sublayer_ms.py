"""Device milliseconds a step owned by the MLP sublayers (`block*/mlp`:
fc1, GELU, fc2 and the residual add), forward and backward, all layers,
first device, with their TP/SP collectives."""

from benchmarks.lib import owners


def compute(observed):
    return owners.ms(observed, owner=r"block\d*/mlp(/|$)")
