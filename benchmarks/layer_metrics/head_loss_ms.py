"""Device milliseconds a step owned by the embedding, the LM head and
the loss (`embed`, `head`, `loss`), forward and backward, first
device."""

from benchmarks.lib import owners


def compute(observed):
    return owners.ms(observed, owner=r"(embed|head|loss)$")
