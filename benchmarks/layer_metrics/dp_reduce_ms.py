"""Device milliseconds a step owned by the data-parallel reduction of
the gradients and the loss (`dp_reduce`), first device."""

from benchmarks.lib import owners


def compute(observed):
    return owners.ms(observed, owner=r"dp_reduce$")
