"""Device milliseconds a step in the backward pass, first device: every
instruction whose owner stands under `transpose(jvp(` in the compiled
step."""

from benchmarks.lib import owners


def compute(observed):
    return owners.ms(observed, direction="bwd$")
