"""Device milliseconds a step in the forward pass, first device: every
instruction whose owner stands under `jvp(` in the compiled step."""

from benchmarks.lib import owners


def compute(observed):
    return owners.ms(observed, direction="fwd$")
