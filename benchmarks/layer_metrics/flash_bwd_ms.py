"""Device milliseconds a step in the flash attention backward kernels
(instructions named `flash_bwd*`), all layers, first device."""

from benchmarks.lib import owners


def compute(observed):
    return owners.ms(observed, name=r"flash_bwd") or None
