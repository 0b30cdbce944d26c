"""Device milliseconds a step in the flash attention kernels, forward
and backward, all layers, on the first device: the sum of their events'
device durations in the steady part of the trace."""

from benchmarks.lib import trace


def compute(observed):
    took = trace.kernel_seconds_per_step(observed, "flash")
    return took and 1e3 * took
