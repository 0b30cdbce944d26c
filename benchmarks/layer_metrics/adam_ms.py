"""Device milliseconds a step in the flat Adam kernel on the first
device."""

from benchmarks.lib import trace


def compute(observed):
    took = trace.kernel_seconds_per_step(observed, "adam")
    return took and 1e3 * took
