"""The Adam pass's share of the HBM peak: the bytes the update of this
device's share of the state must read and write, over the kernel's
time, over the peak bytes/s."""

from benchmarks.lib import trace


def compute(observed):
    took = trace.kernel_seconds_per_step(observed, "adam")
    if not (took and observed.get("peaks")):
        return None
    return (100.0 * observed["work"]["adam_bytes"] / took
            / observed["peaks"]["hbm_bytes_per_s"])
