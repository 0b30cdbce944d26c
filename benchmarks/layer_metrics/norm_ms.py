"""Device milliseconds a step owned by the layer norms (`block*/ln1`,
`block*/ln2`, `final_ln`), forward and backward, first device.  XLA
fuses a norm into its neighbours where it can, and a fusion belongs to
the scope of its root: this is what is left standing under a norm's own
name."""

from benchmarks.lib import owners


def compute(observed):
    return owners.ms(observed, owner=r"(block\d*/ln[12]|final_ln)$")
