"""Device milliseconds a step owned by the delta rule's scope
(`block*/attn/scan`), forward and backward, every KDA layer, first
device: everything `ops.delta_rule.gated_delta_rule` runs, kernels or
compiled `jax.numpy` alike (the chunk-local products, the triangular
inverse, the scan over chunks, the recompute in the backward).  A loop
instruction's own event (`while.N`) spans its body's instructions,
which the trace also holds one by one: the loops' own events are left
out, so nothing counts twice.  None on a program that opens no such
scope."""

from benchmarks.lib import owners


def compute(observed):
    return owners.ms(observed, owner=r"block\d*/attn/scan$",
                     but_name=r"while") or None
