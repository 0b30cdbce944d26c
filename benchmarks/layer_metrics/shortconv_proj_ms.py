"""Device milliseconds a step owned by the two projections of the
convolutional mixers (`block*/attn/in_proj`, H -> 3H, and
`block*/attn/out_proj`, H -> H), forward and backward, every such
layer, first device: the GEMMs on either side of `shortconv_gate_ms`,
and whatever of the gates the compiler fused into them.  None on a
program that opens no such scope."""

from benchmarks.lib import owners


def compute(observed):
    return owners.ms(observed,
                     owner=r"block\d*/attn/(in_proj|out_proj)$") or None
