"""Device milliseconds a step owned by `block*/attn/shortconv`: what a
convolutional mixer spends between its two projections, the input gate,
the causal depthwise taps and the output gate
(`apex_tpu/ops/short_conv.py::gated_short_conv`), forward and backward
(the backward makes the forward's sums again), every such layer, first
device.  None on a program that opens no such scope."""

from benchmarks.lib import owners


def compute(observed):
    return owners.ms(observed, owner=r"block\d*/attn/shortconv$") or None
