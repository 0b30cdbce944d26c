"""Device milliseconds a step owned by `block*/attn/conv`: what a KDA
layer spends between its q, k, v projections and the delta rule, the
short convolution with its taps' document masks, SiLU, the unit scaling
of q and k and the head-major order, forward and backward, every KDA
layer, first device.  The part of `kda_glue_ms` / `kda_glue_packed_ms`
that `ops/conv_stage.py`'s kernels (`conv_stage` / `conv_unstage`) do.
None on a program that opens no such scope."""

from benchmarks.lib import owners


def compute(observed):
    return owners.ms(observed, owner=r"block\d*/attn/conv$") or None
