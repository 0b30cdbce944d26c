"""Device milliseconds a step owned by what a KDA layer spends on
neither its GEMMs nor the delta rule (`block*/attn/conv`: the
convolution, SiLU, the unit scaling of q and k and the head-major
copies; `attn/decay`: the decay's rank pair, softplus and beta;
`attn/onorm`: the output norm, the gate's rank pair and the gate),
forward and backward, every KDA layer, first device.  None on a
program that opens no such scope."""

from benchmarks.lib import owners


def compute(observed):
    return owners.ms(observed,
                     owner=r"block\d*/attn/(conv|decay|onorm)$") or None
