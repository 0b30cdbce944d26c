"""Device milliseconds a step owned by the attention sublayers
(`block*/attn`), forward and backward, all layers, first device: the
QKV and output GEMMs, the flash kernels, the layout copies around them
and, across chips, the sublayer's TP/SP collectives."""

from benchmarks.lib import owners


def compute(observed):
    return owners.ms(observed, owner=r"block\d*/attn(/|$)")
