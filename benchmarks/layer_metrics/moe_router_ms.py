"""Device milliseconds a step owned by the expert layers' routers
(`block*/mlp/router`: the gate GEMM, the sigmoid, the biased top-k and
the weights), forward and backward, first device."""

from benchmarks.lib import owners


def compute(observed):
    return owners.ms(observed, owner=r"block\d*/mlp/router$")
