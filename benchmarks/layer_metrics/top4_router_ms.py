"""Device milliseconds a step owned by the expert layers' routers
(`block*/mlp/router`: the gate GEMM over the 32 published experts, the
sigmoid, the biased top-4 and the weights), forward and backward, first
device: `moe_router_ms` under this cell's name.  None on a program that
opens no such scope."""

from benchmarks.layer_metrics import moe_router_ms


def compute(observed):
    return moe_router_ms.compute(observed) or None
