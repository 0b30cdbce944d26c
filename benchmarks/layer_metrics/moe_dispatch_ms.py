"""Device milliseconds a step owned by the expert layers' grouping and
its inverse (`block*/mlp/dispatch`: the sort by expert, the counts, the
gather of the tokens; `block*/mlp/combine`: the weighted scatter-add
back), forward and backward, first device, without the grouped-matmul
kernels the owner table files there (`moe_expert_gemm_ms` says why).
Required work: none."""

from benchmarks.lib import owners


def compute(observed):
    return owners.ms(observed, owner=r"block\d*/mlp/(dispatch|combine)$",
                     but_name=r"ragged-dot")
