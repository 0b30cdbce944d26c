"""Device milliseconds a step owned by the expert layers' grouping and
its inverse (`block*/mlp/dispatch`: the sort by expert, the counts, the
gather of the tokens; `block*/mlp/combine`: the weighted scatter-add
back), forward and backward, first device, without what
`top4_expert_gemm_ms` takes back from them (the forward down-projection
the owner table files under `mlp/combine`).  Required work: none."""

import re

from benchmarks.layer_metrics import top4_expert_gemm_ms
from benchmarks.lib import owners

MINE = re.compile(r"block\d*/mlp/(dispatch|combine)$")


def compute(observed):
    rows = owners.table(observed)
    if rows is None or not any(MINE.match(r.owner) for r in rows):
        return None
    taken = top4_expert_gemm_ms.adopted(observed)
    return sum(r.ms for r in rows
               if MINE.match(r.owner) and r.name not in taken)
