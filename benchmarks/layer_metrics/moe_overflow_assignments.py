"""Assignments to held experts that found no row in the grouped
buffers (twice the uniform expectation), all expert layers, in a
forward of the run's last batch (`counters["moe_overflow"]`).  Anything
but 0 also makes the run not `correct`: the bound is no capacity."""


def compute(observed):
    overflow = observed.get("counters", {}).get("moe_overflow")
    return None if overflow is None else sum(overflow)
